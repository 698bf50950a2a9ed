"""Seeded random weights for the model families."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def normal(key, shape, dtype, std: float):
    """A float32 standard-normal draw, scaled by ``std`` and cast to
    ``dtype``, as one compiled program: the cast fuses into the draw, so
    a bfloat16 leaf never has a float32 copy of its own size beside it
    (a 30-layer stacked leaf's copy would be gigabytes)."""
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)
