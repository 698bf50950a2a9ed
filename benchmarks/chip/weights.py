"""The benchmark's weights: drawn on the device from the seed, in bf16.

The benchmark makes the weights itself, so that the reference reads
weights that the program under test did not make. They are kept in one
flat dict of the benchmark's own layout, which the block family's
``shapes(arch)`` gives (``families/<family>.py``); the family's
``program_params`` hands the same arrays to the program in its
parameter tree, without a copy.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

VOCAB_MULTIPLE = 256


def padded_vocab(vocab: int) -> int:
    """The vocabulary rounded up to the padding the program expects."""
    return -(-vocab // VOCAB_MULTIPLE) * VOCAB_MULTIPLE


def key_for(seed: int, stream: int) -> jax.Array:
    """A PRNG key from a seed of any size (the driver's exceed 32
    bits): numpy folds it down, JAX gets 31 bits."""
    word = np.random.default_rng([seed, stream]).integers(0, 2 ** 31 - 1)
    return jax.random.PRNGKey(int(word))


@functools.partial(jax.jit, static_argnums=(1,))
def _draw(key, spec):
    out = {}
    for i, (name, shape, std, mean) in enumerate(spec):
        k = jax.random.fold_in(key, i)
        if len(shape) >= 3:
            # one layer at a time: the float32 draw of a whole stacked
            # leaf would be twice its bf16 size
            ks = jax.random.split(k, shape[0])
            out[name] = jax.lax.map(
                lambda kk, s=shape[1:], sd=std, m=mean: (
                    m + sd * jax.random.normal(kk, s, jnp.float32)
                ).astype(jnp.bfloat16), ks)
        else:
            out[name] = (mean + std * jax.random.normal(
                k, shape, jnp.float32)).astype(jnp.bfloat16)
    return out


def make(shapes: dict, seed: int) -> dict:
    """All weights of ``shapes`` (name -> (shape, std, mean), drawn in
    its order), on the default device, in one compiled program."""
    spec = tuple((name, shape, std, mean)
                 for name, (shape, std, mean) in shapes.items())
    w = _draw(key_for(seed, 0), spec)
    jax.block_until_ready(w)
    return w
