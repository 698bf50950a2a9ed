"""The benchmark's weights: drawn on the device from the seed, in bf16.

The benchmark makes the weights itself, so that the reference
(``reference.py``) reads weights that the program under test did not
make. They are kept in one flat dict of the benchmark's own layout:

  embed        (Vp, d)                 token embedding
  attn_norm    (L, d)                  pre-attention RMS gain
  wqkv         (L, d, (Hq + 2 Hkv) hd) q | k | v output columns
  wo           (L, Hq hd, d)
  mlp_norm     (L, d)                  pre-MLP RMS gain
  w_gate_up    (L, d, 2 f)             gate | up output columns
  w_down       (L, f, d)
  final_norm   (d,)
  lm_head      (d, Vp)

``Vp`` is the vocabulary rounded up to a multiple of 256, the padding
the program expects; the reference reads only the first ``V`` rows and
columns. :func:`program_params` hands the same arrays to the program
in its parameter tree, without a copy.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

VOCAB_MULTIPLE = 256


def padded_vocab(vocab: int) -> int:
    return -(-vocab // VOCAB_MULTIPLE) * VOCAB_MULTIPLE


def shapes(arch: dict) -> dict:
    """name -> (shape, std, mean) for an HF-style config dict."""
    d, f = arch["hidden_size"], arch["intermediate_size"]
    hq, hkv = arch["num_attention_heads"], arch["num_key_value_heads"]
    hd, n = arch["head_dim"], arch["num_hidden_layers"]
    vp = padded_vocab(arch["vocab_size"])
    qkv = (hq + 2 * hkv) * hd
    return {
        "embed": ((vp, d), 1.0, 0.0),
        # gains away from 1, so that a dropped gain shows
        "attn_norm": ((n, d), 0.2, 1.0),
        "wqkv": ((n, d, qkv), 1 / math.sqrt(d), 0.0),
        "wo": ((n, hq * hd, d), 1 / math.sqrt(hq * hd), 0.0),
        "mlp_norm": ((n, d), 0.2, 1.0),
        "w_gate_up": ((n, d, 2 * f), 1 / math.sqrt(d), 0.0),
        "w_down": ((n, f, d), 1 / math.sqrt(f), 0.0),
        "final_norm": ((d,), 0.2, 1.0),
        "lm_head": ((d, vp), 1 / math.sqrt(d), 0.0),
    }


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
        if len(shape) == 3:
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


def make(arch: dict, seed: int) -> dict:
    """All weights, on the default device, in one compiled program."""
    spec = tuple((name, shape, std, mean)
                 for name, (shape, std, mean) in shapes(arch).items())
    w = _draw(key_for(seed, 0), spec)
    jax.block_until_ready(w)
    return w


def program_params(w: dict) -> dict:
    """The program's parameter tree (``repro.models.lm`` layout for a
    dense, untied, single-stage model) over the same arrays."""
    block = {"norm1": {"g": w["attn_norm"]},
             "attn": {"wqkv": w["wqkv"], "wo": w["wo"]},
             "norm2": {"g": w["mlp_norm"]},
             "ffn": {"wgi": w["w_gate_up"], "wo": w["w_down"]}}
    return {"embed": w["embed"],
            "stages": [{"stacked": {"0": block}, "shared": {}}],
            "final_norm": {"g": w["final_norm"]},
            "lm_head": w["lm_head"]}
