"""The generic pieces of the plain float32 references.

Each block family's module (``families/<family>.py``) builds its own
reference from these: it follows the published architecture from the
HF-style keys of the configuration file, imports nothing of the
program, reads the benchmark's own weights (``weights.py``) and
computes in float32 with full-precision matmuls, one layer at a time
and in blocks of rows, so that a 16k-token sequence fits beside the
weights on one chip.

``mode`` selects the control, the same forward computed in a lower
precision than the configuration states: ``"int8"`` (symmetric int8,
per output channel for weights and per row for activations) or
``"fp8"`` (float8 e4m3 with the same scaling), applied to both operands
of every matmul that goes through :func:`_mm`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
ROW_BLOCK = 1024     # rows per projection / MLP block
Q_BLOCK = 256        # query rows per attention block
SEQ_STEP = 2048      # sequences pad to a multiple of this: few compiles


def _fake_quant(x, mode, axis):
    if mode is None:
        return x
    amax = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                       1e-30)
    if mode == "int8":
        s = amax / 127.0
        return jnp.clip(jnp.round(x / s), -127, 127) * s
    if mode == "fp8":
        s = amax / 448.0
        return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    raise ValueError(f"unknown control mode {mode!r}")


def _mm(a, w, mode):
    """a (..., K) float32 times a bf16 weight (K, N), in float32."""
    return jnp.dot(_fake_quant(a, mode, -1),
                   _fake_quant(w.astype(jnp.float32), mode, 0),
                   precision=HIGHEST)


def _rms(x, g, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * g.astype(jnp.float32)


def _rope(x, pos, theta):
    """x (S, H, hd): the half-split rotation (HF ``rotate_half``)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freqs[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _by_rows(fn, x):
    s = x.shape[0]
    out = jax.lax.map(fn, x.reshape(s // ROW_BLOCK, ROW_BLOCK, -1))
    return out.reshape(s, -1)


def _attend(q, k, v):
    """Causal softmax attention of q (S, Hq, hd) over k and v (S, Hkv,
    hd), ``Hq / Hkv`` query heads to each kv head, in blocks of
    ``Q_BLOCK`` query rows; returns (S, Hq hd)."""
    s, hq, hd = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    pos = jnp.arange(s)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK)
        qb = qb.reshape(Q_BLOCK, hkv, g, hd)
        sc = jnp.einsum("qhgd,khd->hgqk", qb, k,
                        precision=HIGHEST) * hd ** -0.5
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        sc = jnp.where(pos[None, :] <= qpos[:, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        o = jnp.einsum("hgqk,khd->qhgd", p, v, precision=HIGHEST)
        return o.reshape(Q_BLOCK, hq * hd)

    return jax.lax.map(block, jnp.arange(s // Q_BLOCK)).reshape(s, hq * hd)


def padded_tokens(tokens: np.ndarray) -> np.ndarray:
    """The sequence padded at its end to a multiple of ``SEQ_STEP``;
    causal attention keeps the padding out of every real position."""
    n = len(tokens)
    t = np.zeros((-(-n // SEQ_STEP) * SEQ_STEP,), np.int32)
    t[:n] = tokens
    return t


@jax.jit
def served_gaps(ref, served):
    """Per position: how far the served token's reference logit lies
    below the reference's best."""
    return jnp.max(ref, -1) - jnp.take_along_axis(
        ref, served[:, None], axis=-1)[:, 0]


@jax.jit
def control_gaps(ref, ctrl):
    """Per position: how far the token the control puts first lies
    below the reference's best, in the reference's logits."""
    top = jnp.argmax(ctrl, -1)
    return jnp.max(ref, -1) - jnp.take_along_axis(
        ref, top[:, None], axis=-1)[:, 0]
