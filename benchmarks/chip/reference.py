"""Plain float32 reference of a dense llama-style decoder.

It follows the published architecture of both configurations (pre-norm
RMSNorm blocks, rotary positions with the half-split rotation, grouped
query attention, SwiGLU MLP, untied lm_head) from the HF-style keys of
the configuration file, and imports nothing of the program. It reads
the benchmark's own weights (``weights.py``) and computes in float32
with full-precision matmuls, one layer at a time and in blocks of rows,
so that a 16k-token sequence fits beside the weights on one chip.

``mode`` selects the control, the same forward computed in a lower
precision than the configuration states: ``"int8"`` (symmetric int8,
per output channel for weights and per row for activations) or
``"fp8"`` (float8 e4m3 with the same scaling), applied to both operands
of every projection, MLP and lm_head matmul.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
ROW_BLOCK = 1024     # rows per projection / MLP block
Q_BLOCK = 256        # query rows per attention block
SEQ_STEP = 2048      # sequences pad to a multiple of this: few compiles
LAYER_KEYS = ("attn_norm", "wqkv", "wo", "mlp_norm", "w_gate_up", "w_down")


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


@functools.partial(jax.jit, static_argnames=("arch", "mode"))
def _layer(x, lw, *, arch, mode):
    hq, hkv, hd, eps, theta = arch
    s = x.shape[0]
    g = hq // hkv
    pos = jnp.arange(s)
    h = _rms(x, lw["attn_norm"], eps)
    qkv = _by_rows(lambda r: _mm(r, lw["wqkv"], mode), h)
    q = _rope(qkv[:, :hq * hd].reshape(s, hq, hd), pos, theta)
    k = _rope(qkv[:, hq * hd:(hq + hkv) * hd].reshape(s, hkv, hd), pos,
              theta)
    v = qkv[:, (hq + hkv) * hd:].reshape(s, hkv, hd)

    def attend(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK)
        qb = qb.reshape(Q_BLOCK, hkv, g, hd)
        sc = jnp.einsum("qhgd,khd->hgqk", qb, k,
                        precision=HIGHEST) * hd ** -0.5
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        sc = jnp.where(pos[None, :] <= qpos[:, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        o = jnp.einsum("hgqk,khd->qhgd", p, v, precision=HIGHEST)
        return o.reshape(Q_BLOCK, hq * hd)

    o = jax.lax.map(attend, jnp.arange(s // Q_BLOCK)).reshape(s, hq * hd)
    x = x + _by_rows(lambda r: _mm(r, lw["wo"], mode), o)
    h = _rms(x, lw["mlp_norm"], eps)

    def mlp(r):
        gu = _mm(r, lw["w_gate_up"], mode)
        f = gu.shape[-1] // 2
        return _mm(jax.nn.silu(gu[:, :f]) * gu[:, f:], lw["w_down"], mode)

    return x + _by_rows(mlp, h)


@functools.partial(jax.jit, static_argnames=("vocab", "eps", "mode"))
def _head(x, pos, final_norm, lm_head, *, vocab, eps, mode):
    xs = _rms(x[pos], final_norm, eps)
    return _mm(xs, lm_head[:, :vocab], mode)


def arch_key(arch: dict) -> tuple:
    return (arch["num_attention_heads"], arch["num_key_value_heads"],
            arch["head_dim"], float(arch["rms_norm_eps"]),
            float(arch["rope_theta"]))


def logits(w: dict, arch: dict, tokens: np.ndarray, positions: np.ndarray,
           mode=None) -> jax.Array:
    """Next-token logits (len(positions), vocab) of the sequence
    ``tokens`` at ``positions``, in float32 (or in the control's
    precision). The sequence pads at its end to a multiple of
    ``SEQ_STEP``; causal attention keeps padding out of every real
    position."""
    n = len(tokens)
    s = -(-n // SEQ_STEP) * SEQ_STEP
    t = np.zeros((s,), np.int32)
    t[:n] = tokens
    key = arch_key(arch)
    with jax.default_matmul_precision("highest"):
        x = w["embed"][jnp.asarray(t)].astype(jnp.float32)
        for layer in range(arch["num_hidden_layers"]):
            lw = {k: w[k][layer] for k in LAYER_KEYS}
            x = _layer(x, lw, arch=key, mode=mode)
        return _head(x, jnp.asarray(positions, jnp.int32), w["final_norm"],
                     w["lm_head"], vocab=arch["vocab_size"],
                     eps=float(arch["rms_norm_eps"]), mode=mode)


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
