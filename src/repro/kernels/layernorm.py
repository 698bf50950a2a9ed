"""Row-wise normalization kernel — the paper's post-processing unit.

LayerNorm / RMSNorm over the channel dim, one activation *row panel* per
grid step (same row-streaming structure as the matmul kernel). fp32
statistics regardless of input dtype.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def rownorm(x, gamma, beta=None, *, kind: str, eps: float,
            n_valid: Optional[int] = None):
    """fp32 LayerNorm/RMSNorm of a (bm, D) row panel — the shared norm
    math for the standalone kernel AND the matmul kernel's fused norm
    prologue. ``n_valid`` masks a zero-padded tail of the channel dim so
    statistics are taken over the true D only (the prologue's K panel is
    lane-padded)."""
    x = x.astype(jnp.float32)
    d = x.shape[-1]
    masked = n_valid is not None and n_valid != d
    if masked:
        mask = jax.lax.broadcasted_iota(jnp.int32, x.shape,
                                        x.ndim - 1) < n_valid
        xm = jnp.where(mask, x, 0.0)
    else:
        xm = x
    denom = n_valid if masked else d
    if kind == "layer":
        mu = jnp.sum(xm, -1, keepdims=True) / denom
        xc = x - mu
        if masked:
            xc = jnp.where(mask, xc, 0.0)
    else:                                          # rms
        xc = xm
    var = jnp.sum(jnp.square(xc), -1, keepdims=True) / denom
    y = xc * jax.lax.rsqrt(var + eps) * gamma.astype(jnp.float32)
    if beta is not None:
        y = y + beta.astype(jnp.float32)
    return y


def _norm_kernel(x_ref, g_ref, b_ref, o_ref, *, eps: float, kind: str):
    y = rownorm(x_ref[...], g_ref[...],
                None if b_ref is None else b_ref[...], kind=kind, eps=eps)
    o_ref[...] = y.astype(o_ref.dtype)


def _norm_kernel_nobias(x_ref, g_ref, o_ref, *, eps: float, kind: str):
    _norm_kernel(x_ref, g_ref, None, o_ref, eps=eps, kind=kind)


def layernorm_p(x: jnp.ndarray, gamma: jnp.ndarray,
                beta: jnp.ndarray = None, *, eps: float = 1e-6,
                kind: str = "layer", block_m: int = 256,
                interpret: bool = False) -> jnp.ndarray:
    """x: (M, D); gamma/beta: (D,). kind: 'layer' | 'rms'."""
    m, d = x.shape
    bm = min(block_m, m)
    mp = -(-m // bm) * bm
    if mp != m:
        x = jnp.pad(x, ((0, mp - m), (0, 0)))
    grid = (mp // bm,)
    x_spec = pl.BlockSpec((bm, d), lambda i: (i, 0))
    g_spec = pl.BlockSpec((1, d), lambda i: (0, 0))
    out_shape = jax.ShapeDtypeStruct((mp, d), x.dtype)
    g2 = gamma.reshape(1, d)
    if beta is not None:
        fn = pl.pallas_call(
            functools.partial(_norm_kernel, eps=eps, kind=kind),
            grid=grid, in_specs=[x_spec, g_spec, g_spec],
            out_specs=x_spec, out_shape=out_shape, interpret=interpret,
            name="layernorm", metadata={"kernel": "layernorm",
                                        "norm": kind})
        out = fn(x, g2, beta.reshape(1, d))
    else:
        fn = pl.pallas_call(
            functools.partial(_norm_kernel_nobias, eps=eps, kind=kind),
            grid=grid, in_specs=[x_spec, g_spec],
            out_specs=x_spec, out_shape=out_shape, interpret=interpret,
            name="layernorm", metadata={"kernel": "layernorm",
                                        "norm": kind})
        out = fn(x, g2)
    return out[:m]
