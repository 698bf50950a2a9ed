"""Pallas WKV6 kernel — the row-wise treatment of RWKV's recurrence.

EXPERIMENTS.md §Perf (rwkv6 train, iteration A2) shows the chunked-jnp
WKV is memory-bound on state flux: S (P x P) per head round-trips HBM
every 16-token chunk. This kernel keeps S resident in VMEM across the
whole sequence (the grid iterates chunks innermost per (batch x head)),
so HBM traffic drops to the r/k/v/w reads + y write — the same
structural move as the flash-attention kernel (and the paper's
keep-the-accumulator-on-chip rule, Sec. IV-D).

Chunk math matches models/rwkv6.wkv_chunked (clamped per-channel log
decays; see the numerics note there).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, y_ref, s_out_ref,
                s_scr, *, n_chunks: int, chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    r = r_ref[0].astype(jnp.float32)              # (L, P)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    lw = lw_ref[0].astype(jnp.float32)            # (L, P), < 0
    u = u_ref[0].astype(jnp.float32)              # (1, P) -> broadcast

    cs = jnp.cumsum(lw, axis=0)                   # inclusive
    cs_prev = cs - lw                             # exclusive
    rd = r * jnp.exp(cs_prev)                     # (L, P)
    kd = k * jnp.exp(-cs)
    a = jax.lax.dot_general(rd, kd, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    li = jax.lax.broadcasted_iota(jnp.int32, a.shape, 0)
    lj = jax.lax.broadcasted_iota(jnp.int32, a.shape, 1)
    a = jnp.where(li > lj, a, 0.0)                # strict lower triangle
    diag = jnp.sum(r * u * k, axis=-1, keepdims=True)   # (L, 1)
    y = (jax.lax.dot_general(a, v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
         + diag * v
         + jax.lax.dot_general(rd, s_scr[...], (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32))
    y_ref[0] = y.astype(y_ref.dtype)

    tail = jnp.exp(cs[-1:] - cs)                  # (L, P)
    s_scr[...] = (jnp.exp(cs[-1])[:, None] * s_scr[...]
                  + jax.lax.dot_general(tail * k, v,
                                        (((0,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32))

    @pl.when(ci == n_chunks - 1)
    def _fin():
        s_out_ref[0] = s_scr[...]


def wkv_p(r, k, v, lw, u, *, chunk: int = 16, interpret: bool = False):
    """r/k/v/lw: (B, S, H, P); u: (H, P). Returns (y (B,S,H,P),
    S_fin (B,H,P,P) fp32). S stays in VMEM across the sequence."""
    b, s, h, p = r.shape
    pad = (-s) % chunk
    if pad:
        z = ((0, 0), (0, pad), (0, 0), (0, 0))
        r, k, v = jnp.pad(r, z), jnp.pad(k, z), jnp.pad(v, z)
        lw = jnp.pad(lw, z)                       # pad decay 0 => unused
    sp = s + pad
    nc = sp // chunk

    def bh(x):   # (B, S, H, P) -> (B*H, S, P)
        return x.transpose(0, 2, 1, 3).reshape(b * h, sp, p)

    rf, kf, vf, lwf = bh(r), bh(k), bh(v), bh(lw)
    uf = jnp.broadcast_to(u[None], (b, h, p)).reshape(b * h, 1, p)

    grid = (b * h, nc)
    seq_spec = pl.BlockSpec((1, chunk, p), lambda i, c: (i, c, 0))
    u_spec = pl.BlockSpec((1, 1, p), lambda i, c: (i, 0, 0))
    y, s_fin = pl.pallas_call(
        functools.partial(_wkv_kernel, n_chunks=nc, chunk=chunk),
        grid=grid,
        in_specs=[seq_spec, seq_spec, seq_spec, seq_spec, u_spec],
        out_specs=[seq_spec,
                   pl.BlockSpec((1, p, p), lambda i, c: (i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((b * h, sp, p), r.dtype),
                   jax.ShapeDtypeStruct((b * h, p, p), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((p, p), jnp.float32)],
        interpret=interpret,
        name="wkv",
        metadata={"kernel": "wkv", "chunk": str(chunk)},
    )(rf, kf, vf, lwf, uf)
    y = y.reshape(b, h, sp, p).transpose(0, 2, 1, 3)[:, :s]
    return y, s_fin.reshape(b, h, p, p)
