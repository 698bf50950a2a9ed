"""Row-wise attention kernel (flash-style online softmax).

The paper executes attention on the same dot-product primitive as FC
layers: Q is broadcast as the "weight", K/V rows stream as inputs, and
softmax runs on the post-processing unit between the two matmuls. The
TPU-native version keeps that structure — one *query row panel* is held
stationary (the broadcast operand) while K/V row panels stream past it —
and fuses the softmax between the two dot products via the online
(running max / running sum) recurrence, so the S x S score matrix never
touches HBM.

Supports causal masking, sliding-window (local) attention, GQA/MQA via
an index map folding query heads onto their KV head, a kv_len bound
for padded caches, and an additive score bias (relative-position bias /
shift masks for Swin window attention): bias blocks stream into the
score loop, so the biased S x S matrix is never materialized. A bias of
shape (nb, Hq, Sq, Skv) broadcasts over the batch in cycles of ``nb``
(nb = windows-per-image for Swin's shift masks, 1 for a pure
relative-position bias). When the bias is batch-invariant (nb == 1, no
GQA), the flattened batch*head grid axis is reordered head-major so one
bias block stays VMEM-resident across the whole batch sweep instead of
being re-fetched per (batch, head).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_LANES = 128


def _attn_kernel(q_ref, k_ref, v_ref, *refs,
                 scale: float, causal: bool, window: int, with_bias: bool,
                 bq: int, bk: int, n_k: int, q_offset: int, kv_len: int):
    if with_bias:
        b_ref, o_ref, m_scr, l_scr, acc_scr = refs
    else:
        b_ref, (o_ref, m_scr, l_scr, acc_scr) = None, refs
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * bq + q_offset      # absolute position of first query row
    k_start = ki * bk

    # Block-level skip — the kernel analogue of the ASIC leaving idle PE
    # rows unclocked: skip blocks above the causal diagonal, outside the
    # sliding window, or entirely past kv_len.
    run = k_start < kv_len
    if causal:
        run = jnp.logical_and(run, k_start <= q_start + bq - 1)
    if window > 0:
        run = jnp.logical_and(run, k_start + bk - 1 >= q_start - window + 1)

    @pl.when(run)
    def _body():
        q = q_ref[0]                      # (bq, hd)
        k = k_ref[0]                      # (bk, hd)
        v = v_ref[0]                      # (bk, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if with_bias:
            s = s + b_ref[0].astype(jnp.float32)
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = k_pos < kv_len
        if causal:
            mask = jnp.logical_and(mask, k_pos <= q_pos)
        if window > 0:
            mask = jnp.logical_and(mask, k_pos > q_pos - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[:, :1]                              # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)       # (bq, bk)
        l_new = alpha * l_scr[:, :1] + jnp.sum(p, -1, keepdims=True)
        pv = jax.lax.dot_general(p.astype(v.dtype), v,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha + pv
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == n_k - 1)
    def _fin():
        l = jnp.maximum(l_scr[:, :1], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def flash_attention_p(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                      causal: bool = True, window: int = 0,
                      scale: Optional[float] = None,
                      bias: Optional[jnp.ndarray] = None,
                      block_q: int = 128, block_k: int = 128,
                      q_offset: int = 0,
                      interpret: bool = False) -> jnp.ndarray:
    """q: (B, Hq, Sq, hd); k, v: (B, Hkv, Skv, hd) -> (B, Hq, Sq, hd).

    ``q_offset``: absolute position of q[..., 0, :] (chunked prefill).
    ``bias``: (nb, Hq, Sq, Skv) additive score bias; batch index b uses
    bias row b % nb (nb must divide B).
    """
    b, hq, sq, hd = q.shape
    _, hkv, skv, _ = k.shape
    assert hq % hkv == 0, (hq, hkv)
    group = hq // hkv
    scale = hd ** -0.5 if scale is None else scale

    bq, bk = min(block_q, sq), min(block_k, skv)
    sq_p, skv_p = -(-sq // bq) * bq, -(-skv // bk) * bk
    if sq_p != sq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, sq_p - sq), (0, 0)))
    if skv_p != skv:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, skv_p - skv), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, skv_p - skv), (0, 0)))

    qf = q.reshape(b * hq, sq_p, hd)
    kf = k.reshape(b * hkv, skv_p, hd)
    vf = v.reshape(b * hkv, skv_p, hd)
    n_k = skv_p // bk
    grid = (b * hq, sq_p // bq, n_k)

    nb = 0
    if bias is not None:
        nb = bias.shape[0]
        assert bias.shape[1:] == (hq, sq, skv) and b % nb == 0, (
            bias.shape, (b, hq, sq, skv))
        if (sq_p, skv_p) != (sq, skv):
            bias = jnp.pad(bias, ((0, 0), (0, 0), (0, sq_p - sq),
                                  (0, skv_p - skv)))
        bias = bias.reshape(nb * hq, sq_p, skv_p)

    # Grid axis 0 enumerates (batch, head). With a batch-invariant bias
    # and no GQA head grouping there is no KV-panel reuse to protect, so
    # flip to head-major: the bias block's index then changes only once
    # per batch sweep and stays VMEM-resident (28 KB fetched Hq times
    # instead of B*Hq times for Swin's 49x49 windows).
    head_major = bias is not None and nb == 1 and group == 1
    if head_major:
        def qo_index(bh, qi, ki):
            return ((bh % b) * hq + bh // b, qi, 0)

        def kv_index(bh, qi, ki):
            return ((bh % b) * hkv + (bh // b) // group, ki, 0)

        def bias_index(bh, qi, ki):
            return (bh // b, qi, ki)
    else:
        def qo_index(bh, qi, ki):
            return (bh, qi, 0)

        def kv_index(bh, qi, ki):
            return ((bh // hq) * hkv + (bh % hq) // group, ki, 0)

        def bias_index(bh, qi, ki):
            return (((bh // hq) % nb) * hq + bh % hq, qi, ki)

    in_specs = [
        pl.BlockSpec((1, bq, hd), qo_index),
        pl.BlockSpec((1, bk, hd), kv_index),
        pl.BlockSpec((1, bk, hd), kv_index),
    ]
    inputs = [qf, kf, vf]
    if bias is not None:
        in_specs.append(pl.BlockSpec((1, bq, bk), bias_index))
        inputs.append(bias)

    kernel = functools.partial(
        _attn_kernel, scale=scale, causal=causal, window=window,
        with_bias=bias is not None, bq=bq, bk=bk, n_k=n_k,
        q_offset=q_offset, kv_len=skv)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bq, hd), qo_index),
        out_shape=jax.ShapeDtypeStruct((b * hq, sq_p, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, _LANES), jnp.float32),   # running max
            pltpu.VMEM((bq, _LANES), jnp.float32),   # running denom
            pltpu.VMEM((bq, hd), jnp.float32),       # fp32 accumulator
        ],
        interpret=interpret,
        name="flash_attention",
        metadata={"kernel": "flash_attention", "bq": str(bq),
                  "bk": str(bk), "hd": str(hd)},
    )(*inputs)
    return out.reshape(b, hq, sq_p, hd)[:, :, :sq, :]
