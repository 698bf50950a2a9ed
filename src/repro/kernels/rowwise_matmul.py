"""Row-wise matmul — the paper's dot-product primitive as a Pallas kernel.

Mapping of the paper's ASIC dataflow onto TPU (see DESIGN.md §2–3):

  * **Weight broadcast / weight-stationary.** The grid is ``(n_tiles,
    m_tiles, k_splits)``. For a single-panel contraction the weight
    panel's index map depends only on *n*, so consecutive *m* steps
    revisit the same weight block and Pallas keeps it resident in VMEM —
    the TPU equivalent of broadcasting one weight down all 7 PE rows.
  * **Row-wise streaming.** Activation row panels ``(bm, bk)`` stream
    past the weight panel, one per grid step, exactly like input rows
    streaming through the PE block.
  * **Accumulator / adder tree.** Contractions too large for one VMEM
    panel run over the *innermost* ``k_splits`` grid axis: each step
    multiplies a ``(bm, bk) @ (bk, bn)`` panel pair and adds it into an
    fp32 (int32 for int8) VMEM scratch accumulator. The output block's
    index map ignores the k axis, so partial sums stay on-chip for the
    whole tree — one ``pallas_call``, no HBM round-trips.
  * **Post-processing unit.** Bias + activation (+ int8 dequant, gating,
    residual add) run as the kernel epilogue, predicated on the *final*
    k step only — one parameterized epilogue for every variant.
  * **Norm prologue (PR 2).** The pre-norm of a transformer sublayer
    runs on the activation row panel *inside* the kernel (fp32 stats,
    full-K panel required), so the normalized tensor never exists in
    HBM.
  * **Gated dual-weight path (PR 2).** A second weight panel streams
    next to the first, sharing the same activation rows; the epilogue
    computes ``act(x@w_gate) * (x@w)`` so SwiGLU/GeGLU's gate matmul,
    up matmul and gating multiply are one kernel.

Supports bf16/fp32 and the paper's 8-bit W/A mode (int8 x int8 -> int32
accumulation with per-row activation scales and per-channel weight
scales, as in ``core/quant.py``).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.rowwise import TilePlan, plan_matmul
from repro.kernels.layernorm import rownorm

_ACTIVATIONS = {
    None: lambda x: x,
    "gelu": lambda x: jax.nn.gelu(x, approximate=True),
    "silu": jax.nn.silu,
    "relu": jax.nn.relu,
    "relu2": lambda x: jnp.square(jax.nn.relu(x)),
}


def _apply_epilogue(r, *, activation: Optional[str], int8: bool,
                    gated: bool):
    """The post-processing unit, parameterized over every variant.

    One helper replaces the four inline float/int8 x bias/no-bias code
    paths: int8 dequant -> bias -> (gating | activation) -> residual,
    all in fp32 on the accumulator block(s). ``r`` maps operand names to
    kernel refs; optional stages key off membership.
    """
    h = r["acc"][...]
    if int8:
        h = h.astype(jnp.float32) * r["x_scale"][...] * r["w_scale"][...]
    if "bias" in r:
        h = h + r["bias"][...].astype(jnp.float32)
    if gated:
        g = r["acc_g"][...]
        if int8:
            g = g.astype(jnp.float32) * r["x_scale"][...] * r["wg_scale"][...]
        if "bias_g" in r:
            g = g + r["bias_g"][...].astype(jnp.float32)
        h = _ACTIVATIONS[activation](g) * h
    else:
        h = _ACTIVATIONS[activation](h)
    if "res" in r:
        h = h + r["res"][...].astype(jnp.float32)
    return h


def _pipeline_kernel(*refs, layout, activation: Optional[str], int8: bool,
                     gated: bool, prologue: Optional[str], eps: float,
                     k_true: int):
    """One body for the whole fused pipeline.

    ``layout`` names every ref in order (inputs, then the output, then
    scratch accumulators). Zero the scratch on the first k step, run the
    optional norm prologue on the activation row panel, accumulate a
    (bm, bk) @ (bk, bn) panel product per weight every step (fp32, exact
    int32 for int8), and run the post-processing epilogue only on the
    final k step.
    """
    r = dict(zip(layout, refs))
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        r["acc"][...] = jnp.zeros_like(r["acc"])
        if gated:
            r["acc_g"][...] = jnp.zeros_like(r["acc_g"])

    x = r["x"][...]
    if prologue is not None:
        # Full-K panel per step (k_splits == 1, enforced by the
        # wrapper): fp32 stats over the true K, then back to the
        # streaming dtype so the MXU sees the same operand the unfused
        # norm->matmul composition would.
        beta = r["pbeta"][...] if "pbeta" in r else None
        x = rownorm(x, r["gamma"][...], beta, kind=prologue, eps=eps,
                    n_valid=k_true).astype(r["x"].dtype)

    if int8:
        def dot(a, b):
            return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                                       preferred_element_type=jnp.int32)
    else:
        def dot(a, b):
            return jnp.dot(a, b, preferred_element_type=jnp.float32)

    r["acc"][...] += dot(x, r["w"][...])
    if gated:
        r["acc_g"][...] += dot(x, r["wg"][...])

    @pl.when(ki == pl.num_programs(2) - 1)
    def _epilogue():
        out = _apply_epilogue(r, activation=activation, int8=int8,
                              gated=gated)
        r["out"][...] = out.astype(r["out"].dtype)


def _pad2(x, m, n):
    pm, pn = m - x.shape[0], n - x.shape[1]
    if pm or pn:
        x = jnp.pad(x, ((0, pm), (0, pn)))
    return x


def rowwise_matmul_p(x: jnp.ndarray, w: jnp.ndarray, *,
                     bias: Optional[jnp.ndarray] = None,
                     x_scale: Optional[jnp.ndarray] = None,
                     w_scale: Optional[jnp.ndarray] = None,
                     activation: Optional[str] = None,
                     w_gate: Optional[jnp.ndarray] = None,
                     bias_gate: Optional[jnp.ndarray] = None,
                     wg_scale: Optional[jnp.ndarray] = None,
                     residual: Optional[jnp.ndarray] = None,
                     prologue: Optional[str] = None,
                     gamma: Optional[jnp.ndarray] = None,
                     pbeta: Optional[jnp.ndarray] = None,
                     eps: float = 1e-6,
                     out_dtype=None,
                     plan: Optional[TilePlan] = None,
                     interpret: bool = False) -> jnp.ndarray:
    """One pallas_call over the whole fused pipeline, any ``k_splits``.

    x: (M, K); w: (K, N); bias: (N,) optional.
    int8 mode when x_scale/w_scale given: x,w int8; scales fp32
    (M,1)/(1,N).
    w_gate: (K, N) second weight — gated mode, out = act(x@wg) * (x@w).
    residual: (M, N) added after activation/gating, before the cast.
    prologue: 'layer' | 'rms' — normalize the x row panel in-kernel
    (gamma/pbeta: (K,)); requires the plan to hold the full K in one
    panel (k_splits == 1) and a non-int8 x.
    """
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    int8_mode = x_scale is not None
    gated = w_gate is not None
    if gated:
        assert w_gate.shape == w.shape, (w_gate.shape, w.shape)
        assert not int8_mode or wg_scale is not None
    if prologue is not None:
        assert not int8_mode, "norm prologue runs on fp activations"
        assert gamma is not None
    if plan is None:
        plan = plan_matmul(m, k, n, dtype_bytes=x.dtype.itemsize,
                           n_weights=2 if gated else 1,
                           residual=residual is not None,
                           res_bytes=(residual.dtype.itemsize
                                      if residual is not None else None),
                           prologue=prologue is not None,
                           wide_n=gated or prologue is not None)
    assert k <= plan.bk * plan.k_splits
    if prologue is not None:
        assert plan.k_splits == 1 and plan.bk >= k, (
            "norm prologue needs the full K row resident per grid step; "
            "fall back to the standalone norm kernel", plan)
    out_dtype = out_dtype or (jnp.float32 if int8_mode else x.dtype)

    bm, bk, bn = plan.bm, plan.bk, plan.bn
    mp, np_, kp = plan.m_pad, plan.n_pad, plan.k_pad
    # k innermost: the output block's index map ignores ki, so Pallas
    # holds it (plus the scratch accumulators) in VMEM across the tree.
    grid = (np_ // bn, mp // bm, plan.k_splits)

    x_spec = pl.BlockSpec((bm, bk), lambda ni, mi, ki: (mi, ki))
    w_spec = pl.BlockSpec((bk, bn), lambda ni, mi, ki: (ki, ni))
    o_spec = pl.BlockSpec((bm, bn), lambda ni, mi, ki: (mi, ni))
    krow_spec = pl.BlockSpec((1, bk), lambda ni, mi, ki: (0, ki))
    nrow_spec = pl.BlockSpec((1, bn), lambda ni, mi, ki: (0, ni))

    names, inputs, in_specs = [], [], []

    def add(name, arr, spec):
        names.append(name)
        inputs.append(arr)
        in_specs.append(spec)

    add("x", _pad2(x, mp, kp), x_spec)
    if prologue is not None:
        add("gamma", _pad2(gamma.reshape(1, -1).astype(jnp.float32), 1, kp),
            krow_spec)
        if pbeta is not None:
            add("pbeta",
                _pad2(pbeta.reshape(1, -1).astype(jnp.float32), 1, kp),
                krow_spec)
    add("w", _pad2(w, kp, np_), w_spec)
    if gated:
        add("wg", _pad2(w_gate, kp, np_), w_spec)
    if int8_mode:
        add("x_scale", _pad2(x_scale.astype(jnp.float32), mp, 1),
            pl.BlockSpec((bm, 1), lambda ni, mi, ki: (mi, 0)))
        add("w_scale", _pad2(w_scale.astype(jnp.float32), 1, np_),
            nrow_spec)
        if gated:
            add("wg_scale", _pad2(wg_scale.astype(jnp.float32), 1, np_),
                nrow_spec)
    if bias is not None:
        add("bias", _pad2(bias.reshape(1, -1).astype(jnp.float32), 1, np_),
            nrow_spec)
    if gated and bias_gate is not None:
        add("bias_g",
            _pad2(bias_gate.reshape(1, -1).astype(jnp.float32), 1, np_),
            nrow_spec)
    if residual is not None:
        add("res", _pad2(residual, mp, np_), o_spec)

    acc_dtype = jnp.int32 if int8_mode else jnp.float32
    scratch = [pltpu.VMEM((bm, bn), acc_dtype)]
    layout = tuple(names) + ("out", "acc")
    if gated:
        scratch.append(pltpu.VMEM((bm, bn), acc_dtype))
        layout += ("acc_g",)

    # n/m tiles are independent; only the k axis carries the accumulator.
    params = dict(
        grid=grid, out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        scratch_shapes=scratch, interpret=interpret)
    if not interpret:
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))

    fn = pl.pallas_call(
        functools.partial(_pipeline_kernel, layout=layout,
                          activation=activation, int8=int8_mode,
                          gated=gated, prologue=prologue, eps=eps,
                          k_true=k),
        in_specs=in_specs, **params)
    return fn(*inputs)[:m, :n]
