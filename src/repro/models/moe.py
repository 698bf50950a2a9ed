"""Mixture-of-Experts FFN with token-choice top-k routing.

Two execution paths:

  * **Local** (no mesh — CPU tests): capacity-based scatter/gather
    dispatch on the whole batch.
  * **Expert-parallel shard_map** (under a mesh): tokens stay in their
    (pod, data, model) shards; each shard dispatches its own tokens into
    per-expert capacity buffers, an ``all_to_all`` over 'model' moves
    them to their expert's shard, experts run dense SwiGLU (weights
    FSDP-gathered over 'data' per layer), and a reverse ``all_to_all``
    returns outputs for the local weighted combine. This is the
    Switch-Transformer dispatch mapped onto jax collectives — the
    GSPMD scatter formulation replicates the dispatch buffers.

  Experts are padded up to a multiple of the model axis (qwen2-moe's 60
  -> 64) with router logits masked to -inf: routing never reaches pads.

Shared experts (qwen2-moe) run as a dense sigmoid-gated MLP on the side.
Aux load-balance loss follows Shazeer et al. (f_e * P_e).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import partitioning
from repro.core import quant
from repro.core.types import ModelConfig
from repro.kernels import ops
from repro.models.initializers import normal

MODEL_AXIS_FOR_PADDING = 16


def padded_experts(cfg: ModelConfig) -> int:
    e = cfg.moe.n_experts
    m = MODEL_AXIS_FOR_PADDING
    return -(-e // m) * m if e >= m else e


def init(key, cfg: ModelConfig, stack: Optional[int], dtype):
    mo = cfg.moe
    d, f = cfg.d_model, mo.d_ff
    e = padded_experts(cfg)
    lead = () if stack is None else (stack,)
    llead = () if stack is None else ("layers",)
    ks = jax.random.split(key, 6)

    def w(k, *shape):
        return normal(k, lead + shape, dtype, 1 / math.sqrt(shape[-2]))

    params = {
        "router": w(ks[0], d, e),
        "wi": w(ks[1], e, d, f),
        "wg": w(ks[2], e, d, f),
        "wo": w(ks[3], e, f, d),
    }
    specs = {
        "router": llead + ("embed", None),
        "wi": llead + ("experts", "embed", None),
        "wg": llead + ("experts", "embed", None),
        "wo": llead + ("experts", None, "embed"),
    }
    if mo.n_shared:
        fs = mo.d_ff * mo.n_shared
        params["shared_wi"] = w(ks[4], d, fs)
        params["shared_wg"] = w(ks[5], d, fs)
        params["shared_wo"] = normal(jax.random.fold_in(key, 7),
                                     lead + (fs, d), dtype, 1 / math.sqrt(fs))
        params["shared_gate"] = jnp.zeros(lead + (d, 1), dtype)
        specs.update({"shared_wi": llead + ("embed", "ffn"),
                      "shared_wg": llead + ("embed", "ffn"),
                      "shared_wo": llead + ("ffn", "embed"),
                      "shared_gate": llead + ("embed", None)})
    return params, specs


def _route(xf, router_w, cfg: ModelConfig, e_pad: int):
    """-> (gate_vals (T,k), gate_idx (T,k), probs (T,E_pad))."""
    mo = cfg.moe
    logits = jnp.dot(xf.astype(jnp.float32),
                     router_w.astype(jnp.float32))          # (T, E_pad)
    if e_pad != mo.n_experts:                               # mask pads
        col = jnp.arange(e_pad)
        logits = jnp.where(col < mo.n_experts, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, mo.top_k)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, -1, keepdims=True), 1e-9)
    return gate_vals, gate_idx, probs


def _dispatch_indices(gate_idx, e_pad: int, cap: int):
    """-> (slot (T*k,) in [0, e_pad*cap] (last = dropped), token_idx)."""
    t, k = gate_idx.shape
    onehot = jax.nn.one_hot(gate_idx, e_pad, dtype=jnp.int32)
    flat = onehot.reshape(t * k, e_pad)
    pos = jnp.sum((jnp.cumsum(flat, axis=0) - flat) * flat, axis=-1)
    eid = gate_idx.reshape(t * k)
    keep = pos < cap
    slot = jnp.where(keep, eid * cap + pos, e_pad * cap)
    token_idx = jnp.repeat(jnp.arange(t), k)
    return slot, keep, token_idx


def _expert_mlp(x, wi, wg, wo):
    """x: (E, C, d); weights (E, d, f)/(E, f, d). fp32 compute."""
    xf = x.astype(jnp.float32)
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xf,
                               wg.astype(jnp.float32)))
    h = jnp.einsum("ecd,edf->ecf", xf, wi.astype(jnp.float32)) * g
    return jnp.einsum("ecf,efd->ecd", h, wo.astype(jnp.float32))


def _aux_loss(gate_idx, probs, cfg: ModelConfig):
    mo = cfg.moe
    e = probs.shape[-1]
    f_e = jnp.mean(jnp.sum(jax.nn.one_hot(gate_idx, e), axis=1), axis=0)
    p_e = jnp.mean(probs, axis=0)
    return mo.n_experts * jnp.sum(f_e * p_e) * mo.router_aux_coef


def _shared_expert(params, xf):
    sg = jax.nn.silu(jnp.dot(xf.astype(jnp.float32),
                             params["shared_wg"].astype(jnp.float32)))
    sh = jnp.dot(xf.astype(jnp.float32),
                 params["shared_wi"].astype(jnp.float32)) * sg
    s_out = jnp.dot(sh, params["shared_wo"].astype(jnp.float32))
    s_gate = jax.nn.sigmoid(jnp.dot(
        xf.astype(jnp.float32), params["shared_gate"].astype(jnp.float32)))
    return s_gate * s_out


def _apply_local(params, x, *, cfg: ModelConfig):
    """Single-shard dispatch (tests / no mesh)."""
    mo = cfg.moe
    b, s, d = x.shape
    t = b * s
    e = padded_experts(cfg)
    k = mo.top_k
    cap = max(int(t * k / mo.n_experts * mo.capacity_factor), k)
    xf = x.reshape(t, d)
    gate_vals, gate_idx, probs = _route(xf, params["router"], cfg, e)
    slot, keep, token_idx = _dispatch_indices(gate_idx, e, cap)
    buf = jnp.zeros((e * cap + 1, d), xf.dtype).at[slot].set(xf[token_idx])
    expert_out = _expert_mlp(buf[:e * cap].reshape(e, cap, d),
                             params["wi"], params["wg"], params["wo"])
    flat_out = expert_out.reshape(e * cap, d)
    gathered = jnp.where(keep[:, None],
                         flat_out[jnp.minimum(slot, e * cap - 1)], 0.0)
    out = jnp.zeros((t, d), jnp.float32).at[token_idx].add(
        gathered * gate_vals.reshape(t * k, 1))
    if mo.n_shared:
        out = out + _shared_expert(params, xf)
    return (out.reshape(b, s, d).astype(x.dtype),
            _aux_loss(gate_idx, probs, cfg))


def _apply_ep(params, x, *, cfg: ModelConfig, mesh):
    """Expert-parallel shard_map dispatch over the 'model' axis."""
    mo = cfg.moe
    b, s, d = x.shape
    e = padded_experts(cfg)
    n_model = dict(zip(mesh.axis_names, mesh.devices.shape))["model"]
    e_loc = e // n_model
    x_spec = partitioning.resolve(("batch", "seq", "act_embed"),
                                  mesh, shape=x.shape)
    wi_spec = P("model", "data", None)   # (E, d, f): E over EP, d FSDP
    wo_spec = P("model", None, "data")   # (E, f, d)
    rep = P()
    shared = {k: params[k] for k in
              ("shared_wi", "shared_wg", "shared_wo", "shared_gate")
              if k in params}

    def body(xl, router, wi, wg, wo, shared_w):
        bl, sl, _ = xl.shape
        t_l = bl * sl
        xf = xl.reshape(t_l, d)
        gate_vals, gate_idx, probs = _route(xf, router, cfg, e)
        cap = max(int(t_l * mo.top_k / mo.n_experts
                      * mo.capacity_factor), mo.top_k)
        slot, keep, token_idx = _dispatch_indices(gate_idx, e, cap)
        buf = jnp.zeros((e * cap + 1, d), xf.dtype
                        ).at[slot].set(xf[token_idx])
        buf = buf[:e * cap].reshape(e, cap, d)
        # dispatch all-to-all: (E, C, d) -> (E_loc, n_model*C, d)
        recv = jax.lax.all_to_all(buf, "model", split_axis=0,
                                  concat_axis=1, tiled=True)
        # FSDP: gather this layer's expert weights over 'data'
        wi_f = jax.lax.all_gather(wi, "data", axis=1, tiled=True)
        wg_f = jax.lax.all_gather(wg, "data", axis=1, tiled=True)
        wo_f = jax.lax.all_gather(wo, "data", axis=2, tiled=True)
        out_e = _expert_mlp(recv, wi_f, wg_f, wo_f).astype(xf.dtype)
        # return all-to-all: (E_loc, n_model*C, d) -> (E, C, d)
        back = jax.lax.all_to_all(out_e, "model", split_axis=1,
                                  concat_axis=0, tiled=True)
        flat_out = back.reshape(e * cap, d)
        gathered = jnp.where(keep[:, None],
                             flat_out[jnp.minimum(slot, e * cap - 1)], 0.0)
        out = jnp.zeros((t_l, d), jnp.float32).at[token_idx].add(
            gathered * gate_vals.reshape(-1, 1))
        if shared_w:
            out = out + _shared_expert(shared_w, xf)
        # aux from *globally* averaged routing statistics so the value is
        # identical on every shard (and equals the single-device value)
        f_e = jnp.mean(jnp.sum(jax.nn.one_hot(gate_idx, e), axis=1),
                       axis=0)
        p_e = jnp.mean(probs, axis=0)
        for ax in mesh.axis_names:
            f_e = jax.lax.pmean(f_e, ax)
            p_e = jax.lax.pmean(p_e, ax)
        aux = (mo.n_experts * jnp.sum(f_e * p_e) * mo.router_aux_coef)
        return out.reshape(bl, sl, d).astype(xl.dtype), aux

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(x_spec, rep, wi_spec, wi_spec, wo_spec,
                  {k: rep for k in shared}),
        out_specs=(x_spec, rep),
        check_vma=False)
    return fn(x, params["router"], params["wi"], params["wg"],
              params["wo"], shared)


def apply(params, x, *, cfg: ModelConfig):
    """x: (B, S, d) -> (out, aux_loss)."""
    # Weight-only int8 trees: the expert einsums consume the (E, d, f)
    # leaves directly (no ops.matmul in between), so dequantize here.
    if any(quant.is_quantized(params[k]) for k in ("wi", "wg", "wo")):
        params = dict(params)
        for k in ("wi", "wg", "wo"):
            params[k] = quant.resolve_weight(params[k])
    mesh = partitioning.active_mesh()
    e = padded_experts(cfg)
    if mesh is not None and "model" in mesh.axis_names:
        n_model = dict(zip(mesh.axis_names, mesh.devices.shape))["model"]
        if e % n_model == 0:
            return _apply_ep(params, x, cfg=cfg, mesh=mesh)
    return _apply_local(params, x, cfg=cfg)
