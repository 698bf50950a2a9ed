"""Transformer/SSM block assembly from BlockDefs.

A block = pre-norm mixer (+ residual) then pre-norm FFN (+ residual),
with the mixer/FFN kinds taken from the config's stage compilation
(attn / mamba2 / rwkv6 x mlp / moe / rwkv6_cmix / none). All dense ops
route through the row-wise primitive.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import runtime
from repro.core.partitioning import logical_constraint
from repro.core.types import BlockDef, ModelConfig
from repro.kernels import ops
from repro.models import attention, mamba2, mlp, moe, rwkv6


def _norm_init(cfg: ModelConfig, stack, dtype, name="g"):
    d = cfg.d_model
    lead = () if stack is None else (stack,)
    llead = () if stack is None else ("layers",)
    p = {"g": jnp.ones(lead + (d,), dtype)}
    s = {"g": llead + (None,)}
    if cfg.norm == "layer":
        p["b"] = jnp.zeros(lead + (d,), dtype)
        s["b"] = llead + (None,)
    return p, s


def _norm_apply(p, x, cfg: ModelConfig):
    return ops.layernorm(x, p["g"], p.get("b"), kind=cfg.norm)


def _norm_spec(p, cfg: ModelConfig) -> ops.NormSpec:
    return ops.NormSpec(cfg.norm, p["g"], p.get("b"))


def init_block(key, blk: BlockDef, cfg: ModelConfig, stack, dtype):
    ks = jax.random.split(key, 6)
    params, specs = {}, {}
    params["norm1"], specs["norm1"] = _norm_init(cfg, stack, dtype)
    if blk.mixer == "attn":
        params["attn"], specs["attn"] = attention.init(ks[0], cfg, stack,
                                                       dtype)
    elif blk.mixer == "mamba2":
        params["mamba"], specs["mamba"] = mamba2.init(ks[0], cfg, stack,
                                                      dtype)
    elif blk.mixer == "rwkv6":
        params["tmix"], specs["tmix"] = rwkv6.init(ks[0], cfg, stack, dtype)
    if blk.cross_attn:
        params["norm_x"], specs["norm_x"] = _norm_init(cfg, stack, dtype)
        params["cross"], specs["cross"] = attention.init(ks[1], cfg, stack,
                                                         dtype, cross=True)
    if blk.ffn != "none":
        params["norm2"], specs["norm2"] = _norm_init(cfg, stack, dtype)
    if blk.ffn == "mlp":
        params["ffn"], specs["ffn"] = mlp.init(ks[2], cfg, stack, dtype)
    elif blk.ffn == "moe":
        params["ffn"], specs["ffn"] = moe.init(ks[2], cfg, stack, dtype)
    elif blk.ffn == "rwkv6_cmix":
        params["ffn"], specs["ffn"] = mlp.init_cmix(ks[2], cfg, stack,
                                                    dtype)
    return params, specs


class BlockIO(NamedTuple):
    """Everything a block may consume/produce besides the hidden state."""
    aux: jnp.ndarray                      # scalar aux loss accumulator
    new_cache: Any = None                 # decode: updated cache slice
    prefill_state: Any = None             # prefill: (k,v) or mixer state


def apply_block(blk: BlockDef, params, x, *, cfg: ModelConfig, mode: str,
                positions=None, lengths=None, cache=None, enc_out=None,
                pages=None, chunk_len=None, layer=None,
                window_override: Optional[int] = None) -> tuple:
    """mode: 'train' | 'prefill' | 'decode' | 'panel'.
    Returns (x, BlockIO).

    pages: (B, max_pages) int32 block table for paged decode — required
    when the decode cache's KV leaf is a :class:`PagedKVCache` pool.
    layer: the (traced) layer index into that pool's stacked leaves
    (the layer scan carries paged pools whole; :func:`lm._run_stage`).
    'panel' is the serving engine's chunked-prefill and speculative
    verify mode: x is a row panel of tokens at position offset
    ``lengths`` (the tokens already in the paged cache, exactly the
    decode-mode semantics; per row) of which the first ``chunk_len``
    are real; attention layers attend prefix pages + the panel, leave
    the pool untouched and return the panel's (k, v) as
    ``prefill_state``, which the caller writes afterwards
    (:func:`lm.insert_panel`). Only causal-attention archs may take
    panels (``paging.supports_bucketing`` — recurrent mixers would fold
    the split into their state).
    """
    if mode == "panel":
        assert blk.mixer == "attn" and not blk.cross_attn, (
            "chunked prefill requires every position's state to be "
            f"causal-attention KV; {blk.mixer}/cross_attn blocks must "
            "prefill in one shot (paging.supports_bucketing)")
    aux = jnp.zeros((), jnp.float32)
    new_cache = {}
    prefill_state = {}
    window = blk.window if window_override is None else window_override
    # Fused pipeline (DESIGN.md §3): the attn/mlp sublayers take the RAW
    # hidden state plus a NormSpec — the pre-norm runs as the qkv /
    # gate-up kernel prologue and the residual add rides the output
    # projection's epilogue, so neither intermediate exists in HBM.
    fuse = runtime.pipeline_fusion()

    if blk.mixer == "attn":
        nspec = _norm_spec(params["norm1"], cfg) if fuse else None
        h = x if fuse else _norm_apply(params["norm1"], x, cfg)
        res = x if fuse else None
        if mode == "decode":
            if isinstance(cache["kv"], attention.PagedKVCache):
                out, kv_new = attention.paged_decode_apply(
                    params["attn"], h, cache["kv"], cfg=cfg,
                    lengths=lengths, pages=pages, window=window,
                    norm=nspec, residual=res, layer=layer)
            else:
                out, kv_new = attention.decode_apply(
                    params["attn"], h, cache["kv"], cfg=cfg,
                    lengths=lengths, window=window, norm=nspec,
                    residual=res)
            new_cache["kv"] = kv_new
        elif mode == "panel":
            out, (k, v) = attention.paged_panel_apply(
                params["attn"], h, cache["kv"], cfg=cfg, offset=lengths,
                chunk_len=chunk_len, pages=pages, window=window,
                norm=nspec, residual=res, layer=layer)
            prefill_state["kv"] = (k, v)
        else:
            out, (k, v) = attention.apply(params["attn"], h, cfg=cfg,
                                          positions=positions,
                                          window=window, causal=True,
                                          norm=nspec, residual=res)
            if mode == "prefill":
                prefill_state["kv"] = (k, v)
        x = out if fuse else x + out
    elif blk.mixer == "mamba2":
        h = _norm_apply(params["norm1"], x, cfg)
        state = cache["mamba"] if mode == "decode" else None
        out, s_new = mamba2.apply(params["mamba"], h, cfg=cfg, state=state)
        if mode == "decode":
            new_cache["mamba"] = s_new
        elif mode == "prefill":
            prefill_state["mamba"] = s_new
        x = x + out
    elif blk.mixer == "rwkv6":
        h = _norm_apply(params["norm1"], x, cfg)
        state = cache["rwkv_t"] if mode == "decode" else None
        out, (x_last, wkv) = rwkv6.apply(params["tmix"], h, cfg=cfg,
                                         state=state)
        if mode in ("decode", "prefill"):
            st = {"x_prev_t": x_last, "wkv": wkv}
            if mode == "decode":
                new_cache["rwkv_t"] = st
            else:
                prefill_state["rwkv_t"] = st
        x = x + out

    if blk.cross_attn:
        h = _norm_apply(params["norm_x"], x, cfg)
        if mode == "decode":
            # cross K/V are static after prefill; cached as head-layout
            xk, xv = cache["cross_kv"]
            b = h.shape[0]
            hq, hd = cfg.n_heads, cfg.head_dim
            q = ops.matmul(h, params["cross"]["wq"]).reshape(b, 1, hq, hd)
            out = attention.chunked_attention(
                q.transpose(0, 2, 1, 3), xk.transpose(0, 2, 1, 3),
                xv.transpose(0, 2, 1, 3), causal=False, window=0)
            out = out.transpose(0, 2, 1, 3).reshape(b, 1, hq * hd)
            out = ops.matmul(out, params["cross"]["wo"])
            new_cache["cross_kv"] = cache["cross_kv"]
        else:
            out, (ck, cv) = attention.apply(
                params["cross"], h, cfg=cfg, positions=positions,
                causal=False, kv=(enc_out, enc_out))
            if mode == "prefill":
                prefill_state["cross_kv"] = (ck, cv)
        x = x + out

    if blk.ffn != "none":
        if blk.ffn == "mlp":
            if fuse:
                x = mlp.apply(params["ffn"], x, cfg=cfg,
                              norm=_norm_spec(params["norm2"], cfg),
                              residual=x)
            else:
                h = _norm_apply(params["norm2"], x, cfg)
                x = x + mlp.apply(params["ffn"], h, cfg=cfg)
        elif blk.ffn == "moe":
            h = _norm_apply(params["norm2"], x, cfg)
            out, aux_l = moe.apply(params["ffn"], h, cfg=cfg)
            x = x + out
            aux = aux + aux_l
        elif blk.ffn == "rwkv6_cmix":
            h = _norm_apply(params["norm2"], x, cfg)
            state = cache["rwkv_c"] if mode == "decode" else None
            x_last_c = (state["x_prev_c"] if mode == "decode"
                        else jnp.zeros_like(h[:, 0]))
            hp = rwkv6._token_shift(h, x_last_c)
            out = mlp.apply_cmix(params["ffn"], h, hp)
            if mode == "decode":
                new_cache["rwkv_c"] = {"x_prev_c": h[:, -1]}
            elif mode == "prefill":
                prefill_state["rwkv_c"] = {"x_prev_c": h[:, -1]}
            x = x + out
    # keep the scan carry consistently sharded so GSPMD emits the SP
    # reduce-scatter/all-gather pair instead of full all-reduces
    x = logical_constraint(x, "batch", "seq", "act_embed")
    return x, BlockIO(aux=aux, new_cache=new_cache or None,
                      prefill_state=prefill_state or None)
