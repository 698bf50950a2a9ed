"""Stage-compiled language model: init / train forward / prefill / decode.

Layers are *scan-stacked*: per stage, parameters carry a leading repeat
dim and a single ``lax.scan`` executes the whole stage, so HLO size (and
compile time for the 512-device dry-run) is depth-independent.
Heterogeneous stacks (gemma3's 5-local:1-global, zamba2's mamba+shared-
attention) scan over super-block bodies; zamba2's shared block params are
closed over instead of stacked (single weight copy, per the Zamba2
design).
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import quant
from repro.core import partitioning as part
from repro.core.partitioning import logical_constraint
from repro.core.types import ModelConfig, Stage
from repro.kernels import ops
from repro.models import attention, blocks, mamba2, rope
from repro.models.attention import KVCache
from repro.models.initializers import normal

# ----------------------------------------------------------------------
# Init
# ----------------------------------------------------------------------


def padded_vocab(cfg: ModelConfig) -> int:
    """Vocab rounded up to a shardable multiple (MaxText-style padding);
    the pad columns are masked to -inf in the logits."""
    return -(-cfg.vocab // 256) * 256


def _init_stage(key, stage: Stage, cfg: ModelConfig, dtype):
    stacked_p, stacked_s, shared_p, shared_s = {}, {}, {}, {}
    for i, blk in enumerate(stage.body):
        k = jax.random.fold_in(key, i)
        stack = None if blk.shared else stage.repeat
        p, s = blocks.init_block(k, blk, cfg, stack, dtype)
        if blk.shared:
            shared_p[str(i)], shared_s[str(i)] = p, s
        else:
            stacked_p[str(i)], stacked_s[str(i)] = p, s
    return ({"stacked": stacked_p, "shared": shared_p},
            {"stacked": stacked_s, "shared": shared_s})


def init_lm(key, cfg: ModelConfig, dtype=None):
    """Returns (params, logical_specs) with identical tree structure."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 8)
    d = cfg.d_model
    vp = padded_vocab(cfg)
    params: Dict[str, Any] = {
        "embed": normal(ks[0], (vp, d), dtype, 0.02),
    }
    specs: Dict[str, Any] = {"embed": ("vocab", "embed")}
    params["stages"], specs["stages"] = [], []
    for si, stage in enumerate(cfg.stages()):
        p, s = _init_stage(jax.random.fold_in(ks[1], si), stage, cfg, dtype)
        params["stages"].append(p)
        specs["stages"].append(s)
    params["final_norm"] = {"g": jnp.ones((d,), dtype)}
    specs["final_norm"] = {"g": (None,)}
    if cfg.norm == "layer":
        params["final_norm"]["b"] = jnp.zeros((d,), dtype)
        specs["final_norm"]["b"] = (None,)
    if not cfg.tie_embeddings:
        params["lm_head"] = normal(ks[2], (d, vp), dtype, 1 / math.sqrt(d))
        specs["lm_head"] = ("embed", "vocab")
    if cfg.encdec:
        enc_p, enc_s = [], []
        for si, stage in enumerate(cfg.enc_stages()):
            p, s = _init_stage(jax.random.fold_in(ks[3], si), stage, cfg,
                               dtype)
            enc_p.append(p)
            enc_s.append(s)
        fin_p = {"g": jnp.ones((d,), dtype)}
        fin_s = {"g": (None,)}
        if cfg.norm == "layer":
            fin_p["b"] = jnp.zeros((d,), dtype)
            fin_s["b"] = (None,)
        params["enc"] = {"stages": enc_p, "final_norm": fin_p}
        specs["enc"] = {"stages": enc_s, "final_norm": fin_s}
    return params, specs


# ----------------------------------------------------------------------
# Param-layout migration: fused (wqkv / wgi) <-> seed (wq/wk/wv, wg/wi)
# ----------------------------------------------------------------------


def _cat_leaves(leaves):
    """Concatenate sibling projection leaves along the output axis.
    Weight-only int8 leaves fuse exactly: per-output-channel scales are
    per-column, so the fused panel's scales ARE the concatenated parts'
    scales (see quant.quantize_tree)."""
    if quant.is_quantized(leaves[0]):
        return {"q": jnp.concatenate([l["q"] for l in leaves], axis=-1),
                "s": jnp.concatenate([l["s"] for l in leaves], axis=-1)}
    return jnp.concatenate(leaves, axis=-1)


def _split_leaf(leaf, widths):
    """Inverse of :func:`_cat_leaves`."""
    cuts = list(np.cumsum(widths)[:-1])
    if quant.is_quantized(leaf):
        qs = jnp.split(leaf["q"], cuts, axis=-1)
        ss = jnp.split(leaf["s"], cuts, axis=-1)
        return [{"q": q, "s": s} for q, s in zip(qs, ss)]
    return jnp.split(leaf, cuts, axis=-1)


def _migrate_blocks(cfg: ModelConfig, params, block_fn):
    """Apply ``block_fn(blk, block_params) -> block_params`` to every
    block's param dict (stacked and shared groups, decoder and encoder
    stages); returns a new tree, every other leaf untouched."""
    def stage_list(stages_cfg, stages_p):
        new = []
        for stage, sp in zip(stages_cfg, stages_p):
            ns = {"stacked": dict(sp["stacked"]),
                  "shared": dict(sp["shared"])}
            for i, blk in enumerate(stage.body):
                key = str(i)
                group = "shared" if blk.shared else "stacked"
                if key in ns[group]:
                    ns[group][key] = block_fn(blk, ns[group][key])
            new.append(ns)
        return new

    out = dict(params)
    out["stages"] = stage_list(cfg.stages(), params["stages"])
    if cfg.encdec and "enc" in params:
        enc = dict(params["enc"])
        enc["stages"] = stage_list(cfg.enc_stages(),
                                   params["enc"]["stages"])
        out["enc"] = enc
    return out


def fuse_params(cfg: ModelConfig, params):
    """Migrate a seed-layout param tree (split wq/wk/wv, wg/wi leaves —
    PRs 0–3, old checkpoints) to the fused layout ``init_lm`` now
    produces: one ``wqkv`` leaf per self-attention layer, one ``wkv``
    per cross-attention layer, one ``wgi`` per gated MLP. Idempotent;
    exact (pure concatenation, also for weight-only int8 leaves and for
    per-leaf optimizer moments — see ``train.step.fuse_state``)."""
    def block_fn(blk, p):
        p = dict(p)
        if blk.mixer == "attn" and "attn" in p and "wq" in p["attn"]:
            a = dict(p["attn"])
            a["wqkv"] = _cat_leaves([a.pop("wq"), a.pop("wk"),
                                     a.pop("wv")])
            p["attn"] = a
        if blk.cross_attn and "cross" in p and "wk" in p["cross"]:
            c = dict(p["cross"])
            c["wkv"] = _cat_leaves([c.pop("wk"), c.pop("wv")])
            p["cross"] = c
        if (blk.ffn == "mlp" and "ffn" in p and "wg" in p["ffn"]
                and "wi" in p["ffn"]):
            f = dict(p["ffn"])
            f["wgi"] = _cat_leaves([f.pop("wg"), f.pop("wi")])
            p["ffn"] = f
        return p

    return _migrate_blocks(cfg, params, block_fn)


def unfuse_params(cfg: ModelConfig, params):
    """Inverse of :func:`fuse_params`: recover the seed's split layout
    (e.g. to restore INTO an old checkpoint's tree structure, or to
    export one). ``fuse_params(cfg, unfuse_params(cfg, p))`` is the
    identity."""
    qo, kvo, _ = attention.proj_splits(cfg)

    def block_fn(blk, p):
        p = dict(p)
        if blk.mixer == "attn" and "attn" in p and "wqkv" in p["attn"]:
            a = dict(p["attn"])
            a["wq"], a["wk"], a["wv"] = _split_leaf(a.pop("wqkv"),
                                                    (qo, kvo, kvo))
            p["attn"] = a
        if blk.cross_attn and "cross" in p and "wkv" in p["cross"]:
            c = dict(p["cross"])
            c["wk"], c["wv"] = _split_leaf(c.pop("wkv"), (kvo, kvo))
            p["cross"] = c
        if blk.ffn == "mlp" and "ffn" in p and "wgi" in p["ffn"]:
            f = dict(p["ffn"])
            wgi = f.pop("wgi")
            half = (wgi["q"] if quant.is_quantized(wgi)
                    else wgi).shape[-1] // 2
            f["wg"], f["wi"] = _split_leaf(wgi, (half, half))
            p["ffn"] = f
        return p

    return _migrate_blocks(cfg, params, block_fn)


# ----------------------------------------------------------------------
# Stage execution
# ----------------------------------------------------------------------


def _is_pool(leaf) -> bool:
    return isinstance(leaf, attention.PagedKVCache)


def _run_stage(stage: Stage, sp, x, *, cfg: ModelConfig, mode: str,
               positions=None, lengths=None, cache=None, enc_out=None,
               pages=None, chunk_len=None, causal=True, remat=False):
    """Scan a stage. Returns (x, aux, new_cache_or_prefill_states).
    ``pages`` (the serving block table) is scan-invariant: every layer
    indexes its own pool through the same per-slot table.

    Paged KV pools ride in the scan *carry*, stacked (R, P, ps, Hkv,
    hd), and each layer gathers and scatters its own rows through the
    scanned layer index; with the cache donated the scatter updates the
    while loop's buffer in place. Scanned as ``xs``/``ys`` instead, each
    step would copy every layer's pool out and write it back whole.
    'panel' mode only reads the pools; its caller writes the panel's
    K/V after the scan (:func:`insert_panel`). The other cache leaves
    (dense KV, recurrent state, cross-KV) are per-layer inputs and
    outputs of the scan."""
    stacked, shared = sp["stacked"], sp["shared"]
    cache = cache or {}
    pools = {key: {n: c for n, c in entry.items() if _is_pool(c)}
             for key, entry in cache.items()}
    pools = {key: p for key, p in pools.items() if p}
    per_layer = {key: {n: c for n, c in entry.items() if not _is_pool(c)}
                 for key, entry in cache.items()}

    def body(carry, xs):
        x, aux, pools = carry
        layer, sliced, cache_slice = xs
        pools = dict(pools)
        out_states = {}
        for i, blk in enumerate(stage.body):
            key = str(i)
            bp = sliced[key] if key in sliced else shared[key]
            csl = {**cache_slice.get(key, {}), **pools.get(key, {})} or None
            x, io = blocks.apply_block(
                blk, bp, x, cfg=cfg, mode=mode, positions=positions,
                lengths=lengths, cache=csl, enc_out=enc_out, pages=pages,
                chunk_len=chunk_len, layer=layer,
                window_override=None if causal else 0)
            aux = aux + io.aux
            if mode == "decode" and io.new_cache is not None:
                new = dict(io.new_cache)
                if key in pools:
                    pools[key] = {n: new.pop(n) for n in pools[key]}
                out_states[key] = new
            elif (mode in ("prefill", "panel")
                    and io.prefill_state is not None):
                out_states[key] = io.prefill_state
        return (x, aux, pools), out_states

    if remat and mode == "train":
        body = jax.checkpoint(body, prevent_cse=False)

    (x, aux, pools), states = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32), pools),
        (jnp.arange(stage.repeat), stacked, per_layer), length=stage.repeat)
    if mode == "decode":
        for key, p in pools.items():
            states[key] = {**states.get(key, {}), **p}
    return x, aux, states


def _run_stages(stage_params, stages, x, *, cache=None, **kw):
    aux_total = jnp.zeros((), jnp.float32)
    all_states = []
    for si, (stage, sp) in enumerate(zip(stages, stage_params)):
        stage_cache = cache[si] if cache is not None else None
        x, aux, states = _run_stage(stage, sp, x, cache=stage_cache, **kw)
        aux_total = aux_total + aux
        all_states.append(states)
    return x, aux_total, all_states


# ----------------------------------------------------------------------
# Embedding / logits
# ----------------------------------------------------------------------


def embed(params, tokens, cfg: ModelConfig, extra: Optional[dict] = None):
    w = params["embed"]
    if quant.is_quantized(w):
        # weight-only int8 tree: gather int8 rows, then dequantize only
        # the gathered (B, S, d) block by the per-column scales
        x = (jnp.take(w["q"], tokens, axis=0).astype(jnp.float32)
             * w["s"]).astype(jnp.dtype(cfg.dtype))
    else:
        x = jnp.take(w, tokens, axis=0)
    if cfg.frontend == "vision" and extra and "vis_embeds" in extra:
        ve = extra["vis_embeds"].astype(x.dtype)
        x = jax.lax.dynamic_update_slice(x, ve, (0, 0, 0))
    return x


def unembed(params, x, cfg: ModelConfig):
    x = ops.layernorm(x, params["final_norm"]["g"],
                      params["final_norm"].get("b"), kind=cfg.norm)
    tp = part.tp_axis()
    if cfg.tie_embeddings:
        # tied embeddings stay replicated under TP (the embed gather
        # needs every row anyway), so the logits are already full-width
        w = quant.resolve_weight(params["embed"], x.dtype).T
        logits = ops.matmul(x, w, out_dtype=jnp.float32)
    elif tp is not None:
        # vocab-sharded lm_head: each shard computes its contiguous
        # logit block exactly (pure N-split, bitwise identical columns),
        # then a tiled all-gather rebuilds the full row — the pad mask
        # below must see GLOBAL column indices, hence gather-first
        logits = jax.lax.all_gather(
            ops.matmul(x, params["lm_head"], out_dtype=jnp.float32),
            tp, axis=x.ndim - 1, tiled=True)
    else:
        logits = ops.matmul(x, params["lm_head"], out_dtype=jnp.float32)
    vp = padded_vocab(cfg)
    if vp != cfg.vocab:  # mask pad columns out of the softmax
        col = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                       logits.ndim - 1)
        logits = jnp.where(col < cfg.vocab, logits, -1e30)
    return logical_constraint(logits, "batch", "seq", "vocab_act")


def _positions(cfg: ModelConfig, tokens, extra):
    b, s = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    if cfg.rope == "mrope" and extra and "positions3" in extra:
        return extra["positions3"]
    return pos


def encode(params, frames, cfg: ModelConfig):
    """Whisper encoder: precomputed frame embeddings (B, T, d)."""
    x = frames + rope.sinusoidal_embedding(
        frames.shape[1], cfg.d_model).astype(frames.dtype)[None]
    x, _, _ = _run_stages(params["enc"]["stages"], cfg.enc_stages(), x,
                          cfg=cfg, mode="train", positions=None,
                          causal=False, remat=True)
    fn = params["enc"]["final_norm"]
    return ops.layernorm(x, fn["g"], fn.get("b"), kind=cfg.norm)


def forward(params, tokens, cfg: ModelConfig, *,
            extra: Optional[dict] = None, remat: bool = True):
    """Full train-mode forward -> (logits, aux_loss)."""
    x = embed(params, tokens, cfg, extra)
    x = logical_constraint(x, "batch", "seq", "act_embed")
    if cfg.rope == "none" and not cfg.encdec:
        x = x + rope.sinusoidal_embedding(
            x.shape[1], cfg.d_model).astype(x.dtype)[None]
    enc_out = None
    if cfg.encdec:
        assert extra is not None and "frames" in extra
        enc_out = encode(params, extra["frames"], cfg)
        x = x + rope.sinusoidal_embedding(
            x.shape[1], cfg.d_model).astype(x.dtype)[None]
    positions = _positions(cfg, tokens, extra)
    x, aux, _ = _run_stages(params["stages"], cfg.stages(), x, cfg=cfg,
                            mode="train", positions=positions,
                            enc_out=enc_out, remat=remat)
    return unembed(params, x, cfg), aux


def loss_fn(params, batch, cfg: ModelConfig, *, remat: bool = True):
    """Cross-entropy next-token loss -> (loss, metrics)."""
    logits, aux = forward(params, batch["tokens"], cfg,
                          extra={k: v for k, v in batch.items()
                                 if k not in ("tokens", "labels")} or None,
                          remat=remat)
    labels = batch["labels"]
    valid = (labels >= 0)
    safe = jnp.maximum(labels, 0)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
    nll = (lse - gold) * valid
    ntok = jnp.maximum(valid.sum(), 1)
    loss = nll.sum() / ntok
    metrics = {"loss": loss, "aux_loss": aux, "ntokens": ntok,
               "accuracy": ((jnp.argmax(logits, -1) == safe) * valid
                            ).sum() / ntok}
    return loss + aux, metrics


# ----------------------------------------------------------------------
# KV / SSM cache: init, specs, prefill conversion
# ----------------------------------------------------------------------


def _slot_cache_init(blk, cfg: ModelConfig, repeat, batch, alloc, dtype,
                     pool=None):
    c = {}
    if blk.mixer == "attn":
        if pool is not None:
            # paged serving: (R, n_pages + n_slots scratch, ps, Hkv, hd)
            n_pages, ps = pool
            shape = (repeat, n_pages + batch, ps, cfg.n_kv_heads,
                     cfg.head_dim)
            c["kv"] = attention.PagedKVCache(k=jnp.zeros(shape, dtype),
                                             v=jnp.zeros(shape, dtype))
        else:
            w = blk.window
            s_alloc = min(alloc, w) if w else alloc
            shape = (repeat, batch, s_alloc, cfg.n_kv_heads, cfg.head_dim)
            c["kv"] = KVCache(k=jnp.zeros(shape, dtype),
                              v=jnp.zeros(shape, dtype))
    elif blk.mixer == "mamba2":
        st = mamba2.init_state(cfg, batch, dtype)
        c["mamba"] = jax.tree.map(
            lambda a: jnp.zeros((repeat,) + a.shape, a.dtype), st)
    elif blk.mixer == "rwkv6":
        r = cfg.rwkv
        h = cfg.d_model // r.head_dim
        c["rwkv_t"] = {
            "x_prev_t": jnp.zeros((repeat, batch, cfg.d_model), dtype),
            "wkv": jnp.zeros((repeat, batch, h, r.head_dim, r.head_dim),
                             jnp.float32)}
    if blk.cross_attn:
        shape = (repeat, batch, cfg.cross_len, cfg.n_kv_heads, cfg.head_dim)
        c["cross_kv"] = (jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))
    if blk.ffn == "rwkv6_cmix":
        c["rwkv_c"] = {"x_prev_c": jnp.zeros((repeat, batch, cfg.d_model),
                                             dtype)}
    return c


def _init_cache_tree(cfg: ModelConfig, batch, alloc, dtype, pool=None):
    out = []
    for stage in cfg.stages():
        sc = {}
        for i, blk in enumerate(stage.body):
            c = _slot_cache_init(blk, cfg, stage.repeat, batch, alloc,
                                 dtype, pool=pool)
            if c:
                sc[str(i)] = c
        out.append(sc)
    return out


def init_cache(cfg: ModelConfig, batch: int, alloc: int, dtype=None):
    """Zeroed cache for standalone decode (the decode dry-run cells)."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    return _init_cache_tree(cfg, batch, alloc, dtype)


def init_paged_cache(cfg: ModelConfig, n_slots: int, max_len: int, *,
                     page_size: int = 16, n_pages: int = 0, dtype=None):
    """Serving cache with paged attention KV: every attention layer gets
    a page pool ``(R, n_pages + n_slots, page_size, Hkv, hd)`` indexed
    by the engine's block tables (the ``+ n_slots`` are per-slot
    *scratch* pages idle and mid-prefill slots write to — private rows,
    so lockstep writes from idle slots never serialize on one shared
    page); recurrent / cross-attention state stays per-slot dense.

    ``n_pages == 0`` sizes the pool for full occupancy
    (``n_slots * ceil(max_len / page_size)`` real pages); pass less to
    oversubscribe. Sliding windows must be page-aligned
    (``window % page_size == 0``) so ring pages tile exactly.
    """
    dtype = dtype or jnp.dtype(cfg.dtype)
    max_pages = -(-max_len // page_size)
    n_pages = n_pages or n_slots * max_pages
    for stage in cfg.stages():
        for blk in stage.body:
            if blk.mixer == "attn" and blk.window:
                assert blk.window % page_size == 0, (
                    f"sliding window {blk.window} must be a multiple of "
                    f"page_size {page_size}")
    return _init_cache_tree(cfg, n_slots, max_len, dtype,
                            pool=(n_pages, page_size))


def cache_logical_specs(cache):
    """Logical sharding names for every cache leaf (layer, batch, seq...).
    Dense caches only — paged pools are engine-local (single host)."""
    def spec(leaf):
        names = [None] * leaf.ndim
        names[0] = "layers"
        if leaf.ndim >= 2:
            names[1] = "batch"
        if leaf.ndim == 5:           # (R, B, S, kv_heads, hd)
            names[2] = "kv_seq"
            names[3] = "kv_heads"
        return tuple(names)

    return jax.tree.map(spec, cache)


def _ring_from_prefill(k, window):
    """Convert stacked prefill states (R,B,S,H,hd) to a ring buffer of
    size `window` holding the last `window` tokens at slots p % window."""
    s = k.shape[2]
    if s <= window:
        pad = [(0, 0)] * k.ndim
        pad[2] = (0, window - s)
        return jnp.pad(k, pad)
    p = jnp.arange(s - window, s)
    order = jnp.argsort(p % window)
    return jnp.take(k, p[order], axis=2)


def states_to_cache(cfg: ModelConfig, all_states, alloc: int):
    """Prefill scan outputs -> decode cache (pads KV to alloc)."""
    out = []
    for stage, states in zip(cfg.stages(), all_states):
        sc = {}
        for i, blk in enumerate(stage.body):
            st = states.get(str(i))
            if st is None:
                continue
            c = {}
            if "kv" in st:
                k, v = st["kv"]
                if blk.window:
                    k = _ring_from_prefill(k, blk.window)
                    v = _ring_from_prefill(v, blk.window)
                else:
                    pad = [(0, 0)] * k.ndim
                    pad[2] = (0, alloc - k.shape[2])
                    k, v = jnp.pad(k, pad), jnp.pad(v, pad)
                c["kv"] = KVCache(k=k, v=v)
            if "mamba" in st:
                c["mamba"] = st["mamba"]
            if "rwkv_t" in st:
                c["rwkv_t"] = st["rwkv_t"]
            if "rwkv_c" in st:
                c["rwkv_c"] = st["rwkv_c"]
            if "cross_kv" in st:
                c["cross_kv"] = st["cross_kv"]
            sc[str(i)] = c
        out.append(sc)
    return out


def prefill_states(params, tokens, cfg: ModelConfig, *,
                   extra: Optional[dict] = None, last_pos=None):
    """Full-sequence prefill -> (logits, raw per-layer scan states).

    ``last_pos`` ((B,) int32) supports *bucketed* prefill: tokens are
    right-padded to a static bucket length and the logits are gathered
    at position ``last_pos - 1`` (the last real token). Causal attention
    keeps every real position's activations and KV states untouched by
    the tail padding; the pad tokens' own KV is dropped downstream by
    the block-table length bookkeeping. Recurrent mixers (mamba/rwkv)
    fold padding into their state, so recurrent archs must prefill at
    exact lengths (``last_pos=None``).
    """
    b, s = tokens.shape
    x = embed(params, tokens, cfg, extra)
    x = logical_constraint(x, "batch", "seq", "act_embed")
    if cfg.rope == "none" and not cfg.encdec:
        x = x + rope.sinusoidal_embedding(s, cfg.d_model).astype(
            x.dtype)[None]
    enc_out = None
    if cfg.encdec:
        enc_out = encode(params, extra["frames"], cfg)
        x = x + rope.sinusoidal_embedding(s, cfg.d_model).astype(
            x.dtype)[None]
    positions = _positions(cfg, tokens, extra)
    x, _, states = _run_stages(params["stages"], cfg.stages(), x, cfg=cfg,
                               mode="prefill", positions=positions,
                               enc_out=enc_out, remat=False)
    if last_pos is None:
        xl = x[:, -1:]
    else:
        idx = (jnp.asarray(last_pos, jnp.int32) - 1)[:, None, None]
        xl = jnp.take_along_axis(
            x, jnp.broadcast_to(idx, (b, 1, x.shape[-1])), axis=1)
    logits = unembed(params, xl, cfg)
    return logits[:, 0], states


def prefill(params, tokens, cfg: ModelConfig, *,
            extra: Optional[dict] = None, alloc: Optional[int] = None):
    """Full-sequence prefill -> (last-position logits, dense cache)."""
    logits, states = prefill_states(params, tokens, cfg, extra=extra)
    return logits, states_to_cache(cfg, states, alloc or tokens.shape[1])


# ----------------------------------------------------------------------
# Paged prefill insert (the serving engine's slot-admission write)
# ----------------------------------------------------------------------


def _insert_slot(dst, src, slot):
    """Write a (R, 1, ...) prefill state into batch row ``slot`` of a
    (R, B, ...) per-slot cache leaf."""
    starts = (0, slot) + (0,) * (dst.ndim - 2)
    return jax.lax.dynamic_update_slice(dst, src.astype(dst.dtype), starts)


def _insert_pages(pool, k, v, *, pages, plen, window, page_size):
    """Scatter prefilled KV states (R, 1, S_pad, Hkv, hd) into the
    slot's pages. Positions >= plen (padding) and, for windowed layers,
    < plen - window (evicted from the ring) route out of range and are
    dropped; stale rows left in a partial tail page are masked at read
    time by the kv_len bookkeeping."""
    ps = page_size
    s_pad = k.shape[2]
    p = jnp.arange(s_pad)
    valid = p < plen
    r = p
    if window:
        valid = valid & (p >= plen - window)
        r = p % window
    lp = jnp.clip(r // ps, 0, pages.shape[0] - 1)
    pid = jnp.where(valid, pages[lp], pool.k.shape[1])   # OOB => dropped
    off = r % ps
    new_k = pool.k.at[:, pid, off].set(
        k[:, 0].astype(pool.k.dtype), mode="drop")
    new_v = pool.v.at[:, pid, off].set(
        v[:, 0].astype(pool.v.dtype), mode="drop")
    return attention.PagedKVCache(k=new_k, v=new_v)


def insert_prefill(cfg: ModelConfig, cache, states, *, slot, pages, plen,
                   page_size: int):
    """Insert a single-request prefill into a paged serving cache: the
    explicit replacement for the old shape-guessing ``_scatter_slot``
    tree-map. Attention KV states scatter into the pages the engine
    granted the slot (``pages``: (max_pages,) physical ids); recurrent /
    cross-attention state writes batch row ``slot``. ``slot`` and
    ``plen`` may be traced scalars, so one compiled program serves every
    slot at a given bucket length.

    Shared-page contract (PR 8): one-shot prefill scatters the *whole*
    prompt, so the engine only routes through here on a prefix-cache
    miss — every granted page is slot-private (refcount 1). Cache hits
    take the chunked path, which starts past the shared pages."""
    out = []
    for si, stage in enumerate(cfg.stages()):
        sc = {}
        for i, blk in enumerate(stage.body):
            key = str(i)
            cur = (cache[si] or {}).get(key)
            if cur is None:
                continue
            st = (states[si] or {}).get(key) or {}
            c = dict(cur)
            if "kv" in st:
                k, v = st["kv"]
                c["kv"] = _insert_pages(cur["kv"], k, v, pages=pages,
                                        plen=plen, window=blk.window,
                                        page_size=page_size)
            for name in ("mamba", "rwkv_t", "rwkv_c", "cross_kv"):
                if name in st:
                    c[name] = jax.tree.map(
                        lambda d, s: _insert_slot(d, s, slot),
                        cur[name], st[name])
            sc[key] = c
        out.append(sc)
    return out


def _panel_forward(params, cache, tokens, cfg: ModelConfig, *, offset,
                   chunk_len, pages):
    """Shared forward of :func:`prefill_chunk` and :func:`verify_states`:
    a (B, Sc) row panel at absolute positions ``offset + i`` attends
    each slot's written KV pages plus the panel itself
    (``attention.paged_panel_apply``), with the pools read-only.
    ``offset``/``chunk_len``: scalar or per-row (B,) int32 (tokens
    already in the cache / real panel rows; right padding is masked).
    Returns (hidden states (B, Sc, d), per-layer panel KV states)."""
    b, s = tokens.shape
    offset = jnp.broadcast_to(jnp.asarray(offset, jnp.int32), (b,))
    x = embed(params, tokens, cfg, None)
    x = logical_constraint(x, "batch", "seq", "act_embed")
    if cfg.rope == "none" and not cfg.encdec:
        pe = rope.sinusoidal_embedding(1 << 16, cfg.d_model)
        pos = offset[:, None] + jnp.arange(s, dtype=jnp.int32)[None]
        x = x + pe[pos].astype(x.dtype)
    x, _, states = _run_stages(params["stages"], cfg.stages(), x,
                               cfg=cfg, mode="panel", positions=None,
                               lengths=offset, cache=cache, pages=pages,
                               chunk_len=chunk_len, remat=False)
    return x, states


def prefill_chunk(params, cache, tokens, cfg: ModelConfig, *, offset,
                  chunk_len, pages):
    """Chunked-prefill step: one ``prefill_states``-style forward over a
    row panel of the prompt, resumable across engine steps.

    tokens: (1, Sc_pad) — a chunk of a longer prompt starting at
    absolute position ``offset`` (traced scalar; tokens already in the
    paged cache), right-padded to a static chunk shape with the true
    length in ``chunk_len`` (traced, <= Sc_pad). Every attention layer
    attends the slot's already-written KV pages plus the in-flight
    chunk (:func:`_panel_forward`); then every layer's chunk KV is
    appended at the position offset (:func:`insert_panel`), so
    successive calls rebuild exactly the KV state one-shot prefill +
    ``insert_prefill`` would have written. Writing after the whole scan
    puts each layer's write after its prefix gather by data dependence,
    which lets XLA update the pools in place.
    Returns (next-token logits (1, V) at chunk position chunk_len - 1,
    new_cache). Only causal-attention archs may chunk (the engine gates
    on ``paging.supports_bucketing``); the final chunk's logits are the
    prompt's first-token logits.
    """
    b = tokens.shape[0]
    x, states = _panel_forward(params, cache, tokens, cfg, offset=offset,
                               chunk_len=chunk_len, pages=pages)
    idx = (jnp.asarray(chunk_len, jnp.int32) - 1)[None, None, None]
    xl = jnp.take_along_axis(
        x, jnp.broadcast_to(idx, (b, 1, x.shape[-1])), axis=1)
    logits = unembed(params, xl, cfg)
    cache = insert_panel(cfg, cache, states, pages=pages, offset=offset,
                         n_keep=chunk_len)
    return logits[:, 0], cache


def verify_states(params, cache, tokens, cfg: ModelConfig, *, offset,
                  chunk_len, pages):
    """Speculative-verify forward (the batched, read-only sibling of
    :func:`prefill_chunk`): score a (B, Sc) panel — each slot's last
    committed token plus its draft tokens, right-padded to the static
    ladder width — against the paged cache, WITHOUT writing the panel's
    KV. ``offset``/``chunk_len``: per-row (B,) int32 (tokens already in
    the cache / real panel rows, ``1 + k_b``; 0 rows are fully masked).
    Returns (full panel logits (B, Sc, V), per-layer panel KV states) —
    logits, not a gathered position, because acceptance needs every
    panel position's distribution; the caller then writes only accepted
    rows via :func:`insert_panel`. The split mirrors the
    ``prefill_states`` / ``insert_prefill`` pair: forward first, commit
    separately. Only causal-attention archs verify (the engine gates on
    ``paging.supports_bucketing``)."""
    x, states = _panel_forward(params, cache, tokens, cfg, offset=offset,
                               chunk_len=chunk_len, pages=pages)
    return unembed(params, x, cfg), states


def insert_panel(cfg: ModelConfig, cache, states, *, pages, offset,
                 n_keep):
    """Write panel KV states into the paged cache: every attention layer
    scatters its panel rows ``< n_keep`` (scalar or per-row: a chunk's
    real rows, or the re-scored committed token plus the accepted
    drafts; ``n_keep == 0`` writes nothing — inactive or
    fully-rolled-back slots). The layer walk mirrors
    :func:`insert_prefill`; panel states only ever hold attention KV
    (chunking and verify require a bucketing-capable, attention-only
    arch). A loop over the stacked layer axis scatters each layer's rows
    with :func:`attention.write_chunk_pages` (windowed ring routing
    included) into the stacked pool, in place."""
    out = []
    for si, stage in enumerate(cfg.stages()):
        sc = {}
        for i, blk in enumerate(stage.body):
            key = str(i)
            cur = (cache[si] or {}).get(key)
            if cur is None:
                continue
            st = (states[si] or {}).get(key) or {}
            c = dict(cur)
            if "kv" in st:
                k, v = st["kv"]

                def wr(layer, pool, k=k, v=v, window=blk.window):
                    return attention.write_chunk_pages(
                        pool, k[layer], v[layer], offset, n_keep, pages,
                        window, layer=layer)

                c["kv"] = jax.lax.fori_loop(0, stage.repeat, wr, cur["kv"])
            sc[key] = c
        out.append(sc)
    return out


def cow_copy(cache, src, dst):
    """Copy-on-write page copy across every paged attention layer:
    physical page ``src``'s K/V rows land in page ``dst`` (traced int32
    scalars; see :func:`attention.copy_page`). ``src == dst`` is the
    identity, which is how the engine folds the copy into every chunk
    step — non-COW chunks pass ``(0, 0)`` and compile the same program.
    Non-attention state (recurrent, cross-KV) is untouched."""
    return jax.tree.map(
        lambda c: attention.copy_page(c, src, dst) if _is_pool(c) else c,
        cache, is_leaf=_is_pool)


def decode_step(params, cache, tokens, lengths, cfg: ModelConfig,
                pages=None):
    """One decode step. tokens: (B, 1); lengths: (B,) tokens in cache.
    Returns (logits (B, vocab), new_cache). ``pages`` ((B, max_pages)
    int32 block tables) is required when ``cache`` holds paged KV pools
    (see :func:`init_paged_cache`); every layer indexes its own pool
    through the same table."""
    x = embed(params, tokens, cfg, None)
    if cfg.rope == "none" or cfg.encdec:
        pe = rope.sinusoidal_embedding(1 << 16, cfg.d_model)
        x = x + pe[lengths][:, None].astype(x.dtype)
    x, _, new_cache = _run_stages(params["stages"], cfg.stages(), x,
                                  cfg=cfg, mode="decode", positions=None,
                                  lengths=lengths, cache=cache,
                                  pages=pages, remat=False)
    logits = unembed(params, x, cfg)
    return logits[:, 0], new_cache
