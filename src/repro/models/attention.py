"""Attention layer: GQA/MQA, RoPE/M-RoPE, sliding windows, KV caches.

Three execution paths, all funneling the projections through the
row-wise matmul primitive (the paper's unification):

  * ``dense``   — materialized scores; small sequences / smoke tests.
  * ``chunked`` — jnp online-softmax scan over KV blocks; sub-quadratic
                  memory; what the dry-run lowers (flash-equivalent HLO).
  * ``pallas``/``interpret`` — the row-wise flash kernel.

Decode uses a flash-decode formulation (chunked over the cache with a
running log-sum-exp), optionally sequence-sharded over the model axis
via shard_map with a psum LSE combine (see serve/).
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import runtime
from repro.core import partitioning as part
from repro.core.partitioning import logical_constraint
from repro.core.types import ModelConfig
from repro.kernels import ops
from repro.models import rope as rope_lib
from repro.models.initializers import normal

DENSE_MAX_SEQ = 2048      # above this, 'ref' impl switches to chunked


def proj_splits(cfg: ModelConfig):
    """(q, k, v) output widths inside the fused ``wqkv`` panel."""
    qo = cfg.n_heads * cfg.head_dim
    kvo = cfg.n_kv_heads * cfg.head_dim
    return (qo, kvo, kvo)


def init(key, cfg: ModelConfig, stack: Optional[int], dtype,
         cross: bool = False):
    """Returns (params, logical_specs). stack=None => unstacked (shared).

    Projection weights are stored PRE-FUSED (DESIGN.md §5): self
    attention keeps one ``wqkv`` (d, (Hq + 2*Hkv) * hd) leaf — q, k and
    v column panels concatenated at init time, so the serving hot path
    never pays a per-call weight concatenate. Cross attention (whisper)
    projects q from the decoder stream but k/v from the encoder output,
    so it keeps ``wq`` separate and fuses the encoder-side pair into
    one ``wkv`` (d, 2*Hkv*hd) leaf. ``lm.unfuse_params`` recovers the
    seed's split layout (checkpoint migration).
    """
    d, hd = cfg.d_model, cfg.head_dim
    qo, kvo = cfg.n_heads * hd, cfg.n_kv_heads * hd
    lead = () if stack is None else (stack,)
    llead = () if stack is None else ("layers",)
    ks = jax.random.split(key, 4)

    def w(k, din, dout, scale=1.0):
        return normal(k, lead + (din, dout), dtype, scale / math.sqrt(din))

    if cross:
        params = {"wq": w(ks[0], d, qo), "wkv": w(ks[1], d, 2 * kvo),
                  "wo": w(ks[3], qo, d)}
        specs = {"wq": llead + ("embed", "qkv"),
                 "wkv": llead + ("embed", "qkv"),
                 "wo": llead + ("qkv", "embed")}
    else:
        params = {"wqkv": w(ks[0], d, qo + 2 * kvo), "wo": w(ks[3], qo, d)}
        specs = {"wqkv": llead + ("embed", "qkv"),
                 "wo": llead + ("qkv", "embed")}
    return params, specs


def _out_proj(out, wo, residual):
    """Output projection, TP-aware (serve/placement.py). Under a
    tensor-parallel shard context ``wo`` is row-sharded (each shard
    holds the head group it attended), so the matmul yields a K-partial
    sum that must psum over the TP axis BEFORE the residual rides on —
    a residual folded into the kernel epilogue would be summed once per
    shard. Outside TP this is exactly the fused epilogue path."""
    if part.tp_axis() is None:
        return ops.matmul(out, wo, residual=residual)
    y = part.tp_reduce(ops.matmul(out, wo))
    return y if residual is None else y + residual


class KVCache(NamedTuple):
    """Per-layer KV cache. k/v: (B, S_alloc, Hkv, hd).

    For sliding-window layers S_alloc == window and writes wrap around
    (ring buffer); ``length`` tracking lives with the serving state.
    """
    k: jnp.ndarray
    v: jnp.ndarray


def init_cache(cfg: ModelConfig, batch: int, alloc_len: int, dtype,
               window: int = 0):
    s = min(alloc_len, window) if window else alloc_len
    shape = (batch, s, cfg.n_kv_heads, cfg.head_dim)
    return KVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype))


def cache_specs(window: int = 0):
    names = ("batch", "kv_seq", "kv_heads", None)
    return KVCache(k=names, v=names)


class PagedKVCache(NamedTuple):
    """Per-layer paged KV pool. k/v: (n_pages + n_slots, page_size,
    Hkv, hd).

    Physical pages are shared by every slot in the serving batch; the
    logical order of a slot's tokens lives in the engine's block table
    ((B, max_pages) int32: logical page ``l`` of row ``b`` is physical
    page ``table[b, l]``). The last ``n_slots`` physical pages are
    per-slot scratch pages — idle and mid-prefill slots' tables point
    at their own row so lockstep writes from those slots never touch
    live storage (and never serialize on one shared page).
    Sliding-window layers reuse the first ``window // page_size`` table
    entries as a ring of pages.
    """
    k: jnp.ndarray
    v: jnp.ndarray


def _apply_rope(q, k, cfg: ModelConfig, positions):
    if cfg.rope == "none":
        return q, k
    if cfg.rope == "mrope":
        if positions.ndim == 2:            # text-only: (B,S) -> (3,B,S)
            positions = rope_lib.text_positions3(positions)
        q = rope_lib.apply_mrope(q, positions, cfg.rope_theta)
        k = rope_lib.apply_mrope(k, positions, cfg.rope_theta)
    else:
        q = rope_lib.apply_rope(q, positions, cfg.rope_theta)
        k = rope_lib.apply_rope(k, positions, cfg.rope_theta)
    return q, k


def _chunk_mask(base, chunk, q_pos, limit, causal, window):
    """(B,1,1,Sq,chunk) validity mask for one KV chunk."""
    k_pos = base + jnp.arange(chunk)                           # (chunk,)
    mask = (k_pos[None, :] < limit[:, None])[:, None, None, None, :]
    if causal:
        mask = jnp.logical_and(mask,
                               (k_pos[None, :] <= q_pos)[None, None, None])
    if window > 0:
        mask = jnp.logical_and(
            mask, (k_pos[None, :] > q_pos - window)[None, None, None])
    return mask


def _online_update(carry, qg, kb, vb, mask, scale):
    """One online-softmax accumulation step over a KV chunk — the shared
    row-wise LSE math of the dense-chunk and page-gather paths.

    carry: (m, l, acc) running max / denominator / output accumulator;
    qg: (B,Hkv,g,Sq,hd); kb/vb: (B,Hkv,chunk,hd); mask broadcastable to
    the (B,Hkv,g,Sq,chunk) score shape. q/k stay in model dtype; the
    MXU accumulates in f32 (no materialized f32 operand copies).
    """
    m, l, acc = carry
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qg, kb,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask, s, -1e30)
    m_new = jnp.maximum(m, jnp.max(s, -1))
    p = jnp.where(mask, jnp.exp(s - m_new[..., None]), 0.0)
    alpha = jnp.exp(m - m_new)
    l_new = l * alpha + jnp.sum(p, -1)
    acc_new = acc * alpha[..., None] + jnp.einsum(
        "bhgqk,bhkd->bhgqd", p.astype(vb.dtype), vb,
        preferred_element_type=jnp.float32)
    return m_new, l_new, acc_new


def _chunked_fwd(q, k, v, limit, *, causal, window, q_offset, chunk):
    """Returns (out (B,Hq,Sq,hd), lse (B,Hkv,g,Sq) fp32)."""
    b, hq, sq, hd = q.shape
    _, hkv, skv, _ = k.shape
    g = hq // hkv
    chunk = min(chunk, skv)
    pad = (-skv) % chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    nc = (skv + pad) // chunk
    kc = k.reshape(b, hkv, nc, chunk, hd).transpose(2, 0, 1, 3, 4)
    vc = v.reshape(b, hkv, nc, chunk, hd).transpose(2, 0, 1, 3, 4)
    qg = q.reshape(b, hkv, g, sq, hd)
    scale = hd ** -0.5
    q_pos = q_offset + jnp.arange(sq)[:, None]                 # (Sq,1)

    def step(carry, inp):
        # NB: the chunk base position rides in the carry (not the xs) so
        # XLA cannot hoist/stack the position masks for every chunk — the
        # hoisted form materializes a full Sq x Skv mask in HBM.
        m, l, acc, base = carry
        kb, vb = inp
        mask = _chunk_mask(base, chunk, q_pos, limit, causal, window)
        m_new, l_new, acc_new = _online_update((m, l, acc), qg, kb, vb,
                                               mask, scale)
        return (m_new, l_new, acc_new, base + chunk), None

    m0 = jnp.full((b, hkv, g, sq), -1e30, jnp.float32)
    l0 = jnp.zeros((b, hkv, g, sq), jnp.float32)
    a0 = jnp.zeros((b, hkv, g, sq, hd), jnp.float32)
    (m, l, acc, _), _ = jax.lax.scan(
        step, (m0, l0, a0, jnp.zeros((), jnp.int32)), (kc, vc))
    l = jnp.maximum(l, 1e-30)
    out = acc / l[..., None]
    lse = m + jnp.log(l)
    return out.reshape(b, hq, sq, hd).astype(q.dtype), lse


def _paged_fwd(q, k_pool, v_pool, pages, limit, *, chunk, q_offset=None,
               window: int = 0):
    """Online-softmax over a paged KV pool — the same row-wise LSE math
    as :func:`_chunked_fwd`, but each scan chunk *gathers* its KV rows
    from the pool through the block table instead of slicing a dense
    per-slot cache, so only a slot's live pages ever stream.

    q: (B,Hq,Sq,hd); k_pool/v_pool: (n_pages, page_size, Hkv, hd);
    pages: (B, n_logical_pages) int32 block table; limit: (B,) valid
    token counts (logical positions >= limit are masked out).

    ``q_offset`` ((B,) int32) turns the single-position decode gather
    into a multi-query *prefix* gather for chunked prefill: query row i
    sits at absolute position ``q_offset + i`` and attends causally
    (vacuous while every cached key is below ``limit <= q_offset``, but
    kept explicit so the mask is correct for any limit). ``window``
    marks the table as a sliding-window *ring* of ``window / page_size``
    pages: ring slot r holds the newest written position ≡ r (mod
    window) strictly below ``limit``, and each query additionally masks
    keys at or below ``q_pos - window``. The decode path (q_offset=None,
    window=0) is bit-identical to before.
    Returns (out (B,Hq,Sq,hd), lse (B,Hkv,g,Sq) fp32).
    """
    b, hq, sq, hd = q.shape
    _, ps, hkv, _ = k_pool.shape
    g = hq // hkv
    n_log = pages.shape[1]
    ppc = max(1, min(n_log, chunk // ps))      # pages gathered per chunk
    pad = (-n_log) % ppc
    if pad:
        # padding repeats the table's last entry; fully masked below
        pages = jnp.pad(pages, ((0, 0), (0, pad)), mode="edge")
    nc = (n_log + pad) // ppc
    pid_chunks = pages.reshape(b, nc, ppc).transpose(1, 0, 2)  # (nc,B,ppc)
    bases = jnp.arange(nc) * (ppc * ps)
    qg = q.reshape(b, hkv, g, sq, hd)
    scale = hd ** -0.5

    def step(carry, inp):
        pid, base = inp                                        # (B,ppc)
        kb = jnp.take(k_pool, pid, axis=0)   # (B, ppc, ps, Hkv, hd)
        vb = jnp.take(v_pool, pid, axis=0)
        kb = kb.reshape(b, ppc * ps, hkv, hd).transpose(0, 2, 1, 3)
        vb = vb.reshape(b, ppc * ps, hkv, hd).transpose(0, 2, 1, 3)
        r = base + jnp.arange(ppc * ps)      # logical slot index
        if window:
            # ring: recover the absolute position each slot holds (the
            # newest p ≡ r (mod window) below limit); unwritten slots
            # (limit < window) resolve negative and mask out, padded
            # table slots (r >= window) are never ring storage
            k_pos = (r[None, :] + ((limit[:, None] - 1 - r[None, :])
                                   // window) * window)        # (B, K)
            valid = ((r[None, :] < window) & (k_pos >= 0)
                     & (k_pos < limit[:, None]))
        else:
            k_pos = jnp.broadcast_to(r[None, :], (b, r.shape[0]))
            valid = k_pos < limit[:, None]
        if q_offset is None:
            mask = valid[:, None, None, None, :]
        else:
            q_pos = q_offset[:, None] + jnp.arange(sq)[None]   # (B, Sq)
            qm = k_pos[:, None, :] <= q_pos[..., None]         # causal
            if window:
                qm &= k_pos[:, None, :] > (q_pos[..., None] - window)
            mask = (valid[:, None, :] & qm)[:, None, None, :, :]
        return _online_update(carry, qg, kb, vb, mask, scale), None

    m0 = jnp.full((b, hkv, g, sq), -1e30, jnp.float32)
    l0 = jnp.zeros((b, hkv, g, sq), jnp.float32)
    a0 = jnp.zeros((b, hkv, g, sq, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), (pid_chunks, bases))
    l = jnp.maximum(l, 1e-30)
    out = acc / l[..., None]
    return out.reshape(b, hq, sq, hd).astype(q.dtype), m + jnp.log(l)


def _flash_bwd(res, dout, *, causal, window, q_offset, chunk):
    """Flash-attention backward: recompute p per chunk from saved lse —
    no stacked score saves (the scan-AD default materializes every
    chunk's probabilities for the backward; this is the row-wise
    kernel's recompute-from-stats strategy in jnp)."""
    q, k, v, limit, out, lse = res
    b, hq, sq, hd = q.shape
    _, hkv, skv, _ = k.shape
    g = hq // hkv
    chunk = min(chunk, skv)
    pad = (-skv) % chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    nc = (skv + pad) // chunk
    kc = k.reshape(b, hkv, nc, chunk, hd).transpose(2, 0, 1, 3, 4)
    vc = v.reshape(b, hkv, nc, chunk, hd).transpose(2, 0, 1, 3, 4)
    qg = q.reshape(b, hkv, g, sq, hd)
    do = dout.reshape(b, hkv, g, sq, hd)
    og = out.reshape(b, hkv, g, sq, hd)
    scale = hd ** -0.5
    q_pos = q_offset + jnp.arange(sq)[:, None]
    d_term = jnp.einsum("bhgqd,bhgqd->bhgq", do, og,
                        preferred_element_type=jnp.float32)

    def step(carry, inp):
        dq_acc, base = carry
        kb, vb = inp
        s = jnp.einsum("bhgqd,bhkd->bhgqk", qg, kb,
                       preferred_element_type=jnp.float32) * scale
        mask = _chunk_mask(base, chunk, q_pos, limit, causal, window)
        p = jnp.where(mask, jnp.exp(s - lse[..., None]), 0.0)
        pb = p.astype(vb.dtype)
        dv = jnp.einsum("bhgqk,bhgqd->bhkd", pb, do,
                        preferred_element_type=jnp.float32)
        dp = jnp.einsum("bhgqd,bhkd->bhgqk", do, vb,
                        preferred_element_type=jnp.float32)
        ds = (p * (dp - d_term[..., None]) * scale)
        dsb = ds.astype(kb.dtype)
        dq_acc = dq_acc + jnp.einsum("bhgqk,bhkd->bhgqd", dsb, kb,
                                     preferred_element_type=jnp.float32)
        dk = jnp.einsum("bhgqk,bhgqd->bhkd", dsb, qg,
                        preferred_element_type=jnp.float32)
        return (dq_acc, base + chunk), (dk, dv)

    dq0 = jnp.zeros((b, hkv, g, sq, hd), jnp.float32)
    (dq, _), (dks, dvs) = jax.lax.scan(
        step, (dq0, jnp.zeros((), jnp.int32)), (kc, vc))
    dk = dks.transpose(1, 2, 0, 3, 4).reshape(b, hkv, nc * chunk, hd)
    dv = dvs.transpose(1, 2, 0, 3, 4).reshape(b, hkv, nc * chunk, hd)
    dq = dq.reshape(b, hq, sq, hd)
    return (dq.astype(q.dtype), dk[:, :, :skv].astype(k.dtype),
            dv[:, :, :skv].astype(v.dtype), None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _chunked_attention_diff(q, k, v, limit, causal, window, q_offset,
                            chunk):
    out, _ = _chunked_fwd(q, k, v, limit, causal=causal, window=window,
                          q_offset=q_offset, chunk=chunk)
    return out


def _cad_fwd(q, k, v, limit, causal, window, q_offset, chunk):
    out, lse = _chunked_fwd(q, k, v, limit, causal=causal, window=window,
                            q_offset=q_offset, chunk=chunk)
    return out, (q, k, v, limit, out, lse)


def _cad_bwd(causal, window, q_offset, chunk, res, dout):
    return _flash_bwd(res, dout, causal=causal, window=window,
                      q_offset=q_offset, chunk=chunk)


_chunked_attention_diff.defvjp(_cad_fwd, _cad_bwd)


def chunked_attention(q, k, v, *, causal=True, window: int = 0,
                      q_offset=0, kv_len=None, chunk: int = 1024,
                      pages=None):
    """Online-softmax scan over KV chunks. q: (B,Hq,Sq,hd); k/v GQA.

    q_offset may be a traced scalar (decode). kv_len masks padded cache.
    The train path (static offset, no kv_len) uses the flash custom-VJP.

    pages: optional (B, n_logical_pages) int32 block table. When given,
    k/v are page *pools* (n_pages, page_size, Hkv, hd) and every chunk
    gathers its KV rows through the table (paged decode; causality and
    windowing are expressed through kv_len by the caller).
    """
    b = q.shape[0]
    if pages is not None:
        limit = jnp.broadcast_to(jnp.asarray(kv_len), (b,))
        with jax.named_scope("rowwise_paged_attn"):
            out, _ = _paged_fwd(q, k, v, pages, limit, chunk=chunk)
        return out
    skv = k.shape[2]
    limit = skv if kv_len is None else kv_len
    limit = jnp.broadcast_to(jnp.asarray(limit), (b,))
    with jax.named_scope("rowwise_attn"):
        if isinstance(q_offset, int) and kv_len is None:
            return _chunked_attention_diff(q, k, v, limit, causal, window,
                                           q_offset, chunk)
        out, _ = _chunked_fwd(q, k, v, limit, causal=causal, window=window,
                              q_offset=q_offset, chunk=chunk)
        return out


def _sdpa(q, k, v, *, causal, window, q_offset=0, kv_len=None):
    """Impl dispatch for the core attention op."""
    impl = runtime.resolve_impl()
    static_off = isinstance(q_offset, int)
    if impl == "ref":
        if (q.shape[2] <= DENSE_MAX_SEQ and k.shape[2] <= DENSE_MAX_SEQ
                and static_off and kv_len is None):
            return ops.attention(q, k, v, causal=causal, window=window,
                                 q_offset=q_offset, impl="ref")
        return chunked_attention(q, k, v, causal=causal, window=window,
                                 q_offset=q_offset, kv_len=kv_len)
    if not static_off or kv_len is not None:
        # kernel path currently takes static offsets; decode goes chunked
        return chunked_attention(q, k, v, causal=causal, window=window,
                                 q_offset=q_offset, kv_len=kv_len)
    return ops.attention(q, k, v, causal=causal, window=window,
                         q_offset=q_offset, impl=impl)


def apply(params, x, *, cfg: ModelConfig, positions, window: int = 0,
          causal: bool = True, kv: Optional[tuple] = None,
          norm: Optional[ops.NormSpec] = None, residual=None):
    """Full-sequence forward (train / prefill).

    kv: optional (enc_out, enc_out) override for cross-attention — k
    and v must project from the SAME encoder stream (fused wkv panel).
    norm: fused-pipeline mode — x arrives *un-normalized* and the
    pre-norm runs as the qkv kernel's prologue over the stored wq|wk|wv
    panel (one activation fetch for all projections, no per-call
    weight concat). residual: folded into the output projection's
    epilogue.
    Returns (out, (k_heads, v_heads)) — the heads are cached by prefill.
    """
    b, s, d = x.shape
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    if kv is None:
        q, k, v = _project_qkv(params, x, cfg, norm)
        q = q.reshape(b, s, hq, hd)
        k = k.reshape(b, s, hkv, hd)
        v = v.reshape(b, s, hkv, hd)
        q, k = _apply_rope(q, k, cfg, positions)
    else:
        xk, xv = kv
        assert xk is xv, (
            "cross-attention projects k AND v from one encoder stream "
            "through the fused wkv panel; distinct k/v sources are not "
            "supported")
        sk = xk.shape[1]
        kvo = hkv * hd
        q = ops.matmul(x, params["wq"], norm=norm).reshape(b, s, hq, hd)
        if runtime.pipeline_fusion():
            k, v = ops.qkv_proj(xk, params["wkv"], (kvo, kvo))
        else:
            # seed per-op baseline: the stored panel sliced back into
            # the two projection launches (as _project_qkv does)
            from repro.core import quant
            wkv = quant.resolve_weight(params["wkv"], xk.dtype)
            k = ops.matmul(xk, wkv[..., :kvo])
            v = ops.matmul(xk, wkv[..., kvo:])
        k = k.reshape(b, sk, hkv, hd)
        v = v.reshape(b, sk, hkv, hd)
    qh = q.transpose(0, 2, 1, 3)
    kh = k.transpose(0, 2, 1, 3)
    vh = v.transpose(0, 2, 1, 3)
    qh = logical_constraint(qh, "batch", "heads", "seq", None)
    out = _sdpa(qh, kh, vh, causal=causal, window=window)
    out = out.transpose(0, 2, 1, 3).reshape(b, s, hq * hd)
    return _out_proj(out, params["wo"], residual), (k, v)


def write_cache(cache: KVCache, k_new, v_new, pos, window: int = 0):
    """Insert (B, S_new, Hkv, hd) states at position ``pos`` (scalar or
    per-batch (B,) ), ring-buffered when the layer is windowed."""
    alloc = cache.k.shape[1]
    s_new = k_new.shape[1]
    if isinstance(pos, int) or pos.ndim == 0:
        pos = jnp.broadcast_to(jnp.asarray(pos), (cache.k.shape[0],))
    idx = (pos[:, None] + jnp.arange(s_new)[None]) % alloc     # (B,S_new)

    def upd(buf, new):
        bidx = jnp.arange(buf.shape[0])[:, None]
        return buf.at[bidx, idx].set(new.astype(buf.dtype))

    return KVCache(k=upd(cache.k, k_new), v=upd(cache.v, v_new))


def _project_qkv(params, x, cfg: ModelConfig, norm):
    """q/k/v projections from the stored fused ``wqkv`` panel.

    Fused mode (a norm spec rides along): one wide-N kernel launch over
    the pre-concatenated leaf, outputs sliced per projection — no
    per-call weight concatenate anywhere (DESIGN.md §5). Per-op mode
    (norm is None — the seed baseline kept for before/after benches):
    the stored panel is sliced back into the three projection weights
    and each runs as its own launch.
    """
    splits = proj_splits(cfg)
    if norm is not None:
        return ops.qkv_proj(x, params["wqkv"], splits, norm=norm)
    from repro.core import quant
    w = quant.resolve_weight(params["wqkv"], x.dtype)
    qo, kvo, _ = splits
    return (ops.matmul(x, w[..., :qo]),
            ops.matmul(x, w[..., qo:qo + kvo]),
            ops.matmul(x, w[..., qo + kvo:]))


def _decode_qkv(params, x, cfg: ModelConfig, lengths, norm):
    """Shared decode-step projections: q/k/v heads for the new token,
    RoPE'd at the token's position. x: (B, 1, d)."""
    b = x.shape[0]
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q, k, v = _project_qkv(params, x, cfg, norm)
    q = q.reshape(b, 1, hq, hd)
    k = k.reshape(b, 1, hkv, hd)
    v = v.reshape(b, 1, hkv, hd)
    return _apply_rope(q, k, cfg, lengths[:, None]) + (v,)


def write_pages(pool: PagedKVCache, k_new, v_new, pos, pages,
                window: int = 0):
    """Append the decode token's K/V (B,1,Hkv,hd) at logical position
    ``pos`` (B,) through the block table ``pages`` (B, n_logical).
    Windowed layers treat the first ``window // page_size`` table
    entries as a ring of pages (the paged analog of the dense ring
    buffer's ``pos % window`` write)."""
    ps = pool.k.shape[1]
    r = pos if window == 0 else pos % window
    lp = jnp.clip(r // ps, 0, pages.shape[1] - 1)
    off = r % ps
    pid = jnp.take_along_axis(pages, lp[:, None], axis=1)[:, 0]   # (B,)
    return PagedKVCache(
        k=pool.k.at[pid, off].set(k_new[:, 0].astype(pool.k.dtype)),
        v=pool.v.at[pid, off].set(v_new[:, 0].astype(pool.v.dtype)))


def _merge_partials(out_a, lse_a, out_b, lse_b):
    """Combine two partial online-softmax results over *disjoint* KV
    sets (the prefix-page gather and the in-flight chunk) into the exact
    softmax over their union — the standard flash-decode LSE merge.
    out: (B,Hq,Sq,hd); lse: (B,Hkv,g,Sq) fp32. A fully-masked partial
    carries lse ≈ -1e30 and drops out with weight 0 (the max-shift keeps
    the other side's weight at exp(0) = 1, so the denominator never
    vanishes)."""
    b, hq, sq, hd = out_a.shape
    hkv, g = lse_a.shape[1], lse_a.shape[2]
    oa = out_a.reshape(b, hkv, g, sq, hd).astype(jnp.float32)
    ob = out_b.reshape(b, hkv, g, sq, hd).astype(jnp.float32)
    m = jnp.maximum(lse_a, lse_b)
    wa = jnp.exp(lse_a - m)
    wb = jnp.exp(lse_b - m)
    out = ((oa * wa[..., None] + ob * wb[..., None])
           / (wa + wb)[..., None])
    return out.reshape(b, hq, sq, hd).astype(out_a.dtype)


def write_chunk_pages(pool: PagedKVCache, k_new, v_new, offset, chunk_len,
                      pages, window: int = 0):
    """Append a prefill chunk's K/V (B, Sc, Hkv, hd) at logical
    positions ``offset .. offset + chunk_len - 1`` through the block
    table ``pages`` (B, n_logical) — the multi-token generalization of
    :func:`write_pages`. ``offset`` and ``chunk_len`` are scalar or
    per-row (B,) int32 — per-row ``chunk_len`` is how the speculative
    verify step writes only each slot's *accepted* draft rows (a row
    with ``chunk_len == 0`` writes nothing). Right padding (rows >=
    chunk_len) routes out of range and is dropped. Windowed layers
    write through the ring (``pos % window``)
    and keep only the chunk's last ``window`` positions — earlier rows
    would be clobbered by a later in-chunk position at the same ring
    slot, and no future query needs them — which also keeps the
    scatter's target indices duplicate-free.

    Shared-page contract (PR 8): every page this scatter can touch —
    logical pages ``offset // ps .. (offset + chunk_len - 1) // ps`` —
    must be slot-private (refcount 1). The engine guarantees it: a
    prefix-cache hit starts the chunk schedule *after* the shared
    pages, and the partially-shared boundary page is remapped by
    :func:`copy_page` (``PagePool.cow``) before the first chunk that
    writes into it."""
    b, sc = k_new.shape[:2]
    ps = pool.k.shape[1]
    i = jnp.arange(sc)
    offset = jnp.broadcast_to(jnp.asarray(offset), (b,))
    clen = jnp.broadcast_to(jnp.asarray(chunk_len), (b,))
    pos = offset[:, None] + i[None]                            # (B, Sc)
    valid = i[None] < clen[:, None]
    r = pos
    if window:
        valid &= pos >= (offset + clen)[:, None] - window
        r = pos % window
    lp = jnp.clip(r // ps, 0, pages.shape[1] - 1)              # (B, Sc)
    pid = jnp.where(valid, jnp.take_along_axis(pages, lp, axis=1),
                    pool.k.shape[0])
    off = r % ps
    return PagedKVCache(
        k=pool.k.at[pid, off].set(k_new.astype(pool.k.dtype),
                                  mode="drop"),
        v=pool.v.at[pid, off].set(v_new.astype(pool.v.dtype),
                                  mode="drop"))


def copy_page(pool: PagedKVCache, src, dst):
    """Copy one physical page's K/V rows ``src`` → ``dst`` (traced int32
    scalars) on the *stored* 5-D leaves (R, P, ps, Hkv, hd) — the
    copy-on-write step before a slot's first write into a shared
    prefix-cache page. ``src == dst`` is the identity (the non-COW
    steady state), so the copy folds into the chunk program as two
    scalar operands instead of a separate compiled unit. Rows past the
    kept prefix carry donor garbage; length masking hides them until
    the slot overwrites them — the same contract scratch pages rely
    on."""
    return PagedKVCache(k=pool.k.at[:, dst].set(pool.k[:, src]),
                        v=pool.v.at[:, dst].set(pool.v[:, src]))


def paged_chunk_apply(params, x, pool: PagedKVCache, *, cfg: ModelConfig,
                      offset, chunk_len, pages, window: int = 0,
                      norm: Optional[ops.NormSpec] = None, residual=None):
    """Chunked-prefill forward for one attention layer: a row panel of
    ``Sc`` prompt tokens starting at absolute position ``offset``
    ((B,) int32, traced), of which the first ``chunk_len`` are real
    (right padding masked). x: (B, Sc, d). Returns (out, new_pool);
    norm/residual as in :func:`apply`.

    Attention is the exact softmax over prefix ∪ chunk, assembled from
    two partials sharing the row-wise ``_online_update`` math:

      * the already-written KV pages, via the multi-query
        :func:`_paged_fwd` prefix gather (per-query window masking,
        ring position recovery for sliding-window layers);
      * the in-flight chunk itself, causally, via :func:`_chunked_fwd`
        in chunk-relative coordinates (the window constraint is
        translation-invariant);

    merged by :func:`_merge_partials`. The chunk's own K/V then append
    at the position offset (:func:`write_chunk_pages`) — strictly after
    the prefix gather, so ring writes cannot clobber prefix keys the
    chunk's queries still need.
    """
    out, k, v = _chunk_attn_core(params, x, pool, cfg=cfg, offset=offset,
                                 chunk_len=chunk_len, pages=pages,
                                 window=window, norm=norm,
                                 residual=residual)
    pool = write_chunk_pages(pool, k, v, offset, chunk_len, pages,
                             window)
    return out, pool


def _chunk_attn_core(params, x, pool: PagedKVCache, *, cfg: ModelConfig,
                     offset, chunk_len, pages, window: int,
                     norm: Optional[ops.NormSpec], residual):
    """Shared math of :func:`paged_chunk_apply` /
    :func:`paged_verify_apply`: exact softmax over prefix ∪ chunk with
    no pool mutation. Returns (projected out, chunk k, chunk v)."""
    b, sc, d = x.shape
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    positions = offset[:, None] + jnp.arange(sc, dtype=jnp.int32)[None]
    q, k, v = _project_qkv(params, x, cfg, norm)
    q = q.reshape(b, sc, hq, hd)
    k = k.reshape(b, sc, hkv, hd)
    v = v.reshape(b, sc, hkv, hd)
    q, k = _apply_rope(q, k, cfg, positions)
    qh = q.transpose(0, 2, 1, 3)
    kh = k.transpose(0, 2, 1, 3)
    vh = v.transpose(0, 2, 1, 3)
    clen = jnp.broadcast_to(jnp.asarray(chunk_len), (b,))
    with jax.named_scope("rowwise_chunk_attn"):
        out_c, lse_c = _chunked_fwd(qh, kh, vh, clen, causal=True,
                                    window=window, q_offset=0, chunk=1024)
        ps = pool.k.shape[1]
        tbl = pages[:, :max(window // ps, 1)] if window else pages
        out_p, lse_p = _paged_fwd(qh, pool.k, pool.v, tbl,
                                  jnp.broadcast_to(jnp.asarray(offset),
                                                   (b,)),
                                  chunk=1024, q_offset=offset,
                                  window=window)
        out = _merge_partials(out_c, lse_c, out_p, lse_p)
    out = out.transpose(0, 2, 1, 3).reshape(b, sc, hq * hd)
    return _out_proj(out, params["wo"], residual), k, v


def paged_verify_apply(params, x, pool: PagedKVCache, *,
                       cfg: ModelConfig, offset, chunk_len, pages,
                       window: int = 0,
                       norm: Optional[ops.NormSpec] = None,
                       residual=None):
    """Speculative-verify forward for one attention layer: bit-identical
    attention math to :func:`paged_chunk_apply` over the draft panel
    (the panel is causal over itself plus the slot's written prefix),
    but the panel's K/V are NOT written to the pool — they are returned
    so the engine can score the logits first and then write only the
    accepted prefix rows (:func:`write_chunk_pages` with per-row
    accepted lengths). Deferring the write keeps rejected drafts out of
    the pool entirely, which matters for sliding-window layers: a ring
    write from a rejected row would clobber the very prefix keys the
    re-decode of that position still needs. Returns (out, (k, v))."""
    out, k, v = _chunk_attn_core(params, x, pool, cfg=cfg, offset=offset,
                                 chunk_len=chunk_len, pages=pages,
                                 window=window, norm=norm,
                                 residual=residual)
    return out, (k, v)


def paged_decode_apply(params, x, pool: PagedKVCache, *, cfg: ModelConfig,
                       lengths, pages, window: int = 0,
                       norm: Optional[ops.NormSpec] = None, residual=None):
    """One-token decode against a paged KV pool. x: (B, 1, d); lengths:
    (B,) tokens already written; pages: (B, max_pages) block table.
    Returns (out, new_pool). norm/residual as in :func:`apply`.

    The attention core is the same online-softmax row-wise primitive as
    the dense path, but each chunk gathers only the slot's live pages —
    idle table entries point at the slot's scratch page and are masked
    by kv_len.
    """
    b = x.shape[0]
    hq, hd = cfg.n_heads, cfg.head_dim
    q, k, v = _decode_qkv(params, x, cfg, lengths, norm)
    pool = write_pages(pool, k, v, lengths, pages, window)
    ps = pool.k.shape[1]
    if window:
        tbl = pages[:, :max(window // ps, 1)]
        kv_len = jnp.minimum(lengths + 1, window)
    else:
        tbl = pages
        kv_len = lengths + 1
    qh = q.transpose(0, 2, 1, 3)
    out = chunked_attention(qh, pool.k, pool.v, causal=False, window=0,
                            kv_len=kv_len, pages=tbl)
    out = out.reshape(b, 1, hq * hd)
    return _out_proj(out, params["wo"], residual), pool


def decode_apply(params, x, cache: KVCache, *, cfg: ModelConfig,
                 lengths, window: int = 0,
                 norm: Optional[ops.NormSpec] = None, residual=None):
    """One-token decode. x: (B, 1, d); lengths: (B,) tokens already in
    cache. Returns (out, new_cache). norm/residual as in :func:`apply`.

    Global (non-window) layers use the sequence-sharded flash decode
    when the cache is sharded along seq over 'model' and the
    'decode_attn' rule is 'sharded' — partial per-shard softmax combined
    with a log-sum-exp psum, so the cache is never gathered.
    """
    from repro.core import partitioning
    b, _, d = x.shape
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q, k, v = _decode_qkv(params, x, cfg, lengths, norm)

    mesh = partitioning.active_mesh()
    use_sharded = (
        window == 0 and mesh is not None
        and "model" in mesh.axis_names
        and partitioning.get_rules().get("decode_attn") == "sharded"
        and partitioning.get_rules().get("kv_seq") == "model"
        and cache.k.shape[1] % dict(zip(
            mesh.axis_names, mesh.devices.shape))["model"] == 0)
    if use_sharded:
        out, cache = _decode_seq_sharded(q, k, v, cache, lengths,
                                         cfg=cfg, mesh=mesh)
        out = out.reshape(b, 1, hq * hd)
        return ops.matmul(out, params["wo"], residual=residual), cache

    cache = write_cache(cache, k, v, lengths, window)
    alloc = cache.k.shape[1]
    kh = cache.k.transpose(0, 2, 1, 3)
    vh = cache.v.transpose(0, 2, 1, 3)
    qh = q.transpose(0, 2, 1, 3)

    if window and window <= alloc:
        # Ring buffer holds exactly the last `window` tokens; every valid
        # entry attends (causality is implied by what was written).
        kv_len = jnp.minimum(lengths + 1, alloc)
    else:
        kv_len = lengths + 1
    out = chunked_attention(qh, kh, vh, causal=False, window=0,
                            q_offset=0, kv_len=kv_len)
    out = out.reshape(b, 1, hq * hd)
    return _out_proj(out, params["wo"], residual), cache


def _decode_seq_sharded(q, k_new, v_new, cache: KVCache, lengths, *,
                        cfg: ModelConfig, mesh):
    """Flash-decode with the KV cache sharded along sequence over
    'model': each shard writes/attends its local chunk; partial
    (m, l, acc) combine via pmax/psum of O(B x H x hd) — the cache is
    never all-gathered. Beyond-paper optimization (see EXPERIMENTS §Perf).
    """
    from repro.core import partitioning
    b, _, hq, hd = q.shape
    hkv = cfg.n_kv_heads
    g = hq // hkv
    s_alloc = cache.k.shape[1]
    n_model = dict(zip(mesh.axis_names, mesh.devices.shape))["model"]
    s_loc = s_alloc // n_model
    scale = hd ** -0.5

    r = partitioning.resolve
    cache_spec = r(("batch", "kv_seq", "kv_heads", None), mesh,
                   shape=cache.k.shape)
    q_spec = r(("batch", "kv_heads", None, None), mesh,
               shape=(b, hq, 1, hd))
    new_spec = r(("batch", None, "kv_heads", None), mesh,
                 shape=k_new.shape)
    len_spec = r(("batch",), mesh, shape=lengths.shape)

    def body(qb, knb, vnb, kc, vc, lens):
        bl = qb.shape[0]
        shard = jax.lax.axis_index("model")
        base = shard * s_loc
        # write the new token's K/V if its slot lives on this shard
        pos = lens                                    # (B,) absolute
        lpos = jnp.clip(pos - base, 0, s_loc - 1)
        here = (pos >= base) & (pos < base + s_loc)   # (B,)
        bidx = jnp.arange(bl)
        upd_k = kc.at[bidx, lpos].set(
            jnp.where(here[:, None, None], knb[:, 0].astype(kc.dtype),
                      kc[bidx, lpos]))
        upd_v = vc.at[bidx, lpos].set(
            jnp.where(here[:, None, None], vnb[:, 0].astype(vc.dtype),
                      vc[bidx, lpos]))
        # local partial attention (single query row)
        hkv_l = upd_k.shape[2]
        qg = qb.reshape(bl, hkv_l, g, hd).astype(jnp.float32)
        kh = upd_k.transpose(0, 2, 1, 3).astype(jnp.float32)
        vh = upd_v.transpose(0, 2, 1, 3).astype(jnp.float32)
        s = jnp.einsum("bhgd,bhkd->bhgk", qg, kh) * scale
        k_pos = base + jnp.arange(s_loc)
        valid = k_pos[None] < (lens + 1)[:, None]     # (B, s_loc)
        s = jnp.where(valid[:, None, None], s, -1e30)
        m_i = jnp.max(s, -1)                          # (B, hkv, g)
        p = jnp.where(valid[:, None, None], jnp.exp(s - m_i[..., None]),
                      0.0)
        l_i = jnp.sum(p, -1)
        acc_i = jnp.einsum("bhgk,bhkd->bhgd", p, vh)
        # LSE combine across shards: tiny psums instead of a cache gather
        m = jax.lax.pmax(m_i, "model")
        alpha = jnp.exp(m_i - m)
        l_tot = jax.lax.psum(l_i * alpha, "model")
        acc = jax.lax.psum(acc_i * alpha[..., None], "model")
        out = acc / jnp.maximum(l_tot, 1e-30)[..., None]
        out = out.reshape(bl, 1, hkv_l * g * hd)
        # pin cache dtype: an f32 leak here makes the layer scan convert
        # the WHOLE stacked cache f32<->bf16 every iteration
        return (out.astype(qb.dtype), upd_k.astype(kc.dtype),
                upd_v.astype(vc.dtype))

    out_spec = r(("batch", None, "kv_heads"), mesh,
                 shape=(b, 1, hq * hd))
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(q_spec, new_spec, new_spec, cache_spec, cache_spec,
                  len_spec),
        out_specs=(out_spec, cache_spec, cache_spec),
        check_vma=False)
    out, new_k, new_v = fn(q.transpose(0, 2, 1, 3), k_new, v_new,
                           cache.k, cache.v, lengths)
    return out, KVCache(k=new_k, v=new_v)
