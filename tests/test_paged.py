"""Paged-KV serving: allocator, block-table attention, engine parity.

The fast tier covers the host-side allocator/buckets, the page-gather
attention primitive against the dense chunked oracle, and the modeled
KV-traffic acceptance criterion. The slow tier drives the full engine:
paged continuous batching must reproduce dense-cache greedy decoding
token for token across mixed prompt lengths, sliding-window layers and
slot reuse, while compiling at most ``n_buckets + 1`` programs
(``n_buckets + n_chunk_shapes + 1`` once chunked prefill is on —
chunked-path parity itself lives in ``test_chunked_prefill.py``).
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import (layerwise_paged_step, manual_greedy,
                      random_paged_cache)

from repro.analysis import compile_bound
from repro.configs import REDUCED
from repro.core.block_traffic import (dense_kv_step_bytes, kv_layer_counts,
                                      paged_kv_step_bytes,
                                      serve_kv_traffic)
from repro.core.types import PagingConfig
from repro.models import attention, lm
from repro.serve import sampling
from repro.serve.engine import Engine, Request
from repro.serve.paging import (PagePool, bucket_for, default_buckets,
                                page_aligned_size, supports_bucketing)


# ----------------------------------------------------------------------
# Host-side bookkeeping (fast)
# ----------------------------------------------------------------------


def test_page_pool_alloc_release_reuse():
    pool = PagePool(n_pages=8, page_size=4, n_slots=2, max_pages=4)
    # idle tables point at each slot's PRIVATE scratch page (8, 9) —
    # never at one shared page
    assert list(pool.scratch) == [8, 9]
    assert (pool.tables[0] == 8).all() and (pool.tables[1] == 9).all()
    assert pool.can_admit(16)            # 4 pages of 4 tokens
    pool.admit(0, 16)
    pool.ensure(0, 9)                    # 3 pages
    assert pool.n_alloc[0] == 3 and pool.live_pages() == 3
    assert sorted(pool.tables[0, :3]) == sorted(set(pool.tables[0, :3]))
    # reservations count against admission even before pages are drawn
    assert pool.can_admit(16)            # 8 - 3 live - 1 outstanding >= 4
    assert not pool.can_admit(20)        # 5 pages won't fit
    pool.admit(1, 16)
    pool.ensure(1, 16)
    assert len(pool.free) == 1
    granted = set(pool.tables[0, :3]) | set(pool.tables[1, :4])
    assert len(granted) == 7             # no page granted twice
    pool.release(0)
    assert (pool.tables[0] == pool.scratch[0]).all()
    assert pool.live_pages() == 4 and len(pool.free) == 4
    pool.admit(0, 16)
    pool.ensure(0, 16)                   # reuses the freed pages
    assert pool.live_pages() == 8


def test_bucket_policy():
    assert default_buckets(128) == [16, 32, 64, 128]
    assert default_buckets(48) == [16, 32, 48]
    assert bucket_for(5, [16, 32]) == 16
    assert bucket_for(16, [16, 32]) == 16
    assert bucket_for(17, [16, 32]) == 32
    with pytest.raises(ValueError):
        bucket_for(33, [16, 32])
    assert supports_bucketing(REDUCED["deepseek-7b"]())
    assert supports_bucketing(REDUCED["gemma3-27b"]())
    assert not supports_bucketing(REDUCED["rwkv6-3b"]())      # recurrent
    assert not supports_bucketing(REDUCED["qwen2-moe-a2.7b"]())  # MoE
    # ring pages must tile the window: gemma3 smoke window=16
    assert page_aligned_size(16, REDUCED["gemma3-27b"]()) == 16
    assert page_aligned_size(24, REDUCED["gemma3-27b"]()) == 8


def test_engine_rejects_bad_bucket_overrides():
    """Caller-supplied buckets must cover max_len (else admission would
    fail mid-run after mutating the pool) and are refused outright for
    archs whose prefill state makes padding inexact."""
    key = jax.random.PRNGKey(0)
    cfg = REDUCED["deepseek-7b"]()
    params, _ = lm.init_lm(key, cfg, dtype=jnp.float32)
    with pytest.raises(ValueError):
        Engine(params, cfg, n_slots=2, max_len=64, buckets=[16])
    eng = Engine(params, cfg, n_slots=2, max_len=64, buckets=[32, 64])
    assert eng.buckets == [32, 64]
    rcfg = REDUCED["rwkv6-3b"]()
    rparams, _ = lm.init_lm(key, rcfg, dtype=jnp.float32)
    with pytest.raises(ValueError):
        Engine(rparams, rcfg, n_slots=2, max_len=64, buckets=[16, 64])


# ----------------------------------------------------------------------
# Page-gather attention vs the dense chunked oracle (fast)
# ----------------------------------------------------------------------


def _build_pool(k, v, page_size, rng):
    """Scatter dense (B,S,Hkv,hd) states into a shuffled page pool, the
    one layer of a (1, n_pages + 1, page_size, Hkv, hd) stack."""
    b, s, hkv, hd = k.shape
    npp = s // page_size
    n_pages = b * npp
    perm = rng.permutation(n_pages)
    tables = perm.reshape(b, npp).astype(np.int32)
    pool_k = np.zeros((n_pages + 1, page_size, hkv, hd), np.float32)
    pool_v = np.zeros((n_pages + 1, page_size, hkv, hd), np.float32)
    for bi in range(b):
        for p in range(npp):
            sl = slice(p * page_size, (p + 1) * page_size)
            pool_k[tables[bi, p]] = np.asarray(k[bi, sl])
            pool_v[tables[bi, p]] = np.asarray(v[bi, sl])
    return (jnp.asarray(pool_k)[None], jnp.asarray(pool_v)[None],
            jnp.asarray(tables))


@pytest.mark.parametrize("chunk", [1024, 8])
def test_paged_attention_matches_chunked(chunk):
    key = jax.random.PRNGKey(0)
    b, hq, hkv, hd, ps = 3, 4, 2, 8, 4
    s = 32
    q = jax.random.normal(key, (b, hq, 1, hd))
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, s, hkv, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, hkv, hd))
    lengths = jnp.asarray([5, 32, 11])
    ref = attention.chunked_attention(
        q, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
        causal=False, window=0, kv_len=lengths)
    pool_k, pool_v, tables = _build_pool(k, v, ps,
                                         np.random.default_rng(0))
    out = attention.chunked_attention(q, pool_k, pool_v, causal=False,
                                      window=0, kv_len=lengths,
                                      pages=tables, layer=0, chunk=chunk)
    if chunk >= s:       # one online-softmax step each: the same sums,
        # but XLA:CPU may fuse the gathered and the dense dot products
        # with different FMA contraction, so allow a few float32 ulps
        # at |out| < 2 (seen: 1.5e-7, one ulp)
        assert float(jnp.max(jnp.abs(out - ref))) < 1e-6
    else:                # different chunking: same math, ulp-level
        assert float(jnp.max(jnp.abs(out - ref))) < 1e-5


def test_write_pages_appends_to_tail_page():
    b, hkv, hd, ps = 2, 2, 4, 4
    # two stacked layers: the write lands in layer 1 only
    pool = attention.PagedKVCache(k=jnp.zeros((2, 5, ps, hkv, hd)),
                                  v=jnp.zeros((2, 5, ps, hkv, hd)))
    tables = jnp.asarray([[2, 0], [3, 1]], jnp.int32)
    k_new = jnp.ones((b, 1, hkv, hd))
    v_new = 2 * jnp.ones((b, 1, hkv, hd))
    # slot 0 at position 5 => logical page 1 (physical 0), offset 1;
    # slot 1 at position 2 => logical page 0 (physical 3), offset 2
    pool = attention.write_pages(pool, k_new, v_new,
                                 jnp.asarray([5, 2]), tables,
                                 layer=jnp.int32(1))
    assert bool(jnp.all(pool.k[1, 0, 1] == 1.0))
    assert bool(jnp.all(pool.v[1, 3, 2] == 2.0))
    assert float(jnp.abs(pool.k).sum()) == hkv * hd * b   # nothing else


def test_idle_slot_writes_do_not_alias_one_page():
    """DESIGN.md §4 follow-up (2) regression: idle slots write their own
    scratch page, not one shared trash page — the lockstep writes land
    in disjoint storage (XLA can overlap or drop them instead of
    serializing), and no idle slot can observe another's garbage row."""
    cfg = REDUCED["deepseek-7b"]()
    key = jax.random.PRNGKey(0)
    params, _ = lm.init_lm(key, cfg, dtype=jnp.float32)
    eng = Engine(params, cfg, n_slots=3, max_len=32, eos_id=-1)
    idle_rows = {tuple(set(eng.pool.tables[s])) for s in range(3)}
    assert len(idle_rows) == 3            # pairwise distinct scratch ids
    # device-level: two idle slots' lockstep writes land on their own
    # scratch pages and nothing aliases
    hkv, hd, ps = 2, 4, 4
    pool = attention.PagedKVCache(k=jnp.zeros((1, 4, ps, hkv, hd)),
                                  v=jnp.zeros((1, 4, ps, hkv, hd)))
    tables = jnp.asarray([[2, 2], [3, 3]], jnp.int32)   # scratch = 2, 3
    k_new = jnp.stack([jnp.full((1, hkv, hd), 1.0),
                       jnp.full((1, hkv, hd), 5.0)])
    pool = attention.write_pages(pool, k_new, k_new,
                                 jnp.asarray([0, 0]), tables, layer=0)
    assert bool(jnp.all(pool.k[0, 2, 0] == 1.0))
    assert bool(jnp.all(pool.k[0, 3, 0] == 5.0))
    assert float(jnp.abs(pool.k[0, :2]).sum()) == 0.0   # real pages clean


def test_write_pages_ring_wraps_window():
    hkv, hd, ps = 1, 2, 4
    pool = attention.PagedKVCache(k=jnp.zeros((1, 4, ps, hkv, hd)),
                                  v=jnp.zeros((1, 4, ps, hkv, hd)))
    tables = jnp.asarray([[1, 2, 0]], jnp.int32)   # ring = first 2 pages
    # window=8: position 9 wraps to ring index 1 => page 0 (phys 1) off 1
    pool = attention.write_pages(pool, jnp.ones((1, 1, hkv, hd)),
                                 jnp.ones((1, 1, hkv, hd)),
                                 jnp.asarray([9]), tables, window=8,
                                 layer=0)
    assert bool(jnp.all(pool.k[0, 1, 1] == 1.0))


# ----------------------------------------------------------------------
# The layer scan carries the stacked pools (fast)
# ----------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["deepseek-7b", "gemma3-27b"])
@pytest.mark.parametrize("mode", ["decode", "chunk"])
def test_carried_pools_match_layerwise_oracle(arch, mode):
    """The scan that carries the stacked pools and addresses each
    layer by index gives logits and pools identical to a Python loop
    that slices each layer's pool, runs the attention functions on it
    and restacks. gemma3's 16-token windows are passed by both the
    decode lengths and the chunk offset, so ring writes and reads
    wrap."""
    cfg = REDUCED[arch]()
    key = jax.random.PRNGKey(0)
    params, _ = lm.init_lm(key, cfg, dtype=jnp.float32)
    cache, tables = random_paged_cache(cfg, key)
    if mode == "decode":
        args = (params, cache, jnp.asarray([[3], [7]], jnp.int32),
                jnp.asarray([5, 37], jnp.int32), tables)
        new = jax.jit(lambda p, c, t, n, tb: lm.decode_step(
            p, c, t, n, cfg, pages=tb))
        old = jax.jit(lambda p, c, t, n, tb: layerwise_paged_step(
            p, c, t, cfg, pages=tb, lengths=n))
    else:
        args = (params, cache, jax.random.randint(key, (1, 16), 0,
                                                  cfg.vocab),
                jnp.int32(21), jnp.int32(12), tables[1:])
        new = jax.jit(lambda p, c, t, o, n, tb: lm.prefill_chunk(
            p, c, t, cfg, offset=o, chunk_len=n, pages=tb))
        old = jax.jit(lambda p, c, t, o, n, tb: layerwise_paged_step(
            p, c, t, cfg, pages=tb, offset=o, chunk_len=n))
    logits, pools = new(*args)
    want_logits, want_pools = old(*args)
    # bitwise equal where XLA:CPU compiles the scan and the unrolled
    # loop alike (decode, deepseek's chunk); gemma3's chunk fuses the
    # multiply-adds of its windowed layers differently and reads up to
    # 2.2e-6 apart on unit-scale pools. A wrong layer, page or write
    # order moves values by O(1).
    tol = dict(rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(logits),
                               np.asarray(want_logits), **tol)
    for got, want in zip(jax.tree.leaves(pools),
                         jax.tree.leaves(want_pools)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   **tol)
    # the step really wrote: the new K/V differ from the random prefix
    assert any(bool(jnp.any(a != b)) for a, b in
               zip(jax.tree.leaves(pools), jax.tree.leaves(cache)))


# every instruction ``%name = f32[2,258,...]{...} op(%a, %b, ...)`` of a
# compiled module, fused computations included
_HLO_OP = re.compile(r"%([\w.\-]+) = \w+\[([\d,]*)\]\S* ([\w\-]+)\(([^)]*)\)")


def _pool_traffic_ops(hlo: str, pools):
    """The ops of a compiled program that move a whole pool: a ``copy``
    of a stacked pool or of one layer's pool, a ``dynamic-slice`` that
    takes one layer's pool out of the stack, and a
    ``dynamic-update-slice`` whose update is at least one layer's pool
    (a one-page copy-on-write update is not)."""
    stacked = {tuple(p.k.shape) for p in pools}
    layer = {s[1:] for s in stacked}
    layer_size = min(int(np.prod(s)) for s in layer)
    ops = _HLO_OP.findall(hlo)
    shapes = {name: tuple(int(d) for d in dims.split(",") if d)
              for name, dims, _, _ in ops}
    found = []
    for _, dims, op, operands in ops:
        shape = tuple(int(d) for d in dims.split(",") if d)
        if op == "copy" and (shape in stacked or shape in layer):
            found.append((op, shape))
        elif op == "dynamic-slice" and shape in layer:
            found.append((op, shape))
        elif op == "dynamic-update-slice" and shape in stacked:
            update = shapes.get(operands.split(",")[1].strip().lstrip("%"))
            if update is None or int(np.prod(update)) >= layer_size:
                found.append((op, shape))
    return found


@pytest.mark.parametrize("arch", ["deepseek-7b", "gemma3-27b"])
@pytest.mark.parametrize("entry", ["decode", "chunk", "verify"])
def test_step_programs_update_pools_in_place(arch, entry):
    """The engine's decode, chunk and verify programs, compiled with
    the cache donated, neither copy a pool nor slice a layer's pool out
    of the stack and write it back (:func:`_pool_traffic_ops`), and
    their temporaries stay below one pool's K+V bytes (the pools are
    sized well above the step's activations)."""
    cfg = REDUCED[arch]()
    params, _ = lm.init_lm(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    eng = Engine(params, cfg, n_slots=2, max_len=64, eos_id=-1,
                 paging=PagingConfig(page_size=8, n_pages=512,
                                     prefill_chunk=16, speculate_k=2))
    name = {"decode": "step", "chunk": "chunk", "verify": "spec"}[entry]
    _, fn, args, _ = next(e for e in eng.audit_entry_points()
                          if e[0] == name)
    compiled = fn.lower(*args).compile()
    is_pool = lambda c: isinstance(c, attention.PagedKVCache)  # noqa: E731
    pools = [p for p in jax.tree.leaves(eng.cache, is_leaf=is_pool)
             if is_pool(p)]
    assert pools
    assert not _pool_traffic_ops(compiled.as_text(), pools)
    pool_bytes = min(p.k.nbytes + p.v.nbytes for p in pools)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < pool_bytes, (temp, pool_bytes)


# ----------------------------------------------------------------------
# Traffic model acceptance (fast)
# ----------------------------------------------------------------------


def test_paged_traffic_beats_dense_2x():
    """ISSUE acceptance: on a trace whose mean live length is at most
    max_len / 4, paged decode models >= 2x fewer KV HBM bytes than the
    dense n_slots x max_len lockstep caches."""
    cfg = REDUCED["deepseek-7b"]()
    n_slots, max_len, ps = 4, 128, 16
    lens = [5, 17, 32, 21]                       # prompt lengths
    assert np.mean(lens) <= max_len / 4
    trace = [[ln + t for ln in lens] for t in range(16)]
    out = serve_kv_traffic(trace, cfg, n_slots=n_slots, max_len=max_len,
                           page_size=ps)
    assert out["ratio"] >= 2.0, out
    assert out["paged_bytes"] * 2 <= out["dense_bytes"]


def test_traffic_model_shapes():
    cfg = REDUCED["gemma3-27b"]()                # 2 local : 1 global mix
    n_global, n_local, window = kv_layer_counts(cfg)
    assert n_global > 0 and n_local > 0 and window == 16
    row = 2 * cfg.n_kv_heads * cfg.head_dim * 2
    dense = dense_kv_step_bytes(n_slots=2, max_len=64, n_global=n_global,
                                n_local=n_local, window=window,
                                n_kv_heads=cfg.n_kv_heads,
                                head_dim=cfg.head_dim)
    # windowed layers cap at window, global layers pay max_len
    assert dense == row * 2 * (n_global * 64 + n_local * 16)
    paged = paged_kv_step_bytes([10], page_size=8, n_global=n_global,
                                n_local=n_local, window=window,
                                n_kv_heads=cfg.n_kv_heads,
                                head_dim=cfg.head_dim)
    # 10 live tokens round to 16 (two pages); ring also 16
    assert paged == row * (n_global * 16 + n_local * 16)
    # idle slots cost nothing in the paged model
    assert paged_kv_step_bytes([], page_size=8, n_global=n_global,
                               n_kv_heads=cfg.n_kv_heads,
                               head_dim=cfg.head_dim) == 0


def test_per_row_temperature_sampling():
    key = jax.random.PRNGKey(0)
    logits = jnp.asarray([[0.0, 5.0, 1.0, -2.0],
                          [9.0, 0.0, 0.0, 0.0]])
    # scalar zero (and any non-positive scalar) short-circuits to greedy
    assert sampling.sample(logits, key, temperature=0.0).tolist() == [1, 0]
    assert sampling.sample(logits, key, temperature=-1.0).tolist() == [1, 0]
    # per-row: row 0 greedy, row 1 sampled (valid token either way)
    t = jnp.asarray([0.0, 1.0])
    out = sampling.sample(logits, key, temperature=t)
    assert int(out[0]) == 1
    assert 0 <= int(out[1]) < 4
    # all-greedy rows match the scalar fast path exactly
    out0 = sampling.sample(logits, key, temperature=jnp.zeros(2))
    assert out0.tolist() == [1, 0]
    # 0-d numpy / jnp scalars keep working like python floats
    assert sampling.sample(logits, key,
                           temperature=np.float32(0.0)).tolist() == [1, 0]
    assert 0 <= int(sampling.sample(logits, key,
                                    temperature=jnp.float32(0.8))[0]) < 4


# ----------------------------------------------------------------------
# Engine: paged vs dense greedy parity + compile stability (slow)
# ----------------------------------------------------------------------


@pytest.mark.slow
def test_paged_matches_dense_mixed_lengths_and_slot_reuse():
    """Greedy token streams of the paged engine equal dense-cache decode
    exactly, across mixed prompt lengths with more requests than slots
    (so retired slots hand pages back and are refilled)."""
    cfg = REDUCED["deepseek-7b"]()
    key = jax.random.PRNGKey(0)
    params, _ = lm.init_lm(key, cfg, dtype=jnp.float32)
    plens = [3, 9, 17, 6, 12]
    prompts = [jax.random.randint(jax.random.fold_in(key, i), (p,), 0,
                                  cfg.vocab) for i, p in enumerate(plens)]
    n_new = 6
    eng = Engine(params, cfg, n_slots=2, max_len=32, eos_id=-1,
                 paging=PagingConfig(page_size=8))
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new=n_new))
    done = eng.run()
    assert sorted(c.rid for c in done) == list(range(len(prompts)))
    by_rid = {c.rid: c for c in done}
    for i, p in enumerate(prompts):
        want = manual_greedy(params, cfg, p, n_new, 32)
        assert by_rid[i].tokens == want, (i, by_rid[i].tokens, want)


@pytest.mark.slow
def test_paged_matches_dense_sliding_window():
    """Ring-buffer pages: a gemma-style local/global mix decoding well
    past the window reproduces dense ring-cache decode exactly."""
    cfg = REDUCED["gemma3-27b"]()                # window=16
    key = jax.random.PRNGKey(1)
    params, _ = lm.init_lm(key, cfg, dtype=jnp.float32)
    plens = [20, 5, 11]                          # one prompt > window
    prompts = [jax.random.randint(jax.random.fold_in(key, i), (p,), 0,
                                  cfg.vocab) for i, p in enumerate(plens)]
    n_new = 12                                   # 20 + 12 decodes past 16
    eng = Engine(params, cfg, n_slots=2, max_len=48, eos_id=-1,
                 paging=PagingConfig(page_size=8))
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new=n_new))
    done = eng.run()
    by_rid = {c.rid: c for c in done}
    for i, p in enumerate(prompts):
        want = manual_greedy(params, cfg, p, n_new, 48)
        assert by_rid[i].tokens == want, (i, by_rid[i].tokens, want)


@pytest.mark.slow
def test_engine_compile_stability():
    """Continuous batching over mixed prompt lengths compiles at most
    n_buckets prefill programs + 1 decode program."""
    cfg = REDUCED["deepseek-7b"]()
    key = jax.random.PRNGKey(2)
    params, _ = lm.init_lm(key, cfg, dtype=jnp.float32)
    eng = Engine(params, cfg, n_slots=2, max_len=64, eos_id=-1)
    assert eng.buckets == [16, 32, 64]
    # 8 distinct prompt lengths spanning every bucket
    for i, plen in enumerate([3, 5, 9, 17, 21, 33, 40, 13]):
        eng.submit(Request(rid=i, prompt=jax.random.randint(
            jax.random.fold_in(key, i), (plen,), 0, cfg.vocab),
            max_new=4))
    eng.run()
    counts = eng.compile_counts()
    assert 0 < counts["prefill"] <= len(eng.buckets)
    assert counts["step"] == 1
    assert counts["prefill"] + counts["step"] <= len(eng.buckets) + 1
    # host-side proxy (distinct padded lengths) agrees with the jit cache
    assert counts["prefill"] == len(eng._prefill_lens)
    # the auditor's static enumeration predicts the jit caches EXACTLY:
    # any drift means a shape source the closed-form bound doesn't model
    expected = compile_bound.predict_compile_counts(
        [3, 5, 9, 17, 21, 33, 40, 13], max_len=64)
    assert counts == expected
    assert compile_bound.check_engine_counts(eng, expected).ok


@pytest.mark.slow
def test_compile_stability_mixed_chunked_traffic():
    """The PR 3 bound extended to chunked prefill: mixed chunked /
    unchunked traffic compiles at most n_buckets one-shot prefill
    programs + n_chunk_shapes chunk programs + 1 decode program, with
    the jit caches cross-checked against the host-side program
    counters."""
    cfg = REDUCED["deepseek-7b"]()
    key = jax.random.PRNGKey(6)
    params, _ = lm.init_lm(key, cfg, dtype=jnp.float32)
    eng = Engine(params, cfg, n_slots=2, max_len=64, eos_id=-1,
                 paging=PagingConfig(prefill_chunk=16))
    assert eng.buckets == [16, 32, 64]
    # spans: unchunked (<= chunk), chunk-divisible, non-divisible,
    # plen == max_len, and repeats that must all hit compiled programs
    for i, plen in enumerate([3, 16, 17, 21, 32, 40, 64, 5, 50, 33]):
        eng.submit(Request(rid=i, prompt=jax.random.randint(
            jax.random.fold_in(key, i), (plen,), 0, cfg.vocab),
            max_new=4))
    eng.run()
    counts = eng.compile_counts()
    n_chunk_shapes = len([b for b in eng.buckets
                          if b <= eng.prefill_chunk])
    assert 0 < counts["prefill"] <= len(eng.buckets)
    assert 0 < counts["chunk"] <= n_chunk_shapes
    assert counts["step"] == 1
    assert (counts["prefill"] + counts["chunk"] + counts["step"]
            <= len(eng.buckets) + n_chunk_shapes + 1)
    # host-side program counters agree with the jit caches
    assert counts["prefill"] == len(eng._prefill_lens)
    assert counts["chunk"] == len(eng._chunk_shapes)
    # every chunk shape sits on the bucket ladder at or below the chunk
    assert all(s in eng.buckets and s <= eng.prefill_chunk
               for s in eng._chunk_shapes)
    # static enumeration == runtime jit caches, exactly
    expected = compile_bound.predict_compile_counts(
        [3, 16, 17, 21, 32, 40, 64, 5, 50, 33], max_len=64,
        prefill_chunk=16)
    assert counts == expected
    assert compile_bound.check_engine_counts(eng, expected).ok
    inv = compile_bound.enumerate_programs(
        max_len=64, page_size=eng.page_size, prefill_chunk=16)
    assert set(eng._prefill_lens) <= set(inv.prefill_lens)
    assert set(eng._chunk_shapes) <= set(inv.chunk_shapes)
    assert set(eng._step_widths) <= set(inv.step_widths)


@pytest.mark.slow
def test_table_width_bucketing_parity_and_compile_ladder():
    """With ``table_width_bucketing`` on, the decode step sees block
    tables sliced to the pow2-rounded max live page count instead of
    always ``max_pages``. Streams stay bit-identical to the full-width
    engine and the decode-step compile count is bounded by the width
    ladder (one program per pow2 width <= max_pages) instead of 1."""
    cfg = REDUCED["deepseek-7b"]()
    key = jax.random.PRNGKey(9)
    params, _ = lm.init_lm(key, cfg, dtype=jnp.float32)
    n_new = 4
    prompts = [jax.random.randint(jax.random.fold_in(key, i), (plen,), 0,
                                  cfg.vocab)
               for i, plen in enumerate([3, 9, 17, 26, 5])]

    def run(twb):
        eng = Engine(params, cfg, n_slots=2, max_len=32, eos_id=-1,
                     paging=PagingConfig(page_size=4,
                                         table_width_bucketing=twb))
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=i, prompt=p, max_new=n_new))
        done = {c.rid: c.tokens for c in eng.run()}
        return eng, done

    # the full-width engine is the oracle: its own dense-greedy parity
    # is already pinned by the mixed-lengths test above
    wide_eng, wide = run(False)
    narrow_eng, narrow = run(True)
    assert narrow == wide                       # bit-identical streams
    # full-width engine keeps the PR 3 single-program guarantee...
    assert wide_eng.compile_counts()["step"] == 1
    # ...while the bucketed engine compiles one decode program per
    # pow2 width actually used, bounded by the log2 ladder
    ladder = int(np.log2(narrow_eng.max_pages)) + 1
    steps = narrow_eng.compile_counts()["step"]
    assert 0 < steps <= ladder
    assert steps == len(narrow_eng._step_widths)
    # short-prompt traffic really did use a narrower table
    assert min(narrow_eng._step_widths) < narrow_eng.max_pages
    assert all(w & (w - 1) == 0 for w in narrow_eng._step_widths)


@pytest.mark.slow
def test_oversubscribed_pool_defers_and_completes():
    """A pool smaller than full occupancy defers admission until pages
    free up, and every request still decodes the dense-greedy stream."""
    cfg = REDUCED["deepseek-7b"]()
    key = jax.random.PRNGKey(3)
    params, _ = lm.init_lm(key, cfg, dtype=jnp.float32)
    # 2 slots x 4 max_pages = 8 pages for full occupancy; give 5
    eng = Engine(params, cfg, n_slots=2, max_len=32, eos_id=-1,
                 paging=PagingConfig(page_size=8, n_pages=5))
    prompts = [jax.random.randint(jax.random.fold_in(key, i), (7,), 0,
                                  cfg.vocab) for i in range(3)]
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new=4))
    done = eng.run()
    assert sorted(c.rid for c in done) == [0, 1, 2]
    for i, p in enumerate(prompts):
        want = manual_greedy(params, cfg, p, 4, 32)
        assert next(c for c in done if c.rid == i).tokens == want
    assert eng.pool.live_pages() == 0            # everything reclaimed
    assert len(eng.pool.free) == 5


@pytest.mark.slow
def test_max_new_one_and_submit_validation():
    """max_new=1 completes with exactly the prefill-sampled token (no
    stray decode step), and oversized prompts are rejected at submit
    instead of wedging the run loop. A prompt of exactly max_len is
    serviceable (prefill-only: it writes exactly max_len KV rows and
    retires at admission with the prefill-sampled token)."""
    cfg = REDUCED["deepseek-7b"]()
    key = jax.random.PRNGKey(5)
    params, _ = lm.init_lm(key, cfg, dtype=jnp.float32)
    eng = Engine(params, cfg, n_slots=2, max_len=32, eos_id=-1)
    with pytest.raises(ValueError):
        eng.submit(Request(rid=9, prompt=jnp.zeros((33,), jnp.int32)))
    with pytest.raises(ValueError):
        eng.submit(Request(rid=9, prompt=jnp.zeros((0,), jnp.int32)))
    with pytest.raises(ValueError):
        eng.submit(Request(rid=9, prompt=jnp.zeros((4,), jnp.int32),
                           max_new=0))
    # plen == max_len: accepted, effective max_new clamped to 1
    full_p = jax.random.randint(jax.random.fold_in(key, 32), (32,), 0,
                                cfg.vocab)
    eng.submit(Request(rid=32, prompt=full_p, max_new=5))
    done = eng.run()
    got = next(c for c in done if c.rid == 32)
    assert got.tokens == manual_greedy(params, cfg, full_p, 1, 32)
    assert len(got.tokens) == 1
    assert eng.pool.live_pages() == 0
    eng.completed.clear()            # run() accumulates completions
    for i in range(3):               # more requests than slots
        eng.submit(Request(rid=i, prompt=jax.random.randint(
            jax.random.fold_in(key, i), (5,), 0, cfg.vocab), max_new=1))
    done = eng.run()
    assert sorted(c.rid for c in done) == [0, 1, 2]
    assert all(len(c.tokens) == 1 for c in done)
    for i in range(3):
        prompt = jax.random.randint(jax.random.fold_in(key, i), (5,), 0,
                                    cfg.vocab)
        want = manual_greedy(params, cfg, prompt, 1, 32)
        assert next(c for c in done if c.rid == i).tokens == want
    assert eng.pool.live_pages() == 0
    # prompt at max_len-1 still gets its one in-bounds decode step
    # (write at position max_len-1) before the length cap retires it
    long_p = jax.random.randint(jax.random.fold_in(key, 9), (31,), 0,
                                cfg.vocab)
    eng.submit(Request(rid=9, prompt=long_p, max_new=4))
    done = eng.run()
    got = next(c for c in done if c.rid == 9)
    assert got.tokens == manual_greedy(params, cfg, long_p, 2, 32)
    assert got.ttft_s > 0 and got.latency_s >= got.ttft_s


@pytest.mark.slow
def test_engine_kv_trace_and_ttft_recorded():
    cfg = REDUCED["deepseek-7b"]()
    key = jax.random.PRNGKey(4)
    params, _ = lm.init_lm(key, cfg, dtype=jnp.float32)
    eng = Engine(params, cfg, n_slots=2, max_len=32, eos_id=-1)
    eng.submit(Request(rid=0, prompt=jax.random.randint(key, (6,), 0,
                                                        cfg.vocab),
                       max_new=4))
    done = eng.run()
    assert done[0].ttft_s > 0
    assert len(eng.kv_trace) == 3                # max_new - 1 decode steps
    assert eng.kv_trace[0] == [7]                # 6 prompt + 1 decoded
