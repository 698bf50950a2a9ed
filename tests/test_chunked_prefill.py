"""Chunked prefill: schedule policy, chunk attention/writes, engine
parity.

The fast tier covers the host-side chunk schedule, the multi-token page
write (padding, ring wrap, clobber guard), the prefix-gather + in-chunk
LSE merge against the dense causal oracle, and the modeled stall /
re-read trade. The slow tier drives the full engine: chunked admission
must reproduce the one-shot bucketed engine's greedy streams token for
token across mixed prompt lengths, chunk sizes that are smaller than /
equal to / not dividing the prompt, sliding-window ring wraps
mid-prompt, and the plen == max_len prefill-only edge.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import manual_greedy

from repro.configs import REDUCED
from repro.core.block_traffic import (chunked_prefill_traffic,
                                      chunked_prefill_traffic_cfg)
from repro.core.types import PagingConfig
from repro.models import lm
from repro.models.attention import (PagedKVCache, _chunked_fwd,
                                    _merge_partials, _paged_fwd,
                                    write_chunk_pages)
from repro.serve.engine import Engine, Request
from repro.serve.paging import chunk_schedule


# ----------------------------------------------------------------------
# Chunk schedule policy (fast)
# ----------------------------------------------------------------------


def test_chunk_schedule_shapes_stay_on_ladder():
    buckets = [16, 32, 64, 128]
    # chunk divides plen: all full chunks
    assert chunk_schedule(64, 32, buckets) == [(0, 32, 32), (32, 32, 32)]
    # chunk does not divide plen: final partial chunk pads to a bucket
    assert chunk_schedule(70, 32, buckets) == [
        (0, 32, 32), (32, 32, 32), (64, 6, 16)]
    # plen below the chunk: a single bucketed panel
    assert chunk_schedule(9, 32, buckets) == [(0, 9, 16)]
    for plen in range(1, 129):
        sched = chunk_schedule(plen, 32, buckets)
        # offsets tile the prompt exactly, in order
        assert sched[0][0] == 0
        assert all(a[0] + a[1] == b[0] for a, b in zip(sched, sched[1:]))
        assert sched[-1][0] + sched[-1][1] == plen
        # every compiled shape is a ladder entry at or below the chunk
        assert all(s in buckets and s <= 32 and c <= s
                   for _, c, s in sched)


# ----------------------------------------------------------------------
# Multi-token page writes (fast)
# ----------------------------------------------------------------------


def _empty_pool(n_pages, ps, hkv=2, hd=4):
    """A one-layer stack of pools, written as layer 0."""
    return PagedKVCache(k=jnp.zeros((1, n_pages, ps, hkv, hd)),
                        v=jnp.zeros((1, n_pages, ps, hkv, hd)))


def test_write_chunk_pages_positions_and_padding():
    ps, hkv, hd = 4, 2, 4
    pool = _empty_pool(5, ps, hkv, hd)
    tables = jnp.asarray([[2, 0]], jnp.int32)
    sc = 4
    k_new = (jnp.arange(1, sc + 1, dtype=jnp.float32)[None, :, None, None]
             * jnp.ones((1, sc, hkv, hd)))
    # offset 5, chunk_len 3: positions 5,6,7 -> logical page 1 (phys 0)
    # offsets 1,2,3; the padded row 3 (would-be position 8) is dropped
    pool = write_chunk_pages(pool, k_new, 2 * k_new, jnp.int32(5),
                             jnp.int32(3), tables, layer=0)
    assert bool(jnp.all(pool.k[0, 0, 1] == 1.0))
    assert bool(jnp.all(pool.k[0, 0, 2] == 2.0))
    assert bool(jnp.all(pool.k[0, 0, 3] == 3.0))
    assert bool(jnp.all(pool.v[0, 0, 1] == 2.0))
    # nothing else written anywhere (padding dropped, page 2 untouched)
    assert float(jnp.abs(pool.k).sum()) == (1 + 2 + 3) * hkv * hd
    assert float(jnp.abs(pool.k[0, 0, 0]).sum()) == 0.0


def test_write_chunk_pages_ring_wraps_window():
    ps, hkv, hd = 4, 1, 2
    pool = _empty_pool(4, ps, hkv, hd)
    tables = jnp.asarray([[1, 2, 0]], jnp.int32)  # ring = first 2 pages
    sc = 4
    k_new = (jnp.arange(1, sc + 1, dtype=jnp.float32)[None, :, None, None]
             * jnp.ones((1, sc, hkv, hd)))
    # window=8: positions 6..9 -> ring idx 6,7,0,1 -> (phys 2, off 2/3)
    # and wrap to (phys 1, off 0/1)
    pool = write_chunk_pages(pool, k_new, k_new, jnp.int32(6),
                             jnp.int32(4), tables, window=8, layer=0)
    assert bool(jnp.all(pool.k[0, 2, 2] == 1.0))
    assert bool(jnp.all(pool.k[0, 2, 3] == 2.0))
    assert bool(jnp.all(pool.k[0, 1, 0] == 3.0))
    assert bool(jnp.all(pool.k[0, 1, 1] == 4.0))


def test_write_chunk_pages_keeps_only_last_window_of_chunk():
    """A chunk longer than the window writes only its last ``window``
    positions — the earlier rows would be clobbered at the same ring
    slots anyway and no later query needs them; dropping them keeps the
    scatter's target indices duplicate-free (defined semantics)."""
    ps, hkv, hd = 2, 1, 2
    pool = _empty_pool(3, ps, hkv, hd)
    tables = jnp.asarray([[1, 0]], jnp.int32)     # ring = 2 pages (w=4)
    sc = 6
    k_new = (jnp.arange(1, sc + 1, dtype=jnp.float32)[None, :, None, None]
             * jnp.ones((1, sc, hkv, hd)))
    # window=4, positions 0..5: keep 2..5 at ring idx 2,3,0,1
    pool = write_chunk_pages(pool, k_new, k_new, jnp.int32(0),
                             jnp.int32(6), tables, window=4, layer=0)
    assert bool(jnp.all(pool.k[0, 0, 0] == 3.0))  # pos 2 -> phys 0 off 0
    assert bool(jnp.all(pool.k[0, 0, 1] == 4.0))
    assert bool(jnp.all(pool.k[0, 1, 0] == 5.0))  # pos 4 wraps
    assert bool(jnp.all(pool.k[0, 1, 1] == 6.0))


# ----------------------------------------------------------------------
# Prefix gather + in-chunk merge vs the dense causal oracle (fast)
# ----------------------------------------------------------------------


def _linear_pool(k, v, off, ps, rng):
    """Prefix positions 0..off-1 scattered into shuffled pages."""
    b, _, hkv, hd = k.shape
    npp = -(-off // ps)
    n_pages = b * npp
    perm = rng.permutation(n_pages)
    tables = perm.reshape(b, npp).astype(np.int32)
    pool_k = np.zeros((n_pages + 1, ps, hkv, hd), np.float32)
    pool_v = np.zeros_like(pool_k)
    for bi in range(b):
        for p in range(off):
            pool_k[tables[bi, p // ps], p % ps] = np.asarray(k[bi, p])
            pool_v[tables[bi, p // ps], p % ps] = np.asarray(v[bi, p])
    return jnp.asarray(pool_k), jnp.asarray(pool_v), jnp.asarray(tables)


def _ring_pool(k, v, off, window, ps):
    """Prefix scattered the way successive chunk writes leave a ring:
    slot r holds the newest position ≡ r (mod window) below off."""
    b, _, hkv, hd = k.shape
    n_ring = max(window // ps, 1)
    n_pages = b * n_ring
    tables = np.arange(n_pages).reshape(b, n_ring).astype(np.int32)
    pool_k = np.zeros((n_pages + 1, ps, hkv, hd), np.float32)
    pool_v = np.zeros_like(pool_k)
    for bi in range(b):
        for p in range(max(0, off - window), off):
            r = p % window
            pool_k[tables[bi, r // ps], r % ps] = np.asarray(k[bi, p])
            pool_v[tables[bi, r // ps], r % ps] = np.asarray(v[bi, p])
    return jnp.asarray(pool_k), jnp.asarray(pool_v), jnp.asarray(tables)


@pytest.mark.parametrize("window", [0, 8])
@pytest.mark.parametrize("off", [0, 5, 13])
def test_chunk_attention_matches_dense_causal(window, off, rng):
    """prefix-page gather (+ per-query causal/window offsets) merged
    with the in-chunk causal partial == one dense causal pass over the
    whole sequence, for global and sliding-window layers, including an
    empty prefix (the first chunk)."""
    key = jax.random.PRNGKey(off * 10 + window)
    b, hq, hkv, hd, ps = 2, 4, 2, 8, 4
    total = off + 11                                       # chunk of 11
    sc = total - off
    q = jax.random.normal(key, (b, hq, sc, hd))
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, total, hkv, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, total, hkv, hd))
    kh, vh = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
    limit = jnp.full((b,), total)
    ref, _ = _chunked_fwd(q, kh, vh, limit, causal=True, window=window,
                          q_offset=off, chunk=1024)

    kc = kh[:, :, off:]
    vc = vh[:, :, off:]
    out_c, lse_c = _chunked_fwd(q, kc, vc, jnp.full((b,), sc),
                                causal=True, window=window, q_offset=0,
                                chunk=1024)
    if window:
        pool_k, pool_v, tables = _ring_pool(k, v, off, window, ps)
    else:
        pool_k, pool_v, tables = _linear_pool(k, v, max(off, 1), ps, rng)
    offs = jnp.full((b,), off, jnp.int32)
    out_p, lse_p = _paged_fwd(q, pool_k[None], pool_v[None], tables, offs,
                              layer=0, chunk=1024, q_offset=offs,
                              window=window)
    out = _merge_partials(out_c, lse_c, out_p, lse_p)
    assert float(jnp.max(jnp.abs(out - ref))) < 1e-5


# ----------------------------------------------------------------------
# Traffic model + engine config validation (fast)
# ----------------------------------------------------------------------


def test_chunked_prefill_traffic_model():
    row = 2 * 2 * 8 * 2                       # Hkv=2, hd=8, bf16
    out = chunked_prefill_traffic(70, chunk_size=32, page_size=16,
                                  n_global=3, n_kv_heads=2, head_dim=8)
    assert out["n_chunks"] == 3
    # the removed stall: one 70-row program -> at most a 32-row panel
    assert out["stall_rows_one_shot"] == 70
    assert out["stall_rows_chunked"] == 32
    # re-read: chunk 1 re-gathers 32 prefix rows, chunk 2 re-gathers 64
    assert out["prefix_reread_bytes"] == 3 * (32 + 64) * row
    # a prompt that fits one chunk pays nothing and removes nothing
    one = chunked_prefill_traffic(20, chunk_size=32, page_size=16,
                                  n_global=3, n_kv_heads=2, head_dim=8)
    assert one["n_chunks"] == 1 and one["prefix_reread_bytes"] == 0
    assert one["stall_rows_chunked"] == one["stall_rows_one_shot"] == 20
    # windowed layers re-read at most the ring
    cfg = REDUCED["gemma3-27b"]()             # window=16
    g = chunked_prefill_traffic_cfg(cfg, 64, chunk_size=16, page_size=8)
    grow = 2 * cfg.n_kv_heads * cfg.head_dim * 2
    from repro.core.block_traffic import kv_layer_counts
    n_global, n_local, _ = kv_layer_counts(cfg)
    want = (n_global * (16 + 32 + 48) + n_local * (16 + 16 + 16)) * grow
    assert g["prefix_reread_bytes"] == want


def test_engine_rejects_bad_chunk_config():
    key = jax.random.PRNGKey(0)
    cfg = REDUCED["deepseek-7b"]()
    params, _ = lm.init_lm(key, cfg, dtype=jnp.float32)
    # off the bucket ladder: compile count would be unbounded
    with pytest.raises(ValueError):
        Engine(params, cfg, n_slots=2, max_len=64,
               paging=PagingConfig(prefill_chunk=24))
    # recurrent state cannot be split across chunk forwards
    rcfg = REDUCED["rwkv6-3b"]()
    rparams, _ = lm.init_lm(key, rcfg, dtype=jnp.float32)
    with pytest.raises(ValueError):
        Engine(rparams, rcfg, n_slots=2, max_len=64,
               paging=PagingConfig(prefill_chunk=16))
    eng = Engine(params, cfg, n_slots=2, max_len=64,
                 paging=PagingConfig(prefill_chunk=16))
    assert eng.prefill_chunk == 16


# ----------------------------------------------------------------------
# Engine parity: chunked == one-shot bucketed greedy streams (slow)
# ----------------------------------------------------------------------


def _greedy_engine_run(params, cfg, prompts, *, chunk, max_len, n_new,
                       page_size=8, n_slots=2):
    eng = Engine(params, cfg, n_slots=n_slots, max_len=max_len, eos_id=-1,
                 paging=PagingConfig(page_size=page_size,
                                     prefill_chunk=chunk))
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new=n_new))
    done = eng.run()
    return eng, {c.rid: c for c in done}


@pytest.mark.slow
@pytest.mark.parametrize("chunk", [16, 32])
def test_chunked_matches_one_shot_mixed_lengths(chunk):
    """Greedy streams are identical to the dense-cache oracle across
    prompts that are shorter than the chunk (one-shot path), equal to
    it, a multiple of it, and not divisible by it — with more requests
    than slots so chunked admissions interleave with decode."""
    cfg = REDUCED["deepseek-7b"]()
    key = jax.random.PRNGKey(0)
    params, _ = lm.init_lm(key, cfg, dtype=jnp.float32)
    plens = [3, chunk, chunk + 5, 2 * chunk, 37, 50]
    prompts = [jax.random.randint(jax.random.fold_in(key, i), (p,), 0,
                                  cfg.vocab) for i, p in enumerate(plens)]
    n_new = 5
    eng, by_rid = _greedy_engine_run(params, cfg, prompts, chunk=chunk,
                                     max_len=96, n_new=n_new)
    assert sorted(by_rid) == list(range(len(prompts)))
    for i, p in enumerate(prompts):
        want = manual_greedy(params, cfg, p, n_new, 96)
        assert by_rid[i].tokens == want, (i, by_rid[i].tokens, want)
    # chunked completions carry TTFT + full inter-token latency trails
    for c in by_rid.values():
        assert c.ttft_s > 0 and len(c.itl_s) == len(c.tokens) - 1
    # prompts <= chunk took the one-shot path; longer ones chunked
    assert eng._chunk_shapes and eng._prefill_lens
    # the auditor's static enumeration predicts the jit caches exactly
    from repro.analysis import compile_bound
    expected = compile_bound.predict_compile_counts(
        plens, max_len=96, prefill_chunk=chunk)
    assert eng.compile_counts() == expected
    assert compile_bound.check_engine_counts(eng, expected).ok


@pytest.mark.slow
def test_chunked_sliding_window_ring_wrap_mid_prompt():
    """gemma3-style local/global mix: prompts longer than the window
    chunk-prefill across the ring wrap (later chunks' prefix gathers
    recover ring positions), and decode continues past it — token
    streams must equal the dense ring-cache oracle."""
    cfg = REDUCED["gemma3-27b"]()                 # window=16
    key = jax.random.PRNGKey(1)
    params, _ = lm.init_lm(key, cfg, dtype=jnp.float32)
    plens = [40, 20, 5, 33]                       # 40/33 wrap mid-prompt
    prompts = [jax.random.randint(jax.random.fold_in(key, i), (p,), 0,
                                  cfg.vocab) for i, p in enumerate(plens)]
    n_new = 6
    _, by_rid = _greedy_engine_run(params, cfg, prompts, chunk=16,
                                   max_len=64, n_new=n_new)
    for i, p in enumerate(prompts):
        want = manual_greedy(params, cfg, p, n_new, 64)
        assert by_rid[i].tokens == want, (i, by_rid[i].tokens, want)


@pytest.mark.slow
def test_chunked_plen_eq_max_len_edge():
    """A prompt of exactly max_len chunk-prefills to the last page and
    retires at the final chunk with the prefill-sampled token (the PR 4
    prefill-only clamp), releasing every page."""
    cfg = REDUCED["deepseek-7b"]()
    key = jax.random.PRNGKey(5)
    params, _ = lm.init_lm(key, cfg, dtype=jnp.float32)
    full_p = jax.random.randint(jax.random.fold_in(key, 9), (32,), 0,
                                cfg.vocab)
    eng, by_rid = _greedy_engine_run(params, cfg, [full_p], chunk=16,
                                     max_len=32, n_new=5)
    assert by_rid[0].tokens == manual_greedy(params, cfg, full_p, 1, 32)
    assert len(by_rid[0].tokens) == 1
    assert eng.pool.live_pages() == 0
    assert len(eng._chunk_shapes) == 1            # both chunks shape 16
