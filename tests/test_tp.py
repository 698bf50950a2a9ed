"""Tensor-parallel placement tests.

Fast host-side tests (permutation algebra, validation, CLI parsing,
traffic model, construction-time rejection) run on a single device.

The parity tests need a real multi-device world: they are marked
``dist`` and run in-process in the CI ``dist`` tier, which exports
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` before pytest
starts (the flag must precede jax initialisation, so it cannot be set
from inside a test). On a single-device world they skip.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import layerwise_paged_step, random_paged_cache

from repro.configs import REDUCED
from repro.core import quant
from repro.core.block_traffic import serve_tp_traffic
from repro.core.types import PagingConfig
from repro.models import lm
from repro.serve.engine import Engine, Request
from repro.serve.placement import (CACHE, PARAMS, REP, SingleDevice,
                                   TensorParallel, from_mesh_shape,
                                   shard_perm)

# ---------------------------------------------------------------- fast


def test_shard_perm_is_segmentwise():
    widths = (8, 4, 4)
    t = 2
    idx = shard_perm(widths, t)
    assert sorted(idx) == list(range(sum(widths)))
    # label each source column (segment, position); after permutation a
    # plain t-way split must hand shard i segment-s columns
    # [i*w/t, (i+1)*w/t) for every segment, in segment order
    labels = [(s, c) for s, w in enumerate(widths) for c in range(w)]
    permuted = [labels[i] for i in idx]
    per = len(idx) // t
    for i in range(t):
        shard = permuted[i * per:(i + 1) * per]
        want = [(s, c) for s, w in enumerate(widths)
                for c in range(i * w // t, (i + 1) * w // t)]
        assert shard == want


def test_shard_perm_matmul_equivalence(rng):
    """Permuted-then-split fused panel computes the same projections."""
    widths = (6, 3, 3)
    t = 3
    x = rng.standard_normal((2, 5)).astype(np.float32)
    w = rng.standard_normal((5, sum(widths))).astype(np.float32)
    idx = shard_perm(widths, t)
    wp = w[:, idx]
    full = x @ w
    segs = np.split(full, np.cumsum(widths)[:-1], axis=1)
    per = sum(widths) // t
    for i in range(t):
        local = x @ wp[:, i * per:(i + 1) * per]
        offs = 0
        for s, wdt in enumerate(widths):
            p = wdt // t
            got = local[:, offs:offs + p]
            want = segs[s][:, i * p:(i + 1) * p]
            np.testing.assert_allclose(got, want, rtol=1e-6)
            offs += p


def test_validate_rejects_indivisible_heads():
    cfg = REDUCED["gemma3-27b"]()          # n_kv_heads = 2
    with pytest.raises(ValueError, match="cannot shard"):
        TensorParallel(4).validate(cfg)
    TensorParallel(2).validate(cfg)        # divisible: fine


def test_validate_rejects_non_bucketing_arch():
    cfg = REDUCED["rwkv6-3b"]()
    with pytest.raises(ValueError, match="causal"):
        TensorParallel(2).validate(cfg)


def test_from_mesh_shape_parsing():
    assert isinstance(from_mesh_shape(""), SingleDevice)
    assert isinstance(from_mesh_shape("1"), SingleDevice)
    assert isinstance(from_mesh_shape("model=1"), SingleDevice)
    tp = from_mesh_shape("4")
    assert isinstance(tp, TensorParallel) and tp.n_shards == 4
    tp = from_mesh_shape("model=2")
    assert isinstance(tp, TensorParallel) and tp.n_shards == 2
    with pytest.raises(ValueError, match="axis"):
        from_mesh_shape("data=2")
    with pytest.raises(ValueError):
        from_mesh_shape("banana")
    with pytest.raises(ValueError):
        from_mesh_shape("0")


def test_serve_tp_traffic_model():
    cfg = REDUCED["deepseek-7b"]()
    trace = [[16, 16, 16, 16]] * 10
    kw = dict(n_slots=4, max_len=128, page_size=16)
    t4 = serve_tp_traffic(trace, cfg, tp=4, **kw)
    t2 = serve_tp_traffic(trace, cfg, tp=2, **kw)
    assert t4["single_bytes"] == t2["single_bytes"]
    # sharding must help, monotonically, and the all-reduce term must be
    # priced (nonzero) yet not erase the win
    assert t4["allreduce_bytes"] > 0
    assert t4["per_device_bytes"] < t2["per_device_bytes"]
    assert t2["per_device_bytes"] < t2["single_bytes"]
    assert t4["ratio"] > t2["ratio"] > 1.0
    parts = (t4["kv_bytes"] // 4 + t4["weight_bytes"] // 4
             + t4["lm_head_bytes"] // 4 + t4["allreduce_bytes"])
    assert t4["per_device_bytes"] == parts


def test_engine_rejects_indivisible_mesh_at_construction():
    cfg = REDUCED["gemma3-27b"]()
    params, _ = lm.init_lm(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    # raised by validate() before the mesh (or any device buffer) is
    # built, so it works — and fails fast — on a 1-device world too
    with pytest.raises(ValueError, match="cannot shard"):
        Engine(params, cfg, n_slots=2, max_len=64, eos_id=-1,
               placement=TensorParallel(4))


# ------------------------------------------------- dist (emulated mesh)

PROMPTS = [5, 37, 64, 12, 90, 23, 48, 7]


def _need_devices(n):
    if jax.device_count() < n:
        pytest.skip(f"needs {n} devices (run under XLA_FLAGS="
                    "--xla_force_host_platform_device_count=8)")


def _greedy_streams(params, cfg, place, *, n_slots=4, max_len=128,
                    chunk=32, max_new=8, prompts=PROMPTS):
    rng = np.random.default_rng(0)
    eng = Engine(params, cfg, n_slots=n_slots, max_len=max_len,
                 eos_id=-1, temperature=0.0,
                 paging=PagingConfig(prefill_chunk=chunk),
                 placement=place)
    for rid, plen in enumerate(prompts):
        prompt = jnp.asarray(rng.integers(2, cfg.vocab, size=(plen,)),
                             jnp.int32)
        eng.submit(Request(rid=rid, prompt=prompt, max_new=max_new))
    done = eng.run()
    counts = eng.compile_counts()
    n_chunk_shapes = len([b for b in eng.buckets if b <= chunk])
    assert (counts["prefill"] + counts["chunk"] + counts["step"]
            <= len(eng.buckets) + n_chunk_shapes + 1), counts
    return {c.rid: c.tokens for c in done}, counts


@pytest.mark.slow
@pytest.mark.dist
@pytest.mark.parametrize("t", [1, 2, 4])
def test_tp_parity_deepseek(t):
    """Greedy streams over a mixed trace (chunked prefill mid-stream)
    are bit-identical to single-device, and the compile-count bound
    survives sharding exactly."""
    _need_devices(t)
    cfg = REDUCED["deepseek-7b"]()
    params, _ = lm.init_lm(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    ref, ref_counts = _greedy_streams(params, cfg, SingleDevice())
    got, counts = _greedy_streams(params, cfg, TensorParallel(t))
    assert got == ref
    assert counts == ref_counts


@pytest.mark.slow
@pytest.mark.dist
def test_tp_parity_gemma3_sliding_window():
    """Sliding-window attention + tied embeddings + non-gated MLP: the
    kv-head-sharded pools and replicated unembed stay exact."""
    _need_devices(2)
    cfg = REDUCED["gemma3-27b"]()
    params, _ = lm.init_lm(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    ref, _ = _greedy_streams(params, cfg, SingleDevice())
    got, _ = _greedy_streams(params, cfg, TensorParallel(2))
    assert got == ref


@pytest.mark.slow
@pytest.mark.dist
def test_tp_parity_int8_weights():
    """Weight-only int8 panels: per-output-channel scales split with
    column shards and replicate across row shards."""
    _need_devices(4)
    cfg = REDUCED["deepseek-7b"]()
    params, _ = lm.init_lm(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    qparams = quant.quantize_tree(params, quant.lm_weight_predicate)
    ref, _ = _greedy_streams(qparams, cfg, SingleDevice())
    got, _ = _greedy_streams(qparams, cfg, TensorParallel(4))
    assert got == ref


@pytest.mark.slow
@pytest.mark.dist
@pytest.mark.parametrize("mode", ["decode", "chunk"])
def test_tp_carried_pools_match_layerwise_oracle(mode):
    """2-way TP: inside ``shard_map``, on pools sharded over KV heads,
    the layer scan that carries the stacked pools matches the
    layer-by-layer oracle (``conftest.layerwise_paged_step``) run under
    the same placement, in logits and in every pool."""
    _need_devices(2)
    cfg = REDUCED["deepseek-7b"]()
    key = jax.random.PRNGKey(0)
    params, _ = lm.init_lm(key, cfg, dtype=jnp.float32)
    cache, tables = random_paged_cache(cfg, key)
    tp = TensorParallel(2)
    tp.validate(cfg)
    rcfg = tp.compute_cfg(cfg)
    args = (tp.prepare_params(params, cfg), tp.prepare_cache(cache))
    if mode == "decode":
        args += (jnp.asarray([[3], [7]], jnp.int32),
                 jnp.asarray([5, 37], jnp.int32), tables)

        def new(p, c, t, n, tb):
            return lm.decode_step(p, c, t, n, rcfg, pages=tb)

        def old(p, c, t, n, tb):
            return layerwise_paged_step(p, c, t, rcfg, pages=tb,
                                        lengths=n)
    else:
        args += (jax.random.randint(key, (1, 16), 0, cfg.vocab),
                 jnp.int32(21), jnp.int32(12), tables[1:])

        def new(p, c, t, o, n, tb):
            return lm.prefill_chunk(p, c, t, rcfg, offset=o, chunk_len=n,
                                    pages=tb)

        def old(p, c, t, o, n, tb):
            return layerwise_paged_step(p, c, t, rcfg, pages=tb, offset=o,
                                        chunk_len=n)
    kinds = (PARAMS, CACHE) + (REP,) * (len(args) - 2)
    run = {f: tp.jit(f, kinds=kinds, out_kinds=(REP, CACHE))
           for f in (new, old)}
    logits, pools = run[new](*args)
    want_logits, want_pools = run[old](*args)
    tol = dict(rtol=1e-5, atol=1e-6)       # as in test_paged's oracle
    np.testing.assert_allclose(np.asarray(logits),
                               np.asarray(want_logits), **tol)
    for got, want in zip(jax.tree.leaves(pools),
                         jax.tree.leaves(want_pools)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   **tol)


@pytest.mark.slow
@pytest.mark.dist
@pytest.mark.parametrize("t", [1, 4])
def test_tp_programs_keep_their_names(t):
    """Under TP the shard_map body takes the entry point's name, so the
    programs lower as ``jit_decode_step``, ``jit_prefill`` and
    ``jit_prefill_chunk``, as they do on one device."""
    _need_devices(t)
    cfg = REDUCED["deepseek-7b"]()
    params, _ = lm.init_lm(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    eng = Engine(params, cfg, n_slots=2, max_len=64, eos_id=-1,
                 paging=PagingConfig(prefill_chunk=16),
                 placement=TensorParallel(t))
    names = {"step": "decode_step", "prefill": "prefill",
             "chunk": "prefill_chunk"}
    entries = eng.audit_entry_points()
    assert sorted(e[0] for e in entries) == sorted(names)
    for entry, fn, args, _ in entries:
        assert fn.__name__ == names[entry]
        assert fn.lower(*args).as_text().startswith(
            f"module @jit_{names[entry]} ")
