import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import attention, blocks, lm

try:
    # CI property runs must be reproducible: a derandomized profile is
    # registered and active by default, so every run replays the same
    # example sequence (no flaky shrink chains, failures reproduce from
    # the printed blob). Set HYPOTHESIS_PROFILE=dev locally to explore
    # fresh random examples, or HYPOTHESIS_SEED=<n> to pin a specific
    # non-derandomized draw sequence.
    import random

    from hypothesis import settings

    _seed = os.environ.get("HYPOTHESIS_SEED")
    settings.register_profile("ci", derandomize=_seed is None,
                              deadline=None, print_blob=True)
    settings.register_profile("dev", deadline=None)
    if _seed is not None:
        random.seed(int(_seed))      # hypothesis's entropy fallback
    settings.load_profile(os.environ.get("HYPOTHESIS_PROFILE", "ci"))
except ImportError:                  # fast tier: no hypothesis installed
    pass


@pytest.fixture(autouse=True, scope="module")
def _bound_live_executables():
    """Drop compiled programs between test modules. A full-suite process
    otherwise accumulates every module's jitted engines plus the eager
    dense-oracle scans; past a few hundred live XLA:CPU executables a
    late compile segfaults inside backend_compile. Modules don't share
    engines, so per-module clearing only re-pays the handful of common
    oracle programs."""
    yield
    jax.clear_caches()


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def manual_greedy(params, cfg, prompt, n_new, max_len):
    """Dense-cache greedy decode: the serving engines' parity oracle."""
    logits, cache = lm.prefill(params, prompt[None], cfg, alloc=max_len)
    toks = [int(jnp.argmax(logits[0]))]
    lengths = jnp.asarray([prompt.shape[0]], jnp.int32)
    for _ in range(n_new - 1):
        lg, cache = lm.decode_step(
            params, cache, jnp.asarray([[toks[-1]]], jnp.int32),
            lengths, cfg)
        toks.append(int(jnp.argmax(lg[0])))
        lengths = lengths + 1
    return toks


def random_paged_cache(cfg, key, *, n_slots=2, max_len=64, page_size=8):
    """A paged cache whose pools hold random K/V (a written prefix) and
    shuffled block tables covering every slot's pages."""
    cache = lm.init_paged_cache(cfg, n_slots, max_len, page_size=page_size,
                                dtype=jnp.float32)
    leaves, tdef = jax.tree.flatten(cache)
    cache = jax.tree.unflatten(tdef, [
        jax.random.normal(jax.random.fold_in(key, i), a.shape)
        for i, a in enumerate(leaves)])
    mp = max_len // page_size
    perm = np.random.default_rng(0).permutation(n_slots * mp)
    return cache, jnp.asarray(perm.reshape(n_slots, mp).astype(np.int32))


def layerwise_paged_step(params, cache, tokens, cfg, *, pages, lengths=None,
                         offset=None, chunk_len=None):
    """Layer-by-layer oracle of the paged decode and chunk steps, as
    they ran when the layer scan sliced each layer's pool out and
    stacked it back: a Python loop over every stage's layers slices the
    layer's own pool out (a (1, P, ps, Hkv, hd) stack, read as layer 0),
    runs the block on it, writes the layer's new K/V into it at once, and
    restacks the pools. A decode step when ``lengths`` is given, else
    one chunked-prefill step of ``chunk_len`` real rows at ``offset``.
    Attention-only paged caches (every cached block holds a ``kv``
    pool). Returns (logits (B, V), new cache)."""
    b = tokens.shape[0]
    decode = lengths is not None
    if not decode:
        offset = jnp.broadcast_to(jnp.asarray(offset, jnp.int32), (b,))
    x = lm.embed(params, tokens, cfg, None)
    new_cache = []
    for stage, sp, sc in zip(cfg.stages(), params["stages"], cache):
        pools = {key: [] for key in sc}
        for layer in range(stage.repeat):
            for i, blk in enumerate(stage.body):
                key = str(i)
                bp = (jax.tree.map(lambda a: a[layer], sp["stacked"][key])
                      if key in sp["stacked"] else sp["shared"][key])
                pool = jax.tree.map(lambda a: a[layer:layer + 1],
                                    sc[key]["kv"])
                if decode:
                    x, io = blocks.apply_block(
                        blk, bp, x, cfg=cfg, mode="decode",
                        lengths=lengths, cache={"kv": pool}, pages=pages,
                        layer=0)
                    pool = io.new_cache["kv"]
                else:
                    x, io = blocks.apply_block(
                        blk, bp, x, cfg=cfg, mode="panel", lengths=offset,
                        cache={"kv": pool}, pages=pages,
                        chunk_len=chunk_len, layer=0)
                    k, v = io.prefill_state["kv"]
                    pool = attention.write_chunk_pages(
                        pool, k, v, offset, chunk_len, pages, blk.window,
                        layer=0)
                pools[key].append(pool)
        new_cache.append({key: {"kv": jax.tree.map(
            lambda *a: jnp.concatenate(a), *per_layer)}
            for key, per_layer in pools.items()})
    if not decode:
        idx = jnp.asarray(chunk_len, jnp.int32) - 1
        x = jax.lax.dynamic_slice_in_dim(x, idx, 1, axis=1)
    return lm.unembed(params, x, cfg)[:, 0], new_cache
