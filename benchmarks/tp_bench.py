"""PR 6 tensor-parallel serving bench: scaling + parity + traffic.

Runs the paged serving engine over emulated host meshes (under
``JAX_PLATFORMS=cpu`` the process forces
``--xla_force_host_platform_device_count=8`` before importing jax, so it
must run in its own interpreter — ``benchmarks/run.py`` launches it as a
subprocess before touching jax itself) and writes ``BENCH_PR6.json``:

  * ``parity``  — greedy token streams at mesh sizes {1, 2, 4} checked
    bit-identical against the single-device engine over the mixed-
    length trace (chunked prefill mid-stream included);
  * ``scaling`` — wall time / tokens-per-s per mesh size. Emulated CPU
    "devices" share the same cores, so wall time does NOT drop with
    shards here — the number that transfers to real meshes is the
    modeled per-device traffic;
  * ``traffic`` — ``core.block_traffic.serve_tp_traffic`` over the
    recorded decode trace: per-device KV + weight bytes at tp=4 with
    the all-reduce term. Asserts the acceptance criterion — per-device
    bytes drop >= 3x vs single-device;
  * ``compiles`` — entry-point program counts per mesh size, asserted
    within the ``n_buckets + n_chunk_shapes + 1`` bound (the bound must
    survive sharding).
"""
from __future__ import annotations

import os

_flags = os.environ.get("XLA_FLAGS", "")
if (os.environ.get("JAX_PLATFORMS") == "cpu"
        and "host_platform_device_count" not in _flags):
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import json                                              # noqa: E402
import sys                                               # noqa: E402
import time                                              # noqa: E402

import jax                                               # noqa: E402
import jax.numpy as jnp                                  # noqa: E402

from repro.configs import REDUCED                        # noqa: E402
from repro.core.block_traffic import serve_tp_traffic    # noqa: E402
from repro.core.types import PagingConfig                # noqa: E402
from repro.models import lm                              # noqa: E402
from repro.serve.engine import Engine, Request           # noqa: E402
from repro.serve.placement import (SingleDevice,         # noqa: E402
                                   TensorParallel)

PROMPT_LENS = [5, 9, 17, 33, 12, 47, 7, 24, 14, 40, 6, 20]
MESH_SIZES = (1, 2, 4)


def _drive(params, cfg, placement, *, n_slots, max_len, page_size,
           chunk, max_new):
    key = jax.random.PRNGKey(0)
    eng = Engine(params, cfg, n_slots=n_slots, max_len=max_len,
                 eos_id=-1,
                 paging=PagingConfig(page_size=page_size,
                                     prefill_chunk=chunk),
                 placement=placement)
    from repro.serve.paging import bucket_for
    warm = sorted({bucket_for(p, eng.buckets) for p in PROMPT_LENS})
    for i, plen in enumerate(min(b, max_len - 2) for b in warm):
        eng.submit(Request(rid=-1 - i,
                           prompt=jnp.zeros((plen,), jnp.int32),
                           max_new=2))
    eng.run()
    eng.completed.clear()
    for i, plen in enumerate(PROMPT_LENS):
        prompt = jax.random.randint(jax.random.fold_in(key, i), (plen,),
                                    0, cfg.vocab)
        eng.submit(Request(rid=i, prompt=prompt, max_new=max_new))
    t0 = time.perf_counter()
    done = eng.run()
    wall = time.perf_counter() - t0
    streams = {c.rid: c.tokens for c in done}
    counts = eng.compile_counts()
    n_chunk_shapes = len([b for b in eng.buckets if b <= chunk])
    assert (counts["prefill"] + counts["chunk"] + counts["step"]
            <= len(eng.buckets) + n_chunk_shapes + 1), (
        f"compile bound broken under {placement.describe()}: {counts}")
    return streams, wall, eng, counts


def tp_bench(emit, json_path=None, *, n_slots: int = 4,
             max_len: int = 128, page_size: int = 16, chunk: int = 32,
             max_new: int = 16):
    cfg = REDUCED["deepseek-7b"]()
    params, _ = lm.init_lm(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    kw = dict(n_slots=n_slots, max_len=max_len, page_size=page_size,
              chunk=chunk, max_new=max_new)

    ref, ref_wall, ref_eng, ref_counts = _drive(
        params, cfg, SingleDevice(), **kw)
    total_new = sum(len(t) for t in ref.values())
    scaling = [{"mesh": "single", "tp": 1, "wall_s": ref_wall,
                "tokens_per_s": total_new / ref_wall}]
    parity = {}
    compiles = {"single": ref_counts}
    for t in MESH_SIZES:
        streams, wall, _, counts = _drive(
            params, cfg, TensorParallel(t), **kw)
        ok = streams == ref
        parity[f"tp{t}"] = bool(ok)
        compiles[f"tp{t}"] = counts
        scaling.append({"mesh": f"model={t}", "tp": t, "wall_s": wall,
                        "tokens_per_s": total_new / wall})
        emit(f"bench.tp.wall.tp{t}", wall * 1e6,
             f"parity={'OK' if ok else 'MISMATCH'} "
             f"{total_new / wall:.1f} tok/s")
        assert ok, (
            f"TP={t} greedy stream diverged from single-device: "
            f"{ {r: (ref[r], streams.get(r)) for r in ref if ref[r] != streams.get(r)} }")

    tp_max = MESH_SIZES[-1]
    traffic = serve_tp_traffic(ref_eng.kv_trace, cfg, n_slots=n_slots,
                               max_len=max_len,
                               page_size=ref_eng.page_size, tp=tp_max,
                               dtype_bytes=4)
    emit("bench.tp.traffic", 0,
         f"per-device {traffic['per_device_bytes']}B vs single "
         f"{traffic['single_bytes']}B (ratio {traffic['ratio']:.2f}x, "
         f"all-reduce {traffic['allreduce_bytes']}B)")
    # acceptance (ISSUE 6): per-device modeled KV+weight bytes drop >= 3x
    # at tp=4, with the all-reduce term included
    assert traffic["ratio"] >= 3.0, (
        f"per-device traffic ratio {traffic['ratio']:.2f} < 3.0 at "
        f"tp={tp_max}")
    assert traffic["allreduce_bytes"] > 0

    result = {"parity": parity, "scaling": scaling, "traffic": traffic,
              "compiles": compiles,
              "config": {"arch": cfg.name, "n_slots": n_slots,
                         "max_len": max_len, "page_size": page_size,
                         "prefill_chunk": chunk,
                         "prompt_lens": PROMPT_LENS,
                         "max_new": max_new, "mesh_sizes": MESH_SIZES,
                         "devices": jax.device_count()}}
    if json_path:
        with open(json_path, "w") as f:
            json.dump(result, f, indent=2)
    return result


def main():
    json_path = sys.argv[1] if len(sys.argv) > 1 else "BENCH_PR6.json"

    def emit(name, us, derived):
        print(f"{name},{us:.1f},{derived}")

    tp_bench(emit, json_path=json_path)
    print(f"wrote {json_path}")


if __name__ == "__main__":
    main()
