"""Static invariant auditor CLI.

    PYTHONPATH=src python -m repro.analysis.audit [--arch deepseek-7b]
        [--mesh-shape 4] [--json out.json] [--passes sync,donation,...]

Runs all five passes (DESIGN.md §9) over the shipped serving entry
points of a reduced engine and exits non-zero on any error diagnostic,
so CI can gate merges on it:

  sync            AST taint over src/repro/serve/*.py + callback scan
                  of the traced entry points (one device fetch per
                  step-loop phase, nothing hidden)
  donation        every donated cache aliases an output in the lowered
                  MLIR of step/prefill/chunk
  compile-bound   static shape-signature enumeration == the documented
                  bound, for the plain and table-width-bucketed
                  configs; no weak_type operands in the entry points
  vmem            every pallas_call in the interpret-traced entry
                  points (and a large-K stress shape) fits the modeled
                  VMEM budget and its plan's accounting
  rules           PagePool transaction discipline + decode-path concat
                  rule over serve/engine.py / serve/paging.py

``--mesh-shape 4`` audits the TensorParallel placement; the CLI forces
the emulated device count into XLA_FLAGS *before* importing jax, so it
works on a single-CPU box.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _parse_args(argv=None):
    ap = argparse.ArgumentParser(prog="python -m repro.analysis.audit")
    ap.add_argument("--arch", default="deepseek-7b")
    ap.add_argument("--mesh-shape", type=int, default=1,
                    help="TensorParallel shard count (1 = single "
                         "device); emulated CPU devices are forced "
                         "before jax imports")
    ap.add_argument("--json", default="",
                    help="write per-pass results to this path")
    ap.add_argument("--passes", default="",
                    help="comma-separated subset (default: all five)")
    ap.add_argument("--explain", default="",
                    help="print the catalogue entry for a code and exit")
    return ap.parse_args(argv)


# sanctioned jax.device_get sites per engine function: THE serving
# latency contract. run(): the one decode fetch; _admit_head(): the
# one-shot prefill's first token; _advance_chunks(): the final chunk's
# token (intermediate chunks stay async). Everything else in
# src/repro/serve is allowed zero.
ENGINE_SYNC_ALLOW = {"run": 1, "_admit_head": 1, "_advance_chunks": 1}

SERVE_DIR_MODULES = ("engine.py", "paging.py", "sampling.py",
                     "placement.py", "prefix_cache.py", "faults.py",
                     "spec.py")
RULE_MODULES = ("engine.py", "paging.py", "prefix_cache.py")


def build_engine(arch: str, mesh: int):
    import jax
    import jax.numpy as jnp

    from repro.configs import get_reduced
    from repro.core.types import PagingConfig
    from repro.models import lm
    from repro.serve import placement as placement_mod
    from repro.serve.engine import Engine

    cfg = get_reduced(arch)
    placement = placement_mod.from_mesh_shape(
        str(mesh) if mesh > 1 else "")
    params, _ = lm.init_lm(jax.random.PRNGKey(0), cfg,
                           dtype=jnp.float32)
    return Engine(params, cfg, n_slots=2, max_len=64, eos_id=-1,
                  paging=PagingConfig(page_size=16, prefill_chunk=16,
                                      speculate_k=2),
                  placement=placement), cfg


def run_passes(arch: str, mesh: int, which=None):
    """Run the selected passes; returns a list of PassResult."""
    from repro.analysis import (compile_bound, donation, rules, sync,
                                vmem)
    from repro.core import runtime

    which = which or {"sync", "donation", "compile-bound", "vmem",
                      "rules"}
    serve_dir = os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "serve")
    results = []
    eng = cfg = None
    traced = []
    if which & {"sync", "donation", "compile-bound", "vmem"}:
        eng, cfg = build_engine(arch, mesh)
        # trace each entry point ONCE, under the interpret impl, and
        # share the jaxprs across passes: jitted functions cache their
        # trace by aval signature, so whichever impl traces first is
        # what every later make_jaxpr sees — and only the interpret
        # trace carries the pallas lowering the vmem pass reads
        import jax
        from repro.core import runtime
        with runtime.use_impl("interpret"):
            traced = [(n, jax.make_jaxpr(fn)(*args))
                      for n, fn, args, _ in eng.audit_entry_points()]

    if "sync" in which:
        t0 = time.perf_counter()
        res = sync.PassResult(name="sync")
        for mod in SERVE_DIR_MODULES:
            policy = sync.SyncPolicy(
                device_get_allow=ENGINE_SYNC_ALLOW
                if mod == "engine.py" else {})
            r = sync.audit_file(os.path.join(serve_dir, mod),
                                policy=policy)
            res.diagnostics += r.diagnostics
            res.checked += r.checked
        r = sync.audit_entry_jaxprs(traced)
        res.diagnostics += r.diagnostics
        res.checked += r.checked
        res.wall_s = time.perf_counter() - t0
        results.append(res)

    if "donation" in which:
        t0 = time.perf_counter()
        res = donation.PassResult(name="donation")
        for name, fn, args, donate in eng.audit_entry_points():
            r = donation.audit_donation(fn, args, donate, name=name)
            res.diagnostics += r.diagnostics
            res.checked += r.checked
        res.wall_s = time.perf_counter() - t0
        results.append(res)

    if "compile-bound" in which:
        t0 = time.perf_counter()
        res = compile_bound.PassResult(name="compile-bound")
        for twb in (False, True):
            # speculation ships full-width tables (the engine forbids
            # speculate_k + twb), so the twb leg audits the spec-free
            # ladder and the plain leg carries the engine's k-ladder
            sk = 0 if twb else eng.spec_k
            inv = compile_bound.enumerate_programs(
                max_len=eng.max_len, page_size=eng.page_size,
                prefill_chunk=eng.prefill_chunk,
                buckets=eng.buckets, table_width_bucketing=twb,
                speculate_k=sk)
            r = compile_bound.audit_bound(
                inv, n_buckets=len(eng.buckets),
                n_chunk_shapes=len([b for b in eng.buckets
                                    if b <= eng.prefill_chunk]),
                max_pages=eng.max_pages, table_width_bucketing=twb,
                n_spec_shapes=len(eng.spec_ladder) if sk else 0,
                name=f"{cfg.name}[twb={twb}]")
            res.diagnostics += r.diagnostics
            res.checked += r.checked
        r = compile_bound.weak_type_audit(traced)
        res.diagnostics += r.diagnostics
        res.checked += r.checked
        res.wall_s = time.perf_counter() - t0
        results.append(res)

    if "vmem" in which:
        t0 = time.perf_counter()
        res = vmem.PassResult(name="vmem")
        import jax
        for n, jx in traced:
            r = vmem.audit_vmem(jx, name=n)
            res.diagnostics += r.diagnostics
            res.checked += r.checked
        # large-K stress shape: the adder-tree K-split's whole reason
        # to exist; cross-checked against its own plan
        import jax.numpy as jnp

        from repro.core.rowwise import plan_matmul
        from repro.kernels import ops
        with runtime.use_impl("interpret"):
            m, k, n_ = 256, 16384, 512
            plan = plan_matmul(m, k, n_, dtype_bytes=4)
            jx = jax.make_jaxpr(lambda a, b: ops.matmul(a, b))(
                jnp.zeros((m, k), jnp.float32),
                jnp.zeros((k, n_), jnp.float32))
        r = vmem.crosscheck_plan(jx, plan, name=f"matmul[k={k}]")
        res.diagnostics += r.diagnostics
        res.checked += r.checked
        res.wall_s = time.perf_counter() - t0
        results.append(res)

    if "rules" in which:
        t0 = time.perf_counter()
        res = rules.PassResult(name="rules")
        for mod in RULE_MODULES:
            r = rules.audit_file(os.path.join(serve_dir, mod))
            res.diagnostics += r.diagnostics
            res.checked += r.checked
        res.wall_s = time.perf_counter() - t0
        results.append(res)
    return results


def main(argv=None) -> int:
    args = _parse_args(argv)
    if args.explain:
        from repro.analysis.report import CODES
        print(f"{args.explain}: "
              f"{CODES.get(args.explain, 'unknown code')}")
        return 0
    if args.mesh_shape > 1 and "xla_force_host_platform_device_count" \
            not in os.environ.get("XLA_FLAGS", ""):
        # must happen before jax initialises — which is why every jax
        # import in this module lives inside a function
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") +
            " --xla_force_host_platform_device_count="
            f"{max(8, args.mesh_shape)}").strip()
    which = set(args.passes.split(",")) if args.passes else None
    results = run_passes(args.arch, args.mesh_shape, which)
    failed = False
    for res in results:
        print(res.summary())
        for d in res.diagnostics:
            print(f"  {d}")
        failed = failed or not res.ok
    if args.json:
        with open(args.json, "w") as f:
            json.dump([{
                "pass": r.name, "checked": r.checked,
                "wall_s": r.wall_s, "ok": r.ok,
                "diagnostics": [str(d) for d in r.diagnostics],
            } for r in results], f, indent=2)
    print("audit:", "FAIL" if failed else
          f"OK ({sum(r.checked for r in results)} invariant sites, "
          f"{len(results)} passes)")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
