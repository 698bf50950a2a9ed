"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows and writes the PR 2
block-pipeline artifact (BENCH_PR2.json), the PR 3 paged-serving
artifact (BENCH_PR3.json), the PR 4 decode weight-traffic artifact
(BENCH_PR4.json), the PR 5 chunked-prefill TTFT artifact
(BENCH_PR5.json), the PR 7 preemption-pressure artifact
(BENCH_PR7.json), the PR 8 prefix-cache artifact (BENCH_PR8.json),
the PR 9 static-auditor artifact (BENCH_PR9.json), the PR 10
self-speculative-decoding artifact (BENCH_PR10.json)
and the PR 6 tensor-parallel artifact
(BENCH_PR6.json — run first, as a subprocess: the emulated mesh needs
XLA_FLAGS set before jax initialises, and this process must not hold
the accelerator while the child runs, so it imports jax only after the
child has exited).
"""
from __future__ import annotations

import os
import subprocess
import sys


def main() -> None:
    tp = subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(__file__), "tp_bench.py"),
         "BENCH_PR6.json"])
    if tp.returncode != 0:
        raise SystemExit(tp.returncode)

    from benchmarks.analysis_bench import analysis_bench
    from benchmarks.block_bench import block_bench
    from benchmarks.decode_bench import decode_bench
    from benchmarks.kernel_bench import kernel_suite
    from benchmarks.paper_tables import ALL
    from benchmarks.roofline_report import roofline_report
    from benchmarks.serve_bench import (chunked_prefill_bench,
                                        preemption_bench,
                                        prefix_cache_bench, serve_bench)
    from benchmarks.spec_bench import spec_bench

    rows = []

    def emit(name, us, derived):
        rows.append((name, us, derived))
        print(f"{name},{us:.1f},{derived}")

    print("name,us_per_call,derived")
    for bench in ALL:
        bench(emit)
    kernel_suite(emit)
    roofline_report(emit)
    block_bench(emit, json_path="BENCH_PR2.json")
    serve_bench(emit, json_path="BENCH_PR3.json")
    decode_bench(emit, json_path="BENCH_PR4.json")
    chunked_prefill_bench(emit, json_path="BENCH_PR5.json")
    preemption_bench(emit, json_path="BENCH_PR7.json")
    prefix_cache_bench(emit, json_path="BENCH_PR8.json")
    analysis_bench(emit, json_path="BENCH_PR9.json")
    spec_bench(emit, json_path="BENCH_PR10.json")


if __name__ == "__main__":
    main()
