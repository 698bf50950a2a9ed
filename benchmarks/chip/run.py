"""Run one benchmark cell once and print its result line.

    python3 benchmarks/chip/run.py --workload ds7b-batch --seed 7 \\
        --seconds 51 --trace 0

Run from the root of a checkout, on a machine that holds the chips the
cell asks for: it fails, and prints no result, where JAX finds no TPU.
It draws the weights and the traffic from ``--seed``, warms up every
program the cell's traffic uses, measures for ``--seconds``, checks the
served tokens against a float32 reference, and prints one JSON object
as the last line of standard output: the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics (read from a profiler trace of the
whole window) with ``--trace 1``. The numbers the check
compared, each beside its limit, end standard error and the result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                                            # noqa: E402
import json                                                # noqa: E402
import sys                                                 # noqa: E402
from pathlib import Path                                   # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent.parent
sys.path[:0] = [str(HERE), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profiler trace and a summary of it "
                         "here (default: reduce it, then delete it)")
    ap.add_argument("--control", choices=("fp8", "int8"), default=None,
                    help="put the control in the program's place: the "
                         "reference computed in this lower precision, "
                         "whose tokens the check must find not correct")
    args = ap.parse_args(argv)
    import harness
    try:
        result = harness.run(args.workload, args.seed, args.seconds,
                             bool(args.trace), t_start=T_START, root=ROOT,
                             control=args.control,
                             trace_dir=args.trace_dir)
    except harness.NoChip as err:
        print(f"no result: {err}", file=sys.stderr, flush=True)
        return 3
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
