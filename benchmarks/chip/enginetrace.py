"""Split a serving trace by engine program, by kernel and by engine span.

    python3 benchmarks/chip/enginetrace.py <trace dir or .xplane.pb[.gz]> \\
        [--out split.json]

Reads the trace that ``run.py --trace 1 --trace-dir <dir>`` keeps (the
same file ``devtrace.reduce`` reads) and prints, as JSON:

  programs      per engine program on the device's ``XLA Modules``
                line (``jit_decode_step``, ``jit_prefill_chunk``,
                ``jit_prefill``, and the small host-dispatched ones):
                executions and the device-busy seconds inside them
  kernels       per Pallas kernel, named by the ``kernel`` entry of the
                ``kernel_metadata`` its op carries: executions and
                device seconds; ``program_kernels`` splits them by
                program, and ``unnamed_kernels`` counts the
                ``tpu_custom_call`` ops of each program that name none
  spans         the engine's own host spans (``engine.*``, emitted by
                ``repro.serve.engine``): count and host seconds
  idle_by_span  device-idle seconds split by the innermost engine span
                that covers them on the engine's thread; ``uncovered``
                is idle time outside every engine span
  decode_step_ms  median device duration of ``jit_decode_step``
  loop_host_ms  mean ``engine.iteration`` duration less the time its
                ``*.fetch`` and ``bench.*`` descendants cover: the
                serial host work a lockstep loop adds to each step

Device figures are averaged over the devices traced, as in
``devtrace.reduce``; idle time is the gaps between merged device
operations, from the first to the last.
"""
from __future__ import annotations

import argparse
import bisect
import json
import re
import statistics
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import devtrace                                              # noqa: E402

KERNEL = re.compile(r'kernel_metadata=\{.*?\bkernel\\?"\s*:\s*\\?"(\w+)',
                    re.S)
UNCOVERED = "uncovered"
LOOP_SPAN = "engine.iteration"
DECODE_PROGRAM = "jit_decode_step"


def program_name(event_name: str) -> str:
    """``jit_decode_step(1077...)`` -> ``jit_decode_step``."""
    return event_name.split("(", 1)[0]


def kernel_name(op_text: str):
    """The kernel a ``tpu_custom_call`` op names in its metadata."""
    if "tpu_custom_call" not in op_text:
        return None
    m = KERNEL.search(op_text)
    return m.group(1) if m else ""


def read(path) -> dict:
    """Device modules and ops (per device), and host spans (per thread
    line) named ``engine.*`` or ``bench.*``, in ns."""
    pd = devtrace.load(devtrace.xplane_path(path))
    devices, lines = {}, {}
    for plane in pd.planes:
        if devtrace.DEVICE_PLANE.match(plane.name):
            dev = {"modules": [], "ops": []}
            for line in plane.lines:
                key = {"XLA Modules": "modules",
                       "XLA Ops": "ops"}.get(line.name)
                if key:
                    dev[key] = [(e.start_ns, e.start_ns + e.duration_ns,
                                 e.name) for e in line.events]
            if dev["ops"]:
                devices[plane.name] = dev
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                spans = [(e.start_ns, e.start_ns + e.duration_ns, e.name,
                          {k: v for k, v in e.stats})
                         for e in line.events
                         if e.name.startswith(("engine.", "bench."))]
                if spans:
                    lines[(plane.name, line.name)] = spans
    return {"devices": devices, "lines": lines}


def _overlap(intervals, windows):
    """Per window index: the length of ``intervals`` (sorted, disjoint)
    that falls inside it. ``windows`` are (start, end, key) sorted by
    start and disjoint."""
    out, j = {}, 0
    for s, e, key in windows:
        while j < len(intervals) and intervals[j][1] <= s:
            j += 1
        k = j
        while k < len(intervals) and intervals[k][0] < e:
            lo, hi = max(s, intervals[k][0]), min(e, intervals[k][1])
            if hi > lo:
                out[key] = out.get(key, 0) + hi - lo
            k += 1
    return out


def innermost(spans):
    """Disjoint segments (start, end, name) of one thread's properly
    nested spans, each naming the innermost span open there."""
    segs, stack = [], []
    t = None
    for s, e, name, _ in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            end, nm = stack.pop()
            if t < end:
                segs.append((t, end, nm))
            t = end
        if stack and t < s:
            segs.append((t, s, stack[-1][1]))
        stack.append((e, name))
        t = s
    while stack:
        end, nm = stack.pop()
        if t < end:
            segs.append((t, end, nm))
        t = end
    return segs


def _engine_line(lines):
    """The thread that ran the engine loop: the most iterations."""
    def n(key):
        return sum(1 for sp in lines[key] if sp[2] == LOOP_SPAN)
    keys = [k for k in lines if n(k)]
    return max(keys, key=n) if keys else None


def loop_host_ms(spans):
    """Mean over ``engine.iteration`` spans of their duration less the
    union of their ``*.fetch`` and ``bench.*`` descendants, in ms."""
    its = [sp for sp in spans if sp[2] == LOOP_SPAN]
    away = devtrace._union((s, e) for s, e, name, _ in spans
                           if name.endswith(".fetch")
                           or name.startswith("bench."))
    if not its:
        return None
    per = _overlap(away, [(s, e, i) for i, (s, e, _, _) in
                          enumerate(sorted(its))])
    host = [(e - s - per.get(i, 0)) for i, (s, e, _, _) in
            enumerate(sorted(its))]
    return sum(host) / len(host) / 1e6


def split(path) -> dict:
    ev = read(path)
    if not ev["devices"]:
        raise ValueError(f"no device operations in the trace {path}")
    key = _engine_line(ev["lines"])
    spans = [sp for sp in ev["lines"].get(key, [])
             if sp[2].startswith("engine.")] if key else []
    segs = innermost(spans)
    n = len(ev["devices"])
    busy = idle = 0
    programs, kernels, prog_kernels, unnamed = {}, {}, {}, {}
    idle_by, decode = {}, []
    for dev in ev["devices"].values():
        merged = devtrace._union((s, e) for s, e, _ in dev["ops"])
        gaps = [(e0, s1) for (_, e0), (s1, _) in zip(merged, merged[1:])]
        busy += sum(e - s for s, e in merged)
        idle += sum(e - s for s, e in gaps)
        mods = sorted(dev["modules"])
        inside = _overlap(merged, [(s, e, i) for i, (s, e, _) in
                                   enumerate(mods)])
        for i, (s, e, name) in enumerate(mods):
            p = programs.setdefault(program_name(name),
                                    {"n": 0, "device_s": 0.0})
            p["n"] += 1
            p["device_s"] += inside.get(i, 0) / 1e9
            if program_name(name) == DECODE_PROGRAM:
                decode.append((e - s) / 1e6)
        outside = sum(e - s for s, e in merged) - sum(inside.values())
        if outside > 0:
            p = programs.setdefault("(no program)",
                                    {"n": 0, "device_s": 0.0})
            p["device_s"] += outside / 1e9
        starts = [s for s, _, _ in mods]
        for s, e, text in dev["ops"]:
            k = kernel_name(text)
            if k is None:
                continue
            i = _bisect(starts, s)
            prog = program_name(mods[i][2]) if i is not None \
                and mods[i][1] >= e else "(no program)"
            if not k:
                unnamed[prog] = unnamed.get(prog, 0) + 1
                continue
            kk = kernels.setdefault(k, {"n": 0, "device_s": 0.0})
            kk["n"] += 1
            kk["device_s"] += (e - s) / 1e9
            pk = prog_kernels.setdefault(prog, {})
            pk[k] = pk.get(k, 0.0) + (e - s) / 1e9
        covered = _overlap(gaps, segs)
        for name, t in covered.items():
            idle_by[name] = idle_by.get(name, 0.0) + t / 1e9
        rest = sum(e - s for s, e in gaps) - sum(covered.values())
        idle_by[UNCOVERED] = idle_by.get(UNCOVERED, 0.0) + rest / 1e9

    def per_device(d):
        return {k: (dict(v, device_s=v["device_s"] / n, n=v["n"] / n)
                    if isinstance(v, dict) else v / n)
                for k, v in sorted(d.items(), key=lambda kv: -(
                    kv[1]["device_s"] if isinstance(kv[1], dict)
                    else kv[1]))}

    span_tot = {}
    for s, e, name, _ in spans:
        t = span_tot.setdefault(name, {"n": 0, "host_s": 0.0})
        t["n"] += 1
        t["host_s"] += (e - s) / 1e9
    return {
        "busy_s": busy / n / 1e9, "idle_s": idle / n / 1e9,
        "programs": per_device(programs),
        "kernels": per_device(kernels),
        "program_kernels": {p: {k: v / n for k, v in ks.items()}
                            for p, ks in prog_kernels.items()},
        "unnamed_kernels": unnamed,
        "spans": dict(sorted(span_tot.items())),
        "idle_by_span": per_device(idle_by),
        "decode_step_ms": statistics.median(decode) if decode else None,
        "loop_host_ms": loop_host_ms(ev["lines"][key]) if key else None,
    }


def _bisect(starts, t):
    """Index of the last module starting at or before ``t``."""
    i = bisect.bisect_right(starts, t) - 1
    return i if i >= 0 else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace")
    ap.add_argument("--out", default=None, help="also write the JSON here")
    args = ap.parse_args(argv)
    text = json.dumps(split(args.trace), indent=1)
    if args.out:
        Path(args.out).write_text(text)
    print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
