"""Reduce a profiler trace to device time: busy and idle, time per
operation, and the breakdown the result line carries.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes, read with
``jax.profiler.ProfileData``. Device planes are ``/device:TPU:<n>``;
their ``XLA Ops`` line holds one event per executed operation. Host
planes carry the program's and the benchmark's own spans (``engine.*``,
``bench.*``), on the same clock. :func:`read_events` reads the file once
for :func:`reduce` and ``enginetrace.split`` both.

An op event's name is its HLO instruction text; a Pallas kernel's op
names the kernel in its ``kernel_metadata`` (``enginetrace.kernel_name``).

Operations nest (a ``while`` spans the ops of its body), so busy time is
a union of intervals, and per-op times sum leaf operations only.
"""
from __future__ import annotations

import re
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
CONTAINERS = ("while", "conditional")
HOST_SPANS = ("bench.arrivals", "bench.wait", "engine.admit",
              "engine.chunks")
TOP = 10


def xplane_path(trace_dir) -> Path:
    """The trace file: ``trace_dir`` itself when it is a file (an
    ``.xplane.pb``, or one gzipped), else the newest one under it."""
    if Path(trace_dir).is_file():
        return Path(trace_dir)
    found = sorted(Path(trace_dir).glob("**/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path):
    import gzip
    from jax.profiler import ProfileData
    path = Path(path)
    if path.suffix == ".gz":
        return ProfileData.from_serialized_xspace(
            gzip.decompress(path.read_bytes()))
    return ProfileData.from_file(str(path))


def _stats_text(ev) -> str:
    try:
        return " ".join(f"{k}={v}" for k, v in ev.stats)
    except Exception:
        return ""


def _union(intervals):
    """Merged [start, end) intervals, sorted."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def op_label(name: str) -> str:
    """``<op kind> <output shape>``: the instruction's id without its
    number, and what it produces."""
    head, _, rest = name.partition(" = ")
    op = re.sub(r"(\.\d+)?(\.remat\d*)?$", "", head.lstrip("%"))
    shape = rest.split("{", 1)[0].split(" ", 1)[0][:60]
    return f"{op} {shape}"


def read_events(src) -> dict:
    """Per device, the ops (``XLA Ops``) and program executions (``XLA
    Modules``); per host thread line, the spans named ``engine.*`` or
    ``bench.*`` with their arguments; all in ns. ``src`` is a trace
    directory or file, or what this function returned before."""
    if isinstance(src, dict):
        return src
    pd = load(xplane_path(src))
    devices, lines = {}, {}
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            dev = {"modules": [], "ops": []}
            for line in plane.lines:
                key = {"XLA Modules": "modules",
                       "XLA Ops": "ops"}.get(line.name)
                if key:
                    dev[key] += [(e.start_ns, e.start_ns + e.duration_ns,
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


def _gap_cause(mid, host) -> str:
    """What the host was doing at ``mid``: the benchmark's span that
    covers it, or else the rest of the engine loop."""
    for s, e, name in host:
        if s <= mid < e:
            return name
    return "engine loop: decode dispatch, fetch and bookkeeping"


def reduce(src, wall_s: float) -> dict:
    """Busy time averaged over the devices traced, the span from the
    first device op to the last (a span far below ``wall_s`` means the
    trace lost events), the device seconds of every op label
    (``op_s``), and the top device ops and idle gaps (seconds). ``src``
    is what :func:`read_events` reads."""
    ev = read_events(src)
    if not ev["devices"]:
        raise ValueError("no device operations in the trace")
    host = [sp[:3] for spans in ev["lines"].values() for sp in spans
            if sp[2] in HOST_SPANS]
    busy, span, op_s, gaps = [], [], {}, []
    for dev in ev["devices"].values():
        merged = _union((s, e) for s, e, _ in dev["ops"])
        busy.append(sum(e - s for s, e in merged) / 1e9)
        span.append((merged[-1][1] - merged[0][0]) / 1e9)
        for (_, e0), (s1, _) in zip(merged, merged[1:]):
            gaps.append((s1 - e0, (e0 + s1) / 2))
        for s, e, name in dev["ops"]:
            if name.lstrip("%").startswith(CONTAINERS):
                continue
            label = op_label(name)
            op_s[label] = op_s.get(label, 0.0) + (e - s) / 1e9
    n = len(ev["devices"])
    gaps.sort(reverse=True)
    causes = {}
    for dur, mid in gaps:
        c = _gap_cause(mid, host)
        causes[c] = causes.get(c, 0.0) + dur / 1e9
    op_s = {k: v / n for k, v in sorted(op_s.items(),
                                        key=lambda kv: -kv[1])}
    return {
        "busy_s": sum(busy) / n, "window_s": wall_s,
        "ops_span_s": sum(span) / n, "op_s": op_s,
        "breakdown": {
            "device_ops": [[k, v] for k, v in list(op_s.items())[:TOP]],
            "idle_gaps": [[k, v / n] for k, v in sorted(
                causes.items(), key=lambda kv: -kv[1])[:TOP]]},
    }


def dump(trace_dir, out_file, limit: int = 60):
    """A summary for reading a trace by hand: planes, lines, and the
    longest-running op names with their metadata."""
    import json
    pd = load(xplane_path(trace_dir))
    out = {"planes": []}
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            evs = list(line.events)
            tot = {}
            first = {}
            for e in evs:
                tot[e.name] = tot.get(e.name, 0) + e.duration_ns
                first.setdefault(e.name, e)
            top = sorted(tot.items(), key=lambda kv: -kv[1])[:limit]
            lines.append({"line": line.name, "n": len(evs),
                          "t_min": min((e.start_ns for e in evs), default=0),
                          "t_max": max((e.start_ns + e.duration_ns
                                        for e in evs), default=0),
                          "top": [[k, v, _stats_text(first[k])[:600]]
                                  for k, v in top]})
        out["planes"].append({"plane": plane.name, "lines": lines})
    Path(out_file).write_text(json.dumps(out, indent=1))
