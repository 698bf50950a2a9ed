"""One run of one cell: load, warm up, measure, check, report.

Everything that belongs to one configuration, traffic mix or metric is
a file of its own, found by the name ``BENCHMARK.json`` gives it:

  configs/<config>.json   the model (a registry id, the keys cut from
                          it, HF-style published keys), the block family
                          it belongs to and the engine settings of the
                          deployment it stands for
  families/<family>.py    one block family: the benchmark's weights of
                          that layout, the program's parameter tree over
                          them, the check that file and program agree,
                          the float32 reference and the work counts
  traffic/<mix>.json      the parameters of one mix; its ``kind`` names
                          the generator module traffic/<kind>.py
  metrics/<metric>.py     one reader per metric: ``read(rec)`` returns
                          the number, or None where it finds nothing;
                          ``rec`` is what :func:`run` builds: the
                          served rows, the engine's counters at the
                          window's opening and over it, the booking of
                          work, the family's work counts and, in a
                          traced run, the trace split by device op,
                          program, kernel and engine span
  limits/<cell>.json      the limit of each number compared by the
                          correctness check, with the readings it was
                          set from
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import shutil
import sys
import time
from pathlib import Path
from typing import Optional

import numpy as np

HERE = Path(__file__).resolve().parent
WARM_RID = 10 ** 9       # warm-up requests' ids start here
MAX_RAMP_S = 120.0       # the window opens by then, busy or not


class NoChip(RuntimeError):
    pass


# ----------------------------------------------------------------------
# finding things by name
# ----------------------------------------------------------------------


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    family: object            # the module families/<family>.py
    mix: dict
    end_to_end: list          # metric entries this cell reports
    per_layer: list
    limits: dict


def load_cell(root: Path, name: str) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    cfgs = {c["name"]: c for c in bench["configs"]}
    config_file = cfgs[w["config"]]["file"]
    config = json.loads((root / config_file).read_text())
    if "family" not in config:
        raise KeyError(f"{config_file} names no block family: give it a "
                       f"'family' key, as families/<family>.py is named")
    family = load_module(HERE / "families" / f"{config['family']}.py")
    mix = json.loads((HERE / "traffic" / f"{w['traffic']}.json")
                     .read_text())

    def reports(m):
        return name in m["workloads"] if "workloads" in m else True

    e2e = [m for m in bench["end_to_end"] if reports(m)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
    limits = json.loads((HERE / "limits" / f"{name}.json").read_text())
    return Cell(name, w["chips"], config, family, mix, e2e, per_layer,
                limits)


def reader(name: str):
    """``metrics/<name>.py``; a metric ``<base>.<variant>`` with no file
    of its own (the same quantity, split by cell so that each part has
    its own bound) reads with ``metrics/<base>.py``."""
    path = HERE / "metrics" / f"{name}.py"
    if not path.exists():
        path = HERE / "metrics" / f"{name.split('.')[0]}.py"
    return load_module(path).read


# ----------------------------------------------------------------------
# device, model, engine
# ----------------------------------------------------------------------


def device_info(chips: int, require_tpu: bool) -> dict:
    import jax
    devs = jax.devices()
    d0 = devs[0]
    if require_tpu and d0.platform != "tpu":
        raise NoChip(f"no TPU: jax.devices()[0] is {d0.platform} ({d0})")
    if len(devs) < chips:
        raise NoChip(f"{chips} chips asked, {len(devs)} found")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


def model_config(config: dict, family):
    """The program's config: the registry entry with the file's
    overrides, checked by the family against the file's published
    keys."""
    from repro.configs import get_config
    cfg = dataclasses.replace(get_config(config["registry"]),
                              **config.get("overrides", {}))
    family.check(cfg, config)
    return cfg


def build_engine(cfg, params, eng_cfg: dict, hook):
    from repro.core.types import PagingConfig
    from driver import BenchEngine
    return BenchEngine(
        params, cfg, n_slots=eng_cfg["n_slots"], max_len=eng_cfg["max_len"],
        eos_id=eng_cfg["eos_id"], temperature=0.0, seed=0,
        paging=PagingConfig(page_size=eng_cfg["page_size"],
                            prefill_chunk=eng_cfg["prefill_chunk"],
                            prefix_cache=eng_cfg["prefix_cache"]),
        hook=hook)


def warm_lengths(prompt_range, eng) -> list:
    """Prompt lengths that compile every program the cell's traffic can
    reach, and no other: one-shot buckets for the prompts that fit one
    chunk, and every chunk panel shape (a prefix-cache hit leaves a
    suffix of any length)."""
    from repro.serve.paging import bucket_for
    lo, hi = prompt_range
    chunk = eng.prefill_chunk
    lens = []
    for b in sorted({bucket_for(p, eng.buckets)
                     for p in range(lo, min(hi, chunk) + 1)}):
        lens.append(max(lo, min(b, hi)))
    if hi > chunk or eng.prefix_cache is not None:
        lens += [chunk + s for s in eng.buckets if s <= chunk
                 and chunk + s <= eng.max_len]
    return lens


def warm_up(eng, prompt_range, vocab: int, seed: int) -> dict:
    from repro.analysis.compile_bound import predict_compile_counts
    from repro.serve.engine import Request
    lens = warm_lengths(prompt_range, eng)
    rng = np.random.default_rng([seed, 9])
    for i, n in enumerate(lens):
        eng.submit(Request(rid=WARM_RID + i, max_new=2,
                           prompt=rng.integers(0, vocab, n, np.int32)))
    eng.run_from()
    predicted = predict_compile_counts(
        lens, max_len=eng.max_len, prefill_chunk=eng.prefill_chunk)
    counts = eng.compile_counts()
    if counts != predicted:
        raise RuntimeError(f"warm-up compiled {counts}, predicted "
                           f"{predicted} for lengths {lens}")
    return counts


STEP_PROGRAMS = ("step", "chunk", "spec")


def programs_peak_bytes(eng) -> int:
    """The most device memory one of the engine's step programs (decode,
    chunked prefill, speculative verify) holds while it runs: arguments
    + temporaries + outputs - aliased (donated) bytes, from the
    compiler's memory analysis of each program as the run loop calls
    it. Warm-up built them with these shapes, so nothing compiles here.
    The one-shot prefill is left out: its entry point has the smallest
    bucket, which a cell's traffic need not reach."""
    peak = 0
    for name, fn, args, _ in eng.audit_entry_points():
        if name not in STEP_PROGRAMS:
            continue
        ma = fn.lower(*args).compile().memory_analysis()
        peak = max(peak, ma.argument_size_in_bytes + ma.temp_size_in_bytes
                   + ma.output_size_in_bytes - ma.alias_size_in_bytes)
    return int(peak)


# ----------------------------------------------------------------------
# the window
# ----------------------------------------------------------------------


class Tracer:
    """The profiler over the whole window. ``t0``/``t1`` are the loop
    iterations it spans (the stamps the engine's booking uses);
    ``wall`` is the host time between starting and stopping. The
    Python tracer is off: it would record every call of the engine
    loop and slow the host it measures."""

    def __init__(self, out_dir: Path):
        self.dir = out_dir
        self.t0 = self.t1 = None
        self.wall = None

    def start(self, now: float):
        import jax
        self.t0 = now
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        self._w0 = time.perf_counter()

    def stop(self, now: float):
        import jax
        self.t1 = now
        self.wall = time.perf_counter() - self._w0
        jax.profiler.stop_trace()


class Controller:
    """Called once per engine loop iteration (and while idle): submits
    due arrivals, opens the window when every slot is busy, starts and
    stops the profiler with it, and ends serving when it closes."""

    def __init__(self, arrivals, seconds: float, tracer: Optional[Tracer]):
        self.arr = arrivals
        self.seconds = seconds
        self.tracer = tracer
        self.w0 = self.w1 = None
        self.done = False
        self.snap0 = self.snap1 = None

    def _busy(self, eng) -> bool:
        return all(a is not None or s in eng.chunking
                   for s, a in enumerate(eng.active))

    def tick(self, eng):
        if self.done:
            return
        now = eng.iter_t
        self.arr.inject(eng, now)
        if self.w0 is None:
            if self._busy(eng) or now >= self.arr.start + MAX_RAMP_S:
                self.w0, self.w1 = now, now + self.seconds
                self.snap0 = dict(eng.stats)
                if self.tracer is not None:
                    self.tracer.start(now)
            return
        if now >= self.w1:
            self.snap1 = dict(eng.stats)
            if self.tracer is not None:
                self.tracer.stop(now)
            eng.shutdown()
            self.done = True


def serve(eng, ctl: Controller):
    from jax.profiler import TraceAnnotation
    eng.hook = ctl.tick
    while not ctl.done:
        eng.iter_t = time.perf_counter()
        ctl.tick(eng)
        if ctl.done:
            break
        if eng.queue or eng.chunking or any(a is not None
                                            for a in eng.active):
            eng.run_from()
            continue
        nxt = ctl.arr.next_due() if ctl.arr.pending() else math.inf
        with TraceAnnotation("bench.wait"):
            time.sleep(min(0.02, max(0.0, nxt - time.perf_counter())))


# ----------------------------------------------------------------------
# the record every metric reader reads
# ----------------------------------------------------------------------


def request_rows(eng, ctl: Controller) -> list:
    rows = []
    for c in eng.completed:
        if c.rid >= WARM_RID:
            continue
        due = ctl.arr.due[c.rid]
        times = []
        if c.tokens:
            times = list(due + c.ttft_s + np.concatenate(
                [[0.0], np.cumsum(c.itl_s)]))
        rows.append({"rid": c.rid, "status": c.status,
                     "prompt_len": c.prompt_len, "tokens": c.tokens,
                     "token_times": times})
    return rows


def trace_record(src, wall_s: float, t0: float, t1: float) -> dict:
    """What a traced window leaves for the readers, from one reading of
    the trace: ``devtrace.reduce`` (busy time, the breakdown, device
    seconds per op label) and ``enginetrace.split`` (per program, per
    Pallas kernel, per engine span, the idle time by span, the decode
    step and the host loop), with the loop iterations ``t0``/``t1``
    the trace spans."""
    import devtrace
    import enginetrace
    ev = devtrace.read_events(src)
    return dict(enginetrace.split(ev), **devtrace.reduce(ev, wall_s),
                t0=t0, t1=t1)


# ----------------------------------------------------------------------
# correctness
# ----------------------------------------------------------------------


def sample_for_check(rows, seed: int, check: dict) -> list:
    """The longest finished request, then others drawn from the seed,
    until ``min_tokens`` served tokens or ``max_requests``."""
    done = [r for r in rows if r["status"] == "ok"]
    if not done:
        return []
    longest = max(done, key=lambda r: r["prompt_len"] + len(r["tokens"]))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([seed, 3])
    out = [longest]
    for i in rng.permutation(len(rest)):
        if (sum(len(r["tokens"]) for r in out) >= check["min_tokens"]
                or len(out) >= check["max_requests"]):
            break
        out.append(rest[int(i)])
    return out


def reference_gaps(family, w, arch, prompts, sample,
                   control=None) -> dict:
    """The widest gap between the reference's best logit and that of
    each served token (``program``); with a control, also that of the
    token the lower-precision reference puts first at each of the same
    positions (``control``)."""
    import reference
    served, ctrl = [], []
    for r in sample:
        prompt = prompts[r["rid"]]
        toks = np.asarray(r["tokens"], np.int32)
        seq = np.concatenate([prompt, toks[:-1]])
        pos = np.arange(len(prompt) - 1, len(seq))
        ref = family.logits(w, arch, seq, pos)
        served.append(np.asarray(reference.served_gaps(ref, toks)))
        if control:
            low = family.logits(w, arch, seq, pos, mode=control)
            ctrl.append(np.asarray(reference.control_gaps(ref, low)))
            del low
        del ref
    return {"program": float(np.max(np.concatenate(served))),
            "control": float(np.max(np.concatenate(ctrl))) if control
            else None,
            "tokens": int(sum(len(s) for s in served))}


# ----------------------------------------------------------------------
# one run
# ----------------------------------------------------------------------


def run(cell_name: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, root: Path = HERE.parent.parent,
        require_tpu: bool = True, control: Optional[str] = None,
        trace_dir: Optional[str] = None, log=print) -> dict:
    """One run of the cell. With ``control`` (``"fp8"`` or ``"int8"``)
    the reference computed in that lower precision takes the program's
    place in the comparison: its tokens, not the served ones, are held
    to the limit, so the run must come out not correct."""
    cell = load_cell(root, cell_name)
    device = device_info(cell.chips, require_tpu)

    import jax
    from repro.core import runtime
    import weights
    runtime.init_compile_cache()
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, dur, **kw: compiles.append(ev)
        if "backend_compile" in ev else None)

    arch, family = cell.config, cell.family
    cfg = model_config(arch, family)
    w = weights.make(family.shapes(arch), seed)
    gen = load_module(HERE / "traffic" / f"{cell.mix['kind']}.py")
    traffic = gen.generate(cell.mix, seed, arch["vocab_size"], seconds)
    eng = build_engine(cfg, family.program_params(w, arch), arch["engine"],
                       hook=lambda e: None)
    counts_warm = warm_up(eng, traffic["prompt_range"],
                          arch["vocab_size"], seed)
    n_compiles_warm = len(compiles)

    from driver import Arrivals
    arrivals = Arrivals(traffic["requests"], time.perf_counter())
    tracer = None
    if trace:
        keep = trace_dir is not None
        trace_dir = Path(trace_dir) if keep else root / ".bench_trace"
        tracer = Tracer(trace_dir / f"{cell_name}-{seed}")
    ctl = Controller(arrivals, seconds, tracer)
    serve(eng, ctl)
    setup_s = ctl.w0 - t_start
    compiles_in_window = len(compiles) - n_compiles_warm
    counts_after = eng.compile_counts()
    log(f"[compiles] warm-up {counts_warm}, after the window "
        f"{counts_after}, backend compiles inside the window "
        f"{compiles_in_window}", file=sys.stderr)

    stats = {k: ctl.snap1[k] - ctl.snap0[k] for k in ctl.snap0}
    rows = request_rows(eng, ctl)
    steps = eng.booking.steps_in(ctl.w0, ctl.w1)
    panels = eng.booking.panels_in(ctl.w0, ctl.w1)
    log(f"[window] {len(steps)} decode steps of "
        f"{sum(map(len, steps))} slot-steps, {len(panels)} prompt panels "
        f"of {sum(n for n, _ in panels)} tokens, "
        f"{1e3 * seconds / max(1, len(steps)):.2f} ms per step",
        file=sys.stderr)
    rec = {"cell": cell.name, "seconds": seconds, "setup_s": setup_s,
           "window": (ctl.w0, ctl.w1), "rows": rows, "stats": stats,
           "stats_open": ctl.snap0,
           "n_slots": eng.n_slots, "page_size": eng.page_size,
           "booking": eng.booking, "work": family,
           "dims": family.dims(arch), "peaks": None, "trace": None}
    errors, recoveries = list(eng.errors), eng.stats["recoveries"]
    mem = jax.devices()[0].memory_stats() or {}
    device["memory_peak_bytes"] = int(mem.get("peak_bytes_in_use", 0))
    t_mem = time.perf_counter()
    device["programs_peak_bytes"] = programs_peak_bytes(eng)
    log(f"[memory] allocator peak {device['memory_peak_bytes']} B, step "
        f"programs' peak {device['programs_peak_bytes']} B (compiled in "
        f"{time.perf_counter() - t_mem:.1f} s)", file=sys.stderr)

    # free the program's state before the reference runs
    prompts = {i: r["prompt"] for i, r in enumerate(traffic["requests"])}
    del eng, ctl.arr, arrivals
    gc.collect()

    if trace:
        import devtrace
        peaks = json.loads((HERE / "peaks.json").read_text())
        if device["kind"] not in peaks["devices"]:
            raise KeyError(f"no peaks for device kind {device['kind']!r}")
        rec["peaks"] = peaks["devices"][device["kind"]]
        if keep:
            devtrace.dump(tracer.dir, tracer.dir / "summary.json")
        t_read = time.perf_counter()
        rec["trace"] = trace_record(devtrace.xplane_path(tracer.dir),
                                    tracer.wall, tracer.t0, tracer.t1)
        t_read = time.perf_counter() - t_read
        if not keep:
            shutil.rmtree(tracer.dir, ignore_errors=True)
        log(f"[trace] window {tracer.wall:.3f} s, device ops from first "
            f"to last {rec['trace']['ops_span_s']:.3f} s, busy "
            f"{rec['trace']['busy_s']:.3f} s; read and split in "
            f"{t_read:.1f} s", file=sys.stderr)
        device["busy_s"] = rec["trace"]["busy_s"]
        device["window_s"] = rec["trace"]["window_s"]

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = reader(m["name"])(rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    attempted = [r for r in rows if r["tokens"] or r["status"] == "failed"]
    failed = sum(1 for r in attempted
                 if r["status"] == "failed" or not r["tokens"])
    sample = sample_for_check(rows, seed, cell.mix["check"])
    gaps = (reference_gaps(family, w, arch, prompts, sample, control)
            if sample else {"program": None, "control": None, "tokens": 0})
    log(f"[check] {gaps['tokens']} served tokens of {len(sample)} "
        f"requests compared with the float32 reference: program gap "
        f"{gaps['program']}" + (f", {control} control gap "
                                f"{gaps['control']} (in the program's "
                                f"place)" if control else ""),
        file=sys.stderr)
    gap = gaps["control"] if control else gaps["program"]
    limit = cell.limits["served_logit_gap"]["limit"]
    checks = {
        "served_logit_gap": {"value": gap, "limit": limit},
        "failed_requests": {"value": failed, "limit": 0},
        "engine_recoveries": {"value": recoveries, "limit": 0},
    }
    correct = (gap is not None and gap <= limit and failed == 0
               and recoveries == 0 and not errors)
    result = {"correct": bool(correct), "attempted": len(attempted),
              "failed": failed, "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = rec["trace"]["breakdown"]
    result["checks"] = checks
    return result
