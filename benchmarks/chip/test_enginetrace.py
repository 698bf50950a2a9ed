"""The split by program, kernel and engine span, on traces recorded on
a TPU v5e.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest benchmarks/chip -q

``testdata/engine.xplane.pb.gz`` holds one second of a ds7b-batch
window (decode steps with chunked prefill riding some of them) from a
program that emits its own spans and names its programs and kernels,
recorded with ``run.py --seconds 1 --trace 1 --trace-dir``.
``testdata/decode.xplane.pb.gz`` is the same cell from before the
program did either.
"""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE)]

import devtrace                                              # noqa: E402
import enginetrace                                           # noqa: E402

ENGINE = HERE / "testdata" / "engine.xplane.pb.gz"
OLD = HERE / "testdata" / "decode.xplane.pb.gz"


@pytest.fixture(scope="module")
def split():
    return enginetrace.split(ENGINE)


@pytest.fixture(scope="module")
def old_split():
    return enginetrace.split(OLD)


def _close(a, b, rel=0.01):
    return abs(a - b) <= rel * max(abs(a), abs(b))


@pytest.mark.parametrize("which", ["engine", "old"])
def test_programs_sum_to_busy(split, old_split, which):
    s = split if which == "engine" else old_split
    total = sum(p["device_s"] for p in s["programs"].values())
    assert _close(total, s["busy_s"])


@pytest.mark.parametrize("which", ["engine", "old"])
def test_idle_by_span_sums_to_idle(split, old_split, which):
    s = split if which == "engine" else old_split
    assert s["idle_s"] > 0
    assert _close(sum(s["idle_by_span"].values()), s["idle_s"])


def test_busy_and_idle_agree_with_reduce(split):
    r = devtrace.reduce(ENGINE, wall_s=1.0)
    assert _close(split["busy_s"], r["busy_s"], 1e-9)
    assert _close(split["busy_s"] + split["idle_s"], r["ops_span_s"], 1e-9)


def test_kernels_sum_within_their_programs(split):
    total = sum(k["device_s"] for k in split["kernels"].values())
    by_prog = sum(sum(ks.values())
                  for ks in split["program_kernels"].values())
    assert 0 < total < split["busy_s"]
    assert _close(total, by_prog, 1e-9)
    for prog, ks in split["program_kernels"].items():
        assert sum(ks.values()) <= split["programs"][prog]["device_s"]


@pytest.mark.parametrize("program", ["jit_decode_step",
                                     "jit_prefill_chunk"])
def test_every_kernel_in_the_program_is_named(split, program):
    assert split["programs"][program]["n"] > 0
    assert "rowwise_matmul" in split["program_kernels"][program]
    assert program not in split["unnamed_kernels"]


def test_the_old_trace_names_no_kernel(old_split):
    assert old_split["kernels"] == {}
    assert old_split["unnamed_kernels"]["jit_step_fn"] > 0
    assert old_split["spans"] == {}
    assert set(old_split["idle_by_span"]) == {enginetrace.UNCOVERED}
    assert old_split["decode_step_ms"] is None
    assert old_split["loop_host_ms"] is None


@pytest.mark.parametrize("name", [
    "engine.iteration", "engine.sweep", "engine.chunks",
    "engine.chunks.launch", "engine.chunks.fetch", "engine.decode.prepare",
    "engine.decode.launch", "engine.decode.fetch", "engine.decode.emit"])
def test_engine_spans_are_read(split, name):
    assert split["spans"][name]["n"] > 0


def test_engine_spans_cover_the_idle_time(split):
    idle = split["idle_by_span"]
    assert idle.get(enginetrace.UNCOVERED, 0.0) < 0.1 * split["idle_s"]


def test_step_and_loop_figures(split):
    ev = devtrace.read_events(ENGINE)
    (dev,) = ev["devices"].values()
    steps = sorted((e - s) / 1e6 for s, e, name in dev["modules"]
                   if name.startswith("jit_decode_step("))
    assert steps[0] <= split["decode_step_ms"] <= steps[-1]
    spans = split["spans"]
    per_it = spans["engine.iteration"]["host_s"] * 1e3 \
        / spans["engine.iteration"]["n"]
    assert 0 < split["loop_host_ms"] < per_it


def test_reduce_on_the_old_trace_is_unchanged():
    """What ``devtrace.reduce`` returned for the old trace when it was
    committed: the new split reads beside it and changes nothing."""
    r = devtrace.reduce(OLD, wall_s=1e9)
    assert r["busy_s"] == pytest.approx(0.604748684, abs=1e-9)
    assert r["ops_span_s"] == pytest.approx(0.654354258, abs=1e-9)
    ops = r["breakdown"]["device_ops"]
    assert [k for k, _ in ops[:3]] == [
        "copy bf16[8,2056,16,32,128]",
        "bitcast_dynamic-update-slice_fusion bf16[8,2056,16,32,128]",
        "dynamic-slice_bitcast_fusion bf16[2056,16,32,128]"]
    assert ops[0][1] == pytest.approx(0.10464254, abs=1e-8)
    gaps = dict(r["breakdown"]["idle_gaps"])
    assert gaps == pytest.approx({
        "engine.chunks": 0.03898012,
        "engine loop: decode dispatch, fetch and bookkeeping":
            0.010625454}, abs=1e-8)


@pytest.mark.parametrize("spans,want", [
    # parent with two children and a gap between them
    ([(0, 10, "a", {}), (2, 4, "b", {}), (6, 8, "c", {})],
     [(0, 2, "a"), (2, 4, "b"), (4, 6, "a"), (6, 8, "c"), (8, 10, "a")]),
    # a child that starts with its parent, a grandchild, a later root
    ([(0, 5, "a", {}), (0, 3, "b", {}), (1, 2, "c", {}), (7, 9, "d", {})],
     [(0, 1, "b"), (1, 2, "c"), (2, 3, "b"), (3, 5, "a"), (7, 9, "d")]),
    ([], []),
])
def test_innermost_segments(spans, want):
    assert enginetrace.innermost(spans) == want


def test_overlap_of_intervals_with_windows():
    got = enginetrace._overlap([(0, 3), (5, 9)],
                               [(1, 2, "x"), (2, 6, "y"), (8, 20, "z")])
    assert got == {"x": 1, "y": 2, "z": 1}


@pytest.mark.parametrize("text,want", [
    ('custom_call_target="tpu_custom_call", frontend_attributes='
     '{kernel_metadata={\n"gated":"True",\n"kernel":"rowwise_matmul"}}',
     "rowwise_matmul"),
    ('custom_call_target="tpu_custom_call", frontend_attributes='
     '{kernel_metadata={}}', ""),
    ('%copy.1 = bf16[8] copy(bf16[8] %p)', None),
])
def test_kernel_name_from_op_text(text, want):
    assert enginetrace.kernel_name(text) == want


# ----------------------------------------------------------------------
# the record the harness builds from a trace, and the readers of it
# ----------------------------------------------------------------------


@pytest.fixture(scope="module")
def record():
    import harness
    return {"trace": harness.trace_record(ENGINE, 1.0, 0.0, 1.0),
            "stats_open": {"program_builds": 11, "program_build_s": 5.65}}


def test_trace_record_reads_the_file_once(monkeypatch):
    import harness
    loads = []
    real = devtrace.load
    monkeypatch.setattr(devtrace, "load",
                        lambda p: loads.append(p) or real(p))
    harness.trace_record(ENGINE, 1.0, 0.0, 1.0)
    assert len(loads) == 1


def test_trace_record_holds_reduce_and_split(record, split):
    """The breakdown keeps its labels and its top ten; beside it the
    record carries the whole table of op seconds and the split."""
    tr = record["trace"]
    r = devtrace.reduce(ENGINE, wall_s=1.0)
    assert tr["breakdown"] == r["breakdown"]
    assert tr["busy_s"] == r["busy_s"] and tr["window_s"] == 1.0
    assert [[k, v] for k, v in tr["op_s"].items()][:devtrace.TOP] == \
        r["breakdown"]["device_ops"]
    assert len(tr["op_s"]) > devtrace.TOP
    for key in ("programs", "kernels", "program_kernels", "spans",
                "idle_by_span", "decode_step_ms", "loop_host_ms"):
        assert tr[key] == split[key], key


@pytest.mark.parametrize("metric,key", [
    ("decode_step_ms", "decode_step_ms"),
    ("decode_step_ms.docqa", "decode_step_ms"),
    ("loop_host_ms", "loop_host_ms"),
    ("loop_host_ms.docqa", "loop_host_ms")])
def test_trace_readers(record, split, metric, key):
    """Each reader reads its figure from the record, and reads nothing
    from an untraced run's."""
    import harness
    read = harness.reader(metric)
    assert read(record) == split[key] > 0
    assert read({"trace": None}) is None


def test_program_build_reader(record):
    import harness
    assert harness.reader("program_build_s")(record) == 5.65


def test_every_metric_has_a_reader():
    import json
    import harness
    bench = json.loads((HERE.parent.parent / "BENCHMARK.json").read_text())
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(harness.reader(m["name"])), m["name"]
