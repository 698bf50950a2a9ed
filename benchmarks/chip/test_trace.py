"""The trace reduction, on a small trace recorded on a TPU v5e.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest benchmarks/chip -q

``testdata/decode.xplane.pb.gz`` holds the last second of a ds7b-batch
window on a TPU v5e (decode steps, and chunked prefill riding some of
them), recorded with ``run.py --seconds 1 --trace 1 --trace-dir``.
"""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE)]

import devtrace                                              # noqa: E402

TRACE = HERE / "testdata" / "decode.xplane.pb.gz"


def test_union_merges_overlaps():
    assert devtrace._union([(5, 7), (0, 2), (1, 3), (7, 8)]) == \
        [[0, 3], [5, 8]]


@pytest.fixture(scope="module")
def reduced():
    return devtrace.reduce(TRACE, wall_s=1e9)


def test_busy_time_is_a_union_within_the_trace(reduced):
    ev = devtrace.read_events(TRACE)
    (dev,) = ev["devices"].values()
    lo = min(s for s, _, _ in dev["ops"])
    hi = max(e for _, e, _ in dev["ops"])
    total = sum(e - s for s, e, _ in dev["ops"]) / 1e9
    assert 0 < reduced["busy_s"] <= (hi - lo) / 1e9
    assert reduced["busy_s"] <= total + 1e-9


def test_ops_span_covers_busy_time(reduced):
    assert reduced["busy_s"] <= reduced["ops_span_s"] <= 1.1


def test_breakdown_shape(reduced):
    bd = reduced["breakdown"]
    for key in ("device_ops", "idle_gaps"):
        assert 0 < len(bd[key]) <= devtrace.TOP
        assert all(isinstance(n, str) and s >= 0 for n, s in bd[key])
        assert [s for _, s in bd[key]] == sorted(
            (s for _, s in bd[key]), reverse=True)
