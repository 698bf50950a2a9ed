"""The control: the reference computed in a lower precision than the
configuration states (float8 e4m3 matmul operands for bfloat16), put in
the program's place, must make a run come out not correct, while the
program's own run on the same seed is correct.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest benchmarks/chip -q

``test_control_tiny`` does so for the self-tests' tiny cell on the CPU;
``test_control_on_chip`` for every cell of ``BENCHMARK.json`` at its
own size, on three seeds, and skips where JAX finds no TPU.
"""
from __future__ import annotations

import json
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
REPO = HERE.parent.parent
sys.path[:0] = [str(HERE)]

import tiny                                                  # noqa: E402
from test_selftest import run_tiny                           # noqa: E402

CELLS = [w["name"] for w in json.loads(
    (REPO / "BENCHMARK.json").read_text())["workloads"]]
SEEDS = (2 ** 31 + 101, 2 ** 31 + 202, 2 ** 31 + 303)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("checkout"))


@pytest.mark.parametrize("cell", ["tiny-batch", "tiny-docqa"])
def test_control_tiny(root, cell):
    prog = run_tiny(root, cell)
    ctrl = run_tiny(root, cell, control="fp8")
    assert prog["correct"] is True, prog["checks"]
    assert ctrl["correct"] is False, ctrl["checks"]
    gap = ctrl["checks"]["served_logit_gap"]
    assert gap["value"] > gap["limit"]


@pytest.fixture(scope="module")
def on_tpu():
    probe = subprocess.run(
        [sys.executable, "-c",
         "import jax; print(jax.devices()[0].platform)"],
        capture_output=True, text=True, timeout=300)
    if probe.stdout.strip() != "tpu":
        pytest.skip("no TPU here")


def _run(cell, seed, *extra):
    out = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", cell,
         "--seed", str(seed), "--seconds", "20", "--trace", "0", *extra],
        cwd=REPO, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("cell", CELLS)
def test_control_on_chip(on_tpu, cell, seed):
    prog = _run(cell, seed)
    ctrl = _run(cell, seed, "--control", "fp8")
    assert prog["correct"] is True, prog["checks"]
    assert ctrl["correct"] is False, ctrl["checks"]
