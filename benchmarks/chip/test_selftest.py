"""Self-tests of the benchmark harness, on the CPU at a tiny size.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest benchmarks/chip -q

They are not part of the repository's tier-1 suite (its testpaths are
``tests``). Each whole run goes through a subprocess that skips the
harness's look for a chip and drives the rest of a run.
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
REPO = HERE.parent.parent
sys.path[:0] = [str(HERE), str(REPO / "src")]

import tiny                                                  # noqa: E402

RUN = r"""
import json, sys, time
T = time.perf_counter()
root, cell, fault, control = sys.argv[1], sys.argv[2], sys.argv[3], \
    sys.argv[6] or None
sys.path[:0] = [root + "/benchmarks/chip", sys.argv[4]]
if fault == "token_altered":
    # every sampled token is changed where the engine produces it
    from repro.serve import sampling
    real = sampling.sample
    sampling.sample = lambda *a, **k: (real(*a, **k) + 1) % 256
elif fault == "state_unchanged":
    # the decode step returns the cache it was given: no KV is written
    from repro.models import lm
    real = lm.decode_step
    lm.decode_step = lambda p, cache, *a, **k: (
        real(p, cache, *a, **k)[0], cache)
import harness, pathlib
r = harness.run(cell, int(sys.argv[5]), 2.0, False, t_start=T,
                root=pathlib.Path(root), require_tpu=False,
                control=control)
print(json.dumps(r))
"""


def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return env


def run_tiny(root: Path, cell: str, fault: str = "none",
             seed: int = 2 ** 31 + 77, control: str = "") -> dict:
    out = subprocess.run(
        [sys.executable, "-c", RUN, str(root), cell, fault,
         str(REPO / "src"), str(seed), control],
        capture_output=True, text=True, timeout=900, env=_env())
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("checkout"))


@pytest.mark.parametrize("cell,metric", [("tiny-batch", "tiny_requests"),
                                         ("tiny-docqa", "tokens_per_s.tiny")])
def test_throwaway_cell_found_by_name(root, cell, metric):
    """A configuration, a mix and a metric added as new files, and named
    in BENCHMARK.json, are run with no edit to an existing file; a
    metric ``<base>.<variant>`` reads with the reader of ``<base>``."""
    r = run_tiny(root, cell)
    assert r["correct"], r["checks"]
    assert metric in r["metrics"] and "setup_s" in r["metrics"]
    assert r["metrics"][metric]["value"] > 0
    assert list(r)[-1] == "checks"
    assert r["checks"]["served_logit_gap"]["value"] <= tiny.LIMIT


@pytest.mark.parametrize("fault", ["token_altered", "state_unchanged"])
def test_broken_timed_path_is_not_correct(root, fault):
    r = run_tiny(root, "tiny-batch", fault)
    assert r["correct"] is False, r["checks"]


def test_private_engine_names_pinned():
    """The harness subclasses the engine and reads these private names;
    a rename must fail here, loudly."""
    from repro.configs import get_reduced
    from repro.core.types import PagingConfig
    from repro.models import lm
    from repro.serve import engine as eng_mod
    import jax
    import driver
    for name in ("_sweep_deadlines", "_fill_slots", "_advance_chunks",
                 "run", "submit", "shutdown", "compile_counts"):
        assert callable(getattr(eng_mod.Engine, name)), name
    assert "t0" in {f.name for f in eng_mod._Pending.__dataclass_fields__
                    .values()}
    assert "sched" in eng_mod._ChunkState.__dataclass_fields__
    cfg = get_reduced("deepseek-7b")
    params, _ = lm.init_lm(jax.random.PRNGKey(0), cfg)
    calls = []
    eng = driver.BenchEngine(
        params, cfg, n_slots=2, max_len=64, eos_id=-1,
        paging=PagingConfig(page_size=8, prefill_chunk=16,
                            prefix_cache=True),
        hook=lambda e: calls.append(e.iter_t))
    for name in ("queue", "chunking", "active", "kv_trace", "_host_len"):
        assert hasattr(eng, name), name
    rng = np.random.default_rng(0)
    for rid, n in enumerate((10, 40)):
        eng.submit(eng_mod.Request(rid=rid, max_new=3,
                                   prompt=rng.integers(0, 256, n,
                                                       np.int32)))
    eng.run_from()
    # one hook per loop iteration, and every prompt token booked once
    assert len(calls) >= len(eng.booking.steps) > 0
    assert sum(n for _, n, _ in eng.booking.panels) == 50


def test_program_params_layout():
    """The benchmark's weights fill the program's parameter tree with
    the shapes the program itself would make."""
    import jax
    from repro.models import lm
    import harness
    import weights
    arch = tiny.CONFIG
    cfg = harness.model_config(arch)
    spec = tuple((n, s, sd, m)
                 for n, (s, sd, m) in weights.shapes(arch).items())
    ours = jax.eval_shape(lambda: weights.program_params(
        weights._draw(weights.key_for(0, 0), spec)))
    theirs = jax.eval_shape(lambda: lm.init_lm(jax.random.PRNGKey(0),
                                               cfg)[0])
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        assert a.shape == b.shape and a.dtype == b.dtype


@pytest.mark.parametrize("mix", ["docqa-long", "batch-unshared"])
def test_generators_deterministic_per_seed(mix):
    import harness
    params = json.loads((HERE / "traffic" / f"{mix}.json").read_text())
    gen = harness.load_module(HERE / "traffic" / f"{params['kind']}.py")
    a = gen.generate(params, 2 ** 33 + 5, 1000, 51.0)
    b = gen.generate(params, 2 ** 33 + 5, 1000, 51.0)
    c = gen.generate(params, 2 ** 33 + 6, 1000, 51.0)
    assert len(a["requests"]) == len(b["requests"]) == len(c["requests"])
    for x, y in zip(a["requests"], b["requests"]):
        assert x["due"] == y["due"] and x["max_new"] == y["max_new"]
        assert np.array_equal(x["prompt"], y["prompt"])
    # another seed: the same sizes in another order, other token ids
    sizes = lambda t: sorted((len(r["prompt"]), r["max_new"])   # noqa
                             for r in t["requests"])
    assert sizes(a) == sizes(c)
    assert any(not np.array_equal(x["prompt"], y["prompt"])
               for x, y in zip(a["requests"], c["requests"]))
    assert a["requests"][-1]["due"] == pytest.approx(
        c["requests"][-1]["due"])


@pytest.mark.parametrize("n,block", [(40, 2), (240, 4), (10, 4)])
def test_block_order_keeps_each_block(n, block):
    """Every seed's backlog holds the same items in each block: a window
    that sees the head of the backlog sees the same work."""
    import lengths
    orders = [lengths.block_order(np.random.default_rng([s, 2]), n, block)
              for s in (2 ** 33 + 5, 2 ** 33 + 6, 7)]
    for o in orders:
        assert sorted(o) == list(range(n))
        for b0 in range(0, n, block):
            assert set(o[b0:b0 + block]) == set(range(b0, min(n,
                                                             b0 + block)))
    assert len({tuple(o) for o in orders}) > 1


def test_no_chip_no_result(tmp_path):
    """On a machine without a TPU the command fails and prints no
    result, and so it does in a directory that holds only the
    benchmark's own files."""
    cmd = [sys.executable, "benchmarks/chip/run.py", "--workload",
           "ds7b-batch", "--seed", "1", "--seconds", "1", "--trace", "0"]
    out = subprocess.run(cmd, cwd=REPO, capture_output=True, text=True,
                         timeout=300, env=_env())
    assert out.returncode != 0 and out.stdout.strip() == ""
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip")
    out = subprocess.run(cmd, cwd=tmp_path, capture_output=True, text=True,
                         timeout=300, env=dict(_env(), PYTHONPATH=""))
    assert out.returncode != 0 and out.stdout.strip() == ""
