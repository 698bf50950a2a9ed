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
                                         ("tiny-docqa", "tokens_per_s.tiny"),
                                         ("tiny-granite", "tiny_requests")])
def test_throwaway_cell_found_by_name(root, cell, metric):
    """A configuration, a mix and a metric added as new files, and named
    in BENCHMARK.json, are run with no edit to an existing file; a
    metric ``<base>.<variant>`` reads with the reader of ``<base>``.
    ``tiny-granite`` brings its block family as a new file too: its own
    weights, parameter tree, reference and work counts."""
    r = run_tiny(root, cell)
    assert r["correct"], r["checks"]
    assert metric in r["metrics"] and "setup_s" in r["metrics"]
    assert r["metrics"][metric]["value"] > 0
    assert r["device"]["programs_peak_bytes"] > 0
    assert list(r)[-1] == "checks"
    assert r["checks"]["served_logit_gap"]["value"] <= tiny.LIMIT


@pytest.mark.parametrize("cell,fault", [("tiny-batch", "token_altered"),
                                        ("tiny-batch", "state_unchanged"),
                                        ("tiny-granite", "token_altered")])
def test_broken_timed_path_is_not_correct(root, cell, fault):
    r = run_tiny(root, cell, fault)
    assert r["correct"] is False, r["checks"]


def test_config_without_family_is_refused(root, tmp_path):
    """A configuration names its block family; there is no default."""
    import harness
    bench = json.loads((root / "BENCHMARK.json").read_text())
    shutil.copytree(root / "benchmarks", tmp_path / "benchmarks")
    cfg = dict(tiny.CONFIG)
    del cfg["family"]
    (tmp_path / "benchmarks" / "chip" / "configs" /
     "tiny-dense.json").write_text(json.dumps(cfg))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    with pytest.raises(KeyError, match="family"):
        harness.load_cell(tmp_path, "tiny-batch")


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


@pytest.mark.parametrize("arch,family", [(tiny.CONFIG, "families/llama.py"),
                                         (tiny.GRANITE,
                                          "testdata/granite.py")])
def test_program_params_layout(arch, family):
    """A family's weights fill the program's parameter tree with the
    shapes the program itself would make."""
    import jax
    from repro.models import lm
    import harness
    import weights
    fam = harness.load_module(HERE / family)
    cfg = harness.model_config(arch, fam)
    spec = tuple((n, s, sd, m)
                 for n, (s, sd, m) in fam.shapes(arch).items())
    ours = jax.eval_shape(lambda: fam.program_params(
        weights._draw(weights.key_for(0, 0), spec), arch))
    theirs = jax.eval_shape(lambda: lm.init_lm(jax.random.PRNGKey(0),
                                               cfg)[0])
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        assert a.shape == b.shape and a.dtype == b.dtype


@pytest.mark.parametrize("arch,family,change", [
    (tiny.CONFIG, "families/llama.py", {"num_key_value_heads": 4}),
    (tiny.CONFIG, "families/llama.py", {"rope_theta": 1e6}),
    (tiny.CONFIG, "families/llama.py",
     {"overrides": dict(tiny.CONFIG["overrides"], act="gelu")}),
    (tiny.GRANITE, "testdata/granite.py", {"hidden_act": "silu"}),
    (tiny.GRANITE, "testdata/granite.py",
     {"overrides": dict(tiny.GRANITE["overrides"], norm="rms")})])
def test_check_refuses_what_the_program_does_not_run(arch, family, change):
    """A family's check passes the file as it stands and refuses it where
    a published key, or the program's block, differs."""
    import harness
    fam = harness.load_module(HERE / family)
    harness.model_config(arch, fam)
    with pytest.raises(ValueError):
        harness.model_config(dict(arch, **change), fam)


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


# What the harness computed on tiny.CONFIG at commit
# 854981aa70d0667fb3b18bf4bc2d758478e65858, before the llama layout
# moved into families/llama.py: the sha256 of weights.make(arch, 0), of
# reference.logits (float32 and fp8) of 300 tokens at positions
# 100-299, and work.decode_step and work.prefill_panel.
PIN_WEIGHTS = \
    "7f901b2ac5c366652069ca7f47fd875ea4dc2702a2710025ee860f27ed4cd617"
PIN_LOGITS = {
    None: "91d8967846fcc4e6ede1f617dc9c3582650ce02751da9c2fe8ec6d944b26c8da",
    "fp8": "1ec5716109963e3682287e169ded9806a0a6e14c93f888ca80cbab85ba03daba"}
PIN_DECODE = {"mm_flops": 622592, "mm_bytes": 159744,
              "attn_flops": 2262016, "attn_bytes": 1138688}
PIN_PANEL = {"mm_flops": 62947328, "mm_bytes": 679936,
             "attn_flops": 329383936, "attn_bytes": 387072}


@pytest.mark.parametrize("mode", [None, "fp8"])
def test_llama_family_pinned_to_parent(mode):
    """The llama family is the parent's dense code moved: the same seed
    draws byte-identical weights, and the reference and the work counts
    are the parent's to the bit."""
    import hashlib
    import harness
    import weights
    fam = harness.load_module(HERE / "families" / "llama.py")
    arch = tiny.CONFIG
    w = weights.make(fam.shapes(arch), 0)
    h = hashlib.sha256()
    for k in sorted(w):
        h.update(k.encode())
        h.update(np.asarray(w[k]).view(np.uint16).tobytes())
    assert h.hexdigest() == PIN_WEIGHTS
    toks = np.random.default_rng(5).integers(0, arch["vocab_size"], 300)
    logits = np.asarray(fam.logits(w, arch, toks.astype(np.int32),
                                   np.arange(100, 300), mode=mode))
    assert hashlib.sha256(logits.tobytes()).hexdigest() == PIN_LOGITS[mode]
    m = fam.dims(arch)
    assert fam.decode_step(m, [5, 17, 300, 4096], 16) == PIN_DECODE
    assert fam.prefill_panel(m, 512, 1000) == PIN_PANEL


def test_mfu_reads_the_family_work():
    """``mfu`` counts the window's work through the family the record
    carries: here the parent's pinned counts of one decode step and one
    panel inside the traced iterations, and none outside them."""
    import driver
    import harness
    fam = harness.load_module(HERE / "families" / "llama.py")
    booking = driver.Booking()
    booking.steps += [(1.0, [5, 17, 300, 4096]), (9.0, [1, 2])]
    booking.panels += [(2.0, 512, 1000), (9.5, 64, 0)]
    rec = {"trace": {"busy_s": 0.5, "t0": 0.0, "t1": 5.0}, "work": fam,
           "dims": fam.dims(tiny.CONFIG), "booking": booking,
           "page_size": 16, "peaks": {"bf16_flops": 1e9}}
    want = sum(w["mm_flops"] + w["attn_flops"]
               for w in (PIN_DECODE, PIN_PANEL))
    assert harness.reader("mfu")(rec) == pytest.approx(
        100.0 * want / (0.5 * 1e9), rel=1e-12)
