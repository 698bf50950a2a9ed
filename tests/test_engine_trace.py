"""The engine traces itself: loop-phase spans in a ``jax.profiler``
trace, programs and kernels under stable names, and a build counter.

A tiny engine serves a one-shot prompt and a chunked one on the CPU
under the profiler (Python tracer off, as the chip benchmark records),
and the test reads the ``.xplane.pb`` back with ``ProfileData``.
"""
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import REDUCED
from repro.core.types import PagingConfig
from repro.kernels.flash_attention import flash_attention_p
from repro.kernels.layernorm import layernorm_p
from repro.kernels.rowwise_matmul import rowwise_matmul_p
from repro.kernels.wkv import wkv_p
from repro.models import lm
from repro.serve.engine import Engine, Request
from repro.serve.faults import parse_plan

pytestmark = pytest.mark.slow  # an engine run under the profiler

ONE_SHOT, CHUNKED = 6, 40        # prompt lengths; the chunk is 16


def _engine(faults=None):
    cfg = REDUCED["deepseek-7b"]()
    params, _ = lm.init_lm(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    kw = {} if faults is None else {"faults": parse_plan(faults)}
    return Engine(params, cfg, n_slots=2, max_len=64, eos_id=-1,
                  paging=PagingConfig(page_size=8, prefill_chunk=16), **kw)


def _submit(eng, rid, plen, max_new=4):
    prompt = np.random.default_rng(rid).integers(
        2, eng.cfg.vocab, plen).astype(np.int32)
    eng.submit(Request(rid=rid, prompt=prompt, max_new=max_new))


def _host_events(trace_dir):
    """Every host event: (thread line id, start, end, name, stats)."""
    path = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb",
                            recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                out.append((line.name, ev.start_ns,
                            ev.start_ns + ev.duration_ns, ev.name,
                            {k: v for k, v in ev.stats}))
    return out


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One traced run: both prompts, then a step that raises (an
    injected fault at clock 7) and the recovery that replays them."""
    eng = _engine(faults="exc@7")
    _submit(eng, 0, ONE_SHOT, max_new=12)
    _submit(eng, 1, CHUNKED, max_new=12)
    d = tmp_path_factory.mktemp("trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(d), profiler_options=opts)
    try:
        done = eng.run()
    finally:
        jax.profiler.stop_trace()
    assert sorted(c.status for c in done) == ["ok", "ok"]
    assert eng.stats["recoveries"] == 1
    return eng, _host_events(d)


def _named(events, name):
    return [e for e in events if e[3] == name]


@pytest.mark.parametrize("name", [
    "engine.iteration", "engine.sweep", "engine.admit",
    "engine.admit.fetch", "engine.chunks", "engine.chunks.launch",
    "engine.chunks.fetch", "engine.decode.prepare", "engine.decode.launch",
    "engine.decode.fetch", "engine.decode.emit", "engine.recover",
    "engine.build"])
def test_engine_emits_span(traced, name):
    _, events = traced
    assert _named(events, name), sorted({e[3] for e in events
                                         if e[3].startswith("engine.")})


def _parent(events, ev, name):
    """The ``name`` span on ``ev``'s thread that covers it, or None."""
    for e in _named(events, name):
        if e[0] == ev[0] and e[1] <= ev[1] and ev[2] <= e[2]:
            return e
    return None


@pytest.mark.parametrize("child,parent", [
    ("engine.decode.fetch", "engine.iteration"),
    ("engine.decode.launch", "engine.iteration"),
    ("engine.admit", "engine.iteration"),
    ("engine.admit.fetch", "engine.admit"),
    ("engine.admit.fetch", "engine.iteration"),
    ("engine.chunks.launch", "engine.chunks"),
    ("engine.chunks.fetch", "engine.chunks"),
    ("engine.recover", "engine.iteration"),
    ("engine.build", "engine.iteration")])
def test_spans_nest(traced, child, parent):
    _, events = traced
    kids = _named(events, child)
    assert kids
    assert all(_parent(events, k, parent) is not None for k in kids)


def test_admission_spans_carry_the_request(traced):
    _, events = traced
    seen = {}
    for e in _named(events, "engine.admit"):
        seen.setdefault(e[4]["rid"], []).append(e[4])
    # each rid is admitted once, then once more on the replay after the
    # injected fault
    assert sorted(seen) == [0, 1]
    for rid, plen in ((0, ONE_SHOT), (1, CHUNKED)):
        args = seen[rid][0]
        assert args["prompt_len"] == plen and args["hit"] == 0
        assert 0 <= args["slot"] < 2
    chunk_rids = {e[4]["rid"] for e in _named(events,
                                              "engine.chunks.launch")}
    assert chunk_rids == {1}
    offsets = sorted({e[4]["offset"] for e in _named(
        events, "engine.chunks.launch")})
    assert offsets == [0, 16, 32]


def test_iteration_spans_count_the_loop(traced):
    eng, events = traced
    its = _named(events, "engine.iteration")
    assert [e[4]["clock"] for e in its] == list(range(eng.clock))
    assert max(e[4]["n_active"] for e in its) == 2


@pytest.mark.parametrize("program", ["decode_step", "prefill",
                                     "prefill_chunk"])
def test_programs_keep_their_names(traced, program):
    _, events = traced
    assert _named(events, f"PjitFunction({program})")


def test_build_spans_match_the_counter(traced):
    eng, events = traced
    builds = _named(events, "engine.build")
    assert len(builds) == eng.stats["program_builds"]
    assert eng.stats["program_builds"] == sum(eng.compile_counts().values())
    assert {e[4]["program"] for e in builds} == {"decode_step", "prefill",
                                                "prefill_chunk"}


def test_build_counter_grows_only_on_new_signatures():
    eng = _engine()
    _submit(eng, 0, ONE_SHOT)
    eng.run()
    first = eng.stats["program_builds"]
    assert first == 2                      # prefill(16) + decode_step
    assert eng.stats["program_build_s"] > 0
    # steady decode at the same signatures: no build
    _submit(eng, 1, ONE_SHOT + 1, max_new=8)
    eng.run()
    assert eng.stats["program_builds"] == first
    # the first chunked prompt builds the chunk program, the next none
    _submit(eng, 2, CHUNKED)
    eng.run()
    assert eng.stats["program_builds"] == first + 1
    _submit(eng, 3, CHUNKED + 3)
    _submit(eng, 4, ONE_SHOT)
    eng.run()
    assert eng.stats["program_builds"] == first + 1
    assert eng.stats["program_builds"] == sum(eng.compile_counts().values())


# --- kernels: the name and metadata reach the TPU op -----------------

def _bf16(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.bfloat16)


_KERNELS = {
    "rowwise_matmul": (
        lambda x, w, wg, g: rowwise_matmul_p(
            x, w, w_gate=wg, activation="silu", prologue="rms", gamma=g),
        [_bf16(8, 256), _bf16(256, 512), _bf16(256, 512),
         jax.ShapeDtypeStruct((256,), jnp.float32)],
        ('"gated":"True"', '"prologue":"rms"', '"k":"256"', '"n":"512"')),
    "flash_attention": (
        lambda q, k, v: flash_attention_p(q, k, v, causal=True),
        [_bf16(1, 2, 128, 128)] * 3, ('"bq":', '"bk":')),
    "layernorm": (
        lambda x, g: layernorm_p(x, g, kind="rms"),
        [_bf16(16, 256), _bf16(256)], ('"norm":"rms"',)),
}
# the wkv kernel does not lower for the TPU on the CPU (cumsum has no
# Mosaic rule there); its metadata is checked in the jaxpr only
_WKV = (lambda r, k, v, lw, u: wkv_p(r, k, v, lw, u),
        [_bf16(1, 32, 2, 64)] * 4 + [_bf16(2, 64)])


@pytest.mark.parametrize("kernel", sorted(_KERNELS) + ["wkv"])
def test_pallas_call_carries_the_kernel_name(kernel):
    fn, shapes = _WKV if kernel == "wkv" else _KERNELS[kernel][:2]
    jaxpr = jax.make_jaxpr(fn)(*shapes)
    calls = [e for e in jaxpr.jaxpr.eqns
             if e.primitive.name == "pallas_call"]
    assert calls
    assert all(e.params["metadata"]["kernel"] == kernel for e in calls)


@pytest.mark.parametrize("kernel", sorted(_KERNELS))
def test_kernel_metadata_reaches_the_tpu_op(kernel):
    fn, shapes, extra = _KERNELS[kernel]
    text = jax.jit(fn).trace(*shapes).lower(
        lowering_platforms=("tpu",)).as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert calls
    for ln in calls:
        assert f'kernel_name = "{kernel}"' in ln
        meta = ln[ln.index("kernel_metadata"):].replace("\\22", '"')
        assert f'"kernel":"{kernel}"' in meta
        for item in extra:
            assert item in meta, (item, meta[:300])
