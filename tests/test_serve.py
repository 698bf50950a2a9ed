"""Serving engine: continuous batching equals manual greedy decoding."""
import jax
import jax.numpy as jnp
import pytest

from conftest import manual_greedy

from repro.configs import REDUCED
from repro.core import runtime
from repro.launch import serve as serve_cli
from repro.models import lm
from repro.serve import sampling
from repro.serve.engine import Engine, ProgramError, Request

pytestmark = pytest.mark.slow  # engine decode loops, ~20s+ on CPU


def test_engine_matches_manual_decode():
    cfg = REDUCED["deepseek-7b"]()
    key = jax.random.PRNGKey(0)
    params, _ = lm.init_lm(key, cfg, dtype=jnp.float32)
    prompts = [jax.random.randint(jax.random.fold_in(key, i),
                                  (6 + i,), 0, cfg.vocab)
               for i in range(3)]
    n_new = 5
    eng = Engine(params, cfg, n_slots=2, max_len=32, eos_id=-1)
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new=n_new))
    done = eng.run()
    assert len(done) == 3
    by_rid = {c.rid: c for c in done}
    for i, p in enumerate(prompts):
        want = manual_greedy(params, cfg, p, n_new, 32)
        assert by_rid[i].tokens == want, (i, by_rid[i].tokens, want)


def test_continuous_batching_refills_slots():
    cfg = REDUCED["rwkv6-3b"]()
    key = jax.random.PRNGKey(1)
    params, _ = lm.init_lm(key, cfg, dtype=jnp.float32)
    eng = Engine(params, cfg, n_slots=2, max_len=24, eos_id=-1)
    for i in range(5):   # more requests than slots
        eng.submit(Request(rid=i, prompt=jax.random.randint(
            jax.random.fold_in(key, i), (4,), 0, cfg.vocab), max_new=3))
    done = eng.run()
    assert sorted(c.rid for c in done) == [0, 1, 2, 3, 4]
    assert all(len(c.tokens) == 3 for c in done)


def test_sampling_modes():
    key = jax.random.PRNGKey(0)
    logits = jnp.asarray([[0.0, 5.0, 1.0, -2.0]])
    assert int(sampling.greedy(logits)[0]) == 1
    s = sampling.sample(logits, key, temperature=0.5, top_k=2)
    assert int(s[0]) in (1, 2)
    s = sampling.sample(logits, key, temperature=1.0, top_p=0.5)
    assert int(s[0]) == 1


_CLI = ["--arch", "deepseek-7b", "--smoke", "--requests", "3", "--slots", "2",
        "--max-len", "32", "--page-size", "8", "--max-new", "3"]


@pytest.mark.parametrize("step_raises", [False, True])
def test_serve_cli_exit_status(monkeypatch, step_raises):
    """Without a fault plan, a run whose steps keep raising ends every
    request `failed` and the CLI exits 1; a clean run exits 0."""
    monkeypatch.setattr(runtime, "init_compile_cache", lambda: None)
    if step_raises:
        def lost(self):
            raise RuntimeError("device lost")
        monkeypatch.setattr(Engine, "_ship_tables", lost)
    assert serve_cli.main(_CLI) == (1 if step_raises else 0)


def test_program_build_failure_is_not_retried(monkeypatch):
    """A program that fails to trace or compile would fail again on every
    replay: it leaves the recovery boundary at once."""
    monkeypatch.setattr(runtime, "init_compile_cache", lambda: None)
    recovered = []
    monkeypatch.setattr(Engine, "_recover", lambda self: recovered.append(1))

    def broken(*args, **kwargs):
        raise TypeError("cannot lower")
    monkeypatch.setattr(lm, "decode_step", broken)
    with pytest.raises(ProgramError, match="cannot lower"):
        serve_cli.main(_CLI)
    assert not recovered
