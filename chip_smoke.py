"""Bring-up check: the serving path and the row-wise kernels on a TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # tensor-parallel serving on four

One process drives the chip. Without an option it runs four phases:

  1. device   - a TPU is present and the models resolve to the Pallas
                kernels (there is no CPU fallback);
  2. kernels  - the row-wise kernels at deepseek-7b widths, at decode
                (M = 8) and prefill (M = 512) row counts, and causal
                flash attention at S = 512, each against ``impl="ref"``;
  3. serving  - deepseek-7b at its published widths in bf16, cut to 8
                of its 30 layers, served by the paged engine: 8 seeded
                requests, then its prefill logits and its token streams
                checked against a float32 reference forward;
  4. swin     - Swin-T at its full config on a batch of 8 images.

``--chips 4`` runs only the phase-3 engine under 4-way tensor
parallelism and the single-device engine it is compared with.

Every phase prints one line. Any failed check exits 1. The last line of
a passing run is ``{"ok": true, "device": {...}}``. Random weights and
inputs come from a fixed seed. The wall time printed for serving is a
cold run, compiles included: it is not a benchmark.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.core import runtime                           # noqa: E402

# Tolerances. Each is a relative error against the reference: the RMS of
# the difference over the RMS of the reference (``rms``), or the largest
# difference over the largest reference magnitude (``max``).
#
# bf16-out kernels: both sides multiply the same bf16 operands with fp32
# accumulation and round the result to bf16 once; they differ in
# summation order, so by at most one bf16 ulp of an element (2^-8 of
# its magnitude). A dropped norm, gamma or residual, or a tile read from
# the wrong block, is an error of order one.
TOL_KERNEL_BF16 = 1e-2            # max
# fp32-out lm_head: only the fp32 summation order differs (K = 4096).
TOL_KERNEL_F32 = 1e-3             # max
# flash attention rounds the softmax weights to bf16 before the PV
# product (the reference keeps them fp32): 2^-9 per weight.
TOL_ATTENTION = 2e-2              # max
# deepseek-7b logits, bf16 serving path vs the fp32 reference: bf16
# activations between kernels cost 1.1-1.3% RMS at 8 layers (measured
# on XLA:CPU at widths 512 and 1024 with the reference kernels in bf16).
# Weights stored in 8 bits (2^-4 steps) would be far outside.
TOL_LM_LOGITS = 3e-2              # rms
# Teacher-forced greedy check: a served token must be the reference
# argmax wherever the reference's top-2 margin exceeds this many logits
# (about 3x the largest bf16-vs-fp32 logit error seen at those widths).
GREEDY_MARGIN = 0.25
# Swin-T logits, bf16 Pallas path vs fp32 reference: bf16 costs 0.24%
# RMS (same XLA:CPU measurement, batch 2).
TOL_SWIN = 1e-2                   # rms
# Tensor-parallel vs single-device serving logits: both bf16, but the
# row-sharded projections round four partial sums before their psum.
TOL_TP = 3e-2                     # rms

SEED = 0
SERVE_LAYERS = 8
SLOTS, MAX_LEN, PAGE, CHUNK, MAX_NEW = 4, 1024, 16, 512, 16
# 30..900 tokens: one-shot admissions at buckets 32/64/256/512, a full
# 512-row chunk panel plus a partial final chunk (rid 1), and rid 5
# sharing rid 1's first 640 tokens (40 pages) to hit the prefix cache.
PROMPT_LENS = (30, 900, 45, 200, 480, 700, 60, 350)
SHARED = {5: (1, 640)}            # rid -> (source rid, shared tokens)


class Failed(Exception):
    pass


def check(ok: bool, what: str):
    if not ok:
        raise Failed(what)


def rel_err(out, ref, kind: str) -> float:
    import numpy as np
    a = np.asarray(out, np.float64)
    b = np.asarray(ref, np.float64)
    if kind == "rms":
        return float(np.sqrt(np.sum((a - b) ** 2) / np.sum(b ** 2)))
    return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))


# ----------------------------------------------------------------------
# phase 1: device
# ----------------------------------------------------------------------


def phase_device(chips: int):
    import jax
    devs = jax.devices()
    d0 = devs[0]
    check(d0.platform == "tpu",
          f"no TPU found: jax.devices()[0] is {d0.platform} ({d0})")
    impl = runtime.resolve_impl()
    check(impl == "pallas", f"models resolve impl={impl}, not pallas")
    check(len(devs) >= chips, f"{chips} chips asked, {len(devs)} found")
    print(f"[device] platform={d0.platform} kind={d0.device_kind} "
          f"count={len(devs)} impl={impl}", flush=True)
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


# ----------------------------------------------------------------------
# phase 2: kernels
# ----------------------------------------------------------------------


def phase_kernels():
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.kernels import ops

    impl = runtime.resolve_impl()     # pallas: phase 1 checked it
    cfg = get_config("deepseek-7b")
    d, f, v = cfg.d_model, cfg.d_ff, cfg.vocab
    hd = cfg.head_dim
    bf = jnp.bfloat16
    key = jax.random.PRNGKey(SEED)
    ks = iter(jax.random.split(key, 16))

    def w(shape, fan_in):
        return (jax.random.normal(next(ks), shape) / fan_in ** 0.5
                ).astype(bf)

    # weights travel as arguments: closed over, they would be baked
    # into each executable as constants
    mats = {"wqkv": w((d, 3 * d), d), "wgi": w((d, 2 * f), d),
            "wdown": w((f, d), f), "whead": w((d, v), d),
            # gamma away from 1 and rows away from unit RMS, so that a
            # dropped norm or gamma shows
            "gamma": 1.0 + 0.5 * jax.random.normal(next(ks), (d,))}

    def norm(mm):
        return ops.NormSpec("rms", mm["gamma"])

    cases = {   # name: (op of (x, x_ff, residual, mats, impl), tolerance)
        "qkv+rms": (lambda x, h, r, mm, impl: jnp.concatenate(ops.qkv_proj(
            x, mm["wqkv"], (d, d, d), norm=norm(mm), impl=impl), -1),
            TOL_KERNEL_BF16),
        "gate|up+rms": (lambda x, h, r, mm, impl: ops.gate_up_proj(
            x, mm["wgi"], activation="silu", norm=norm(mm), impl=impl),
            TOL_KERNEL_BF16),
        "down+res": (lambda x, h, r, mm, impl: ops.matmul(
            h, mm["wdown"], residual=r, impl=impl), TOL_KERNEL_BF16),
        "lm_head f32": (lambda x, h, r, mm, impl: ops.matmul(
            x, mm["whead"], out_dtype=jnp.float32, impl=impl),
            TOL_KERNEL_F32),
    }
    worst = {}
    for m in (8, 512):
        args = ((3.0 * jax.random.normal(next(ks), (m, d)) + 0.5).astype(bf),
                jax.random.normal(next(ks), (m, f)).astype(bf),
                jax.random.normal(next(ks), (m, d)).astype(bf), mats)
        for name, (fn, tol) in cases.items():
            out = jax.jit(lambda *a, fn=fn: fn(*a, impl))(*args)
            with jax.default_matmul_precision("highest"):
                ref = jax.jit(lambda *a, fn=fn: fn(*a, "ref"))(*args)
            err = rel_err(out, ref, "max")
            worst[f"{name}@M={m}"] = err
            check(err <= tol, f"kernel {name} M={m}: max rel err {err:.3g} "
                              f"> {tol:g}")
    qkv = jax.random.normal(next(ks), (3, 1, cfg.n_heads, 512, hd)
                            ).astype(bf)
    out = jax.jit(lambda q, k, v: ops.attention(
        q, k, v, causal=True, impl=impl))(*qkv)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda q, k, v: ops.attention(
            q, k, v, causal=True, impl="ref"))(*qkv)
    err = rel_err(out, ref, "max")
    worst["attention causal S=512"] = err
    check(err <= TOL_ATTENTION,
          f"attention S=512: max rel err {err:.3g} > {TOL_ATTENTION:g}")
    return ("[kernels] "
            + " ".join(f"{k}={e:.2e}" for k, e in worst.items())
            + f" (tol bf16 {TOL_KERNEL_BF16:g}, f32 {TOL_KERNEL_F32:g}, "
            f"attn {TOL_ATTENTION:g})")


# ----------------------------------------------------------------------
# phase 3: serving
# ----------------------------------------------------------------------


def serve_config():
    from repro.configs import get_config
    full = get_config("deepseek-7b")
    return full, dataclasses.replace(full, n_layers=SERVE_LAYERS)


def make_prompts(vocab: int):
    import numpy as np
    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(0, vocab, size=n, dtype=np.int32)
               for n in PROMPT_LENS]
    for rid, (src, n) in SHARED.items():
        prompts[rid][:n] = prompts[src][:n]
    return prompts


def run_engine(params, cfg, prompts, placement):
    """Build the Engine as ``repro.launch.serve`` does and serve
    ``prompts`` greedily. Returns (engine, completions by rid, wall s)."""
    from repro.core.types import PagingConfig
    from repro.serve import faults as faults_mod
    from repro.serve.engine import Engine, Request
    plan = faults_mod.parse_plan("")
    eng = Engine(params, cfg, n_slots=SLOTS, max_len=MAX_LEN,
                 eos_id=-1, temperature=0.0,
                 top_k=0, top_p=1.0, seed=SEED,
                 paging=PagingConfig(
                     page_size=PAGE, n_pages=0,
                     prefill_chunk=CHUNK,
                     prefix_cache=True,
                     prefill_token_budget=0,
                     speculate_k=0),
                 placement=placement, faults=plan,
                 preempt_patience=None)
    for rid, p in enumerate(prompts):
        eng.submit(Request(rid=rid, prompt=p, max_new=MAX_NEW))
    t0 = time.perf_counter()
    done = eng.run()
    wall = time.perf_counter() - t0
    return eng, {c.rid: c for c in done}, wall


def check_engine(eng, done, tag: str):
    from repro.analysis import compile_bound
    statuses = [done[r].status if r in done else "missing"
                for r in range(len(PROMPT_LENS))]
    check(all(s == "ok" for s in statuses),
          f"{tag}: statuses {statuses}")
    check(not eng.errors, f"{tag}: errors {eng.errors}")
    check(eng.stats["recoveries"] == 0,
          f"{tag}: recoveries {eng.stats['recoveries']}")
    check(eng.stats["prefix_hits"] >= 1, f"{tag}: no prefix-cache hit")
    inv = compile_bound.enumerate_programs(
        max_len=MAX_LEN, page_size=PAGE, prefill_chunk=CHUNK)
    counts = eng.compile_counts()
    bound = {"prefill": len(inv.prefill_lens),
             "chunk": len(inv.chunk_shapes), "step": len(inv.step_widths)}
    check(all(counts[k] <= bound[k] for k in bound),
          f"{tag}: compiles {counts} exceed the bound {bound}")
    return counts, bound


def serving_prefill_logits(eng, prompt):
    """First-token logits of ``prompt`` through the engine's own chunked
    prefill program: its placement, its prepared parameters, a fresh
    one-slot page pool and the engine's chunk schedule."""
    import jax.numpy as jnp
    import numpy as np
    from repro.models import lm
    from repro.serve.paging import chunk_schedule
    from repro.serve.placement import CACHE, PARAMS, REP
    pl = eng.placement
    rcfg = pl.compute_cfg(eng.cfg)
    cache = pl.prepare_cache(lm.init_paged_cache(
        eng.cfg, 1, MAX_LEN, page_size=eng.page_size, dtype=eng.cache_dtype))
    fn = pl.jit(lambda p, c, t, off, n, pg: lm.prefill_chunk(
        p, c, t, rcfg, offset=off, chunk_len=n, pages=pg),
        kinds=(PARAMS, CACHE) + (REP,) * 4, out_kinds=(REP, CACHE),
        donate=(1,))
    pages = pl.put_rep(jnp.arange(eng.max_pages, dtype=jnp.int32)[None])
    logits = None
    for off, n, shape in chunk_schedule(len(prompt), CHUNK, eng.buckets):
        tok = np.zeros((1, shape), np.int32)
        tok[0, :n] = prompt[off:off + n]
        logits, cache = fn(eng.params, cache, pl.put_rep(jnp.asarray(tok)),
                           pl.put_rep(jnp.int32(off)),
                           pl.put_rep(jnp.int32(n)), pages)
    return np.asarray(logits[0], np.float32)


def reference_logits(params, cfg):
    """fp32 reference forward: the same bf16 weights, fp32 activations,
    the plain jnp kernels and full-precision matmuls. Returns a function
    of one token sequence (padded to MAX_LEN, so it compiles once)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import lm
    ref_params = dict(params, embed=params["embed"].astype(jnp.float32))

    def fwd(p, t):
        with runtime.use_impl("ref"):
            return lm.forward(p, t, cfg, remat=False)[0][0]

    with jax.default_matmul_precision("highest"):
        jfwd = jax.jit(fwd).lower(
            ref_params, jnp.zeros((1, MAX_LEN), jnp.int32)).compile()

    def run(seq):
        t = np.zeros((1, MAX_LEN), np.int32)
        t[0, :len(seq)] = seq
        return jfwd(ref_params, jnp.asarray(t))
    return run


def teacher_forced(ref_run, prompt, tokens):
    """Check served tokens against the reference argmax, conditioning
    the reference on the served prefix. Returns (checked, skipped)."""
    import numpy as np
    plen = len(prompt)
    seq = np.concatenate([prompt, np.asarray(tokens[:-1], np.int32)])
    lg = np.asarray(ref_run(seq)[plen - 1:plen - 1 + len(tokens)],
                    np.float32)
    top2 = np.sort(lg, axis=-1)[:, -2:]
    checked = skipped = 0
    for i, tok in enumerate(tokens):
        if top2[i, 1] - top2[i, 0] < GREEDY_MARGIN:
            skipped += 1
            continue
        checked += 1
        check(int(np.argmax(lg[i])) == tok,
              f"token {i} of a {plen}-token prompt: served {tok}, "
              f"reference argmax {int(np.argmax(lg[i]))} (margin "
              f"{top2[i, 1] - top2[i, 0]:.3f})")
    return checked, skipped


def init_params(cfg):
    import jax
    from repro.models import lm
    params, _ = lm.init_lm(jax.random.PRNGKey(SEED), cfg)
    jax.block_until_ready(params)
    return params


def phase_serving():
    from repro.serve.placement import SingleDevice
    full, cfg = serve_config()
    params = init_params(cfg)
    prompts = make_prompts(cfg.vocab)
    eng, done, wall = run_engine(params, cfg, prompts, SingleDevice())
    counts, bound = check_engine(eng, done, "serving")
    n_tok = sum(len(c.tokens) for c in done.values())

    ref_run = reference_logits(params, cfg)
    served = serving_prefill_logits(eng, prompts[1])[:cfg.vocab]
    ref = ref_run(prompts[1])[len(prompts[1]) - 1, :cfg.vocab]
    err = rel_err(served, ref, "rms")
    check(err <= TOL_LM_LOGITS,
          f"prefill logits of rid 1: rms rel err {err:.3g} > "
          f"{TOL_LM_LOGITS:g}")
    checked = skipped = 0
    for rid, p in enumerate(prompts):
        c, s = teacher_forced(ref_run, p, done[rid].tokens)
        checked += c
        skipped += s
    statuses = sorted({c.status for c in done.values()})
    return (f"[serving] cut: {full.name} n_layers {full.n_layers} -> "
            f"{cfg.n_layers} (dataclasses.replace), widths as published "
            f"(d={cfg.d_model} heads={cfg.n_heads}x{cfg.head_dim} "
            f"d_ff={cfg.d_ff} vocab={cfg.vocab} {cfg.dtype}); "
            f"requests={len(done)} statuses={statuses} "
            f"errors={eng.errors} recoveries={eng.stats['recoveries']} "
            f"prefix_hits={eng.stats['prefix_hits']} "
            f"hit_tokens={eng.stats['prefix_hit_tokens']} "
            f"compiles={counts} bound={bound} decoded={n_tok} tokens "
            f"cold_wall_s={wall:.3f} (compiles included, not a benchmark) "
            f"prefill_logits_rms_err={err:.3e} (tol {TOL_LM_LOGITS:g}) "
            f"greedy_vs_ref checked={checked} near_ties_skipped={skipped} "
            f"(margin {GREEDY_MARGIN:g})")


# ----------------------------------------------------------------------
# phase 4: Swin-T
# ----------------------------------------------------------------------


def phase_swin():
    import jax
    import jax.numpy as jnp
    from repro.configs.swin_t import CONFIG
    from repro.models import vision
    key = jax.random.PRNGKey(SEED)
    params = vision.init_swin(key, CONFIG, dtype=jnp.bfloat16)
    images = jax.random.normal(jax.random.fold_in(key, 1),
                               (8, CONFIG.img_size, CONFIG.img_size,
                                CONFIG.in_chans))

    def fwd(p, x):
        return vision.swin_forward(p, x, CONFIG)

    out = jax.jit(fwd)(params, images.astype(jnp.bfloat16))
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    with runtime.use_impl("ref"), jax.default_matmul_precision("highest"):
        ref = jax.jit(fwd)(p32, images)
    err = rel_err(out, ref, "rms")
    check(out.shape == (8, CONFIG.num_classes),
          f"swin logits shape {out.shape}")
    check(err <= TOL_SWIN, f"swin: rms rel err {err:.3g} > {TOL_SWIN:g}")
    return (f"[swin] {CONFIG.name} img={CONFIG.img_size} depths="
            f"{CONFIG.depths} window={CONFIG.window} batch=8 "
            f"logits={tuple(out.shape)} rms_err_vs_f32_ref={err:.3e} "
            f"(tol {TOL_SWIN:g})")


# ----------------------------------------------------------------------
# --chips 4: tensor-parallel serving
# ----------------------------------------------------------------------


def phase_tp(chips: int):
    from repro.serve.placement import SingleDevice, TensorParallel
    full, cfg = serve_config()
    params = init_params(cfg)
    prompts = make_prompts(cfg.vocab)
    one, done1, wall1 = run_engine(params, cfg, prompts, SingleDevice())
    check_engine(one, done1, "single-device")
    tp, done4, wall4 = run_engine(params, cfg, prompts,
                                  TensorParallel(chips))
    counts, bound = check_engine(tp, done4, f"tp={chips}")
    err = rel_err(serving_prefill_logits(tp, prompts[1])[:cfg.vocab],
                  serving_prefill_logits(one, prompts[1])[:cfg.vocab], "rms")
    check(err <= TOL_TP, f"tp={chips} vs single-device prefill logits: "
                         f"rms rel err {err:.3g} > {TOL_TP:g}")
    same = sum(done1[r].tokens == done4[r].tokens for r in done1)
    first = [next((i for i, (a, b) in enumerate(
        zip(done1[r].tokens, done4[r].tokens)) if a != b), None)
        for r in sorted(done1)]
    return (f"[tp] {full.name} {cfg.n_layers}/{full.n_layers} layers "
            f"placement={tp.placement.describe()} per-shard d_ff="
            f"{cfg.d_ff // chips} statuses=ok errors=[] recoveries=0 "
            f"compiles={counts} bound={bound} prefill_logits_rms_err_vs_"
            f"single={err:.3e} (tol {TOL_TP:g}) identical_streams={same}/"
            f"{len(done1)} first_divergence={first} cold_wall_s single="
            f"{wall1:.3f} tp={wall4:.3f} (compiles included, not a "
            f"benchmark)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: tensor-parallel serving against the "
                         "single-device engine, and nothing else")
    args = ap.parse_args(argv)
    runtime.init_compile_cache()
    phases = ([phase_kernels, phase_serving, phase_swin] if args.chips == 1
              else [lambda: phase_tp(args.chips)])
    try:
        device = phase_device(args.chips)
        for phase in phases:
            t0 = time.perf_counter()
            line = phase()
            print(f"{line} phase_s={time.perf_counter() - t0:.1f}",
                  flush=True)
    except Failed as err:
        print(f"FAILED: {err}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
