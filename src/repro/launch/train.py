"""Training driver: data pipeline -> sharded train step -> checkpoints.

Production behaviors: exact resume (checkpoint step == data step), async
checkpointing, SIGTERM preemption hook (final sync save), NaN-step
skipping, optional cross-pod int8 gradient compression, host-device mesh
for local runs.

  PYTHONPATH=src python -m repro.launch.train --arch deepseek-7b \
      --smoke --steps 50 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import signal
import sys
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import checkpointer as ckpt
from repro.configs import get_config, get_reduced
from repro.core import partitioning, runtime
from repro.data.pipeline import DataConfig, PrefetchIterator, SyntheticLM
from repro.models import lm
from repro.optim import adamw
from repro.train import step as tsl


def main(argv=None):
    runtime.init_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="deepseek-7b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--compress-pods", action="store_true")
    ap.add_argument("--mesh", default="none",
                    help="none | host (2,2,2 host devices)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = get_reduced(args.arch) if args.smoke else get_config(args.arch)
    print(f"arch={cfg.name} layers={cfg.n_layers} d={cfg.d_model}")

    tcfg = tsl.TrainConfig(
        opt=adamw.AdamWConfig(lr=args.lr),
        warmup_steps=max(args.steps // 20, 2), total_steps=args.steps,
        microbatches=args.microbatches,
        compress_pods=args.compress_pods)

    mesh = None
    if args.mesh == "host":
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh()

    key = jax.random.PRNGKey(args.seed)
    params, pspecs = lm.init_lm(key, cfg, dtype=jnp.float32)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"params: {n_params/1e6:.2f}M")
    state = tsl.init_state(params, tcfg)

    start_step = 0
    saver = None
    if args.ckpt_dir:
        saver = ckpt.AsyncCheckpointer(args.ckpt_dir)
        latest = ckpt.latest_step(args.ckpt_dir)
        if latest is not None:
            state, extra = ckpt.restore(args.ckpt_dir, latest, state)
            start_step = extra["data_step"]
            print(f"resumed from step {start_step}")

    ds = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                                global_batch=args.batch, seed=args.seed))
    it = PrefetchIterator(ds.iter_from(start_step))

    # preemption hook: a final synchronous checkpoint on SIGTERM
    preempted = {"flag": False}

    def on_sigterm(signum, frame):
        preempted["flag"] = True

    signal.signal(signal.SIGTERM, on_sigterm)

    # straggler mitigation: EMA of step latency; steps slower than
    # STRAGGLER_X times the EMA are logged (on a multi-host deployment
    # this signal feeds the controller that drains/replaces the slow
    # host and triggers an elastic restore onto the shrunk mesh —
    # checkpointing + reshard-on-load already support that path).
    STRAGGLER_X = 3.0
    ema = {"dt": None, "flagged": 0}

    def track_step_time(dt):
        if ema["dt"] is None:
            ema["dt"] = dt
            return False
        slow = dt > STRAGGLER_X * ema["dt"]
        ema["dt"] = 0.9 * ema["dt"] + 0.1 * dt
        if slow:
            ema["flagged"] += 1
            print(f"[straggler] step took {dt*1e3:.0f}ms "
                  f"(EMA {ema['dt']*1e3:.0f}ms) — flagged "
                  f"{ema['flagged']} total")
        return slow

    step_fn = tsl.make_train_step(cfg, tcfg, mesh=mesh)
    ctx = partitioning.use_mesh(mesh) if mesh is not None else None
    if ctx:
        ctx.__enter__()
    try:
        jstep = jax.jit(step_fn)
        t0 = time.time()
        for i in range(start_step, args.steps):
            batch = jax.tree.map(jnp.asarray, next(it))
            t_step = time.time()
            state, metrics = jstep(state, batch)
            jax.block_until_ready(metrics["loss"])
            track_step_time(time.time() - t_step)
            if i % 10 == 0 or i == args.steps - 1:
                dt = time.time() - t0
                tok_s = (i - start_step + 1) * args.batch * args.seq / dt
                print(f"step {i}: loss={float(metrics['loss']):.4f} "
                      f"acc={float(metrics['accuracy']):.3f} "
                      f"gnorm={float(metrics['grad_norm']):.2f} "
                      f"skip={int(metrics.get('skipped', 0))} "
                      f"tok/s={tok_s:.0f}")
            if saver and ((i + 1) % args.ckpt_every == 0):
                saver.save_async(i + 1, state, extra={"data_step": i + 1})
            if preempted["flag"]:
                print("SIGTERM: sync checkpoint + exit")
                if saver:
                    saver.wait()
                    ckpt.save(args.ckpt_dir, i + 1, state,
                              extra={"data_step": i + 1})
                sys.exit(0)
        if saver:
            saver.wait()
            ckpt.save(args.ckpt_dir, args.steps, state,
                      extra={"data_step": args.steps})
    finally:
        it.close()
        if ctx:
            ctx.__exit__(None, None, None)
    print("done")


if __name__ == "__main__":
    main()
