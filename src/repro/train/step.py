"""Train-step factory: microbatch accumulation, NaN guards, LR schedule,
optional cross-pod int8 gradient compression (shard_map over 'pod').
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.types import ModelConfig
from repro.models import lm
from repro.optim import adamw, compression


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    opt: adamw.AdamWConfig = adamw.AdamWConfig()
    warmup_steps: int = 100
    total_steps: int = 10_000
    microbatches: int = 1           # gradient accumulation
    compress_pods: bool = False     # int8+EF cross-pod gradient reduce
    remat: bool = True
    skip_nonfinite: bool = True     # fault tolerance: skip bad steps


class TrainState(NamedTuple):
    params: Any
    opt: adamw.AdamWState
    residual: Any                   # EF buffer (empty dict if unused)


def init_state(params, tcfg: TrainConfig) -> TrainState:
    res = (compression.init_residual(params) if tcfg.compress_pods else {})
    return TrainState(params=params, opt=adamw.init(params), residual=res)


def state_logical_specs(param_specs, tcfg: TrainConfig):
    res = param_specs if tcfg.compress_pods else {}
    return TrainState(params=param_specs,
                      opt=adamw.state_specs(param_specs),
                      residual=res)


def fuse_state(state: TrainState, cfg: ModelConfig) -> TrainState:
    """Migrate a seed-layout TrainState (split wq/wk/wv, wg/wi leaves)
    to the fused param layout (DESIGN.md §5), so old training
    checkpoints keep resuming. AdamW moments are per-element, so
    concatenating mu/nu alongside the params is EXACT — the migrated
    state steps bit-identically to the unmigrated one (global-norm
    clipping sums over leaves, invariant under the re-grouping). EF
    residuals (cross-pod compression) mirror the grad tree and fuse the
    same way."""
    from repro.models import lm
    fuse = lambda tree: lm.fuse_params(cfg, tree)   # noqa: E731
    opt = state.opt._replace(mu=fuse(state.opt.mu), nu=fuse(state.opt.nu))
    res = fuse(state.residual) if state.residual else state.residual
    return TrainState(params=fuse(state.params), opt=opt, residual=res)


def _grads_and_metrics(params, batch, cfg, tcfg):
    def loss_fn(p, b):
        return lm.loss_fn(p, b, cfg, remat=tcfg.remat)

    if tcfg.microbatches <= 1:
        (_, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch)
        return grads, metrics

    n = tcfg.microbatches
    micro = jax.tree.map(
        lambda x: x.reshape((n, x.shape[0] // n) + x.shape[1:]), batch)

    def acc_step(carry, mb):
        g_acc, m_acc = carry
        (_, m), g = jax.value_and_grad(loss_fn, has_aux=True)(params, mb)
        g_acc = jax.tree.map(
            lambda a, b: a + b.astype(jnp.float32) / n, g_acc, g)
        m_acc = jax.tree.map(lambda a, b: a + b / n, m_acc, m)
        return (g_acc, m_acc), None

    g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    m0 = {"loss": 0.0, "aux_loss": 0.0, "ntokens": 0.0, "accuracy": 0.0}
    m0 = jax.tree.map(lambda x: jnp.zeros((), jnp.float32), m0)
    (grads, metrics), _ = jax.lax.scan(acc_step, (g0, m0), micro)
    return grads, metrics


def _apply_update(state: TrainState, grads, metrics, cfg, tcfg):
    lr_scale = adamw.cosine_schedule(
        state.opt.step, warmup=tcfg.warmup_steps, total=tcfg.total_steps)
    new_params, new_opt, gnorm = adamw.apply(
        tcfg.opt, state.opt, state.params, grads, lr_scale)
    metrics = dict(metrics)
    metrics["grad_norm"] = gnorm
    metrics["lr_scale"] = lr_scale
    if tcfg.skip_nonfinite:
        ok = jnp.isfinite(metrics["loss"]) & jnp.isfinite(gnorm)
        new_params = jax.tree.map(
            lambda n, o: jnp.where(ok, n, o), new_params, state.params)
        new_opt = jax.tree.map(
            lambda n, o: jnp.where(ok, n, o), new_opt,
            state.opt._replace(step=state.opt.step + 1))
        metrics["skipped"] = (~ok).astype(jnp.float32)
    return TrainState(params=new_params, opt=new_opt,
                      residual=state.residual), metrics


def make_train_step(cfg: ModelConfig, tcfg: TrainConfig, mesh=None,
                    param_specs=None):
    """Returns train_step(state, batch) -> (new_state, metrics).

    param_specs (logical spec tree): when given, gradients are pinned to
    the parameter sharding right after AD so the cross-device reduction
    lowers to reduce-scatter instead of a full all-reduce.
    """
    from repro.core import partitioning

    if not tcfg.compress_pods:
        def train_step(state: TrainState, batch):
            grads, metrics = _grads_and_metrics(state.params, batch, cfg,
                                                tcfg)
            if param_specs is not None:
                grads = partitioning.constrain_tree(grads, param_specs)
            return _apply_update(state, grads, metrics, cfg, tcfg)
        return train_step

    assert mesh is not None and "pod" in mesh.axis_names

    def train_step(state: TrainState, batch):
        def body(params, residual, batch_local):
            grads, metrics = _grads_and_metrics(params, batch_local, cfg,
                                                tcfg)
            grads, new_res = compression.compressed_pmean_tree(
                grads, residual, "pod")
            metrics = jax.tree.map(
                lambda m: jax.lax.pmean(m, "pod"), metrics)
            return grads, new_res, metrics

        rep = jax.tree.map(lambda _: P(), state.params)
        batch_spec = jax.tree.map(lambda _: P("pod"), batch)
        metric_spec = {k: P() for k in
                       ("loss", "aux_loss", "ntokens", "accuracy")}
        # manual over 'pod' only; data/model stay GSPMD-auto inside
        fn = jax.shard_map(body, mesh=mesh,
                           in_specs=(rep, rep, batch_spec),
                           out_specs=(rep, rep, metric_spec),
                           axis_names=frozenset({"pod"}),
                           check_vma=False)
        grads, new_res, metrics = fn(state.params, state.residual, batch)
        new_state, metrics = _apply_update(
            state._replace(residual=new_res), grads, metrics, cfg, tcfg)
        return new_state, metrics

    return train_step
