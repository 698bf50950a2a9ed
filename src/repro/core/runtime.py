"""Global runtime context: which kernel implementation the models use.

  * 'ref'       — pure-jnp oracles (XLA fuses them; default on CPU and
                  for the dry-run, so cost_analysis reflects real math)
  * 'pallas'    — compiled Pallas kernels (real TPU)
  * 'interpret' — Pallas kernels in interpret mode (CPU correctness runs)

Selected process-wide (launcher flag) or via context manager in tests.

Also owns where the launchers keep JAX's persistent compilation cache
(:func:`init_compile_cache`).

Also owns the **pipeline-fusion** switch (PR 2): when on (default), the
models fuse the pre-norm prologue, multi-head projections and
residual/gating epilogues into single row-wise kernel launches; when
off they compose the per-op kernels the way the seed did. The off path
exists so benchmarks can report before/after launch counts and HBM
traffic for the same weights.
"""
from __future__ import annotations

import contextlib
import os
from pathlib import Path

import jax

_IMPL = "auto"
_FUSE_PIPELINE = True

# fixed, so that one run finds what the last run compiled: the cache
# key includes the path
_CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def init_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; call it before the
    first compile. ``JAX_COMPILATION_CACHE_DIR``, when set, names the
    directory and nothing else is set; otherwise the cache lives in
    ``.jax_cache`` at the root of the checkout. Returns the directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(_CHECKOUT_CACHE)
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def resolve_impl() -> str:
    if _IMPL != "auto":
        return _IMPL
    platform = jax.default_backend()
    return "pallas" if platform == "tpu" else "ref"


def set_impl(impl: str) -> None:
    global _IMPL
    assert impl in ("auto", "ref", "pallas", "interpret"), impl
    _IMPL = impl


@contextlib.contextmanager
def use_impl(impl: str):
    global _IMPL
    prev = _IMPL
    set_impl(impl)
    try:
        yield
    finally:
        _IMPL = prev


def pipeline_fusion() -> bool:
    return _FUSE_PIPELINE


def set_pipeline_fusion(on: bool) -> None:
    global _FUSE_PIPELINE
    _FUSE_PIPELINE = bool(on)


@contextlib.contextmanager
def use_pipeline_fusion(on: bool):
    global _FUSE_PIPELINE
    prev = _FUSE_PIPELINE
    _FUSE_PIPELINE = bool(on)
    try:
        yield
    finally:
        _FUSE_PIPELINE = prev
