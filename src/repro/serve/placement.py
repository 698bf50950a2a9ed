"""Device-placement policies for the serving engine.

The engine never talks to devices directly: a *placement* object owns
where parameters, page pools and the jitted entry points live, so the
same host loop serves one device or a tensor-parallel mesh (multi-host
later slots in as a third policy — ROADMAP).

``SingleDevice`` is the identity policy (exactly the pre-policy engine).

``TensorParallel`` is Megatron-style TP over a 1-D ``model`` mesh axis,
run inside ``jax.shard_map`` so the existing model code traces
unchanged against a *local* config (heads / d_ff divided by the shard
count):

  * fused wqkv / wgi panels (DESIGN.md §5) are column-sharded
    **segment-wise**: the stored columns are permuted into per-shard
    order ``[q_0|k_0|v_0 | q_1|k_1|v_1 | ...]`` first, so the plain
    contiguous split hands every shard a valid local fused panel and
    the in-kernel segment slicing (``proj_splits`` of the local cfg)
    still lands on projection boundaries. GQA grouping survives because
    q heads are stored grouped per kv head and the shard count divides
    ``n_kv_heads``;
  * attention ``wo`` and the MLP down projection are row-sharded along
    the contraction dim (contiguous head- / channel-major rows — no
    permutation needed); their matmuls yield K-partial sums finished by
    one ``psum`` per projection (``partitioning.tp_reduce``), with
    bias / residual applied strictly after;
  * per-layer page pools shard on the KV-head axis — each shard's
    decode gathers touch only its own heads' pages;
  * an untied ``lm_head`` vocab-shards (exact N-split) and the logits
    all-gather back; tied embeddings stay replicated;
  * block tables, lengths, temperatures, tokens and the ``PagePool``
    free list stay host-side / replicated — the host loop is oblivious;
  * the speculative *verify* entry point shards exactly like chunk
    prefill: a replicated ``(B, 1+k)`` token panel in, head-sharded
    paged writes, all-gathered panel logits out. No new placement code
    — ``Placement.jit`` sees one more (PARAMS, CACHE, REP...) program.

Weight-only int8 ``{"q", "s"}`` leaves shard with their weight: scales
are per-output-channel, so column-sharded panels permute / split the
scale row identically and row-sharded projections replicate it.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import partitioning, quant
from repro.core.types import GATED_ACTS, ModelConfig
from repro.models import attention, lm
from repro.serve.paging import supports_bucketing

# argument-kind sentinels for Placement.jit: how each operand is placed
PARAMS = "params"        # the prepared (sharded) parameter tree
CACHE = "cache"          # the prepared (sharded) paged cache tree
REP = "rep"              # replicated host value (tokens, tables, key...)


class SingleDevice:
    """Identity placement: everything on the default device."""

    n_shards = 1
    axis: Optional[str] = None

    def validate(self, cfg: ModelConfig) -> None:
        pass

    def compute_cfg(self, cfg: ModelConfig) -> ModelConfig:
        return cfg

    def prepare_params(self, params, cfg: ModelConfig):
        return params

    def prepare_cache(self, cache):
        return cache

    def put_rep(self, x):
        return x

    def jit(self, fn, *, kinds: Sequence[str], out_kinds: Sequence[str],
            donate: Sequence[int] = ()):
        return jax.jit(fn, donate_argnums=tuple(donate))

    def describe(self) -> str:
        return "single-device"


def shard_perm(widths: Sequence[int], t: int) -> np.ndarray:
    """Column permutation turning a fused multi-segment panel into
    per-shard order: segment s has ``widths[s]`` columns; shard i's
    slice of EVERY segment lands contiguously at block i, so a plain
    t-way split of the permuted axis yields valid local fused panels."""
    offs = np.concatenate([[0], np.cumsum(widths)])[:-1]
    idx = []
    for s in range(t):
        for o, w in zip(offs, widths):
            p = w // t
            idx.extend(range(o + s * p, o + (s + 1) * p))
    return np.asarray(idx, np.int64)


def _permute_cols(leaf, idx):
    if quant.is_quantized(leaf):
        return {"q": leaf["q"][..., idx], "s": leaf["s"][..., idx]}
    return leaf[..., idx]


def _col_spec(leaf, axis):
    """Shard the output (last) axis; int8 scales are per-output-channel
    and split with it."""
    if quant.is_quantized(leaf):
        return {"q": P(*([None] * (leaf["q"].ndim - 1)), axis),
                "s": P(*([None] * (leaf["s"].ndim - 1)), axis)}
    return P(*([None] * (leaf.ndim - 1)), axis)


def _row_spec(leaf, axis):
    """Shard the contraction (second-to-last) axis; int8 scales are
    per-output-channel => replicated."""
    if quant.is_quantized(leaf):
        return {"q": P(*([None] * (leaf["q"].ndim - 2)), axis, None),
                "s": P(*([None] * leaf["s"].ndim))}
    return P(*([None] * (leaf.ndim - 2)), axis, None)


def _rep_spec(leaf):
    if quant.is_quantized(leaf):
        return {"q": P(*([None] * leaf["q"].ndim)),
                "s": P(*([None] * leaf["s"].ndim))}
    return P(*([None] * leaf.ndim))


class TensorParallel:
    """Head-/segment-sharded tensor parallelism over a 1-D mesh axis."""

    def __init__(self, n_shards: int, *, axis: str = "model"):
        if n_shards < 1:
            raise ValueError(f"mesh axis size must be >= 1, got {n_shards}")
        self.n_shards = int(n_shards)
        self.axis = axis
        self._mesh: Optional[Mesh] = None
        self._pspec = None           # params spec tree (set by prepare)
        self._cspec = None           # cache spec tree

    # -- validation (engine construction time, never mid-step) ---------

    def validate(self, cfg: ModelConfig) -> None:
        t = self.n_shards
        if not supports_bucketing(cfg):
            raise ValueError(
                f"{cfg.name}: tensor-parallel serving supports pure "
                "causal attention+MLP stacks only (recurrent/MoE/cross-"
                "attention state has no head sharding)")
        bad = []
        if cfg.n_heads % t:
            bad.append(f"n_heads={cfg.n_heads}")
        if cfg.n_kv_heads % t:
            bad.append(f"n_kv_heads={cfg.n_kv_heads}")
        if cfg.d_ff % t:
            seg = ("each wgi gate/up segment" if cfg.act in GATED_ACTS
                   else "the wi panel")
            bad.append(f"d_ff={cfg.d_ff} ({seg})")
        if not cfg.tie_embeddings and lm.padded_vocab(cfg) % t:
            bad.append(f"padded vocab={lm.padded_vocab(cfg)}")
        if bad:
            raise ValueError(
                f"mesh axis '{self.axis}'={t} cannot shard {cfg.name}: "
                f"it must divide every fused-panel segment and head "
                f"count (DESIGN.md §5), but not: " + ", ".join(bad))

    def compute_cfg(self, cfg: ModelConfig) -> ModelConfig:
        """The per-shard config the model code traces against."""
        t = self.n_shards
        return dataclasses.replace(cfg, n_heads=cfg.n_heads // t,
                                   n_kv_heads=cfg.n_kv_heads // t,
                                   d_ff=cfg.d_ff // t)

    # -- mesh ----------------------------------------------------------

    def mesh(self) -> Mesh:
        if self._mesh is None:
            devs = jax.devices()
            if len(devs) < self.n_shards:
                raise ValueError(
                    f"mesh axis '{self.axis}'={self.n_shards} needs "
                    f"{self.n_shards} devices, found {len(devs)} (set "
                    "XLA_FLAGS=--xla_force_host_platform_device_count=N "
                    "to emulate)")
            self._mesh = Mesh(np.array(devs[:self.n_shards]), (self.axis,))
        return self._mesh

    # -- parameter / cache placement -----------------------------------

    def prepare_params(self, params, cfg: ModelConfig):
        """Permute fused panels into per-shard segment order, build the
        spec tree, and device_put with NamedShardings."""
        t, ax = self.n_shards, self.axis
        mesh = self.mesh()
        qkv_idx = shard_perm(attention.proj_splits(cfg), t)
        gated = cfg.act in GATED_ACTS
        gi_idx = (shard_perm((cfg.d_ff, cfg.d_ff), t) if gated else None)

        def permute_fn(blk, p):
            p = dict(p)
            if blk.mixer == "attn" and "attn" in p:
                a = dict(p["attn"])
                a["wqkv"] = _permute_cols(a["wqkv"], qkv_idx)
                p["attn"] = a
            if blk.ffn == "mlp" and "ffn" in p and gated:
                f = dict(p["ffn"])
                f["wgi"] = _permute_cols(f["wgi"], gi_idx)
                p["ffn"] = f
            return p

        def spec_fn(blk, p):
            p = dict(p)
            if blk.mixer == "attn" and "attn" in p:
                a = dict(p["attn"])
                a["wqkv"] = _col_spec(a["wqkv"], ax)
                a["wo"] = _row_spec(a["wo"], ax)
                p["attn"] = a
            if blk.ffn == "mlp" and "ffn" in p:
                f = dict(p["ffn"])
                key = "wgi" if gated else "wi"
                f[key] = _col_spec(f[key], ax)
                f["wo"] = _row_spec(f["wo"], ax)
                p["ffn"] = f
            return p

        permuted = lm._migrate_blocks(cfg, params, permute_fn)
        chimera = lm._migrate_blocks(cfg, permuted, spec_fn)
        isP = lambda x: isinstance(x, P)                   # noqa: E731
        specs = jax.tree.map(
            lambda leaf: leaf if isP(leaf) else _rep_spec(leaf),
            chimera, is_leaf=isP)
        if not cfg.tie_embeddings:
            specs["lm_head"] = _col_spec(params["lm_head"], ax)
        self._pspec = specs
        shardings = jax.tree.map(lambda s: NamedSharding(mesh, s),
                                 specs, is_leaf=isP)
        return jax.device_put(permuted, shardings)

    def prepare_cache(self, cache):
        """Paged KV pools (R, n_pages + n_slots, ps, Hkv, hd) shard on
        the KV-head axis — each shard's page gathers stream only its own
        heads. Everything else in the tree is rejected by validate()."""
        ax = self.axis
        mesh = self.mesh()

        def spec(leaf):
            assert leaf.ndim == 5, (
                "TP cache holds paged attention pools only, got rank "
                f"{leaf.ndim}")
            return P(None, None, None, ax, None)

        self._cspec = jax.tree.map(spec, cache)
        shardings = jax.tree.map(lambda s: NamedSharding(mesh, s),
                                 self._cspec, is_leaf=lambda x:
                                 isinstance(x, P))
        return jax.device_put(cache, shardings)

    def put_rep(self, x):
        """Commit a replicated engine-state array to the mesh. The jit
        signature includes operand shardings: recurring operands that
        start host-side but come back as shard_map outputs (lengths,
        last tokens) would otherwise retrace every entry point once and
        break the compile-count bound."""
        return jax.device_put(x, NamedSharding(self.mesh(), P()))

    # -- jit -----------------------------------------------------------

    def jit(self, fn, *, kinds: Sequence[str], out_kinds: Sequence[str],
            donate: Sequence[int] = ()):
        """Wrap an engine entry point in shard_map over the mesh. kinds
        name each positional arg's placement (PARAMS / CACHE / REP);
        PARAMS and CACHE expand to the spec trees recorded by prepare_*
        (prepare must run first). The traced body activates the TP shard
        context so the model's output projections psum."""
        mesh = self.mesh()
        assert self._pspec is not None and self._cspec is not None, \
            "prepare_params/prepare_cache must run before jit"

        def expand(kind):
            if kind == PARAMS:
                return self._pspec
            if kind == CACHE:
                return self._cspec
            return P()

        in_specs = tuple(expand(k) for k in kinds)
        out_specs = tuple(expand(k) for k in out_kinds)
        ax = self.axis

        # the program keeps the entry point's name under TP too, so
        # the trace reads jit_decode_step and not jit_body
        @functools.wraps(fn)
        def body(*args):
            with partitioning.tp_shard(ax):
                return fn(*args)

        mapped = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                               out_specs=out_specs, check_vma=False)
        # pin output shardings to the exact NamedShardings put_rep /
        # prepare_* commit inputs to: shard_map alone emits equivalent
        # but unequal specs (P(None, None) vs P()), and a fed-back
        # output with a spec that hashes differently would specialize a
        # second executable per program — doubling the compile bound
        isP = lambda x: isinstance(x, P)                   # noqa: E731
        out_sh = tuple(jax.tree.map(
            lambda s: NamedSharding(mesh, s), expand(k), is_leaf=isP)
            for k in out_kinds)
        return jax.jit(mapped, donate_argnums=tuple(donate),
                       out_shardings=out_sh)

    def describe(self) -> str:
        return f"tensor-parallel {self.axis}={self.n_shards}"


def from_mesh_shape(spec: str):
    """Parse a ``--mesh-shape`` CLI value into a placement policy.
    Accepts '' / '1' (single device), 'N', or 'model=N'."""
    s = (spec or "").strip()
    if not s:
        return SingleDevice()
    axis = "model"
    if "=" in s:
        axis, _, s = s.partition("=")
        axis = axis.strip()
        if axis != "model":
            raise ValueError(
                f"unknown mesh axis '{axis}' in --mesh-shape (serving "
                "shards over the 'model' axis only, e.g. 'model=4')")
    try:
        n = int(s)
    except ValueError:
        raise ValueError(
            f"--mesh-shape '{spec}' is not 'N' or 'model=N'") from None
    if n < 1:
        raise ValueError(f"--mesh-shape size must be >= 1, got {n}")
    return SingleDevice() if n == 1 else TensorParallel(n, axis=axis)
