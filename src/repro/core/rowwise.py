"""Row-wise scheduling — the paper's core contribution, adapted to TPU.

The paper decomposes conv / fully-connected / attention into a *single
dot-product primitive* on a PE array, with weights broadcast down rows
(weight-stationary) for reuse. On TPU the analogue is:

  * every dense op lowers to ONE primitive, ``rowwise_matmul`` (Pallas),
    whose grid is ordered so the weight panel stays resident in VMEM
    while activation *row* panels stream past it (= weight broadcast);
  * tile shapes are *planned* from the model's dimensions so they divide
    evenly and align to the MXU, the way the paper sizes its 12x7x4
    array to "channels are multiples of 96, spatial multiples of 7";
  * contraction dims too large for one VMEM panel are split along a
    third, innermost grid axis and accumulated in a VMEM-resident fp32
    block across the k steps (= the paper's accumulator block + adder
    tree for large C_in) — partial sums never touch HBM.

``plan_matmul`` is the scheduler: it returns the tile plan plus the
utilization this schedule achieves (useful MACs / occupied MAC slots),
mirroring the paper's >=99% utilization analysis.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

# ----------------------------------------------------------------------
# Hardware geometries
# ----------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TPUGeometry:
    """TPU v5e-like geometry used by the planner."""

    mxu: Tuple[int, int] = (128, 128)      # systolic array
    sublane: int = 8                       # fp32 sublanes; bf16 packs 16
    lane: int = 128
    vmem_bytes: int = 16 * 1024 * 1024     # per-core VMEM
    peak_bf16_flops: float = 197e12
    hbm_bw: float = 819e9
    ici_bw: float = 50e9                   # per link


V5E = TPUGeometry()


def vmem_budget(geom: TPUGeometry = V5E) -> int:
    """Bytes a planned kernel's working set may take: the per-core VMEM
    less 2 MiB of headroom for semaphores and compiler temporaries. The
    planner and the static VMEM audit both hold kernels to it."""
    return geom.vmem_bytes - 2 * 1024 * 1024

# dtype -> minimum (second-to-last, last) tile the TPU packs natively
_MIN_TILE = {2: (16, 128), 4: (8, 128), 1: (32, 128)}


@dataclasses.dataclass(frozen=True)
class TilePlan:
    """A planned decomposition of an (M,K,N) matmul into row-wise tiles."""

    bm: int
    bk: int                 # K panel held in VMEM per grid step
    bn: int
    k_splits: int           # adder-tree depth (third grid axis)
    grid: Tuple[int, int, int]  # (n_tiles, m_tiles, k_splits) — k innermost
    m_pad: int
    k_pad: int
    n_pad: int
    utilization: float      # useful MACs / occupied MAC-slots
    vmem_bytes: int         # working set incl. the scratch accumulator
    flops: int
    bytes_moved: int        # modeled HBM traffic for this schedule

    @property
    def arithmetic_intensity(self) -> float:
        return self.flops / max(self.bytes_moved, 1)


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _pick_block(dim: int, target: int, align: int) -> int:
    """Largest block <= target that is a multiple of `align` and keeps
    padding low: prefer an exact divisor of the aligned dim."""
    dim_al = _round_up(dim, align)
    best = align
    b = align
    while b <= min(target, dim_al):
        if dim_al % b == 0:
            best = b
        b += align
    return best


def plan_matmul(m: int, k: int, n: int, *, dtype_bytes: int = 2,
                acc_bytes: int = 4, geom: TPUGeometry = V5E,
                target_bm: int = 256, target_bn: int = 256,
                k_max: Optional[int] = None, fused: bool = True,
                n_weights: int = 1, residual: bool = False,
                res_bytes: Optional[int] = None,
                prologue: bool = False, wide_n: bool = False,
                out_bytes: Optional[int] = None) -> TilePlan:
    """Plan a row-wise (weight-stationary) schedule for x(M,K) @ w(K,N).

    VMEM budget per grid step: x panel (bm, bk) + w panel(s) (bk, bn),
    both double-buffered, plus the fp32/int32 output block AND its
    scratch accumulator(s) (the in-kernel adder tree keeps both
    resident).

    Pipeline-fusion knobs (PR 2, see DESIGN.md §3):

      * ``n_weights``   — weight operands sharing the x panel (2 for the
                          gated gate|up kernel): charges extra w panels,
                          an extra scratch accumulator, and n_weights x
                          the weight HBM term.
      * ``residual``    — an extra (bm, bn) input operand read once,
                          priced at ``res_bytes`` (defaults to
                          ``dtype_bytes``; pass the residual's real
                          itemsize when it differs, e.g. an fp32
                          residual on the int8 path).
      * ``prologue``    — in-kernel norm: gamma/beta row operands. The
                          prologue needs the full K row per step, so
                          callers must check ``k_splits == 1`` and fall
                          back to a separate norm kernel otherwise.
      * ``wide_n``      — raise the bn target toward the whole (padded)
                          N so one activation row panel feeds every
                          fused projection (the paper's column weight
                          sharing lifted to the qkv / gate|up level).
      * ``out_bytes``   — price the single fused output write at the
                          real output dtype instead of ``acc_bytes``
                          (the legacy ``fused=False`` loop keeps fp32
                          pricing: its partials really are fp32).

    ``fused=False`` prices the seed's Python adder-tree loop instead
    (outputs round-tripping HBM once per split); kept only so
    benchmarks can report before/after traffic.
    """
    sub, lane = _MIN_TILE[dtype_bytes]
    rb = dtype_bytes if res_bytes is None else res_bytes
    if wide_n:
        target_bn = max(target_bn, min(2048, _round_up(n, lane)))
    bm = _pick_block(m, target_bm, sub)
    bn = _pick_block(n, target_bn, lane)

    # The fused kernel keeps 1 + n_weights (bm, bn) accumulator-width
    # buffers resident (output block + one scratch per weight); the
    # seed's looped kernel held only the output block, so legacy pricing
    # must not charge scratch.
    out_bufs = (1 + n_weights) if fused else 1

    def _need(bm, bk, bn):
        need = ((2 * bm * bk + n_weights * 2 * bk * bn) * dtype_bytes
                + out_bufs * bm * bn * acc_bytes)
        if residual:
            need += 2 * bm * bn * rb
        if prologue:
            need += 2 * 2 * bk * 4          # gamma/beta fp32 rows
            # the norm's fp32 copy of the (bm, bk) row panel, which the
            # TPU compiler allocates beside the pipelined buffers: it
            # wanted 16.4 MiB against its 16 MiB limit for a (256, 4096)
            # x (4096, 512) prologue plan modeled at 13.1 MiB
            need += bm * bk * 4
        return need

    # Choose the K panel: as large as fits the VMEM budget.
    budget = vmem_budget(geom)
    if k_max is None:
        k_max = 8192
    bk = min(_round_up(k, lane), k_max)
    # A wide-N target can blow the budget on its own; give N back first
    # (down to the default 256) before shrinking the K panel, so the
    # prologue's full-K requirement survives whenever it can. While the
    # K panel is whole, rows cost no HBM traffic (each weight panel is
    # fetched once) but columns do (the row panel is fetched once per
    # column tile), so a prologue first gives rows back, down to one
    # MXU height.
    while prologue and _need(bm, bk, bn) > budget and bm > geom.mxu[0]:
        bm = _pick_block(m, bm // 2, sub)
    while _need(bm, bk, bn) > budget and bn > 256:
        bn = _pick_block(n, max(bn // 2, 256), lane)
    while prologue and _need(bm, bk, bn) > budget and bm > sub:
        bm = _pick_block(m, bm // 2, sub)
    while True:
        if _need(bm, bk, bn) <= budget or bk <= lane:
            break
        bk = max(lane, bk // 2)
    k_splits = math.ceil(k / bk)

    if fused and k_splits > 1:
        # Fused-adder-tree regime: with k innermost, the w panel is
        # re-fetched once per m tile and the x panel once per n tile —
        # bk no longer buys any HBM reuse, only bm/bn do. So shrink the
        # K panel and spend the VMEM budget on the widest (bm, bn)
        # output block instead, minimizing both re-fetch factors.
        bk = min(bk, 4 * lane)
        bm = _pick_block(m, max(target_bm, 1024), sub)
        bn = _pick_block(n, max(target_bn, 1024), lane)
        while _need(bm, bk, bn) > budget:
            if bm >= bn and bm > sub:
                bm = _pick_block(m, bm // 2, sub)
            elif bn > lane:
                bn = _pick_block(n, bn // 2, lane)
            elif bm > sub:
                bm = _pick_block(m, bm // 2, sub)
            elif bk > lane:
                bk = max(lane, bk // 2)
            else:
                break
        k_splits = math.ceil(k / bk)

    m_pad, k_pad, n_pad = _round_up(m, bm), _round_up(k, bk), _round_up(n, bn)
    grid = (n_pad // bn, m_pad // bm, k_splits)
    m_tiles, n_tiles = m_pad // bm, n_pad // bn

    useful = m * k * n
    occupied = m_pad * k_pad * n_pad
    flops = 2 * useful
    # HBM traffic. Activations are re-fetched once per n-tile column in
    # both regimes. Weights: fetched once when the panel is stationary
    # across m steps (k_splits == 1, index map ignores mi), once per m
    # tile when the k axis cycles under them. Outputs: the fused adder
    # tree accumulates in VMEM and writes each block exactly once; the
    # legacy loop wrote fp32 partials per split and re-read them
    # (k_splits - 1) times.
    if fused:
        w_factor = 1 if k_splits == 1 else m_tiles
        out_term = m_pad * n_pad * (acc_bytes if out_bytes is None
                                    else out_bytes)
    else:
        # Seed pricing: fp32 partials written once per split and re-read
        # (k_splits - 1) times — always at acc_bytes, whatever the
        # output dtype.
        w_factor = 1
        out_term = m_pad * n_pad * acc_bytes * (2 * k_splits - 1)
    bytes_moved = (k_pad * n_pad * dtype_bytes * w_factor * n_weights
                   + m_pad * k_pad * dtype_bytes * n_tiles
                   + out_term)
    if residual:
        bytes_moved += m_pad * n_pad * rb
    if prologue:
        bytes_moved += 2 * k_pad * 4
    return TilePlan(bm=bm, bk=bk, bn=bn, k_splits=k_splits, grid=grid,
                    m_pad=m_pad, k_pad=k_pad, n_pad=n_pad,
                    utilization=useful / occupied,
                    vmem_bytes=_need(bm, bk, bn),
                    flops=flops, bytes_moved=bytes_moved)


# ----------------------------------------------------------------------
# Model-level schedule report (the paper's Section III/IV analysis,
# generalized): walk a model's GEMMs, plan each, aggregate utilization.
# ----------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class OpRecord:
    name: str
    kind: str            # 'conv' | 'fc' | 'attn'
    m: int
    k: int
    n: int
    count: int = 1       # how many identical GEMMs (e.g. layers, windows)

    @property
    def macs(self) -> int:
        return self.m * self.k * self.n * self.count


@dataclasses.dataclass
class ScheduleReport:
    ops: list
    plans: list

    @property
    def total_flops(self) -> int:
        return sum(2 * op.macs for op in self.ops)

    @property
    def utilization(self) -> float:
        useful = sum(op.macs for op in self.ops)
        occupied = sum(op.macs / max(p.utilization, 1e-12)
                       for op, p in zip(self.ops, self.plans))
        return useful / max(occupied, 1e-12)

    def dominant(self, frac: float = 0.97) -> dict:
        """FLOPs share per op kind (the paper's Fig. 2 claim)."""
        total = sum(op.macs for op in self.ops)
        shares = {}
        for op in self.ops:
            shares[op.kind] = shares.get(op.kind, 0) + op.macs / total
        return shares


def schedule_model(ops, **plan_kwargs) -> ScheduleReport:
    plans = [plan_matmul(op.m, op.k, op.n, **plan_kwargs) for op in ops]
    return ScheduleReport(ops=list(ops), plans=plans)
