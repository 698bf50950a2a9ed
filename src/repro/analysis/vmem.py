"""Pallas VMEM/scratch budget pass.

The row-wise kernels are planned against a per-core VMEM budget
(``plan_matmul``: ``geom.vmem_bytes`` minus 2 MB of headroom for
semaphores and runtime state — the paper's 149 KB-SRAM discipline at
TPU scale). The plan, however, is only a *model*: nothing stops a
kernel author from passing ``pallas_call`` block shapes the plan never
priced. This pass closes that gap by recomputing each traced kernel's
actual VMEM residency from the equation itself:

    2 x (input block bytes)       double-buffered HBM->VMEM pipeline
    + 1 x (output block bytes)    revisited across the K-innermost grid
    + 1 x (VMEM scratch bytes)    accumulators live across K steps

and failing any kernel above the modeled budget (RWA401), or above its
own plan's accounting when one is supplied (RWA402 — the model
undercounts, so the utilisation/ratio numbers built on it lie).

Works on any jaxpr: on CPU dev boxes, trace under
``runtime.use_impl('interpret')`` so the pallas lowering (and its
``grid_mapping``) appears in the graph.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

from repro.analysis.jaxprs import iter_eqns
from repro.analysis.report import Diagnostic, PassResult
from repro.core.rowwise import vmem_budget


def _block_bytes(shape, dtype) -> int:
    size = 1
    for d in shape:
        size *= int(d)
    return size * dtype.itemsize


@dataclasses.dataclass(frozen=True)
class KernelFootprint:
    """Static VMEM residency of one ``pallas_call`` equation."""
    name: str
    grid: Tuple[int, ...]
    in_bytes: int                    # sum of input block bytes (single)
    out_bytes: int                   # sum of output block bytes
    scratch_bytes: int               # VMEM scratch (SMEM excluded)

    @property
    def resident_bytes(self) -> int:
        return 2 * self.in_bytes + self.out_bytes + self.scratch_bytes


def kernel_footprints(jaxpr_like) -> List[KernelFootprint]:
    out = []
    for eqn in iter_eqns(jaxpr_like):
        if eqn.primitive.name != "pallas_call":
            continue
        gm = eqn.params["grid_mapping"]
        blocks = list(gm.block_mappings)
        n_in = gm.num_inputs
        # the block as the kernel sees it in VMEM: squeezed
        # (grid-mapped) dims are already dropped from its shape
        in_b = sum(_block_bytes(bm.transformed_block_aval.shape,
                                bm.transformed_block_aval.dtype)
                   for bm in blocks[:n_in])
        out_b = sum(_block_bytes(bm.transformed_block_aval.shape,
                                 bm.transformed_block_aval.dtype)
                    for bm in blocks[n_in:])
        scratch = 0
        n_scratch = gm.num_scratch_operands
        if n_scratch:
            inner = eqn.params["jaxpr"]
            for v in inner.invars[-n_scratch:]:
                aval = v.aval
                if str(getattr(aval, "memory_space", "vmem")) != "vmem":
                    continue         # SMEM scalars don't charge VMEM
                scratch += _block_bytes(aval.shape, aval.dtype)
        out.append(KernelFootprint(
            name=str(eqn.params.get("name", "pallas_call")),
            grid=tuple(int(g) for g in gm.grid),
            in_bytes=in_b, out_bytes=out_b, scratch_bytes=scratch))
    return out


def audit_vmem(jaxpr_like, name: str = "graph", *,
               budget: Optional[int] = None) -> PassResult:
    """RWA401 for every traced kernel whose residency exceeds the
    modeled budget (default: the planner's own, ``vmem_budget()``)."""
    budget = budget if budget is not None else vmem_budget()
    result = PassResult(name="vmem")
    for fp in kernel_footprints(jaxpr_like):
        result.checked += 1
        if fp.resident_bytes > budget:
            result.diagnostics.append(Diagnostic(
                code="RWA401", path=name,
                message=f"kernel `{fp.name}` grid={fp.grid} resident "
                        f"{fp.resident_bytes:,} B (2x{fp.in_bytes:,} in "
                        f"+ {fp.out_bytes:,} out + {fp.scratch_bytes:,} "
                        f"scratch) > budget {budget:,} B"))
    return result


def crosscheck_plan(jaxpr_like, plan, name: str = "matmul", *,
                    budget: Optional[int] = None) -> PassResult:
    """RWA402 when a traced kernel's actual residency exceeds what its
    ``TilePlan`` charged: the planner's utilisation and traffic numbers
    are built on ``plan.vmem_bytes``, so an undercount there corrupts
    every downstream roofline figure. Also applies the RWA401 budget."""
    result = audit_vmem(jaxpr_like, name, budget=budget)
    for fp in kernel_footprints(jaxpr_like):
        result.checked += 1
        if fp.resident_bytes > plan.vmem_bytes:
            result.diagnostics.append(Diagnostic(
                code="RWA402", path=name,
                message=f"kernel `{fp.name}` resident "
                        f"{fp.resident_bytes:,} B exceeds its plan's "
                        f"accounting ({plan.vmem_bytes:,} B): "
                        "plan_matmul undercounts this launch"))
    return result
