"""Structured jaxpr traversal for the invariant auditor.

The repo's older tests asserted kernel-launch invariants by counting
substrings of ``str(jaxpr)`` — which breaks on primitive renames and
false-matches on kernel *names* containing the primitive's. These
helpers walk the equation graph itself (recursing into every sub-jaxpr:
pjit bodies, scan/while carries, cond branches, custom_jvp rules), so a
count of ``pallas_call`` eqns means actual kernel launches.
"""
from __future__ import annotations

from typing import Iterator, List

# host-callback primitives: any of these inside a serving entry point
# is a per-step host round-trip hiding in the traced graph
CALLBACK_PRIMITIVES = frozenset({
    "pure_callback", "io_callback", "debug_callback", "callback",
    "host_callback_call", "outside_call",
})


def _as_jaxpr(jaxpr_like):
    """Accept a ClosedJaxpr, a Jaxpr, or anything carrying `.jaxpr`."""
    inner = getattr(jaxpr_like, "jaxpr", None)
    if inner is not None and hasattr(inner, "eqns"):
        return inner
    return jaxpr_like


def iter_eqns(jaxpr_like) -> Iterator:
    """Yield every equation reachable from `jaxpr_like`, depth-first,
    recursing through sub-jaxprs stashed in eqn params (pjit/scan/cond/
    remat bodies and lists thereof)."""
    stack = [_as_jaxpr(jaxpr_like)]
    seen = set()
    while stack:
        j = stack.pop()
        if id(j) in seen or not hasattr(j, "eqns"):
            continue
        seen.add(id(j))
        for eqn in j.eqns:
            yield eqn
            for v in eqn.params.values():
                for sub in _sub_jaxprs(v):
                    stack.append(sub)


def _sub_jaxprs(v) -> List:
    if hasattr(v, "eqns"):
        return [v]
    if hasattr(v, "jaxpr") and hasattr(v.jaxpr, "eqns"):
        return [v.jaxpr]
    if isinstance(v, (list, tuple)):
        out = []
        for x in v:
            out.extend(_sub_jaxprs(x))
        return out
    return []


def primitive_eqns(jaxpr_like, name: str) -> List:
    return [e for e in iter_eqns(jaxpr_like) if e.primitive.name == name]


def count_primitive(jaxpr_like, name: str) -> int:
    """Structured replacement for `str(jaxpr).count(name)`."""
    return len(primitive_eqns(jaxpr_like, name))


def callback_eqns(jaxpr_like) -> List:
    return [e for e in iter_eqns(jaxpr_like)
            if e.primitive.name in CALLBACK_PRIMITIVES]


def weak_type_invars(jaxpr_like) -> List:
    """Input vars whose aval is weak_type: a Python-scalar operand that
    would compile a second program the moment a strongly-typed value of
    the same shape arrives."""
    j = _as_jaxpr(jaxpr_like)
    return [v for v in j.invars
            if getattr(v.aval, "weak_type", False)]


# -- weight-sized concatenations (decode hot-path rule) -----------------
#
# One definition, shared by the tests and the AST rule pass.

def weight_concat_eqns(jaxpr_like, min_bytes: int) -> List:
    """Concatenate eqns whose output is at least `min_bytes`: in a
    decode graph these are per-token weight-panel rebuilds the fused
    param layout (DESIGN.md §5) exists to eliminate."""
    hits = []
    for eqn in iter_eqns(jaxpr_like):
        if eqn.primitive.name != "concatenate":
            continue
        aval = eqn.outvars[0].aval
        size = 1
        for d in aval.shape:
            size *= int(d)
        if size * aval.dtype.itemsize >= min_bytes:
            hits.append(eqn)
    return hits


def min_weight_bytes(cfg, itemsize: int = 4) -> int:
    """Threshold separating weight-panel concats from small activation
    concats: the smallest per-layer projection panel (KV heads)."""
    return cfg.d_model * cfg.n_kv_heads * cfg.head_dim * itemsize
