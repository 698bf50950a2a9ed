"""Sync-point pass: find hidden host<->device synchronisation in the
serving hot path.

The engine's latency contract (DESIGN.md §4/§7) is *one* device fetch
per step-loop iteration: the sampled tokens and their finite-ness flags
travel in a single ``jax.device_get``. Anything else that forces a
transfer — ``.item()``, ``int()/float()/bool()`` on a device array,
``np.asarray`` on a device value, a stray ``block_until_ready`` — adds
a blocking round-trip per call site and silently serialises the loop.

This pass runs an intra-procedural taint analysis over each module's
AST. Device-ness propagates forward from *producers*:

  * calls into ``jnp.* / jax.numpy.* / jax.random.* / jax.lax.* /
    jax.nn.*`` and ``jax.device_put``;
  * calls of configured device-returning methods (the engine's jitted
    ``self._step/_admit/_chunk`` entry points, ``placement.put_rep``);
  * ``self.X`` attribute loads where ``X`` was ever assigned a tainted
    value in the class (collected to a fixpoint across methods);
  * attribute loads whose name matches a dataclass field annotated
    ``jnp.ndarray`` anywhere in the module — a user-supplied device
    array travels under that name whatever object carries it.

Taint dies where host-ness is guaranteed: ``jax.device_get(...)``
results, and ``.shape/.dtype/.ndim/.size`` metadata reads (those are
tracer-safe). Unknown calls conservatively forward the taint of their
arguments. Sinks raise diagnostics (RWA101/102/103/105); the count of
``jax.device_get`` call sites per function is matched against an
explicit allowlist (RWA104) so a refactor that adds "just one more
fetch" fails the audit instead of doubling step latency.

Purely syntactic and flow-approximate by design (branches merge by
union), so it can run on every commit without tracing anything.
"""
from __future__ import annotations

import ast
import dataclasses
from typing import Dict, List, Optional, Set, Tuple

from repro.analysis.report import Diagnostic, PassResult

# attribute reads that return host metadata, never device bytes
_META_ATTRS = frozenset({"shape", "dtype", "ndim", "size", "itemsize",
                         "weak_type", "sharding"})
# dotted-name prefixes whose calls produce device values
_PRODUCER_PREFIXES = ("jnp.", "jax.numpy.", "jax.random.", "jax.lax.",
                      "jax.nn.", "jax.device_put", "jax.jit")
# numpy constructors that materialise their argument on the host
_NP_SINKS = frozenset({"asarray", "array", "concatenate", "stack",
                       "ascontiguousarray", "copy"})


@dataclasses.dataclass
class SyncPolicy:
    """What the audited module is allowed to do.

    ``device_get_allow`` maps function name -> sanctioned number of
    ``jax.device_get`` call sites (unlisted functions get 0). The
    engine profile sanctions exactly one per step-loop phase:
    ``run`` / ``_admit_head`` / ``_advance_chunks``.
    """
    device_get_allow: Dict[str, int] = dataclasses.field(
        default_factory=dict)
    device_methods: Tuple[str, ...] = ("_step", "_admit", "_chunk",
                                       "_spec", "put_rep")
    # names bound to device-returning callables (`put = placement.put_rep`)
    device_aliases: Tuple[str, ...] = ("put",)


def _dotted(node: ast.AST) -> str:
    """'jax.random.split' for the callee of jax.random.split(...)."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def _device_dataclass_fields(tree: ast.Module) -> Set[str]:
    """Field names annotated `jnp.ndarray` in any class of the module:
    values travelling under these names are device arrays by contract,
    so reading one and materialising it on the host is a sync."""
    fields: Set[str] = set()
    for cls in [n for n in ast.walk(tree) if isinstance(n, ast.ClassDef)]:
        for stmt in cls.body:
            if isinstance(stmt, ast.AnnAssign) and \
                    isinstance(stmt.target, ast.Name):
                ann = stmt.annotation
                name = _dotted(ann) if isinstance(
                    ann, (ast.Attribute, ast.Name)) else ""
                if name in ("jnp.ndarray", "jax.Array", "jnp.array"):
                    fields.add(stmt.target.id)
    return fields


class _FunctionTaint(ast.NodeVisitor):
    """One function body: forward taint, record diagnostics."""

    def __init__(self, path: str, fname: str, policy: SyncPolicy,
                 device_attrs: Set[str], device_fields: Set[str]):
        self.path, self.fname, self.policy = path, fname, policy
        self.device_attrs = device_attrs      # self.X names (mutated!)
        self.device_fields = device_fields
        self.tainted: Set[str] = set()
        self.diags: List[Diagnostic] = []
        # distinct call *sites* (loop bodies walk twice for the taint
        # fixpoint — a site must not count per walk)
        self.device_get_sites: Set[int] = set()
        self.checked = 0

    @property
    def device_gets(self) -> int:
        return len(self.device_get_sites)

    # -- expression taint ------------------------------------------------

    def taint_of(self, node: Optional[ast.AST]) -> bool:
        if node is None:
            return False
        if isinstance(node, ast.Name):
            return node.id in self.tainted
        if isinstance(node, ast.Attribute):
            if node.attr in _META_ATTRS:
                return False          # metadata read kills the taint
            if isinstance(node.value, ast.Name) and \
                    node.value.id == "self":
                return node.attr in self.device_attrs
            if node.attr in self.device_fields:
                return True
            return self.taint_of(node.value)
        if isinstance(node, ast.Call):
            return self.call_taint(node)
        if isinstance(node, ast.Subscript):
            return self.taint_of(node.value)
        if isinstance(node, ast.BinOp):
            return self.taint_of(node.left) or self.taint_of(node.right)
        if isinstance(node, ast.UnaryOp):
            return self.taint_of(node.operand)
        if isinstance(node, ast.Compare):
            return self.taint_of(node.left) or \
                any(self.taint_of(c) for c in node.comparators)
        if isinstance(node, ast.BoolOp):
            return any(self.taint_of(v) for v in node.values)
        if isinstance(node, ast.IfExp):
            return self.taint_of(node.body) or self.taint_of(node.orelse)
        if isinstance(node, (ast.Tuple, ast.List)):
            return any(self.taint_of(e) for e in node.elts)
        if isinstance(node, ast.Starred):
            return self.taint_of(node.value)
        return False

    def call_taint(self, node: ast.Call) -> bool:
        callee = _dotted(node.func)
        args_tainted = any(self.taint_of(a) for a in node.args) or \
            any(self.taint_of(kw.value) for kw in node.keywords)
        if callee == "jax.device_get":
            self.device_get_sites.add(id(node))
            return False              # the sanctioned fetch: host after
        if callee.startswith(_PRODUCER_PREFIXES):
            return True
        if callee.split(".")[0] in self.policy.device_aliases:
            return True
        # sinks ---------------------------------------------------------
        if isinstance(node.func, ast.Attribute):
            attr = node.func.attr
            base_tainted = self.taint_of(node.func.value)
            if attr == "item" and base_tainted:
                self._diag("RWA101", node,
                           "`.item()` on a device value blocks on a "
                           "device->host transfer")
                return False
            if attr == "block_until_ready" and base_tainted:
                self._diag("RWA105", node,
                           "block_until_ready() serialises the step "
                           "loop outside a sanctioned fetch")
                return base_tainted
            if attr in _NP_SINKS and callee.startswith("np.") and \
                    args_tainted:
                self._diag("RWA103", node,
                           f"np.{attr}() on a device value is a hidden "
                           "device->host sync")
                return False          # result is host-resident
            if attr in self.policy.device_methods:
                return True
        if isinstance(node.func, ast.Name):
            if node.func.id in ("int", "float", "bool") and args_tainted:
                self._diag("RWA102", node,
                           f"{node.func.id}() on a device value is a "
                           "hidden blocking sync")
                return False
            if node.func.id in self.policy.device_aliases:
                return True
        # unknown callable: forward the arguments' taint
        return args_tainted

    def _diag(self, code: str, node: ast.AST, msg: str):
        self.diags.append(Diagnostic(
            code=code, message=f"{msg} (in {self.fname})",
            path=self.path, line=getattr(node, "lineno", 0)))

    # -- statement walk --------------------------------------------------

    def run(self, fn: ast.FunctionDef):
        self.exec_body(fn.body)
        allowed = self.policy.device_get_allow.get(self.fname, 0)
        self.checked += 1             # the per-function fetch contract
        if self.device_gets != allowed and (self.device_gets or allowed):
            self.diags.append(Diagnostic(
                code="RWA104",
                message=(f"{self.fname} has {self.device_gets} "
                         f"jax.device_get site(s), contract allows "
                         f"{allowed}"),
                path=self.path, line=fn.lineno))

    def exec_body(self, body: List[ast.stmt]):
        for stmt in body:
            self.exec_stmt(stmt)

    def exec_stmt(self, stmt: ast.stmt):
        if isinstance(stmt, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
            value = stmt.value
            tainted = self.taint_of(value)
            self.checked += 1
            targets = stmt.targets if isinstance(stmt, ast.Assign) \
                else [stmt.target]
            for tgt in targets:
                self.assign(tgt, value, tainted)
        elif isinstance(stmt, ast.Expr):
            self.taint_of(stmt.value)
            self.checked += 1
        elif isinstance(stmt, (ast.If,)):
            self.taint_of(stmt.test)
            self.exec_body(stmt.body)
            self.exec_body(stmt.orelse)
        elif isinstance(stmt, (ast.For, ast.While)):
            if isinstance(stmt, ast.For):
                self.assign(stmt.target, None,
                            self.taint_of(stmt.iter))
            else:
                self.taint_of(stmt.test)
            # two passes approximate the loop fixpoint (taint introduced
            # late in the body reaches uses at the top)
            self.exec_body(stmt.body)
            self.exec_body(stmt.body)
            self.exec_body(stmt.orelse)
        elif isinstance(stmt, ast.Try):
            self.exec_body(stmt.body)
            for h in stmt.handlers:
                self.exec_body(h.body)
            self.exec_body(stmt.orelse)
            self.exec_body(stmt.finalbody)
        elif isinstance(stmt, ast.With):
            self.exec_body(stmt.body)
        elif isinstance(stmt, (ast.Return, ast.Raise, ast.Assert)):
            for child in ast.iter_child_nodes(stmt):
                if isinstance(child, ast.expr):
                    self.taint_of(child)
        # nested defs/classes are analysed as their own functions

    def assign(self, tgt: ast.AST, value: Optional[ast.AST],
               tainted: bool):
        if isinstance(tgt, (ast.Tuple, ast.List)):
            # elementwise only when the value side unpacks one-to-one
            if isinstance(value, (ast.Tuple, ast.List)) and \
                    len(value.elts) == len(tgt.elts):
                for t, v in zip(tgt.elts, value.elts):
                    self.assign(t, v, self.taint_of(v))
            else:
                for t in tgt.elts:
                    self.assign(t, None, tainted)
            return
        if isinstance(tgt, ast.Name):
            if tainted:
                self.tainted.add(tgt.id)
            else:
                self.tainted.discard(tgt.id)
        elif tainted and isinstance(tgt, ast.Attribute) and \
                isinstance(tgt.value, ast.Name) and tgt.value.id == "self":
            # never un-taint a self attribute: another method may still
            # hold a device value under the same name
            self.device_attrs.add(tgt.attr)


def audit_source(src: str, path: str = "<string>", *,
                 policy: Optional[SyncPolicy] = None) -> PassResult:
    """Run the sync-point pass over one module's source text."""
    policy = policy or SyncPolicy()
    tree = ast.parse(src)
    device_fields = _device_dataclass_fields(tree)
    result = PassResult(name="sync")

    funcs: List[Tuple[str, ast.FunctionDef]] = []
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            funcs.append((node.name, node))

    # fixpoint over self.X device attributes: a method assigning
    # `self.cache = self._step(...)` taints `self.cache` for every
    # other method; two rounds converge for assignment chains one deep
    # (all this codebase has), a third is cheap insurance
    device_attrs: Set[str] = set()
    for _ in range(3):
        before = set(device_attrs)
        for fname, fn in funcs:
            probe = _FunctionTaint(path, fname, policy, device_attrs,
                                   device_fields)
            probe.exec_body(fn.body)
        if device_attrs == before:
            break

    for fname, fn in funcs:
        ft = _FunctionTaint(path, fname, policy, set(device_attrs),
                            device_fields)
        # keep attr discoveries local to the reporting run
        ft.device_attrs = set(device_attrs)
        ft.run(fn)
        result.diagnostics.extend(ft.diags)
        result.checked += ft.checked
    return result


def audit_file(path: str, *, policy: Optional[SyncPolicy] = None) \
        -> PassResult:
    with open(path) as f:
        return audit_source(f.read(), path=path, policy=policy)


def audit_entry_jaxprs(entries, *, allow_callbacks: int = 0) -> PassResult:
    """Jaxpr side of the pass: the traced entry points themselves must
    not smuggle host round-trips in as callback primitives."""
    from repro.analysis import jaxprs as jxp
    result = PassResult(name="sync")
    for name, jaxpr in entries:
        cbs = jxp.callback_eqns(jaxpr)
        result.checked += 1
        if len(cbs) > allow_callbacks:
            result.diagnostics.append(Diagnostic(
                code="RWA106",
                message=(f"{len(cbs)} host-callback eqn(s) in traced "
                         f"entry point ({cbs[0].primitive.name})"),
                path=name))
    return result
