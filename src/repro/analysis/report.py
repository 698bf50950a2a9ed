"""Diagnostics for the static invariant auditor.

Every analysis pass reports through the same two shapes:

  * :class:`Diagnostic` — one ruff-style finding, carrying a stable
    ``RWAnnn`` code, the offending location and a one-line message.
  * :class:`PassResult` — one pass run: its diagnostics plus how many
    invariant sites it actually checked (a pass that checked nothing is
    suspicious, not clean) and its wall time.

Code families (catalogued in DESIGN.md §9):

  RWA1xx  sync-point pass       hidden host<->device synchronisation
  RWA2xx  donation pass         donated buffer not aliased in place
  RWA3xx  compile-bound pass    shape-signature set exceeds the bound
  RWA4xx  Pallas VMEM pass      kernel footprint over the VMEM budget
  RWA5xx  AST rule pass         pool-transaction / decode-path rules
"""
from __future__ import annotations

import dataclasses
from typing import List

CODES = {
    "RWA101": "`.item()` on a device value forces a blocking transfer",
    "RWA102": "int()/float()/bool() on a device value is a hidden sync",
    "RWA103": "np.asarray/np.array on a device value is a hidden sync",
    "RWA104": "device fetch count differs from the step-loop contract",
    "RWA105": "block_until_ready() outside a sanctioned fetch site",
    "RWA106": "host callback primitive inside a jitted entry point",
    "RWA201": "donated buffer is not aliased to any output (silently "
              "copied: the donation was dropped by XLA)",
    "RWA202": "donated buffer has no shape/dtype-matching output to "
              "alias onto",
    "RWA203": "two donated buffers alias the same output",
    "RWA301": "reachable shape-signature set exceeds the documented "
              "compile bound",
    "RWA302": "weak_type operand in a jitted entry point fragments the "
              "jit cache",
    "RWA303": "runtime compiled-program count disagrees with the "
              "static enumeration",
    "RWA401": "pallas kernel block+scratch residency exceeds the "
              "modeled VMEM budget",
    "RWA402": "traced kernel footprint exceeds plan_matmul's accounting",
    "RWA501": "PagePool.begin not paired with commit/rollback on a "
              "normal exit path",
    "RWA502": "eviction (_make_room/reclaim) inside an open pool "
              "transaction",
    "RWA503": "pool mutation outside a transaction in the decode path",
    "RWA504": "jnp.concatenate/stack in a decode module (weight-sized "
              "concats belong in the fused param layout)",
}


@dataclasses.dataclass(frozen=True)
class Diagnostic:
    code: str
    message: str
    path: str = ""                   # file, or entry-point name
    line: int = 0
    severity: str = "error"          # "error" gates; "warning" informs

    def __post_init__(self):
        assert self.code in CODES, f"unregistered diagnostic {self.code}"

    def __str__(self):
        loc = f"{self.path}:{self.line}: " if self.path else ""
        return f"{loc}{self.code} {self.message}"


@dataclasses.dataclass
class PassResult:
    name: str
    diagnostics: List[Diagnostic] = dataclasses.field(default_factory=list)
    checked: int = 0                 # invariant sites actually examined
    wall_s: float = 0.0

    @property
    def ok(self) -> bool:
        return not any(d.severity == "error" for d in self.diagnostics)

    def errors(self) -> List[Diagnostic]:
        return [d for d in self.diagnostics if d.severity == "error"]

    def summary(self) -> str:
        state = "ok" if self.ok else f"{len(self.errors())} error(s)"
        return (f"[{self.name}] {state}: {self.checked} site(s) checked "
                f"in {self.wall_s * 1e3:.0f} ms")
