"""Structured diagnostics shared by the plan checker and the linter.

Every failure class has a STABLE code — ``GTA0xx`` for plan diagnostics,
``GTL1xx`` for trace-hygiene lint rules, ``GTL2xx`` for lock-discipline
lint rules, ``GTC0xx`` for the lowered-HLO collective auditor — so CI can
gate on specific codes, suppressions
can name them, and the docs table (DESIGN.md "Static analysis") stays the
single reference. Codes are append-only: a retired rule keeps its number.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional, Tuple

ERROR = "error"
WARN = "warn"

# code → (summary, default severity). The one registry both pillars and the
# DESIGN.md table draw from; tests assert the table and this dict agree.
CODES = {
    # --- plan checker (GTA0xx) ---
    "GTA001": ("unknown key in strategy/config JSON (typo'd fields silently no-op)", WARN),
    "GTA002": ("field fails to decode/validate (bad degree, dp_type, enum value)", ERROR),
    "GTA003": ("world size not a power of two, or pp does not divide it", ERROR),
    "GTA004": ("parallel-degree product exceeds the per-stage mesh extent", ERROR),
    "GTA005": ("pp_division malformed (length, sum vs layer count, empty stage)", ERROR),
    "GTA006": ("plan layer count disagrees with the model's total layers", ERROR),
    "GTA007": ("attention heads not divisible by the tp (or a2a cp) degree", ERROR),
    "GTA008": ("vocab size not divisible by vocab_tp", ERROR),
    "GTA009": ("global batch not divisible by chunks × the layer's dp extent", ERROR),
    "GTA010": ("sequence length not divisible by the sp/cp shard degree", ERROR),
    "GTA011": ("interleaved-schedule (vpp) constraint violated", ERROR),
    "GTA012": ("known XLA SPMD CHECK-crash cell: pp>1 × 1F1B × tp>1 × sp=0 × vocab_tp>1", ERROR),
    "GTA013": ("stage-stack seam: layers at the same stage position disagree (pp>1)", ERROR),
    "GTA014": ("expert-parallel degree invalid for the model's expert count or expert path", ERROR),
    "GTA015": ("cost-model memory estimate exceeds the device budget", ERROR),
    "GTA016": ("abstract sharding pass: annotated dim unsharded or spec invalid", WARN),
    "GTA017": ("checkpoint topology/plan fingerprint does not match the live mesh", ERROR),
    "GTA018": ("tp_overlap (collective-matmul) set on a layer with tp == 1", ERROR),
    "GTA019": ("tp or cp > 1 on layers that do not implement it (models/mixers.limits)", ERROR),
    "GTA020": ("pp > 1 over interleaved layer kinds or the dropless expert path", ERROR),
    # --- trace-hygiene linter (GTL1xx) ---
    "GTL100": ("malformed suppression: '# gta: disable=<rule>' needs a reason", ERROR),
    "GTL101": ("host-device sync on a jitted result inside a hot loop", WARN),
    "GTL102": ("Python/numpy RNG inside a traced (jitted) function", ERROR),
    "GTL103": ("numpy buffer mutated after being handed to async dispatch", ERROR),
    "GTL104": ("Python branch on a traced argument inside a jitted function", ERROR),
    "GTL105": ("jax.jit constructed inside a loop (fresh cache per iteration)", WARN),
    "GTL106": ("unhashable literal passed as a static jit argument", ERROR),
    # --- lock-discipline linter (GTL2xx, analysis/concurrency.py) ---
    "GTL200": ("guarded-by declaration names a lock the class never creates", ERROR),
    "GTL201": ("guarded field accessed outside its declared lock", ERROR),
    "GTL202": ("lock-order inversion: acquisition-order graph has a cycle", ERROR),
    "GTL203": ("blocking call while holding a lock", ERROR),
    "GTL204": ("thread leak: non-daemon thread without a reachable join, or started before __init__ completes", ERROR),
    "GTL205": ("Condition.wait outside a while-predicate loop (lost wakeup)", ERROR),
    "GTL206": ("check-then-act: guarded read and dependent write hold the lock separately", ERROR),
    # --- HLO collective auditor (GTC0xx, analysis/comm_audit.py) ---
    "GTC001": ("comm fidelity: predicted/lowered volume ratio outside the tolerance band", ERROR),
    "GTC002": ("plan term predicts communication but the lowering grounds none", WARN),
    "GTC003": ("lowered collective attributable to no plan term (unsolicited comm)", WARN),
    "GTC004": ("program failed to lower during the comm audit", ERROR),
    "GTC005": ("collective replica groups match no mesh-axis subgroup", WARN),
    "GTC010": ("silent replication: plan-sharded tensor lowered fully replicated", WARN),
    "GTC011": ("inter-layer resharding seam the plan never declared", WARN),
    "GTC012": ("tp_overlap layer still lowers a monolithic (non-overlapped) collective", WARN),
}


@dataclass(frozen=True)
class Diagnostic:
    """One finding: stable code + provenance + a one-line fix hint."""

    code: str
    message: str  # one-line statement of the defect
    hint: str = ""  # one-line fix hint naming the offending field
    field: str = ""  # JSON field / config attribute (e.g. "tp_sizes_enc[3]")
    source: Optional[str] = None  # file path when checking a file
    line: int = 0  # 1-based source line (linter findings)
    severity: str = ""  # defaulted from CODES when empty

    def __post_init__(self):
        if self.code not in CODES:
            raise ValueError(f"unregistered diagnostic code {self.code!r}")
        if not self.severity:
            object.__setattr__(self, "severity", CODES[self.code][1])

    def render(self) -> str:
        where = ""
        if self.source:
            where = f"{self.source}:{self.line}: " if self.line else f"{self.source}: "
        fld = f" [{self.field}]" if self.field else ""
        hint = f"\n    hint: {self.hint}" if self.hint else ""
        return f"{where}{self.code} {self.severity}: {self.message}{fld}{hint}"


def errors(diags: List[Diagnostic]) -> List[Diagnostic]:
    return [d for d in diags if d.severity == ERROR]


def warnings(diags: List[Diagnostic]) -> List[Diagnostic]:
    return [d for d in diags if d.severity == WARN]


def format_report(diags: List[Diagnostic], clean: str = "plan OK") -> str:
    if not diags:
        return clean
    lines = [d.render() for d in diags]
    ne, nw = len(errors(diags)), len(warnings(diags))
    lines.append(f"{ne} error(s), {nw} warning(s)")
    return "\n".join(lines)
