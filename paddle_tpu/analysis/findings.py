"""Typed findings for the static program auditor.

A :class:`Finding` is one perf hazard (or convention violation) the
auditor proved from the jaxpr / lowered StableHLO text of a compiled
program — without executing it. Severity semantics (the CLI's
``--fail-on`` and the tier-1 gate key off these):

* ``high``   — a real, avoidable perf/memory hazard on the audited path
  (undonated large dead buffer, rejected donation, f64 compute, a
  replicated param with a usable mesh axis, a host array baked into the
  executable). The shipped models must audit high-clean.
* ``medium`` — likely waste that needs a human look (large silent float
  upcast, f32 matmul inside a bf16 region, collective-bytes budget
  exceeded).
* ``low``    — style/risk notes (retrace-prone static args).
* ``info``   — context the auditor wants on the record.

Every finding lands on the PR-6 observability plane:
``analysis_finding`` events (severity mapped high->error, medium->warn,
low->info, info->debug) and the
``analysis_findings_total{check=,severity=}`` metric family; audits
themselves count in ``analysis_audits_total{entry=}``.
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from ..profiler import events as _events_mod
from ..profiler import metrics as _metrics_mod

__all__ = ["Finding", "AuditReport", "SEVERITIES", "CHECKS",
           "recent_reports"]

#: ascending order (the CLI's --fail-on threshold indexes into this)
SEVERITIES = ("info", "low", "medium", "high")

#: the check families the auditor implements
CHECKS = ("donation", "dtype", "sharding", "bloat")

_EVENT_SEVERITY = {"high": "error", "medium": "warn", "low": "info",
                   "info": "debug"}

_REG = _metrics_mod.default_registry()
_M_FINDINGS = _REG.counter(
    "analysis_findings_total",
    "static program-auditor findings by check and severity")
_M_AUDITS = _REG.counter(
    "analysis_audits_total",
    "program audits run, by jit entry point")

#: newest emitted audit reports, for the ObservabilityServer /snapshot
#: endpoint (bounded; a long-lived daemon auditing every engine it
#: builds must not grow this without limit)
_RECENT_REPORTS: "deque[dict]" = deque(maxlen=16)


def recent_reports() -> List[dict]:
    """The newest emitted audit reports (dict form, oldest first) —
    what the ObservabilityServer surfaces under `program_audit`. Each
    entry is `AuditReport.to_dict(max_findings=8)` plus an `emitted_ts`
    wall-clock stamp."""
    return list(_RECENT_REPORTS)


@dataclass
class Finding:
    """One auditor finding: what, how bad, where, and how to fix it."""

    check: str            # one of CHECKS
    severity: str         # one of SEVERITIES
    code: str             # stable slug, e.g. "undonated-large-input"
    message: str          # human sentence stating the hazard
    param: str = ""       # offending arg/param/const path or op name
    scope: str = ""       # named-scope attribution (PR-11 metadata)
    nbytes: int = 0       # size of the offending buffer (0 = n/a)
    fix_hint: str = ""    # what to change

    def __post_init__(self):
        if self.severity not in SEVERITIES:
            raise ValueError(f"severity must be one of {SEVERITIES}, "
                             f"got {self.severity!r}")
        if self.check not in CHECKS:
            raise ValueError(f"check must be one of {CHECKS}, "
                             f"got {self.check!r}")

    def to_dict(self) -> dict:
        d = {"check": self.check, "severity": self.severity,
             "code": self.code, "message": self.message}
        for k in ("param", "scope", "fix_hint"):
            v = getattr(self, k)
            if v:
                d[k] = v
        if self.nbytes:
            d["nbytes"] = int(self.nbytes)
        return d

    def __str__(self):
        where = f" [{self.param}]" if self.param else ""
        scope = f" (scope: {self.scope})" if self.scope else ""
        hint = f" — fix: {self.fix_hint}" if self.fix_hint else ""
        return (f"{self.severity.upper():<6} {self.check}/{self.code}"
                f"{where}{scope}: {self.message}{hint}")


@dataclass
class AuditReport:
    """All findings of one program audit, plus identity of the program."""

    name: str                      # program label (e.g. "GPT#1")
    entry: str                     # jit entry audited (train_step, ...)
    findings: List[Finding] = field(default_factory=list)
    # the compiled-program check (auditor.pool_relayout_report): None
    # where the audit did not compile
    pool_relayout_copies: Optional[int] = None
    temp_size_in_bytes: Optional[int] = None

    def add(self, finding: Finding):
        self.findings.append(finding)

    def counts(self) -> Dict[str, int]:
        out = {s: 0 for s in SEVERITIES}
        for f in self.findings:
            out[f.severity] += 1
        return out

    def by_severity(self, floor: str) -> List[Finding]:
        """Findings at or above `floor` severity."""
        lo = SEVERITIES.index(floor)
        return [f for f in self.findings
                if SEVERITIES.index(f.severity) >= lo]

    @property
    def clean(self) -> bool:
        return not self.findings

    def to_dict(self, max_findings: Optional[int] = None) -> dict:
        ranked = sorted(
            self.findings,
            key=lambda f: -SEVERITIES.index(f.severity))
        if max_findings is not None:
            ranked = ranked[:max_findings]
        d = {"name": self.name, "entry": self.entry,
             "counts": self.counts(),
             "findings": [f.to_dict() for f in ranked]}
        if self.pool_relayout_copies is not None:
            d["pool_relayout_copies"] = self.pool_relayout_copies
            d["temp_size_in_bytes"] = self.temp_size_in_bytes
        return d

    def emit(self):
        """Land this report on the observability plane: one
        `analysis_finding` event per finding + the metric families.
        Never raises (audits run inside training entry points)."""
        try:
            rec = self.to_dict(max_findings=8)
            rec["emitted_ts"] = time.time()
            _RECENT_REPORTS.append(rec)
        except Exception:
            pass
        try:
            if _metrics_mod.enabled():
                _M_AUDITS.inc(entry=self.entry)
                for f in self.findings:
                    _M_FINDINGS.inc(check=f.check, severity=f.severity)
            for f in self.findings:
                _events_mod.emit(
                    "analysis_finding",
                    severity=_EVENT_SEVERITY[f.severity],
                    program=self.name, entry=self.entry,
                    check=f.check, code=f.code, finding_severity=f.severity,
                    param=f.param, scope=f.scope, nbytes=int(f.nbytes),
                    message=f.message, fix_hint=f.fix_hint)
        except Exception:
            pass

    def render(self) -> str:
        """Human table for the CLI."""
        if not self.findings:
            return f"{self.name} [{self.entry}]: clean (0 findings)"
        lines = [f"{self.name} [{self.entry}]: "
                 f"{len(self.findings)} finding(s)"]
        for f in sorted(self.findings,
                        key=lambda f: -SEVERITIES.index(f.severity)):
            lines.append("  " + str(f))
        return "\n".join(lines)
