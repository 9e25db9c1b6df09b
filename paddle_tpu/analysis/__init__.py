"""Static analysis: program auditor + framework convention lints.

`auditor` vets compiled programs (jaxpr + lowered StableHLO) for perf
hazards at trace time — donation, dtype hygiene, sharding, executable
bloat — producing typed `findings` that land on the observability
plane. `conventions` is the AST-level lint pack over the package source
(env-knob parsing, fault-site registry, thread hygiene, event kinds).

Operator surfaces: `tools/program_audit.py` (offline CLI, CI gate via
--fail-on), the per-config `program_audit` block in bench.py, and the
`analysis_finding` event / `analysis_*` metric families.
"""
from .auditor import (AUDIT_ENV, audit_collectives_by_link, audit_program,
                      audit_sharding, enabled, maybe_audit,
                      pool_relayout_report, reset_seen)
from .findings import (CHECKS, SEVERITIES, AuditReport, Finding,
                       recent_reports)

__all__ = ["AUDIT_ENV", "audit_program", "audit_collectives_by_link",
           "audit_sharding", "pool_relayout_report", "enabled",
           "maybe_audit", "reset_seen",
           "AuditReport", "Finding", "CHECKS", "SEVERITIES",
           "recent_reports"]
