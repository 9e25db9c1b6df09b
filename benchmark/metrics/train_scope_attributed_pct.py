"""Seconds of device operations whose `op_name` path holds one of the
program's scopes (attention, mlp, ln, embed, logits, loss, optimizer) as
a share of the seconds of all device operations of the traced window."""
from benchmark import program_trace


def read(run):
    return (program_trace.summary() or {}).get("scope_attributed_pct")
