"""Median duration of the traced window's `pt.engine.prefill` spans: one
admission from the queue pop to its first token on the host (block-table
row, the bucketed prefill program, the blocking fetch). The other half of
`ttft_p95_ms`."""
from benchmark import program_trace


def read(run):
    return (program_trace.summary() or {}).get("prefill_ms")
