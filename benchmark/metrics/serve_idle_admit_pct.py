"""Share of the traced window in which the device was idle inside
`pt.engine.admit` or anything under it (queue pop, block-table row, the
prefill's dispatch and the wait for its token)."""
from benchmark import program_trace


def read(run):
    return program_trace.idle_pct(program_trace.summary(), "admit")
