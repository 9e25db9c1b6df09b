"""The rest of the device's idle share: instants inside
`pt.engine.bookkeep`, `pt.engine.fetch`, `pt.engine.submit`, the self
time of `pt.engine.step`, and outside every program span (the
benchmark's own `collect` / `submit`)."""
from benchmark import program_trace


def read(run):
    return program_trace.idle_pct(program_trace.summary(), "other")
