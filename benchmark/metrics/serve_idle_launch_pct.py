"""Share of the traced window in which the device was idle while the
engine stood between two decode programs: idle instants whose innermost
program span is `pt.engine.capacity`, `.lanes`, `.upload` or
`.dispatch`. With `serve_idle_admit_pct` and `serve_idle_other_pct` it
sums to `serve_device_idle_pct`."""
from benchmark import program_trace


def read(run):
    return program_trace.idle_pct(program_trace.summary(), "launch")
