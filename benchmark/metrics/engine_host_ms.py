"""What the host costs a decode iteration: the median, over the traced
window's `pt.engine.step` spans that dispatched a decode program, of the
step less the `*.fetch` spans under it (the waits for the device)."""
from benchmark import program_trace


def read(run):
    return (program_trace.summary() or {}).get("engine_host_ms")
