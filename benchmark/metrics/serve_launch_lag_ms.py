"""What a launch costs when nothing hides it: the median of (start of the
execution on the first chip) - (start of its `pt.engine.dispatch` /
`.prefill.dispatch` span), over the programs of the traced window whose
call opened with the device idle and nothing queued
(`benchmark/launch_trace.py`). The jit call's own Python, its NumPy
arguments' transfer and the launch in the runtime."""
from benchmark import launch_trace


def read(run):
    return launch_trace.metric("serve_launch_lag_ms")
