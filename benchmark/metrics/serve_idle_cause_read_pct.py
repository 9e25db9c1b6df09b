"""Share of the traced window in which the device was idle, nothing was on
its way to it, and the host stood inside `pt.engine.fetch` or
`pt.engine.prefill.fetch`: the device has finished what it had and the
host still waits for the tokens (`benchmark/launch_trace.py`). With
`serve_idle_cause_call_pct` and `serve_idle_cause_host_pct` it sums to
`serve_device_idle_pct`."""
from benchmark import launch_trace


def read(run):
    return launch_trace.metric("serve_idle_cause_read_pct")
