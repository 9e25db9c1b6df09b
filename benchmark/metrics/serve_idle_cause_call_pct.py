"""Share of the traced window in which the device was idle with a program
on its way: idle instants at or after the start of the call span of the
next execution to start (the jit call's Python, its arguments' transfer,
the launch in the runtime), wherever the host stands by then
(`benchmark/launch_trace.py`). With `serve_idle_cause_read_pct` and
`serve_idle_cause_host_pct` it sums to `serve_device_idle_pct`."""
from benchmark import launch_trace


def read(run):
    return launch_trace.metric("serve_idle_cause_call_pct")
