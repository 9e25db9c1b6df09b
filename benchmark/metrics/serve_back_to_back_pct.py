"""The share of the mechanism of PR 32 that really hides the host: decode
programs that started within 50 us of the end of the execution before
them (any program), over all decode programs that started inside the
traced window (`benchmark/launch_trace.py`). `serve_ahead_pct` on the
`LAUNCHES` line is the share DISPATCHED ahead; the difference came too
late to find the device busy."""
from benchmark import launch_trace


def read(run):
    return launch_trace.metric("serve_back_to_back_pct")
