"""Seconds of device operations whose `op_name` path holds
`attention/window` (a sliding-window layer's ring write and its kernel, in
the decode and the prefill program) as a share of the seconds of all
device operations of the traced serving window."""
from benchmark import window_trace


def read(run):
    found = window_trace.summary()
    if not found or not found["device_op_s"]:
        return None
    return 100.0 * sum(found["window_s"].values()) / found["device_op_s"]
