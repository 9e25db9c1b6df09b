"""Seconds of device operations that ran under the prefill program
(`jit__prefill_fn`, every bucket) as a share of the seconds of all device
operations of the traced window."""
from benchmark import program_trace


def read(run):
    return (program_trace.summary() or {}).get("prefill_device_pct")
