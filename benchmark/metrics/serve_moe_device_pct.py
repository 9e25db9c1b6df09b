"""Seconds of device operations whose `op_name` path holds `mlp/moe` (the
expert blocks: router, grouped products, shared expert, the sort and the
combine) as a share of the seconds of all device operations of the traced
serving window."""
from benchmark import nemotron_trace


def read(run):
    found = nemotron_trace.summary()
    if not found or not found["device_op_s"]:
        return None
    return 100.0 * found["moe_s"] / found["device_op_s"]
