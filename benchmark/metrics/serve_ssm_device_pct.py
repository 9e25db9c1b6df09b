"""Seconds of device operations whose `op_name` path holds `attention/ssm`
(the Mamba-2 mixers: projections, convolution, recurrence, gated norm, the
moves between lanes and slots) as a share of the seconds of all device
operations of the traced serving window."""
from benchmark import nemotron_trace


def read(run):
    found = nemotron_trace.summary()
    if not found or not found["device_op_s"]:
        return None
    return 100.0 * found["ssm_s"] / found["device_op_s"]
