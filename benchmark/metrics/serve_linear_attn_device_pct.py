"""Seconds of device operations whose `op_name` path holds
`attention/linear` (the linear-attention layers' convolution, recurrence,
gates and state rows; not their projections' matrix products, which the
path of `nn.Linear` puts under `attention` alone) as a share of the
seconds of all device operations of the traced serving window."""
from benchmark import scope_trace


def read(run):
    found = scope_trace.summary()
    if not found or not found["device_op_s"]:
        return None
    return 100.0 * found["linear_s"] / found["device_op_s"]
