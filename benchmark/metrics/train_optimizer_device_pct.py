"""Seconds of device operations of the `optimizer` scope as a share of
the seconds of all device operations of the traced window. An operation
counts if its `op_name` path holds `optimizer` or, where jax left it no
path (benchmark/program_trace.py says when), if its `source` lies in
`paddle_tpu/optimizer/`; compiler-made operations without metadata count
for no scope, so this is a lower bound."""
from benchmark import program_trace


def read(run):
    return (program_trace.summary() or {}).get("optimizer_device_pct")
