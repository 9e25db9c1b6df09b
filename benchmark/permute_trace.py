"""Device seconds of the traced window under the expert layers' `permute`
scope (`ops/moe.held_experts`: the sort of the token x expert assignments,
the gather of their rows, their return to the assignments' order and the
weighted sum), beside the seconds under `mlp/moe`, the whole expert
layer: `scope_trace.py`'s reading, for one inner scope of
`nemotron_trace.py`'s.

The path is the operation's `tf_op` as `program_trace.op_table` reads it:
``.../mlp/moe/experts/jit(_held_impl)/permute/...``. This reader takes the
same trace, the same window and the same first chip, and sums over every
program in it (the decode step and each prefill bucket). A trace without
the scope (a program that has none) gives `None`: its metric is left out.
"""
from __future__ import annotations

from benchmark import program_trace, tracing

MOE = "/mlp/moe/"                 # path components of an operation's `tf_op`
EXPERTS = "/mlp/moe/experts/"
PERMUTE = "/permute/"

_summary = None   # of the newest trace: parsed once a process


def reduce(planes: dict):
    """From `program_trace.read_file`'s plain lists: `moe_s` (the seconds
    of operations inside the window under `mlp/moe`) and `permute_s`
    (those of them under `permute` below `mlp/moe/experts`). None where
    nothing ran under `permute`."""
    devices = {k: v for k, v in planes.get("devices", {}).items() if v}
    if not devices:
        return None
    events = devices[sorted(devices)[0]]
    window = [(s, s + d) for n, s, d, *_ in planes.get("spans", [])
              if n == tracing.WINDOW_SPAN]
    if window:
        lo, hi = window[0]
    else:
        lo = min(s for _, s, _ in events)
        hi = max(s + d for _, s, d in events)
    ops = planes.get("ops", {})
    moe = permute = 0.0
    for key, s, d in events:
        inside = min(s + d, hi) - max(s, lo)
        tf_op = ops.get(key, ("", ""))[0]
        if inside <= 0 or MOE not in tf_op:
            continue
        moe += inside
        below = tf_op.partition(EXPERTS)[2]
        if PERMUTE in "/" + below:
            permute += inside
    if not permute:
        return None
    return {"moe_s": moe / 1e9, "permute_s": permute / 1e9}


def summary():
    """`reduce` of the traced window this run took, parsed once; None
    where there is no trace or no `permute` scope in it."""
    global _summary
    if _summary is None:
        path = program_trace.newest_trace()
        _summary = (reduce(program_trace.read_file(path)) if path
                    else None) or {}
    return _summary or None
