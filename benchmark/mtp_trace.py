"""Device seconds of the traced window under the scope of a model's
drafting module: `scope_trace.py`'s reading, for the `mtp` scope of
`models/exaone_moe.py` (the multi-token-prediction module: `mtp/project`,
`mtp/attention/full`, `mtp/route`, `mtp/experts`, `mtp/shared`,
`mtp/logits`), which sits in the decode program and in the prefill
program alike. The same trace, the same window and the same first chip as
`program_trace.reduce`. A trace of a program that has no `mtp` scope gives
`None`: its metrics are left out.
"""
from __future__ import annotations

from collections import defaultdict

from benchmark import program_trace, tracing

MTP = "/mtp/"                     # a path component of an operation's `tf_op`
DECODE_PROGRAM = "jit__fused_step_fn"
PREFILL_PROGRAM = program_trace.PREFILL_PROGRAM

_summary = None   # of the newest trace: parsed once a process


def reduce(planes: dict):
    """From `program_trace.read_file`'s plain lists: `device_op_s` (all
    operations inside the window) and `mtp_s` by program (those under
    `mtp`). None where nothing ran under it."""
    spans = planes.get("spans", [])
    devices = {k: v for k, v in planes.get("devices", {}).items() if v}
    if not devices:
        return None
    events = devices[sorted(devices)[0]]
    window = [(s, s + d) for n, s, d, *_ in spans
              if n == tracing.WINDOW_SPAN]
    if window:
        lo, hi = window[0]
    else:
        lo = min(s for _, s, _ in events)
        hi = max(s + d for _, s, d in events)
    ops = planes.get("ops", {})
    total, mtp = 0.0, defaultdict(float)
    for key, s, d in events:
        inside = min(s + d, hi) - max(s, lo)
        if inside <= 0:
            continue
        total += inside
        # `jit(f)/jit(main)/mtp/...`: a component, wherever it stands
        if MTP in "/" + ops.get(key, ("", ""))[0]:
            mtp[key.split("/", 1)[0]] += inside
    if not mtp:
        return None
    return {"device_op_s": total / 1e9,
            "mtp_s": {k: v / 1e9 for k, v in mtp.items()}}


def summary():
    """`reduce` of the traced window this run took, parsed once; None
    where there is no trace or no `mtp` scope in it."""
    global _summary
    if _summary is None:
        path = program_trace.newest_trace()
        _summary = (reduce(program_trace.read_file(path)) if path
                    else None) or {}
    return _summary or None
