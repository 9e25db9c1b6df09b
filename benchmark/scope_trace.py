"""Device seconds of the traced window by a scope of the program's
linear-attention layers, with the window's own count of the work they did.

`program_trace.reduce` keeps one scope per operation, out of a fixed list.
The linear-attention layers' operations sit under `attention` there, and
below it under `linear` and then `conv` or `delta_rule`
(`paddle_tpu/models/olmo_hybrid.py`; the path is the operation's `tf_op`
as `program_trace.op_table` reads it). This reader takes the same trace,
the same window and the same first chip, and sums by those inner scopes.
A trace of a program that has no such scope gives `None`: its metrics are
left out.
"""
from __future__ import annotations

from collections import defaultdict

from benchmark import program_trace, tracing

LINEAR = "/attention/linear/"     # path components of an operation's `tf_op`
DELTA_RULE = "/delta_rule/"
DECODE_PROGRAM = "jit__fused_step_fn"
PREFILL_PROGRAM = program_trace.PREFILL_PROGRAM

_summary = None   # of the newest trace: parsed once a process


def reduce(planes: dict):
    """From `program_trace.read_file`'s plain lists: `device_op_s` (all
    operations inside the window), `linear_s` (those under
    `attention/linear`), `delta_rule_s` by program, `bare_copy_s` by
    program (the compiler's own `copy` / `copy-start` / `copy-done`
    operations, which carry no metadata: on the chip it moves a state
    into fast memory with them, under the shadow of other work, before
    the scoped operation reads it), `decode_lanes` (active
    lanes summed over the window's decode iterations, from the
    `pt.engine.lanes` spans) and `prefill_tokens` (the real tokens of
    each prefill whose span lies in the window). None where nothing ran
    under `attention/linear`."""
    spans = planes.get("spans", [])
    devices = {k: v for k, v in planes.get("devices", {}).items() if v}
    window = [(s, s + d) for n, s, d, *_ in spans
              if n == tracing.WINDOW_SPAN]
    if not devices:
        return None
    events = devices[sorted(devices)[0]]
    if window:
        lo, hi = window[0]
    else:
        lo = min(s for _, s, _ in events)
        hi = max(s + d for _, s, d in events)
    ops = planes.get("ops", {})
    total = linear = 0.0
    delta, copies = defaultdict(float), defaultdict(float)
    for key, s, d in events:
        inside = min(s + d, hi) - max(s, lo)
        if inside <= 0:
            continue
        total += inside
        program, op = key.split("/", 1)
        tf_op = ops.get(key, ("", ""))[0]
        if LINEAR in tf_op:
            linear += inside
        if DELTA_RULE in tf_op:
            delta[program] += inside
        elif not tf_op and op.startswith("copy"):
            copies[program] += inside
    if not linear:
        return None
    lanes, prompts = 0, []
    for name, start, dur, _, args in spans:
        if not (lo <= start and start + dur <= hi):
            continue
        if name == "pt.engine.lanes":
            lanes += int(args.get("active", 0))
        elif name == "pt.engine.prefill":
            prompts.append(int(args.get("prompt_tokens", 0)))
    return {"device_op_s": total / 1e9, "linear_s": linear / 1e9,
            "delta_rule_s": {k: v / 1e9 for k, v in delta.items()},
            "bare_copy_s": {k: v / 1e9 for k, v in copies.items()},
            "decode_lanes": lanes, "prefill_tokens": prompts}


def summary():
    """`reduce` of the traced window this run took, parsed once; None
    where there is no trace or no linear-attention scope in it."""
    global _summary
    if _summary is None:
        path = program_trace.newest_trace()
        _summary = (reduce(program_trace.read_file(path)) if path
                    else None) or {}
    return _summary or None
