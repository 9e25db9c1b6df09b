"""Device seconds of the traced window by the scopes of the program's
sliding-window and full attention layers, with the window's own count of
the prefills it held: `scope_trace.py`'s reading, for `models/mellum.py`'s
scopes.

`program_trace.reduce` keeps one scope per operation, out of a fixed list:
both kinds of attention layer sit under `attention` there, and below it a
layer's append and kernel under `window` (a sliding layer: the ring's
write and the paged kernel in the decode program, the ring's rewrite and
the flash kernel in the prefill program) or `full`, and the rotation under
`rope` (the path is the operation's `tf_op` as `program_trace.op_table`
reads it). This reader takes the same trace, the same window and the same
first chip, and sums by those inner scopes. A trace of a program that has
no `attention/window` scope gives `None`: its metrics are left out.
"""
from __future__ import annotations

from collections import defaultdict

from benchmark import program_trace, tracing

WINDOW = "/attention/window/"     # path components of an operation's `tf_op`
FULL = "/attention/full/"
ROPE = "/attention/rope/"
DECODE_PROGRAM = "jit__fused_step_fn"
PREFILL_PROGRAM = program_trace.PREFILL_PROGRAM

_summary = None   # of the newest trace: parsed once a process


def reduce(planes: dict):
    """From `program_trace.read_file`'s plain lists: `device_op_s` (all
    operations inside the window), `window_s`, `full_s` and `rope_s` by
    program (those under `attention/window`, `attention/full` and
    `attention/rope`), `decode_iterations` and `prefill_tokens` (the real
    tokens of each prefill whose span lies in the window). None where
    nothing ran under `attention/window`."""
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
    total = 0.0
    by = {scope: defaultdict(float) for scope in (WINDOW, FULL, ROPE)}
    for key, s, d in events:
        inside = min(s + d, hi) - max(s, lo)
        if inside <= 0:
            continue
        total += inside
        tf_op = ops.get(key, ("", ""))[0]
        for scope, seconds in by.items():
            if scope in tf_op:
                seconds[key.split("/", 1)[0]] += inside
                break
    if not by[WINDOW]:
        return None
    iterations, prompts = 0, []
    for name, start, dur, _, args in spans:
        if not (lo <= start and start + dur <= hi):
            continue
        if name == "pt.engine.lanes":
            iterations += 1
        elif name == "pt.engine.prefill":
            prompts.append(int(args.get("prompt_tokens", 0)))
    ns = lambda d: {k: v / 1e9 for k, v in d.items()}  # noqa: E731
    return {"device_op_s": total / 1e9, "window_s": ns(by[WINDOW]),
            "full_s": ns(by[FULL]), "rope_s": ns(by[ROPE]),
            "decode_iterations": iterations, "prefill_tokens": prompts}


def summary():
    """`reduce` of the traced window this run took, parsed once; None
    where there is no trace or no `attention/window` scope in it."""
    global _summary
    if _summary is None:
        path = program_trace.newest_trace()
        _summary = (reduce(program_trace.read_file(path)) if path
                    else None) or {}
    return _summary or None
