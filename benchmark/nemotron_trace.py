"""Device seconds of the traced window by the scopes of the program's
Mamba-2 and expert blocks, with the window's own count of the work they
did: `scope_trace.py`'s reading, for `models/nemotron_h.py`'s scopes.

`program_trace.reduce` keeps one scope per operation, out of a fixed list:
the Mamba-2 mixer's operations sit under `attention` there and below it
under `ssm`, then `conv` or `scan`; an expert block's under `mlp` and
below it `moe`, then `route`, `experts` or `shared` (the path is the
operation's `tf_op` as `program_trace.op_table` reads it). This reader
takes the same trace, the same window and the same first chip, and sums by
those inner scopes. A trace of a program that has neither scope gives
`None`: its metrics are left out.
"""
from __future__ import annotations

from collections import defaultdict

from benchmark import program_trace, tracing

SSM = "/attention/ssm/"           # path components of an operation's `tf_op`
SCAN = "/attention/ssm/scan/"
MOE = "/mlp/moe/"
DECODE_PROGRAM = "jit__fused_step_fn"
PREFILL_PROGRAM = program_trace.PREFILL_PROGRAM

_summary = None   # of the newest trace: parsed once a process


def reduce(planes: dict):
    """From `program_trace.read_file`'s plain lists: `device_op_s` (all
    operations inside the window), `ssm_s` and `moe_s` (those under
    `attention/ssm` and `mlp/moe`), `scan_s` and `moe_program_s` by
    program, `bare_copy_s` by program (the compiler's own `copy` /
    `copy-start` / `copy-done` operations, which carry no metadata: on the
    chip it moves a state or a weight matrix into fast memory with them,
    under the shadow of other work, before the scoped operation reads it),
    `decode_lanes` (active lanes summed over the window's decode
    iterations, from the `pt.engine.lanes` spans), `decode_iterations` and
    `prefill_tokens` (the real tokens of each prefill whose span lies in
    the window). None where nothing ran under either scope."""
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
    total = ssm = moe = 0.0
    scan, moe_by, copies = (defaultdict(float), defaultdict(float),
                            defaultdict(float))
    for key, s, d in events:
        inside = min(s + d, hi) - max(s, lo)
        if inside <= 0:
            continue
        total += inside
        program, op = key.split("/", 1)
        tf_op = ops.get(key, ("", ""))[0]
        if SSM in tf_op:
            ssm += inside
            if SCAN in tf_op:
                scan[program] += inside
        elif MOE in tf_op:
            moe += inside
            moe_by[program] += inside
        elif not tf_op and op.startswith("copy"):
            copies[program] += inside
    if not ssm and not moe:
        return None
    lanes, iterations, prompts = 0, 0, []
    for name, start, dur, _, args in spans:
        if not (lo <= start and start + dur <= hi):
            continue
        if name == "pt.engine.lanes":
            lanes += int(args.get("active", 0))
            iterations += 1
        elif name == "pt.engine.prefill":
            prompts.append(int(args.get("prompt_tokens", 0)))
    ns = lambda d: {k: v / 1e9 for k, v in d.items()}  # noqa: E731
    return {"device_op_s": total / 1e9, "ssm_s": ssm / 1e9,
            "moe_s": moe / 1e9, "scan_s": ns(scan), "moe_program_s": ns(moe_by),
            "bare_copy_s": ns(copies), "decode_lanes": lanes,
            "decode_iterations": iterations, "prefill_tokens": prompts}


def summary():
    """`reduce` of the traced window this run took, parsed once; None
    where there is no trace or neither scope in it."""
    global _summary
    if _summary is None:
        path = program_trace.newest_trace()
        _summary = (reduce(program_trace.read_file(path)) if path
                    else None) or {}
    return _summary or None
