"""Device seconds of the traced window by the scopes of the program's
latent-attention layers, with the window's own count of the prefills it
held: `window_trace.py`'s reading, for `models/deepseek_v2.py`'s scopes.

`program_trace.reduce` keeps one scope per operation, out of a fixed list:
the latent attention sits under `attention` there, and below it under
`latent`, then `proj`, `absorb`, `decode` (the row's append and the
absorbed kernel, in the decode program) or `prefill` (the expansion, the
flash forward, the rows' append, in the prefill program); the rotation
sits under `attention/rope` (the path is the operation's `tf_op` as
`program_trace.op_table` reads it). This reader takes the same trace, the
same window and the same first chip, and sums by those inner scopes. A
trace of a program that has no `attention/latent` scope gives `None`: its
metrics are left out.
"""
from __future__ import annotations

from collections import defaultdict

from benchmark import program_trace, tracing

LATENT = "/attention/latent/"     # path components of an operation's `tf_op`
DECODE = "/attention/latent/decode/"
PREFILL = "/attention/latent/prefill/"
FLASH = "_fa_fwd_pallas"          # the flash forward's operations, by name
DECODE_PROGRAM = "jit__fused_step_fn"
PREFILL_PROGRAM = program_trace.PREFILL_PROGRAM

_summary = None   # of the newest trace: parsed once a process


def reduce(planes: dict):
    """From `program_trace.read_file`'s plain lists: `device_op_s` (all
    operations inside the window), `latent_s`, `decode_s` and `flash_s`
    by program (those under `attention/latent`, under
    `attention/latent/decode`, and the flash forward's under
    `attention/latent/prefill`), `decode_iterations` and `prefill_tokens`
    (the real tokens of each prefill whose span lies in the window). None
    where nothing ran under `attention/latent`."""
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
    latent, decode, flash = (defaultdict(float), defaultdict(float),
                             defaultdict(float))
    for key, s, d in events:
        inside = min(s + d, hi) - max(s, lo)
        if inside <= 0:
            continue
        total += inside
        tf_op = ops.get(key, ("", ""))[0]
        if LATENT not in tf_op:
            continue
        program, op = key.split("/", 1)
        latent[program] += inside
        if DECODE in tf_op:
            decode[program] += inside
        elif PREFILL in tf_op and op.startswith(FLASH):
            flash[program] += inside
    if not latent:
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
    return {"device_op_s": total / 1e9, "latent_s": ns(latent),
            "decode_s": ns(decode), "flash_s": ns(flash),
            "decode_iterations": iterations, "prefill_tokens": prompts}


def summary():
    """`reduce` of the traced window this run took, parsed once; None
    where there is no trace or no `attention/latent` scope in it."""
    global _summary
    if _summary is None:
        path = program_trace.newest_trace()
        _summary = (reduce(program_trace.read_file(path)) if path
                    else None) or {}
    return _summary or None
