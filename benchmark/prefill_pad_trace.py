"""The rows the window's prefill programs computed, and how many of them
were bucket padding.

`ServingEngine` pads a prompt to the smallest of its prefill buckets that
holds it, and every layer then computes the whole bucket: a padded row
costs device time like a real one. Each admission's `pt.engine.prefill`
span carries `bucket` (the rows of the program it ran) and `prompt_tokens`
(the rows that were the prompt's own), so the share is read from the
spans' arguments alone, whatever ladder of buckets the engine took: over
the prefill spans that lie inside the traced window,

    prefill_pad_pct = 100 x sum(bucket - prompt_tokens) / sum(bucket).

`run["counters"]` takes a fixed list of the engine's stats, so
`eng.stats["prefill_padded_tokens"]` (PR 38) does not reach a metric; the
spans hold the same two numbers an admission. The trace is read as
`program_trace.read_file` reads it, once a process; the first read prints one line, `PREFILL_PAD {json}`, with the
window's admissions by bucket. A trace without such a span, or whose
spans lack either argument, gives None: the metric is left out.
"""
from __future__ import annotations

import json
import os
from collections import defaultdict

from benchmark import program_trace, tracing

PREFILL_SPAN = "pt.engine.prefill"

_summary = None   # of the newest trace: parsed once a process


def reduce(spans) -> dict | None:
    """From `program_trace.read_file`'s `spans`: `prefills`, `rows` (the
    buckets' sum), `padded_rows`, `by_bucket` ({bucket: [admissions,
    prompt rows]}) and `prefill_pad_pct`, over the prefill spans inside
    `bench.window` (the whole trace without one). None where no span
    carries both arguments."""
    window = [(s[1], s[1] + s[2]) for s in spans
              if s[0] == tracing.WINDOW_SPAN]
    lo, hi = window[0] if window else (float("-inf"), float("inf"))
    rows = live = 0
    by_bucket = defaultdict(lambda: [0, 0])
    for name, start, dur, _, args in spans:
        if name != PREFILL_SPAN or not (lo <= start and start + dur <= hi):
            continue
        if "bucket" not in args or "prompt_tokens" not in args:
            continue
        bucket, tokens = int(args["bucket"]), int(args["prompt_tokens"])
        rows += bucket
        live += tokens
        by_bucket[bucket][0] += 1
        by_bucket[bucket][1] += tokens
    if not rows:
        return None
    return {"prefills": sum(n for n, _ in by_bucket.values()),
            "rows": rows, "padded_rows": rows - live,
            "by_bucket": {str(b): v for b, v in sorted(by_bucket.items())},
            "prefill_pad_pct": 100.0 * (rows - live) / rows}


def summary():
    """`reduce` of the traced window this run took
    (`program_trace.newest_trace`), parsed once; the first read prints
    the `PREFILL_PAD` line. None where there is nothing to read."""
    global _summary
    if _summary is None:
        path = program_trace.newest_trace()
        _summary = (reduce(program_trace.read_file(path)["spans"])
                    if path else None) or {}
        if _summary:
            print("PREFILL_PAD " + json.dumps(
                {"trace": os.path.basename(os.path.dirname(path)),
                 **_summary}), flush=True)
    return _summary or None
