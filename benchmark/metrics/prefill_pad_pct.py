"""The share of the rows the window's prefill programs computed that were
bucket padding: 100 x sum(bucket - prompt_tokens) / sum(bucket) over the
`pt.engine.prefill` spans of the traced window
(`benchmark/prefill_pad_trace.py`). Every layer computes the whole bucket,
so in a cell whose prefills are bound by their operations this is the
share of prefill device time that no prompt asked for."""
from benchmark import prefill_pad_trace


def read(run):
    return (prefill_pad_trace.summary() or {}).get("prefill_pad_pct")
