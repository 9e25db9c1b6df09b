"""How long after a prefill program ends on the device the host has the
first token: the median, over the prefills read inside the traced window,
of (end of the `pt.engine.prefill.fetch` span) - (end of the prefill's
execution on the first chip), joined by the launch number `seq`
(`benchmark/launch_trace.py`). The device is idle through it unless a
decode iteration is queued behind the prefill, which the engine never
does today."""
from benchmark import launch_trace


def read(run):
    return launch_trace.metric("prefill_read_tail_ms")
