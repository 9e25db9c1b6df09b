"""How long after a decode program ends on the device the host has its
tokens: the median, over the decode programs read inside the traced
window, of (end of the `pt.engine.fetch` span that read the launch) -
(end of its execution on the first chip), joined by the launch number
`seq` (`benchmark/launch_trace.py`). An iteration dispatched ahead is read
late by design: the `LAUNCHES` line splits the tail by `ahead`."""
from benchmark import launch_trace


def read(run):
    return launch_trace.metric("decode_read_tail_ms")
