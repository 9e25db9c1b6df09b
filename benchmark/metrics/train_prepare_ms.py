"""What `TrainStep.__call__` costs the host besides calling its program:
the LOWER QUARTILE, over the traced window's `pt.train.call` spans, of the
call less its `pt.train.dispatch` (rng split, lr upload, batch unwrapping,
the retrace watchdog, the audit gate, the loss wrapper). The quartile for
`train_dispatch_ms`'s reason: once the device's queue is full a call waits
a whole step, and it waits inside `pt.train.prepare` (the first line that
touches the device), so a median reads the wait (46-48 ms on the chip,
PR 25) and not the host's work."""
from benchmark import program_trace


def read(run):
    return (program_trace.summary() or {}).get("train_prepare_ms")
