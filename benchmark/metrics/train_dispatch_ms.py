"""What one `TrainStep.__call__` that does not read the loss costs the
host: the LOWER QUARTILE of the calls' wall. The calls are of two kinds:
while the host is ahead a call only enqueues (5-7 ms on the chip, PR 24);
once the device's queue is full it waits a whole step (114 ms). A traced
window held 15 of each, so a median sits on the edge between them."""
from benchmark import yardstick


def read(run):
    xs = run["spans"].get("train_call")
    return 1e3 * yardstick.quantile(xs, 0.25) if xs else None
