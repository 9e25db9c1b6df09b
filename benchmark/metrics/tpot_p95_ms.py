"""95th percentile, over ALL requests completed in the window, of
(done - first token) / (tokens - 1): the cadence a streaming client
sees."""
from benchmark import yardstick


def read(run):
    return yardstick.p95_ms(run["samples"]["tpot_s"])
