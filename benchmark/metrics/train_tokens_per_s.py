"""Tokens trained per second of wall clock: every step of the window over
all of its seconds, closed by `block_until_ready` on the last loss."""


def read(run):
    return run["counters"]["tokens"] / run["window_s"]
