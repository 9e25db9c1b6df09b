"""Decoded tokens over (decode iterations x max_batch) in the window,
from the engine's own counters: the share of lanes that did work."""


def read(run):
    c = run["counters"]
    if not c["iterations"]:
        return None
    return 100.0 * c["decode_tokens"] / (c["iterations"] * c["max_batch"])
