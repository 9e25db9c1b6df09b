"""Median wall of one `ServingEngine.step()` of the window."""
from benchmark import yardstick


def read(run):
    return yardstick.median_ms(run["spans"].get("engine_step"))
