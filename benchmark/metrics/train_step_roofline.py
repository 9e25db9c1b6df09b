"""Model FLOPs of the window's steps (families/<family>.py, non-causal
MFU convention) over the chip's peak, as a share of the seconds the
device was busy in the traced window. Compute-bound by construction."""
from benchmark import yardstick


def read(run):
    trace = run.get("trace")
    if not trace or not trace.get("busy_s"):
        return None
    peak = yardstick.peaks(run["device"]["kind"])["flops_per_s"]
    return 100.0 * run["work"]["flops"] / peak / trace["busy_s"]
