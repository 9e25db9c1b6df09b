"""The least device seconds the traced window's work needs, as a share of
the seconds the device was busy. The least: each decode iteration reads
the weights once and the live K/V (bytes over peak bandwidth); each
prefill takes the larger of its operations over peak FLOP/s and the
weights' bytes over peak bandwidth. Needs no split of device time by
program."""
from benchmark import yardstick


def read(run):
    trace = run.get("trace")
    if not trace or not trace.get("busy_s"):
        return None
    peaks = yardstick.peaks(run["device"]["kind"])
    bw, fl = peaks["hbm_bytes_per_s"], peaks["flops_per_s"]
    least = sum(b / bw for b in run["work"]["decode_bytes"])
    least += sum(max(f / fl, b / bw) for f, b in run["work"]["prefills"])
    return 100.0 * least / trace["busy_s"]
