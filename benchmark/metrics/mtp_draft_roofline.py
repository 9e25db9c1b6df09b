"""The multi-token-prediction module of the decode step against its
bandwidth roofline: the least seconds to read what the module needs in the
window's decode iterations (`families/exaone_moe.mtp_bytes`: its own
weights but the routed experts, and the head, once an iteration; the
routed experts of its block that met a row, `mtp_moe` as the program
counts them on the device; K and V of every live token in its pool once a
lane, whatever the rows that query it) over the peak bandwidth, as a share
of the seconds of the operations under the scope `mtp` in the decode
program (`jit__fused_step_fn`)."""
from benchmark import mtp_trace, yardstick


def read(run):
    found = mtp_trace.summary()
    work = run["work"].get("mtp")
    if not found or not work or not work.get("bytes"):
        return None
    seconds = found["mtp_s"].get(mtp_trace.DECODE_PROGRAM)
    if not seconds:
        return None
    bw = yardstick.peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * work["bytes"] / bw / seconds
