"""The state-space recurrence of the decode step against its bandwidth
roofline: the least seconds to read and write the state of every active
lane of the window's decode iterations
(`families/nemotron_h.ssm_step_bytes` over the peak bandwidth) as a share
of the seconds of the operations under the scope `attention/ssm/scan` in
the decode program (`jit__fused_step_fn`: the one-token update and the
moves between lanes and slots) plus that program's `copy*` operations
without metadata, for the reason `delta_rule_decode_roofline.py` gives:
the chip's compiler stages a state in fast memory with asynchronous copies
that carry no scope. Some of those copies move weights, so the share reads
low rather than high."""
from benchmark import nemotron_trace, yardstick


def read(run):
    found = nemotron_trace.summary()
    work = run["work"].get("ssm")
    if not found or not work:
        return None
    seconds = found["scan_s"].get(nemotron_trace.DECODE_PROGRAM)
    if not seconds or not found["decode_lanes"]:
        return None
    seconds += found["bare_copy_s"].get(nemotron_trace.DECODE_PROGRAM, 0.0)
    bw = yardstick.peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * work["step_bytes"](found["decode_lanes"]) / bw / seconds
