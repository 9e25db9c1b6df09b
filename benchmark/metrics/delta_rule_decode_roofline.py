"""The recurrence of the decode step against its bandwidth roofline: the
least seconds to read and write the state of every active lane of the
window's decode iterations (`families/olmo_hybrid.delta_rule_step_bytes`
over the peak bandwidth) as a share of the seconds of the operations
under the scope `delta_rule` in the decode program (`jit__fused_step_fn`:
the one-token update and the moves between lanes and slots) plus that
program's `copy*` operations without metadata. The copies belong in it:
the chip's compiler streams each state into fast memory with
asynchronous copies that carry no scope, and the scoped operations alone
then read as fast as the bandwidth roofline or faster (98.8 and 100.2 %
in two traced runs, PERF.md, PR 27). Some of those copies move weights,
so the share reads low rather than high."""
from benchmark import scope_trace, yardstick


def read(run):
    found = scope_trace.summary()
    work = run["work"].get("delta_rule")
    if not found or not work:
        return None
    seconds = found["delta_rule_s"].get(scope_trace.DECODE_PROGRAM)
    if not seconds or not found["decode_lanes"]:
        return None
    seconds += found["bare_copy_s"].get(scope_trace.DECODE_PROGRAM, 0.0)
    bw = yardstick.peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * work["step_bytes"](found["decode_lanes"]) / bw / seconds
