"""The sliding-window layers of the decode step against their bandwidth
roofline: the least seconds to read the ring rows the layers attended
over (`window_rows`, which the program counts on the device: the sum over
the window's decode iterations and active lanes of min(context, window),
times K and V of one row over the sliding layers,
`families/mellum.window_row_bytes`) over the peak bandwidth, as a share of
the seconds of the operations under the scope `attention/window` in the
decode program (`jit__fused_step_fn`: the ring's write and the paged
kernel's page walk). A ring holds at most its window, so a context of
5,000 tokens counts 1,024."""
from benchmark import window_trace, yardstick


def read(run):
    found = window_trace.summary()
    work = run["work"].get("window")
    if not found or not work or not work.get("rows"):
        return None
    seconds = found["window_s"].get(window_trace.DECODE_PROGRAM)
    if not seconds:
        return None
    bw = yardstick.peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * work["rows"] * work["row_bytes"] / bw / seconds
