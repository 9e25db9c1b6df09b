"""The sliding-window layers' attention of prefill against its roofline:
for each prefill of the window the larger of its least operations over
the peak FLOP/s and its least bytes over the peak bandwidth
(`families/mellum.window_prefill_work` of the prompt's real tokens: 4 x
heads x head size operations a (query, key) pair of the BAND, the sum over
t of min(t, window) pairs, and q, k, v and the output through memory),
summed, as a share of the seconds of the operations under the scope
`attention/window` in the prefill program (`jit__prefill_fn`, every
bucket: the ring's rewrite and the flash kernel). The peak is the chip's
bfloat16 one and the kernel's products run at `highest` (six passes), so
the share cannot pass a sixth."""
from benchmark import window_trace, yardstick


def read(run):
    found = window_trace.summary()
    work = run["work"].get("window")
    if not found or not work:
        return None
    seconds = found["window_s"].get(window_trace.PREFILL_PROGRAM)
    if not seconds or not found["prefill_tokens"]:
        return None
    peaks = yardstick.peaks(run["device"]["kind"])
    least = 0.0
    for tokens in found["prefill_tokens"]:
        flops, nbytes = work["prefill_work"](tokens)
        least += max(flops / peaks["flops_per_s"],
                     nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
