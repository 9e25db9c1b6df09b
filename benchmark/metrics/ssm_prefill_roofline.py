"""The chunked state-space scan of prefill against its roofline: for each
prefill of the window the larger of its least operations over the peak
FLOP/s and its least bytes over the peak bandwidth
(`families/nemotron_h.ssm_prefill_work` of the prompt's real tokens: 6 x
heads x head size x state size operations a token and layer), summed, as a
share of the seconds of the operations under the scope
`attention/ssm/scan` in the prefill program (`jit__prefill_fn`, every
bucket)."""
from benchmark import nemotron_trace, yardstick


def read(run):
    found = nemotron_trace.summary()
    work = run["work"].get("ssm")
    if not found or not work:
        return None
    seconds = found["scan_s"].get(nemotron_trace.PREFILL_PROGRAM)
    if not seconds or not found["prefill_tokens"]:
        return None
    peaks = yardstick.peaks(run["device"]["kind"])
    least = 0.0
    for tokens in found["prefill_tokens"]:
        flops, nbytes = work["prefill_work"](tokens)
        least += max(flops / peaks["flops_per_s"],
                     nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
