"""The expanded attention of prefill against its roofline: for each
prefill of the window the larger of its least operations over the peak
FLOP/s and its least bytes over the peak bandwidth
(`families/deepseek_v2.latent_prefill_work` of the prompt's real tokens:
every head's scores and weighted sum over the causal triangle, and q, k,
v and the output through memory, over the layers), summed, as a share of
the seconds of the flash forward's operations under the scope
`attention/latent/prefill` in the prefill program (`jit__prefill_fn`,
every bucket). The peak is the chip's bfloat16 one and the kernel's
products run at `highest` (six passes), so the share cannot pass a sixth."""
from benchmark import latent_trace, yardstick


def read(run):
    found = latent_trace.summary()
    work = run["work"].get("latent")
    if not found or not work:
        return None
    seconds = found["flash_s"].get(latent_trace.PREFILL_PROGRAM)
    if not seconds or not found["prefill_tokens"]:
        return None
    peaks = yardstick.peaks(run["device"]["kind"])
    least = 0.0
    for tokens in found["prefill_tokens"]:
        flops, nbytes = work["prefill_work"](tokens)
        least += max(flops / peaks["flops_per_s"],
                     nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
