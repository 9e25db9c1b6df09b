"""The absorbed decode of the latent-attention layers against its
roofline: the larger of the least seconds to read the latent rows the
layers attended over (`latent_rows`, which the program counts on the
device: the sum over the window's decode iterations, layers and active
lanes of the context, new token included, times one row's 512 + 64
float32 numbers, `families/deepseek_v2.latent_row_bytes`) over the peak
bandwidth and of their least operations (every head's score over a row and
weighted sum over its latent, `latent_decode_flops`) over the peak FLOP/s,
as a share of the seconds of the operations under the scope
`attention/latent/decode` in the decode program (`jit__fused_step_fn`:
the row's append and the kernel's page walk). The peak is the chip's
bfloat16 one and the kernel's products run in six passes: a row's 2.81 ns
of bytes against 6 x 1.41 ns of passes, so the share cannot pass a third."""
from benchmark import latent_trace, yardstick


def read(run):
    found = latent_trace.summary()
    work = run["work"].get("latent")
    if not found or not work or not work.get("rows"):
        return None
    seconds = found["decode_s"].get(latent_trace.DECODE_PROGRAM)
    if not seconds:
        return None
    peaks = yardstick.peaks(run["device"]["kind"])
    least = max(work["rows"] * work["row_bytes"] / peaks["hbm_bytes_per_s"],
                work["decode_flops"] / peaks["flops_per_s"])
    return 100.0 * least / seconds
