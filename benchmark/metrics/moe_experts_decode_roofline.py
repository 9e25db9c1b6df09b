"""The expert blocks of the decode step against their bandwidth roofline:
the least seconds to read the weights of the routed experts that met a
token (`moe_experts_touched`, which the program counts on the device over
the window's decode iterations and expert blocks, times one expert's two
matrices) and the shared experts' once an iteration, over the peak
bandwidth, as a share of the seconds of the operations under the scope
`mlp/moe` in the decode program (`jit__fused_step_fn`) plus that program's
`copy*` operations without metadata: the compiler fetches the shared
expert's matrices into fast memory with asynchronous copies that carry no
scope, and without them the scoped products would be timed without their
reads. Those copies move the Mamba-2 weights too, so the share reads low
rather than high. Not every expert held an iteration: an expert no token
was routed to need not be read."""
from benchmark import nemotron_trace, yardstick


def read(run):
    found = nemotron_trace.summary()
    work = run["work"].get("moe")
    if not found or not work or not work.get("experts_touched"):
        return None
    seconds = found["moe_program_s"].get(nemotron_trace.DECODE_PROGRAM)
    if not seconds:
        return None
    seconds += found["bare_copy_s"].get(nemotron_trace.DECODE_PROGRAM, 0.0)
    nbytes = (work["experts_touched"] * work["expert_bytes"]
              + work["iterations"] * work["shared_bytes"])
    bw = yardstick.peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * nbytes / bw / seconds
