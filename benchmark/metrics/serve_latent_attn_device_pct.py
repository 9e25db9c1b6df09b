"""Seconds of device operations whose `op_name` path holds
`attention/latent` (a latent-attention layer's projections, absorptions,
the absorbed kernel and the row's append in the decode program; its
projections, the expansion, the flash forward and the rows' append in the
prefill program) as a share of the seconds of all device operations of the
traced serving window."""
from benchmark import latent_trace


def read(run):
    found = latent_trace.summary()
    if not found or not found["device_op_s"]:
        return None
    return 100.0 * sum(found["latent_s"].values()) / found["device_op_s"]
