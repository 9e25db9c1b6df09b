"""Seconds of device operations whose `op_name` path holds the scope `mtp`
(the multi-token-prediction module: its projection, its block's attention,
router, experts and shared expert, and its logits, in the decode and the
prefill program) as a share of the seconds of all device operations of the
traced serving window: what drafting costs beside the model itself, the
second row of the verify step not counted."""
from benchmark import mtp_trace


def read(run):
    found = mtp_trace.summary()
    if not found or not found["device_op_s"]:
        return None
    return 100.0 * sum(found["mtp_s"].values()) / found["device_op_s"]
