"""Seconds of device operations under `mlp/moe/experts/.../permute` (the
expert layers' moves of rows around their grouped products: the sort of
the token x expert assignments, the gather of rows, the return to the
assignments' order and the weighted sum) as a share of the seconds under
`mlp/moe`, in every program of the traced serving window. Where a chip
holds few of the experts, most assignments it sorts and carries back are
absent experts' rows (ROADMAP R4)."""
from benchmark import permute_trace


def read(run):
    found = permute_trace.summary()
    if not found or not found["moe_s"]:
        return None
    return 100.0 * found["permute_s"] / found["moe_s"]
