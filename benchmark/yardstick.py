"""The yardstick: peaks, order statistics and seed folding.

Kept with the benchmark, not imported from the program, so that no PR that
claims a gain can move what its gain is measured against.
"""
from __future__ import annotations

import math
from typing import Sequence

# Published peaks of one chip, keyed by jax's `device_kind`.
# Source: Google Cloud documentation, "TPU v5e" system architecture:
# 197 TFLOP/s bf16, 819 GB/s HBM.
# A device that is not in the table is an error, never a default.
PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}: add a row "
            f"to benchmark/yardstick.PEAKS with its source") from None


def quantile(values: Sequence[float], q: float) -> float:
    """The q-quantile (0..1) by linear interpolation between order
    statistics (numpy's default 'linear' method), over ALL values given."""
    if not values:
        raise ValueError("quantile of no values")
    xs = sorted(values)
    pos = q * (len(xs) - 1)
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values: Sequence[float]) -> float:
    return quantile(values, 0.5)


def p95_ms(seconds: Sequence[float]):
    """95th percentile of ALL the samples, in ms; None of no samples."""
    return 1e3 * quantile(seconds, 0.95) if seconds else None


def median_ms(seconds: Sequence[float]):
    return 1e3 * median(seconds) if seconds else None


def fold_seed(seed: int) -> int:
    """Any whole number (the driver's seeds pass 2**31) -> a seed that
    32 signed bits hold, for generators that want one."""
    return int(seed) % (2 ** 31 - 1)
