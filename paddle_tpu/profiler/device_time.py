"""Per-op device-time attribution for host spans.

PR 2's op spans measure HOST dispatch latency (enqueue, not execution) —
on TPU the async dispatch returns in microseconds while the op runs on the
chip for milliseconds, so host spans alone cannot separate "dispatch-bound"
from "device-bound". Two attribution modes, recorded alongside each span:

* ``estimate`` (default, on a device with published peaks): a roofline
  bound from the cost model — max(flops / peak_flops, bytes / peak_hbm_bw) for
  the span's op. Clearly labeled an ESTIMATE: cost-analysis numbers are
  cache-oblivious upper bounds, the same provenance bench.py already
  documents for hbm_gb_per_step.
* ``measured`` (`PADDLE_TPU_DEVICE_TIME=sync`): block_until_ready after
  each traced op, so the span's device time is the wall until device
  completion. This SERIALIZES the async dispatch pipeline — a profiling
  mode, never the default (the reference pays the same price for
  `nvprof --sync`-style tracing).

The full-fidelity third mode lives in `profiler/xplane.py`: a bounded
`jax.profiler` capture session whose parsed trace is correlated back onto
host spans (`device_src="xplane"`), replacing the estimate with measured
backend execution time wherever the correlation lands.

Peaks come from ONE table, `PEAKS`, keyed by the `device_kind` string jax
reports, each row with its source. A device that is not in the table has no
roofline: `device_peaks` raises `UnknownDeviceError`, and the estimator
attributes nothing (a CPU run gets no invented "device time").
"""
from __future__ import annotations

import os
from typing import Dict, List, NamedTuple, Optional, Tuple

__all__ = ["sync_mode", "estimate_ns", "attribute", "split_rows",
           "PEAKS", "Peaks", "UnknownDeviceError", "device_peaks",
           "platform_peaks", "reset_peaks"]


class Peaks(NamedTuple):
    bf16_flops: float        # dense bf16 matmul FLOP/s of one chip
    hbm_bytes_per_s: float   # HBM bandwidth of one chip
    source: str


#: device_kind (as `jax.devices()[0].device_kind` prints it) -> published
#: peaks of ONE chip. Add a row, with its source, before benchmarking on a
#: new part; there is no default.
PEAKS: Dict[str, Peaks] = {
    "TPU v5 lite": Peaks(
        197e12, 819e9,
        "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, 16 GB "
        "HBM2e at 819 GB/s per chip"),
}


class UnknownDeviceError(LookupError):
    """No published peaks for this device_kind: a utilization or a roofline
    share against it would be a made-up number."""


def device_peaks(kind: Optional[str] = None) -> Peaks:
    """Peaks of `kind` (default: this process's first device)."""
    if kind is None:
        import jax
        kind = jax.devices()[0].device_kind
    try:
        return PEAKS[kind]
    except KeyError:
        raise UnknownDeviceError(
            f"no published peaks for device_kind {kind!r} (known: "
            f"{sorted(PEAKS)}); add a row with its source to "
            f"paddle_tpu.profiler.device_time.PEAKS") from None


# (platform, Peaks-or-None) of this process's device, probed once: a
# process cannot change backends (tests that patch PEAKS call reset_peaks)
_peaks_cache: Optional[Tuple[str, Optional[Peaks]]] = None


def _probe() -> Tuple[str, Optional[Peaks]]:
    global _peaks_cache
    if _peaks_cache is None:
        import jax
        d = jax.devices()[0]
        _peaks_cache = (d.platform, PEAKS.get(d.device_kind))
    return _peaks_cache


def platform_peaks() -> Tuple[str, float, float]:
    """(platform, peak_flops/s, peak_bytes/s) used by the estimator;
    raises `UnknownDeviceError` off the table."""
    plat, peaks = _probe()
    if peaks is None:
        peaks = device_peaks()  # raises, naming the kind
    return plat, peaks.bf16_flops, peaks.hbm_bytes_per_s


def reset_peaks():
    """Drop the cached device probe — for tests that patch `PEAKS` or the
    backend."""
    global _peaks_cache
    _peaks_cache = None


def sync_mode() -> bool:
    """True when PADDLE_TPU_DEVICE_TIME=sync: measure completion instead of
    estimating (serializes dispatch — profiling runs only)."""
    return os.environ.get("PADDLE_TPU_DEVICE_TIME", "").lower() == "sync"


def estimate_ns(flops: float, nbytes: float) -> int:
    """Roofline device-time estimate in ns: the op is bound by compute or
    memory, whichever is slower at the platform's peaks."""
    _, peak_flops, peak_bw = platform_peaks()
    sec = max((flops or 0.0) / peak_flops, (nbytes or 0.0) / peak_bw)
    return int(sec * 1e9)


def attribute(outs, flops: float, nbytes: float,
              start_ns: int) -> Tuple[Optional[int], Optional[str]]:
    """(device_ns, source) for one traced op. In sync mode, waits for the
    op's outputs and reports wall-until-completion as "measured"; otherwise
    returns the roofline "estimate" — or (None, None) on a device with no
    published peaks."""
    if sync_mode():
        try:
            import jax
            from .recorder import now_ns
            jax.block_until_ready(outs)
            return max(0, now_ns() - start_ns), "measured"
        except Exception:
            pass  # fall through to the estimate
    if _probe()[1] is None:
        return None, None  # no published peaks: nothing to estimate from
    return estimate_ns(flops, nbytes), "estimate"


#: provenance ranking: a row's src label is its best span's source
#: (xplane = correlated from a real jax.profiler trace, the authoritative
#: mode; measured = sync-mode wall; estimate = roofline bound)
SRC_PRIORITY = {"estimate": 0, "measured": 1, "xplane": 2}


def split_rows(spans) -> List[dict]:
    """Aggregate host-vs-device time per op name from spans that carry
    device attribution — the bench JSON's `device_time.rows` shape,
    sorted by device time desc."""
    acc: Dict[str, dict] = {}
    for s in spans:
        if getattr(s, "device_ns", None) is None:
            continue
        row = acc.setdefault(s.name, {"op": s.name, "calls": 0,
                                      "host_ms": 0.0, "device_ms": 0.0,
                                      "src": s.device_src or "estimate"})
        row["calls"] += 1
        row["host_ms"] += s.dur_ns / 1e6
        row["device_ms"] += s.device_ns / 1e6
        if SRC_PRIORITY.get(s.device_src, 0) > SRC_PRIORITY.get(row["src"], 0):
            row["src"] = s.device_src
    rows = sorted(acc.values(), key=lambda r: -r["device_ms"])
    for r in rows:
        r["host_ms"] = round(r["host_ms"], 4)
        r["device_ms"] = round(r["device_ms"], 4)
    return rows
