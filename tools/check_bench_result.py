#!/usr/bin/env python
"""Benchmark regression gate — compare a bench.py JSON result against a
baseline and fail on regressions.

Reference: `tools/check_op_benchmark_result.py` (the op-benchmark CI gate:
parse the PR run and the develop-branch logs, alarm when speed or accuracy
regress past a threshold). Here the artifacts are the driver's
`BENCH_r{N}.json` files / a raw `python bench.py` output line: every config
with a throughput-like metric is compared, and a relative drop beyond
--threshold (default 5%) fails the gate. Higher-is-better metrics only —
step_time_ms is derived from them and would double-count.

The gate also validates the current round's `observability` sections
against the runtime's schema contracts: every `step_records` entry must
pass `profiler.monitor.validate_step_record` and every `events_tail`/
`events` entry must pass `profiler.events.validate_event` (top-level and
per-config blocks alike) — a bench emitting malformed telemetry fails like
a perf regression does.

CLI:
    python tools/check_bench_result.py --baseline BENCH_r04.json \
        --current BENCH_r05.json [--threshold 0.05] [--no-obs-check]
Exit code 0 = no regression, 1 = regression/invalid observability,
2 = unusable inputs.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List, Optional, Tuple

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# throughput metrics, higher is better
_METRICS = ("tokens_per_sec_chip", "samples_per_sec_chip",
            "examples_per_sec")


def _load(path: str) -> dict:
    """Accept a raw `python bench.py` line, a pretty-printed bench object,
    or a driver BENCH_r{N}.json wrapper (bench line embedded in `tail`)."""
    with open(path) as f:
        txt = f.read().strip()
    try:
        doc = json.loads(txt)
        if isinstance(doc, dict):
            if "configs" in doc or "value" in doc:
                return doc
            tail = doc.get("tail")
            if isinstance(tail, str):
                txt = tail  # fall through to line scanning below
    except json.JSONDecodeError:
        pass
    for line in reversed(txt.splitlines()):
        line = line.strip()
        if line.startswith("{") and '"metric"' in line:
            return json.loads(line)
    raise ValueError(f"{path}: no bench JSON object found")


def _configs(doc: dict) -> Dict[str, dict]:
    cfgs = doc.get("configs") or {}
    # a bare headline value still gates the flagship
    if not cfgs and doc.get("value") is not None:
        cfgs = {"headline": {"tokens_per_sec_chip": doc["value"]}}
    return cfgs


def _metric_of(cfg: dict) -> Optional[Tuple[str, float]]:
    for m in _METRICS:
        if isinstance(cfg.get(m), (int, float)):
            return m, float(cfg[m])
    return None


def _config_platform(cfg: dict, doc: dict,
                     assumed: Optional[str]) -> Optional[str]:
    """Declared platform of one config: per-config field, else the
    round-level field, else the caller's --assume-baseline-platform."""
    p = cfg.get("platform") if isinstance(cfg, dict) else None
    if not (isinstance(p, str) and p):
        p = doc.get("platform")
    if not (isinstance(p, str) and p):
        p = assumed
    return p if isinstance(p, str) else None


def _config_scale(cfg: dict) -> str:
    """Declared bench scale of one config; rounds predating the field
    were all full-scale TPU-box runs, so undeclared means "full"."""
    s = cfg.get("scale") if isinstance(cfg, dict) else None
    return s if isinstance(s, str) and s else "full"


def compare(baseline: dict, current: dict, threshold: float,
            baseline_platform: Optional[str] = None):
    """[(config, metric, base, cur, rel_change, status)] — status in
    {"ok", "improved", "regressed", "new", "missing", "incomparable"}.

    A config pair whose two sides DECLARE different platforms (r06+
    records per-config `platform`; older rounds can be stated via
    --assume-baseline-platform, e.g. `tpu` for the r01-r05 driver rounds)
    is "incomparable": a CPU dev-box round vs a TPU round is not a
    regression, and gating on it would either mask real TPU regressions
    or fail every cross-box run. Undeclared-vs-declared still compares
    (best effort), so the gate's behavior on old file pairs is unchanged.
    """
    rows = []
    base_cfgs = _configs(baseline)
    cur_cfgs = _configs(current)
    # round-level platforms identify the BOX: when they are known to
    # differ, every row is incomparable — even an all-CPU config (the
    # wide&deep PS trainer) ran on a different host
    rp_base = _config_platform({}, baseline, baseline_platform)
    rp_cur = _config_platform({}, current, None)
    rounds_differ = bool(rp_base and rp_cur and rp_base != rp_cur)
    for name, bc in base_cfgs.items():
        bm = _metric_of(bc)
        if bm is None:
            continue
        metric, bval = bm
        if bval <= 0:
            # a zero/negative baseline (crashed bench round) can't gate
            # anything — comparing against it would pass any collapse
            rows.append((name, metric, bval, None, None, "missing"))
            continue
        # compare the SAME metric, never a different one the current round
        # happens to also report (units would be incomparable)
        cc = cur_cfgs.get(name) or {}
        cval = cc.get(metric)
        if not isinstance(cval, (int, float)):
            rows.append((name, metric, bval, None, None, "missing"))
            continue
        rel = (cval - bval) / bval
        bp = _config_platform(bc, baseline, baseline_platform)
        cp = _config_platform(cc, current, None)
        # a scale=ci round vs a full-scale baseline (or vice versa) is as
        # incomparable as a different box — the dims/iters differ
        if rounds_differ or (bp and cp and bp != cp) \
                or _config_scale(bc) != _config_scale(cc):
            rows.append((name, metric, bval, float(cval), rel,
                         "incomparable"))
            continue
        status = ("regressed" if rel < -threshold
                  else "improved" if rel > threshold else "ok")
        rows.append((name, metric, bval, float(cval), rel, status))
    for name, cc in cur_cfgs.items():
        if name not in base_cfgs and _metric_of(cc):
            m, v = _metric_of(cc)
            rows.append((name, m, None, v, None, "new"))
    return rows


def _obs_blocks(doc: dict):
    """Yield (where, observability-dict) for the top level and each config."""
    obs = doc.get("observability")
    if isinstance(obs, dict):
        yield "observability", obs
    for name, cfg in (doc.get("configs") or {}).items():
        sub = cfg.get("observability") if isinstance(cfg, dict) else None
        if isinstance(sub, dict):
            yield f"configs.{name}.observability", sub


# the async-checkpoint metric families and the snapshot shape each must
# have when it appears in an observability metrics block
_ASYNC_CKPT_FAMILIES = {
    "checkpoint_async_pending": "gauge",
    "checkpoint_async_bytes": "counter",
    "checkpoint_async_seconds": "histogram",
}


def _validate_async_ckpt_metrics(where: str, metrics: dict) -> List[str]:
    """`checkpoint_async_*` families in a metrics snapshot must be
    well-formed: the right metric kind, numeric non-negative values, and
    (histograms) buckets/sum/count that agree — a bench advertising async
    saves with a garbled hidden-cost histogram fails the gate."""
    problems = []
    for name, fam in metrics.items():
        if not name.startswith("checkpoint_async"):
            continue
        want = _ASYNC_CKPT_FAMILIES.get(name)
        if want is None:
            problems.append(f"{where}.metrics.{name}: unknown "
                            f"checkpoint_async family (expected one of "
                            f"{sorted(_ASYNC_CKPT_FAMILIES)})")
            continue
        if not isinstance(fam, dict) or fam.get("kind") != want:
            problems.append(f"{where}.metrics.{name}: kind "
                            f"{fam.get('kind') if isinstance(fam, dict) else fam!r}"
                            f", expected {want}")
            continue
        values = fam.get("values") or []
        if not isinstance(values, list) or \
                not all(isinstance(v, dict) for v in values):
            problems.append(f"{where}.metrics.{name}.values is not a "
                            f"list of series objects")
            continue
        for i, v in enumerate(values):
            if want == "histogram":
                buckets, cnt = v.get("buckets"), v.get("count")
                if not isinstance(buckets, dict) or \
                        not isinstance(cnt, (int, float)) or \
                        not isinstance(v.get("sum"), (int, float)):
                    problems.append(f"{where}.metrics.{name}[{i}]: "
                                    f"histogram needs buckets/sum/count")
                elif buckets.get("+Inf") != cnt or v["sum"] < 0 or cnt < 0:
                    problems.append(
                        f"{where}.metrics.{name}[{i}]: inconsistent "
                        f"histogram (+Inf bucket {buckets.get('+Inf')} != "
                        f"count {cnt}, or negative sum)")
            else:
                val = v.get("value")
                if not isinstance(val, (int, float)) or val < 0:
                    problems.append(f"{where}.metrics.{name}[{i}]: "
                                    f"value {val!r} is not a non-negative "
                                    f"number")
    return problems


# legal provenance labels for device-time rows: roofline estimate, sync-mode
# wall measurement, or xplane-trace correlation (profiler/xplane.py)
_DEVICE_SRCS = ("estimate", "measured", "xplane")


def _validate_device_time(where: str, dt: dict) -> List[str]:
    """An `observability.device_time` block must be rows of per-op
    host-vs-device aggregates whose `src` (and the block `mode`) is a
    known provenance — a bench claiming measured attribution with a
    garbled or unknown source label fails the gate."""
    problems = []
    if not isinstance(dt, dict):
        return [f"{where}.device_time is not an object"]
    mode = dt.get("mode")
    if mode is not None and mode not in _DEVICE_SRCS:
        problems.append(f"{where}.device_time.mode {mode!r} not in "
                        f"{_DEVICE_SRCS}")
    rows = dt.get("rows")
    if rows is None:
        return problems
    if not isinstance(rows, list):
        return problems + [f"{where}.device_time.rows is not a list"]
    for i, r in enumerate(rows):
        if not isinstance(r, dict):
            problems.append(f"{where}.device_time.rows[{i}] is not an "
                            f"object")
            continue
        if not isinstance(r.get("op"), str) or not r.get("op"):
            problems.append(f"{where}.device_time.rows[{i}].op "
                            f"{r.get('op')!r} is not a non-empty string")
        for key in ("calls", "host_ms", "device_ms"):
            v = r.get(key)
            if not isinstance(v, (int, float)) or isinstance(v, bool) \
                    or v < 0:
                problems.append(f"{where}.device_time.rows[{i}].{key} "
                                f"{v!r} is not a non-negative number")
        if r.get("src") not in _DEVICE_SRCS:
            problems.append(f"{where}.device_time.rows[{i}].src "
                            f"{r.get('src')!r} not in {_DEVICE_SRCS}")
    return problems


# training-health + AMP metric families: name -> (kind, required labels,
# non-negative values?). Gauges that can legally go negative (a loss) skip
# the non-negative check; counters never may.
_HEALTH_FAMILIES = {
    "health_loss": ("gauge", (), False),
    "health_grad_norm": ("gauge", (), True),
    "health_update_ratio": ("gauge", (), True),
    "health_layer_grad_norm": ("gauge", ("group",), True),
    "health_nonfinite_total": ("counter", ("src",), True),
    "health_alerts_total": ("counter", ("signal",), True),
    "health_rollback_total": ("counter", (), True),
    "fleet_health_status": ("gauge", ("host",), True),
    "amp_found_inf_total": ("counter", (), True),
    "amp_loss_scale": ("gauge", (), True),
}


def _validate_health_metrics(where: str, metrics: dict) -> List[str]:
    """`health_*` / `amp_*` families must be the documented kind, carry
    their required labels, and hold finite values (counters and norms
    non-negative) — label hygiene for the numerics plane."""
    problems = []
    for name, fam in metrics.items():
        if not (name.startswith("health_") or name.startswith("amp_")
                or name == "fleet_health_status"):
            continue
        spec = _HEALTH_FAMILIES.get(name)
        if spec is None:
            problems.append(f"{where}.metrics.{name}: unknown health/amp "
                            f"family (expected one of "
                            f"{sorted(_HEALTH_FAMILIES)})")
            continue
        kind, req_labels, nonneg = spec
        if not isinstance(fam, dict) or fam.get("kind") != kind:
            problems.append(f"{where}.metrics.{name}: kind "
                            f"{fam.get('kind') if isinstance(fam, dict) else fam!r}"
                            f", expected {kind}")
            continue
        for i, v in enumerate(fam.get("values") or []):
            if not isinstance(v, dict):
                problems.append(f"{where}.metrics.{name}[{i}] is not a "
                                f"series object")
                continue
            val = v.get("value")
            if not isinstance(val, (int, float)) or isinstance(val, bool):
                problems.append(f"{where}.metrics.{name}[{i}]: value "
                                f"{val!r} is not a number")
            elif val != val or val in (float("inf"), float("-inf")):
                problems.append(f"{where}.metrics.{name}[{i}]: value "
                                f"{val!r} is not finite (the plane must "
                                f"keep NaN/Inf out of gauges)")
            elif nonneg and val < 0:
                problems.append(f"{where}.metrics.{name}[{i}]: value "
                                f"{val!r} is negative")
            labels = v.get("labels") or {}
            for lk in req_labels:
                if lk not in labels:
                    problems.append(f"{where}.metrics.{name}[{i}]: series "
                                    f"missing the {lk!r} label")
    return problems


def _validate_health_block(where: str, h: dict) -> List[str]:
    """The bench `observability.health` block: the sentinel-overhead
    measurement (health on vs off on the GPT-2 config) plus the last
    decoded sentinel stats."""
    problems = []
    if not isinstance(h, dict):
        return [f"{where}.health is not an object"]
    if "error" in h:
        return problems  # a failed probe reports itself; nothing to gate
    for key in ("step_ms_off", "step_ms_on"):
        v = h.get(key)
        if v is not None and (not isinstance(v, (int, float))
                              or isinstance(v, bool) or v < 0):
            problems.append(f"{where}.health.{key} {v!r} is not a "
                            f"non-negative number")
    ov = h.get("overhead_frac")
    if ov is not None and (not isinstance(ov, (int, float))
                           or isinstance(ov, bool) or ov < -1.0):
        problems.append(f"{where}.health.overhead_frac {ov!r} is not a "
                        f"number > -1")
    for key in ("interval", "groups"):
        v = h.get(key)
        if v is not None and (not isinstance(v, int)
                              or isinstance(v, bool) or v < 0):
            problems.append(f"{where}.health.{key} {v!r} is not a "
                            f"non-negative integer")
    sent = h.get("sentinel")
    if sent is not None:
        if not isinstance(sent, dict):
            problems.append(f"{where}.health.sentinel is not an object")
        else:
            nf = sent.get("nonfinite")
            if nf is not None and not isinstance(nf, bool):
                problems.append(f"{where}.health.sentinel.nonfinite "
                                f"{nf!r} is not a bool")
            for key in ("loss", "grad_norm", "update_ratio"):
                v = sent.get(key)
                if v is not None and (not isinstance(v, (int, float))
                                      or isinstance(v, bool)):
                    problems.append(f"{where}.health.sentinel.{key} "
                                    f"{v!r} is not numeric or null")
    return problems


# continuous-batching serving metric families: name -> (kind, required
# labels). All values non-negative. The latency histograms additionally
# carry a `path` label since serving v2 (fused|eager decode) — optional
# here so pre-v2 bench artifacts stay valid, but when present the value
# must be one of _SERVING_PATHS.
_SERVING_FAMILIES = {
    "serving_queue_depth": ("gauge", ("model",)),
    "serving_batch_occupancy": ("gauge", ("model",)),
    "serving_ttft_seconds": ("histogram", ("model",)),
    "serving_tpot_seconds": ("histogram", ("model",)),
    "serving_goodput_tokens_total": ("counter", ("model",)),
    # request-scoped phase histograms (profiler/reqtrace.py)
    "serving_queue_wait_seconds": ("histogram", ("model",)),
    "serving_prefill_seconds": ("histogram", ("model",)),
    "serving_preempt_requeue_seconds": ("histogram", ("model",)),
    # self-healing plane (inference/hotswap.py + the engine watchdog)
    "serving_swap_total": ("counter", ("model", "outcome")),
    "serving_swap_pause_seconds": ("histogram", ("model",)),
    "serving_swap_step": ("gauge", ("model",)),
    "serving_restart_total": ("counter", ("model", "reason")),
    "serving_suspended": ("gauge", ("model",)),
    # disaggregated prefill/decode handoff plane (inference/disagg.py)
    "serving_handoff_depth": ("gauge", ("model",)),
    "serving_handoff_wait_seconds": ("histogram", ("model",)),
    "serving_handoff_bytes_total": ("counter", ("model",)),
    "serving_stage_occupancy": ("gauge", ("model", "stage")),
}

#: legal `stage` label values on serving_stage_occupancy (the two-stage
#: disaggregated pipeline)
_STAGES = ("prefill", "decode")

#: families whose gauge value may legitimately be negative
#: (serving_swap_step is -1 until a hot-swap lands)
_SERVING_SIGNED = ("serving_swap_step",)

#: legal `outcome` label values on serving_swap_total
_SWAP_OUTCOMES = ("applied", "rolled_back", "rejected", "failed")

# serving SLO-plane families (profiler/slo.py): breach excursions and
# the live window p99 per signal
_SLO_FAMILIES = {
    "slo_breaches_total": ("counter", ("model", "signal")),
    "slo_breached": ("gauge", ("model", "signal")),
    "slo_window_p99_seconds": ("gauge", ("model", "signal")),
}

#: legal decode-path label values on the serving latency histograms
_SERVING_PATHS = ("fused", "eager")


def _validate_serving_metrics(where: str, metrics: dict) -> List[str]:
    """`serving_*` families must be the documented kind, carry the
    `model` label, and hold non-negative values (histograms: consistent
    buckets/sum/count) — the serving plane's observability contract."""
    problems = []
    for name, fam in metrics.items():
        if not name.startswith("serving_"):
            continue
        spec = _SERVING_FAMILIES.get(name)
        if spec is None:
            problems.append(f"{where}.metrics.{name}: unknown serving "
                            f"family (expected one of "
                            f"{sorted(_SERVING_FAMILIES)})")
            continue
        kind, req_labels = spec
        if not isinstance(fam, dict) or fam.get("kind") != kind:
            problems.append(
                f"{where}.metrics.{name}: kind "
                f"{fam.get('kind') if isinstance(fam, dict) else fam!r}"
                f", expected {kind}")
            continue
        for i, v in enumerate(fam.get("values") or []):
            if not isinstance(v, dict):
                problems.append(f"{where}.metrics.{name}[{i}] is not a "
                                f"series object")
                continue
            if kind == "histogram":
                buckets, cnt = v.get("buckets"), v.get("count")
                if not isinstance(buckets, dict) or \
                        not isinstance(cnt, (int, float)) or \
                        not isinstance(v.get("sum"), (int, float)):
                    problems.append(f"{where}.metrics.{name}[{i}]: "
                                    f"histogram needs buckets/sum/count")
                elif buckets.get("+Inf") != cnt or v["sum"] < 0 or cnt < 0:
                    problems.append(
                        f"{where}.metrics.{name}[{i}]: inconsistent "
                        f"histogram (+Inf bucket {buckets.get('+Inf')} != "
                        f"count {cnt}, or negative sum)")
            else:
                val = v.get("value")
                if not isinstance(val, (int, float)) or \
                        isinstance(val, bool) or val != val or \
                        (val < 0 and name not in _SERVING_SIGNED):
                    problems.append(f"{where}.metrics.{name}[{i}]: value "
                                    f"{val!r} is not a non-negative number")
            labels = v.get("labels") or {}
            for lk in req_labels:
                if lk not in labels:
                    problems.append(f"{where}.metrics.{name}[{i}]: series "
                                    f"missing the {lk!r} label")
            path = labels.get("path")
            if path is not None and path not in _SERVING_PATHS:
                problems.append(f"{where}.metrics.{name}[{i}]: path label "
                                f"{path!r} is not one of {_SERVING_PATHS}")
            if name == "serving_swap_total" and \
                    labels.get("outcome") not in _SWAP_OUTCOMES:
                problems.append(
                    f"{where}.metrics.{name}[{i}]: outcome label "
                    f"{labels.get('outcome')!r} is not one of "
                    f"{_SWAP_OUTCOMES}")
            if name == "serving_stage_occupancy" and \
                    labels.get("stage") not in _STAGES:
                problems.append(
                    f"{where}.metrics.{name}[{i}]: stage label "
                    f"{labels.get('stage')!r} is not one of {_STAGES}")
    return problems


def _validate_slo_metrics(where: str, metrics: dict) -> List[str]:
    """`slo_*` families must be the documented kind and carry the
    model+signal labels; an unknown `slo_*` family is NAMED (a typo'd
    breach counter silently passing is exactly what this gate exists to
    catch)."""
    problems = []
    for name, fam in metrics.items():
        if not name.startswith("slo_"):
            continue
        spec = _SLO_FAMILIES.get(name)
        if spec is None:
            problems.append(f"{where}.metrics.{name}: unknown slo family "
                            f"(expected one of {sorted(_SLO_FAMILIES)})")
            continue
        kind, req_labels = spec
        if not isinstance(fam, dict) or fam.get("kind") != kind:
            problems.append(
                f"{where}.metrics.{name}: kind "
                f"{fam.get('kind') if isinstance(fam, dict) else fam!r}"
                f", expected {kind}")
            continue
        for i, v in enumerate(fam.get("values") or []):
            if not isinstance(v, dict):
                problems.append(f"{where}.metrics.{name}[{i}] is not a "
                                f"series object")
                continue
            if not _nonneg_num(v.get("value")):
                problems.append(f"{where}.metrics.{name}[{i}]: value "
                                f"{v.get('value')!r} is not a "
                                f"non-negative number")
            labels = v.get("labels") or {}
            for lk in req_labels:
                if lk not in labels:
                    problems.append(f"{where}.metrics.{name}[{i}]: series "
                                    f"missing the {lk!r} label")
    return problems


def _finite_nonneg(v) -> bool:
    return _nonneg_num(v) and v != float("inf")


_TRACE_PHASES = ("queued", "prefill", "decode", "preempted", "complete",
                 "failed")


def _validate_trace(where: str, t: dict) -> List[str]:
    """One request-trace record: ids, non-negative per-phase durations
    over the known phase names, spans with end >= start."""
    problems = []
    if not isinstance(t, dict):
        return [f"{where} is not a trace object"]
    for key in ("trace_id", "rid"):
        v = t.get(key)
        if not isinstance(v, int) or isinstance(v, bool) or v < 1:
            problems.append(f"{where}.{key}: {v!r} is not a positive id")
    for key in ("preemptions", "decode_iterations", "decode_tokens"):
        if key in t and not _nonneg_num(t.get(key)):
            problems.append(f"{where}.{key}: {t.get(key)!r} is not a "
                            f"non-negative number")
    e2e = t.get("e2e_s")
    if e2e is not None and not _finite_nonneg(e2e):
        problems.append(f"{where}.e2e_s: {e2e!r} is not finite "
                        f"non-negative")
    phases = t.get("phases")
    if phases is not None:
        if not isinstance(phases, dict):
            problems.append(f"{where}.phases is not an object")
        else:
            for ph, dur in phases.items():
                if ph not in _TRACE_PHASES:
                    problems.append(f"{where}.phases.{ph}: unknown phase "
                                    f"(expected one of {_TRACE_PHASES})")
                if not _finite_nonneg(dur):
                    problems.append(f"{where}.phases.{ph}: duration "
                                    f"{dur!r} is not finite non-negative")
    for i, s in enumerate(t.get("spans") or []):
        if not isinstance(s, dict) or s.get("phase") not in _TRACE_PHASES:
            problems.append(f"{where}.spans[{i}]: bad span/phase")
            continue
        start, end = s.get("start"), s.get("end")
        if end is not None and isinstance(start, (int, float)) \
                and isinstance(end, (int, float)) and end < start:
            problems.append(f"{where}.spans[{i}]: end {end} < start "
                            f"{start}")
    return problems


def _validate_reqtrace_block(where: str, rt: dict) -> List[str]:
    """The bench `observability.reqtrace` block / `/requests` payload:
    live + completed trace lists, each conforming to the trace shape."""
    if not isinstance(rt, dict):
        return [f"{where} is not an object"]
    if "error" in rt:
        return []  # a failed probe reports itself
    problems = []
    for key in ("live", "completed"):
        lst = rt.get(key)
        if lst is None:
            continue
        if not isinstance(lst, list):
            problems.append(f"{where}.{key} is not a list")
            continue
        for i, t in enumerate(lst):
            problems.extend(_validate_trace(f"{where}.{key}[{i}]", t))
    return problems


def _validate_slo_block(where: str, s: dict) -> List[str]:
    """The bench `observability.slo` block / `/slo` payload: per-signal
    window quantiles finite and monotone (p50 <= p95 <= p99), breach
    counts non-negative."""
    if not isinstance(s, dict):
        return [f"{where} is not an object"]
    if "error" in s:
        return []  # a failed probe reports itself
    problems = []
    targets = s.get("targets")
    if targets is not None and not isinstance(targets, dict):
        problems.append(f"{where}.targets is not an object")
    elif targets:
        for sig, t in targets.items():
            if not _finite_nonneg(t):
                problems.append(f"{where}.targets.{sig}: {t!r} is not "
                                f"finite non-negative")
    signals = s.get("signals")
    if signals is not None:
        if not isinstance(signals, dict):
            problems.append(f"{where}.signals is not an object")
        else:
            for sig, qs in signals.items():
                w = f"{where}.signals.{sig}"
                if not isinstance(qs, dict):
                    problems.append(f"{w} is not an object")
                    continue
                if not _nonneg_num(qs.get("count")):
                    problems.append(f"{w}.count: {qs.get('count')!r} is "
                                    f"not a non-negative number")
                vals = [qs.get(q) for q in ("p50", "p95", "p99")]
                if any(v is not None for v in vals):
                    if not all(_finite_nonneg(v) for v in vals):
                        problems.append(f"{w}: quantiles {vals!r} must "
                                        f"all be finite non-negative")
                    elif not (vals[0] <= vals[1] <= vals[2]):
                        problems.append(f"{w}: quantiles not monotone "
                                        f"(p50 {vals[0]} <= p95 {vals[1]} "
                                        f"<= p99 {vals[2]} violated)")
    stats = s.get("stats")
    if stats is not None:
        if not isinstance(stats, dict):
            problems.append(f"{where}.stats is not an object")
        else:
            for key in ("breaches", "recoveries", "observations"):
                if key in stats and not _nonneg_num(stats.get(key)):
                    problems.append(f"{where}.stats.{key}: "
                                    f"{stats.get(key)!r} is not a "
                                    f"non-negative count")
    breached = s.get("breached")
    if breached is not None and not isinstance(breached, dict):
        problems.append(f"{where}.breached is not an object")
    return problems


def _validate_decode_block(where: str, cfg: dict) -> List[str]:
    """The `gpt2_decode` bench config: serving percentiles (TTFT/TPOT),
    goodput fields, and the paged-vs-dense A/B rows — a decode round
    claiming super-linear speedup with malformed numbers fails the
    gate like a perf regression does."""
    problems = []
    srv = cfg.get("serving")
    if srv is not None:
        if not isinstance(srv, dict):
            problems.append(f"{where}.serving is not an object")
        else:
            for fam in ("ttft_s", "tpot_s"):
                blk = srv.get(fam)
                if blk is None:
                    problems.append(f"{where}.serving.{fam} is missing")
                    continue
                if not isinstance(blk, dict):
                    problems.append(f"{where}.serving.{fam} is not an "
                                    f"object")
                    continue
                for q in ("p50", "p99"):
                    v = blk.get(q)
                    if v is not None and not _nonneg_num(v):
                        problems.append(f"{where}.serving.{fam}.{q} {v!r} "
                                        f"is not a non-negative number or "
                                        f"null")
            qw = srv.get("queue_wait_s")  # optional (added with reqtrace)
            if qw is not None:
                if not isinstance(qw, dict):
                    problems.append(f"{where}.serving.queue_wait_s is "
                                    f"not an object")
                else:
                    for q in ("p50", "p99"):
                        v = qw.get(q)
                        if v is not None and not _nonneg_num(v):
                            problems.append(
                                f"{where}.serving.queue_wait_s.{q} {v!r} "
                                f"is not a non-negative number or null")
            ws = srv.get("wall_s")
            if ws is not None and not _nonneg_num(ws):
                problems.append(f"{where}.serving.wall_s {ws!r} is not a "
                                f"non-negative number")
    for key in ("goodput_tokens", "streams", "completed", "preemptions"):
        v = cfg.get(key)
        if v is not None and (not isinstance(v, int)
                              or isinstance(v, bool) or v < 0):
            problems.append(f"{where}.{key} {v!r} is not a non-negative "
                            f"integer")
    for key in ("tokens_per_sec_chip", "decode_tokens_per_sec",
                "batch_occupancy_mean"):
        v = cfg.get(key)
        if v is not None and not _nonneg_num(v):
            problems.append(f"{where}.{key} {v!r} is not a non-negative "
                            f"number or null")
    ab = cfg.get("paged_vs_dense")
    if ab is not None:
        if not isinstance(ab, dict):
            problems.append(f"{where}.paged_vs_dense is not an object")
        elif "error" not in ab:  # a failed probe reports itself
            rows = ab.get("rows")
            if not isinstance(rows, list) or not rows:
                problems.append(f"{where}.paged_vs_dense.rows is not a "
                                f"non-empty list")
            else:
                for i, r in enumerate(rows):
                    if not isinstance(r, dict):
                        problems.append(
                            f"{where}.paged_vs_dense.rows[{i}] is not an "
                            f"object")
                        continue
                    c = r.get("ctx")
                    if not isinstance(c, int) or isinstance(c, bool) \
                            or c <= 0:
                        problems.append(
                            f"{where}.paged_vs_dense.rows[{i}].ctx {c!r} "
                            f"is not a positive integer")
                    for key in ("paged_ms_per_token",
                                "dense_ms_per_token"):
                        if not _nonneg_num(r.get(key)):
                            problems.append(
                                f"{where}.paged_vs_dense.rows[{i}].{key} "
                                f"{r.get(key)!r} is not a non-negative "
                                f"number")
            for key in ("paged_growth", "dense_growth",
                        "speedup_at_max_ctx"):
                v = ab.get(key)
                if v is not None and not _nonneg_num(v):
                    problems.append(f"{where}.paged_vs_dense.{key} {v!r} "
                                    f"is not a non-negative number or null")
    fve = cfg.get("fused_vs_eager")
    if fve is not None:
        if not isinstance(fve, dict):
            problems.append(f"{where}.fused_vs_eager is not an object")
        elif "error" not in fve:  # a failed probe reports itself
            for key in ("fused_ms_per_token", "eager_ms_per_token"):
                if not _nonneg_num(fve.get(key)):
                    problems.append(f"{where}.fused_vs_eager.{key} "
                                    f"{fve.get(key)!r} is not a "
                                    f"non-negative number")
            sp = fve.get("speedup")
            if sp is not None and not _nonneg_num(sp):
                problems.append(f"{where}.fused_vs_eager.speedup {sp!r} "
                                f"is not a non-negative number or null")
            # the bit-parity claim: both decode paths MUST emit the same
            # tokens — a fused path that drifts is a correctness bug the
            # gate treats like a regression
            if fve.get("identical_tokens") is not True:
                problems.append(f"{where}.fused_vs_eager.identical_tokens "
                                f"{fve.get('identical_tokens')!r}: fused "
                                f"and eager decode disagreed on tokens")
    shp = cfg.get("shared_prefix")
    if shp is not None:
        if not isinstance(shp, dict):
            problems.append(f"{where}.shared_prefix is not an object")
        elif "error" not in shp:
            for side in ("on", "off"):
                blk = shp.get(side)
                if not isinstance(blk, dict):
                    problems.append(f"{where}.shared_prefix.{side} is not "
                                    f"an object")
                    continue
                for key in ("min_free_pages", "prefix_hit_tokens",
                            "shared_admissions", "cow_copies",
                            "preemptions", "completed", "leaked_pages"):
                    v = blk.get(key)
                    if not isinstance(v, int) or isinstance(v, bool) \
                            or v < 0:
                        problems.append(
                            f"{where}.shared_prefix.{side}.{key} {v!r} is "
                            f"not a non-negative integer")
                # a leaked page means a refcount failed to return to zero
                if blk.get("leaked_pages") not in (None, 0):
                    problems.append(
                        f"{where}.shared_prefix.{side}.leaked_pages "
                        f"{blk.get('leaked_pages')!r}: allocator held "
                        f"pages after all requests finished")
            off = shp.get("off")
            if isinstance(off, dict) and off.get("prefix_hit_tokens"):
                problems.append(
                    f"{where}.shared_prefix.off.prefix_hit_tokens "
                    f"{off.get('prefix_hit_tokens')!r}: sharing disabled "
                    f"but prefix hits were recorded")
    tpd = cfg.get("tp_decode")
    if tpd is not None:
        if not isinstance(tpd, dict):
            problems.append(f"{where}.tp_decode is not an object")
        elif "error" not in tpd and "skipped" not in tpd:
            for key in ("single_ms_per_token", "tp_ms_per_token"):
                if not _nonneg_num(tpd.get(key)):
                    problems.append(f"{where}.tp_decode.{key} "
                                    f"{tpd.get(key)!r} is not a "
                                    f"non-negative number")
            deg = tpd.get("tp_degree")
            if not isinstance(deg, int) or isinstance(deg, bool) \
                    or deg < 2:
                problems.append(f"{where}.tp_decode.tp_degree {deg!r} is "
                                f"not an integer >= 2")
            ratio = tpd.get("tpot_ratio")
            if ratio is not None and not _nonneg_num(ratio):
                problems.append(f"{where}.tp_decode.tpot_ratio {ratio!r} "
                                f"is not a non-negative number or null")
            # the bit-parity claim: head-sharding is a LAYOUT change —
            # TP tokens drifting from single-chip is a correctness bug
            if tpd.get("identical_tokens") is not True:
                problems.append(f"{where}.tp_decode.identical_tokens "
                                f"{tpd.get('identical_tokens')!r}: TP and "
                                f"single-chip decode disagreed on tokens")
            link = tpd.get("collective_bytes_by_link")
            if isinstance(link, dict) and "error" not in link:
                for lk in ("ici", "dcn"):
                    if not _nonneg_num(link.get(lk)):
                        problems.append(
                            f"{where}.tp_decode.collective_bytes_by_link"
                            f".{lk} {link.get(lk)!r} is not a "
                            f"non-negative number")
    dis = cfg.get("disagg")
    if dis is not None:
        if not isinstance(dis, dict):
            problems.append(f"{where}.disagg is not an object")
        elif "error" not in dis and "skipped" not in dis:
            for key in ("colocated_ms_per_token", "disagg_ms_per_token"):
                if not _nonneg_num(dis.get(key)):
                    problems.append(f"{where}.disagg.{key} "
                                    f"{dis.get(key)!r} is not a "
                                    f"non-negative number")
            for key in ("handoffs", "prefill_workers"):
                v = dis.get(key)
                if not isinstance(v, int) or isinstance(v, bool) or v < 1:
                    problems.append(f"{where}.disagg.{key} {v!r} is not a "
                                    f"positive integer")
            # the disaggregation claim itself: EVERY prefill ran on a
            # prefill worker — a nonzero decode-side prefill count means
            # the stages were never actually split
            if dis.get("decode_prefills") != 0:
                problems.append(f"{where}.disagg.decode_prefills "
                                f"{dis.get('decode_prefills')!r}: the "
                                f"decode engine ran prefills itself")
            if dis.get("identical_tokens") is not True:
                problems.append(f"{where}.disagg.identical_tokens "
                                f"{dis.get('identical_tokens')!r}: "
                                f"disagg and co-located decode disagreed "
                                f"on tokens")
    return problems


# fleet-controller metric families: name -> (kind, required labels).
_CONTROLLER_FAMILIES = {
    "controller_decisions_total": ("counter", ("policy", "outcome")),
    "controller_evictions_total": ("counter", ("host",)),
    "controller_rollbacks_total": ("counter", ("host",)),
    "controller_readmissions_total": ("counter", ("host",)),
    "controller_relaunch_to_first_step_seconds": ("gauge", ("policy",)),
    # HA control plane: election term, takeovers, fenced stale actuations
    "controller_leader_term": ("gauge", ()),
    "controller_takeovers_total": ("counter", ("reason",)),
    "controller_fenced_total": ("counter", ("policy",)),
}

#: legal controller_decision outcomes (the decision contract);
#: `fenced` = the actuation carried a stale leadership term and was
#: rejected at the actuation boundary
_CONTROLLER_OUTCOMES = ("applied", "dry_run", "failed", "fenced")


def _validate_controller_metrics(where: str, metrics: dict) -> List[str]:
    """`controller_*` families must be the documented kind, carry their
    required labels, and hold non-negative values — the self-driving
    fleet's observability contract."""
    problems = []
    for name, fam in metrics.items():
        if not name.startswith("controller_"):
            continue
        spec = _CONTROLLER_FAMILIES.get(name)
        if spec is None:
            problems.append(f"{where}.metrics.{name}: unknown controller "
                            f"family (expected one of "
                            f"{sorted(_CONTROLLER_FAMILIES)})")
            continue
        kind, req_labels = spec
        if not isinstance(fam, dict) or fam.get("kind") != kind:
            problems.append(
                f"{where}.metrics.{name}: kind "
                f"{fam.get('kind') if isinstance(fam, dict) else fam!r}"
                f", expected {kind}")
            continue
        for i, v in enumerate(fam.get("values") or []):
            if not isinstance(v, dict):
                problems.append(f"{where}.metrics.{name}[{i}] is not a "
                                f"series object")
                continue
            val = v.get("value")
            if not isinstance(val, (int, float)) or isinstance(val, bool) \
                    or val != val or val < 0:
                problems.append(f"{where}.metrics.{name}[{i}]: value "
                                f"{val!r} is not a non-negative number")
            labels = v.get("labels") or {}
            for lk in req_labels:
                if lk not in labels:
                    problems.append(f"{where}.metrics.{name}[{i}]: series "
                                    f"missing the {lk!r} label")
            if name == "controller_decisions_total" \
                    and labels.get("outcome") not in _CONTROLLER_OUTCOMES:
                problems.append(
                    f"{where}.metrics.{name}[{i}]: outcome "
                    f"{labels.get('outcome')!r} not in "
                    f"{_CONTROLLER_OUTCOMES}")
    return problems


# disaggregated-serving fault-tolerance families: name -> (kind,
# required labels)
_DISAGG_FAMILIES = {
    "disagg_worker_restarts_total": ("counter", ()),
    "disagg_requeue_total": ("counter", ("reason",)),
}


def _validate_disagg_metrics(where: str, metrics: dict) -> List[str]:
    """`disagg_*` families must be the documented kind, carry their
    required labels, and hold non-negative values — the disaggregated
    pipeline's fault-tolerance observability contract."""
    problems = []
    for name, fam in metrics.items():
        if not name.startswith("disagg_"):
            continue
        spec = _DISAGG_FAMILIES.get(name)
        if spec is None:
            problems.append(f"{where}.metrics.{name}: unknown disagg "
                            f"family (expected one of "
                            f"{sorted(_DISAGG_FAMILIES)})")
            continue
        kind, req_labels = spec
        if not isinstance(fam, dict) or fam.get("kind") != kind:
            problems.append(
                f"{where}.metrics.{name}: kind "
                f"{fam.get('kind') if isinstance(fam, dict) else fam!r}"
                f", expected {kind}")
            continue
        for i, v in enumerate(fam.get("values") or []):
            if not isinstance(v, dict):
                problems.append(f"{where}.metrics.{name}[{i}] is not a "
                                f"series object")
                continue
            val = v.get("value")
            if not isinstance(val, (int, float)) or isinstance(val, bool) \
                    or val != val or val < 0:
                problems.append(f"{where}.metrics.{name}[{i}]: value "
                                f"{val!r} is not a non-negative number")
            labels = v.get("labels") or {}
            for lk in req_labels:
                if lk not in labels:
                    problems.append(f"{where}.metrics.{name}[{i}]: series "
                                    f"missing the {lk!r} label")
    return problems


def _validate_controller_decision(where: str, ev: dict) -> List[str]:
    """Beyond the generic event schema, a `controller_decision` event
    must carry the decision contract: policy, action, a legal outcome,
    and a decision id — the fields operators and tooling key on."""
    problems = []
    if not isinstance(ev.get("policy"), str) or not ev.get("policy"):
        problems.append(f"{where}: 'policy' must be a non-empty string, "
                        f"got {ev.get('policy')!r}")
    if not isinstance(ev.get("action"), str) or not ev.get("action"):
        problems.append(f"{where}: 'action' must be a non-empty string, "
                        f"got {ev.get('action')!r}")
    if ev.get("outcome") not in _CONTROLLER_OUTCOMES:
        problems.append(f"{where}: 'outcome' {ev.get('outcome')!r} not in "
                        f"{_CONTROLLER_OUTCOMES}")
    dec = ev.get("decision")
    if not isinstance(dec, int) or isinstance(dec, bool) or dec < 1:
        problems.append(f"{where}: 'decision' must be a positive integer "
                        f"id, got {dec!r}")
    if "evidence" in ev and not isinstance(ev["evidence"], dict):
        problems.append(f"{where}: 'evidence' must be an object, got "
                        f"{type(ev['evidence']).__name__}")
    return problems


def _nonneg_num(v) -> bool:
    return (isinstance(v, (int, float)) and not isinstance(v, bool)
            and v == v and v >= 0)


def _validate_segments(where: str, seg: dict) -> List[str]:
    """A `profile.segments` block (measured per-segment device-time
    attribution from profiler/xplane.segment_breakdown): every segment
    row needs non-negative device_ms / events and a frac in [0, 1] (or
    null on an empty trace); attributed_frac likewise. A bench claiming
    measured segment attribution with malformed rows fails the gate."""
    problems = []
    if not isinstance(seg, dict):
        return [f"{where} is not an object"]
    rows = seg.get("segments")
    if rows is None or not isinstance(rows, dict):
        return [f"{where}.segments is not an object"]
    for name, r in rows.items():
        if not isinstance(r, dict):
            problems.append(f"{where}.segments[{name!r}] is not an object")
            continue
        if not _nonneg_num(r.get("device_ms")):
            problems.append(f"{where}.segments[{name!r}].device_ms "
                            f"{r.get('device_ms')!r} is not a non-negative "
                            f"number")
        ev = r.get("events")
        if not isinstance(ev, int) or isinstance(ev, bool) or ev < 0:
            problems.append(f"{where}.segments[{name!r}].events {ev!r} is "
                            f"not a non-negative integer")
        fr = r.get("frac")
        if fr is not None and (not _nonneg_num(fr) or fr > 1.0 + 1e-9):
            problems.append(f"{where}.segments[{name!r}].frac {fr!r} is "
                            f"not in [0, 1] or null")
    if not _nonneg_num(seg.get("total_device_ms")):
        problems.append(f"{where}.total_device_ms "
                        f"{seg.get('total_device_ms')!r} is not a "
                        f"non-negative number")
    af = seg.get("attributed_frac")
    if af is not None and (not _nonneg_num(af) or af > 1.0 + 1e-9):
        problems.append(f"{where}.attributed_frac {af!r} is not in "
                        f"[0, 1] or null")
    return problems


def _validate_conv_fusion(where: str, cf: dict) -> List[str]:
    """A resnet `conv_fusion` A/B probe block: on/off probe times and
    cost-analysis HBM bytes must be non-negative numbers (or null), the
    engagement flags bools, and kernel_stats non-negative counters."""
    problems = []
    if not isinstance(cf, dict):
        return [f"{where} is not an object"]
    if "error" in cf:
        return problems  # a failed probe reports itself; nothing to gate
    for key in ("enabled", "engaged"):
        v = cf.get(key)
        if v is not None and not isinstance(v, bool):
            problems.append(f"{where}.{key} {v!r} is not a bool")
    for key in ("probe_ms_on", "probe_ms_off", "speedup_vs_off",
                "hbm_gb_per_step_on", "hbm_gb_per_step_off"):
        v = cf.get(key)
        if v is not None and not _nonneg_num(v):
            problems.append(f"{where}.{key} {v!r} is not a non-negative "
                            f"number or null")
    pct = cf.get("hbm_pct_saved")
    if pct is not None and (not isinstance(pct, (int, float))
                            or isinstance(pct, bool) or pct != pct
                            or pct > 100.0):
        problems.append(f"{where}.hbm_pct_saved {pct!r} is not a number "
                        f"<= 100 or null")
    ks = cf.get("kernel_stats")
    if ks is not None:
        if not isinstance(ks, dict):
            problems.append(f"{where}.kernel_stats is not an object")
        else:
            for k, v in ks.items():
                if not isinstance(v, int) or isinstance(v, bool) or v < 0:
                    problems.append(f"{where}.kernel_stats[{k!r}] {v!r} is "
                                    f"not a non-negative integer")
    mab = cf.get("micro_ab")
    if mab is not None:
        if not isinstance(mab, dict):
            problems.append(f"{where}.micro_ab is not an object")
        else:
            for i, r in enumerate(mab.get("rows") or []):
                if not isinstance(r, dict):
                    problems.append(f"{where}.micro_ab.rows[{i}] is not "
                                    f"an object")
                    continue
                if not isinstance(r.get("shape"), str):
                    problems.append(f"{where}.micro_ab.rows[{i}].shape "
                                    f"{r.get('shape')!r} is not a string")
                for key in ("composed_gb_cost_analysis", "fused_gb_model"):
                    if not _nonneg_num(r.get(key)):
                        problems.append(
                            f"{where}.micro_ab.rows[{i}].{key} "
                            f"{r.get(key)!r} is not a non-negative number")
                ps = r.get("pct_saved")
                if not isinstance(ps, (int, float)) or isinstance(ps, bool)\
                        or ps != ps or ps > 100.0:
                    problems.append(f"{where}.micro_ab.rows[{i}].pct_saved "
                                    f"{ps!r} is not a number <= 100")
    return problems


def _validate_device_memory_metrics(where: str, metrics: dict) -> List[str]:
    """`device_memory_*` families must be gauges of non-negative values
    whose series carry the `device` label."""
    problems = []
    for name, fam in metrics.items():
        if not name.startswith("device_memory_"):
            continue
        if not isinstance(fam, dict) or fam.get("kind") != "gauge":
            problems.append(f"{where}.metrics.{name}: kind "
                            f"{fam.get('kind') if isinstance(fam, dict) else fam!r}"
                            f", expected gauge")
            continue
        for i, v in enumerate(fam.get("values") or []):
            if not isinstance(v, dict):
                problems.append(f"{where}.metrics.{name}[{i}] is not a "
                                f"series object")
                continue
            val = v.get("value")
            if not isinstance(val, (int, float)) or val < 0:
                problems.append(f"{where}.metrics.{name}[{i}]: value "
                                f"{val!r} is not a non-negative number")
            if "device" not in (v.get("labels") or {}):
                problems.append(f"{where}.metrics.{name}[{i}]: series "
                                f"missing the 'device' label")
    return problems


_AUDIT_SEVERITIES = ("info", "low", "medium", "high")
_AUDIT_CHECKS = ("donation", "dtype", "sharding", "bloat")


def _validate_program_audit(where: str, pa) -> List[str]:
    """A config's `program_audit` block: aggregate severity counts, a
    `clean_high` verdict consistent with them, and per-report findings
    whose check/severity are legal — the static auditor's bench
    contract. An `error` block (audit failed on this box) is legal but
    must name the error."""
    problems = []
    if not isinstance(pa, dict):
        return [f"{where}.program_audit is not an object"]
    if "error" in pa:
        if not isinstance(pa["error"], str) or not pa["error"]:
            problems.append(f"{where}.program_audit.error must be a "
                            f"non-empty string")
        return problems
    counts = pa.get("counts")
    if not isinstance(counts, dict):
        problems.append(f"{where}.program_audit.counts missing")
        counts = {}
    for sev in _AUDIT_SEVERITIES:
        v = counts.get(sev)
        if not isinstance(v, int) or isinstance(v, bool) or v < 0:
            problems.append(f"{where}.program_audit.counts.{sev}: "
                            f"{v!r} is not a non-negative int")
    ch = pa.get("clean_high")
    if not isinstance(ch, bool):
        problems.append(f"{where}.program_audit.clean_high must be a bool")
    elif isinstance(counts.get("high"), int) and \
            ch != (counts["high"] == 0):
        problems.append(f"{where}.program_audit.clean_high={ch} "
                        f"contradicts counts.high={counts['high']}")
    reports = pa.get("reports")
    if not isinstance(reports, list):
        problems.append(f"{where}.program_audit.reports is not a list")
        return problems
    for i, rep in enumerate(reports):
        if not isinstance(rep, dict):
            problems.append(f"{where}.program_audit.reports[{i}] is not "
                            f"an object")
            continue
        for key in ("name", "entry"):
            if not isinstance(rep.get(key), str) or not rep.get(key):
                problems.append(f"{where}.program_audit.reports[{i}]."
                                f"{key} must be a non-empty string")
        for j, f in enumerate(rep.get("findings") or []):
            loc = f"{where}.program_audit.reports[{i}].findings[{j}]"
            if not isinstance(f, dict):
                problems.append(f"{loc} is not an object")
                continue
            if f.get("check") not in _AUDIT_CHECKS:
                problems.append(f"{loc}.check {f.get('check')!r} not in "
                                f"{_AUDIT_CHECKS}")
            if f.get("severity") not in _AUDIT_SEVERITIES:
                problems.append(f"{loc}.severity {f.get('severity')!r} "
                                f"not in {_AUDIT_SEVERITIES}")
            for key in ("code", "message"):
                if not isinstance(f.get(key), str) or not f.get(key):
                    problems.append(f"{loc}.{key} must be a non-empty "
                                    f"string")
    return problems


# static-analysis metric families: name -> (kind, required labels)
_ANALYSIS_FAMILIES = {
    "analysis_findings_total": ("counter", ("check", "severity")),
    "analysis_audits_total": ("counter", ("entry",)),
}


def _validate_analysis_metrics(where: str, metrics: dict) -> List[str]:
    """`analysis_*` families must be counters with non-negative values,
    check/severity labels drawn from the auditor's legal sets, and a
    non-empty entry label."""
    problems = []
    for name, fam in metrics.items():
        if not name.startswith("analysis_"):
            continue
        spec = _ANALYSIS_FAMILIES.get(name)
        if spec is None:
            problems.append(f"{where}.metrics.{name}: unknown analysis "
                            f"family (expected one of "
                            f"{sorted(_ANALYSIS_FAMILIES)})")
            continue
        kind, req_labels = spec
        if not isinstance(fam, dict) or fam.get("kind") != kind:
            problems.append(
                f"{where}.metrics.{name}: kind "
                f"{fam.get('kind') if isinstance(fam, dict) else fam!r}, "
                f"expected {kind}")
            continue
        values = fam.get("values") or []
        if not isinstance(values, list):
            problems.append(f"{where}.metrics.{name}.values is not a list")
            continue
        for i, v in enumerate(values):
            if not isinstance(v, dict):
                problems.append(f"{where}.metrics.{name}[{i}] is not a "
                                f"series object")
                continue
            val = v.get("value")
            if not isinstance(val, (int, float)) or \
                    isinstance(val, bool) or val != val or val < 0:
                problems.append(f"{where}.metrics.{name}[{i}]: value "
                                f"{val!r} is not a non-negative number")
            labels = v.get("labels") or {}
            for lk in req_labels:
                if lk not in labels:
                    problems.append(f"{where}.metrics.{name}[{i}]: series "
                                    f"missing the {lk!r} label")
            if "severity" in labels and \
                    labels["severity"] not in _AUDIT_SEVERITIES:
                problems.append(f"{where}.metrics.{name}[{i}]: severity "
                                f"label {labels['severity']!r} not in "
                                f"{_AUDIT_SEVERITIES}")
            if "check" in labels and labels["check"] not in _AUDIT_CHECKS:
                problems.append(f"{where}.metrics.{name}[{i}]: check "
                                f"label {labels['check']!r} not in "
                                f"{_AUDIT_CHECKS}")
    return problems


def validate_observability(doc: dict) -> List[str]:
    """Schema problems in the document's observability sections (empty =
    valid). step_records must conform to the step-record contract,
    events/events_tail to the event contract (`controller_decision`
    events additionally to the decision contract: policy/action/legal
    outcome/decision id), `checkpoint_async_*` / `device_memory_*` /
    `health_*` / `amp_*` / `controller_*` / `disagg_*` /
    `serving_*` / `slo_*` / `analysis_*` metric families to their
    kind/label/shape
    contracts, `reqtrace`/`slo` observability blocks to the request-trace
    and SLO-window shapes (quantiles finite + monotone p50<=p95<=p99,
    breach counts non-negative),
    per-config `program_audit` blocks to the static-auditor contract
    (severity counts, clean_high verdict, legal check/severity per
    finding), `gpt2_decode`
    configs (a `serving`/`paged_vs_dense` block) to the decode-bench
    contract (TTFT/TPOT percentiles, goodput fields, A/B rows),
    `device_time` blocks to
    the per-op row shape with a known provenance label (estimate /
    measured / xplane) and `health` blocks to the sentinel-overhead
    shape; a missing section is fine (old rounds), a malformed one is
    not."""
    from paddle_tpu.profiler.events import validate_event
    from paddle_tpu.profiler.monitor import validate_step_record
    problems = []
    # per-config `profile`/`conv_fusion` blocks sit beside (not inside)
    # observability
    for name, cfg in (doc.get("configs") or {}).items():
        if not isinstance(cfg, dict):
            continue
        prof = cfg.get("profile")
        if isinstance(prof, dict) and prof.get("segments") is not None:
            problems.extend(_validate_segments(
                f"configs.{name}.profile.segments", prof["segments"]))
        cf = cfg.get("conv_fusion")
        if cf is not None:
            problems.extend(_validate_conv_fusion(
                f"configs.{name}.conv_fusion", cf))
        if cfg.get("serving") is not None \
                or cfg.get("paged_vs_dense") is not None:
            problems.extend(_validate_decode_block(f"configs.{name}", cfg))
        pa = cfg.get("program_audit")
        if pa is not None:
            problems.extend(_validate_program_audit(f"configs.{name}", pa))
    for where, obs in _obs_blocks(doc):
        metrics = obs.get("metrics")
        if isinstance(metrics, dict):
            problems.extend(_validate_async_ckpt_metrics(where, metrics))
            problems.extend(_validate_device_memory_metrics(where, metrics))
            problems.extend(_validate_health_metrics(where, metrics))
            problems.extend(_validate_controller_metrics(where, metrics))
            problems.extend(_validate_disagg_metrics(where, metrics))
            problems.extend(_validate_serving_metrics(where, metrics))
            problems.extend(_validate_slo_metrics(where, metrics))
            problems.extend(_validate_analysis_metrics(where, metrics))
        rt = obs.get("reqtrace")
        if rt is not None:
            problems.extend(_validate_reqtrace_block(f"{where}.reqtrace",
                                                     rt))
        slo_blk = obs.get("slo")
        if slo_blk is not None:
            problems.extend(_validate_slo_block(f"{where}.slo", slo_blk))
        dt = obs.get("device_time")
        if dt is not None:
            problems.extend(_validate_device_time(where, dt))
        h = obs.get("health")
        if h is not None:
            problems.extend(_validate_health_block(where, h))
        recs = obs.get("step_records")
        if recs is not None:
            if not isinstance(recs, list):
                problems.append(f"{where}.step_records is not a list")
            else:
                for i, rec in enumerate(recs):
                    try:
                        validate_step_record(rec)
                    except ValueError as e:
                        problems.append(f"{where}.step_records[{i}]: {e}")
        for key in ("events_tail", "events"):
            evs = obs.get(key)
            if evs is None:
                continue
            if not isinstance(evs, list):
                problems.append(f"{where}.{key} is not a list")
                continue
            for i, ev in enumerate(evs):
                try:
                    validate_event(ev)
                except ValueError as e:
                    problems.append(f"{where}.{key}[{i}]: {e}")
                    continue
                if isinstance(ev, dict) \
                        and ev.get("kind") == "controller_decision":
                    problems.extend(_validate_controller_decision(
                        f"{where}.{key}[{i}]", ev))
    return problems


def format_rows(rows) -> str:
    lines = [f"{'config':<24} {'metric':<22} {'baseline':>12} "
             f"{'current':>12} {'change':>8}  status"]
    for name, metric, b, c, rel, status in rows:
        bs = f"{b:,.1f}" if b is not None else "-"
        cs = f"{c:,.1f}" if c is not None else "-"
        rs = f"{100 * rel:+.1f}%" if rel is not None else "-"
        lines.append(f"{name:<24} {metric:<22} {bs:>12} {cs:>12} {rs:>8}  "
                     f"{status}")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--baseline", required=True)
    ap.add_argument("--current", required=True)
    ap.add_argument("--threshold", type=float, default=0.05,
                    help="relative drop that fails the gate (default 5%%)")
    ap.add_argument("--no-obs-check", action="store_true",
                    help="skip observability schema validation of the "
                         "current round")
    ap.add_argument("--assume-baseline-platform", default=None,
                    metavar="PLAT",
                    help="platform the baseline round ran on when its "
                         "file predates per-config platform fields "
                         "(r01-r05 driver rounds ran on the TPU box: "
                         "pass 'tpu'); configs whose declared platforms "
                         "differ are reported 'incomparable' instead of "
                         "gated")
    args = ap.parse_args(argv)
    try:
        current = _load(args.current)
        rows = compare(_load(args.baseline), current, args.threshold,
                       baseline_platform=args.assume_baseline_platform)
    except (OSError, ValueError) as e:
        print(f"check_bench_result: {e}", file=sys.stderr)
        return 2
    print(format_rows(rows))
    obs_problems = [] if args.no_obs_check else validate_observability(current)
    bad = [r for r in rows if r[5] in ("regressed", "missing")]
    if obs_problems:
        print(f"\nobservability schema violations in {args.current}:")
        for p in obs_problems:
            print(f"  - {p}")
    if bad or obs_problems:
        msgs = []
        if bad:
            msgs.append(f"{len(bad)} config(s) regressed or missing "
                        f"(threshold {100 * args.threshold:.0f}%)")
        if obs_problems:
            msgs.append(f"{len(obs_problems)} observability schema "
                        f"violation(s)")
        print(f"\nFAIL: " + "; ".join(msgs))
        return 1
    print("\nOK: no regressions")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
