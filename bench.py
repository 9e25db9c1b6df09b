#!/usr/bin/env python
"""Headline benchmarks, single chip: GPT-2 small (flagship) + the BASELINE.md
target configs (ResNet-50 synthetic ImageNet, BERT-Base seq128).

Whole train step (fwd+bwd+optimizer) is ONE XLA executable
(`paddle_tpu.jit.TrainStep`) — the TPU answer to the reference's
InterpreterCore hot loop (`/root/reference/paddle/fluid/framework/new_executor/`).

Prints ONE JSON line: the flagship GPT-2 metric is `value`; the other
configs live in the same object under "configs", each with step time, MFU
(achieved FLOP/s from XLA cost_analysis over bf16 peak), and HBM bytes per
step. The reference publishes no in-repo numbers (BASELINE.json
`published: {}`), so vs_baseline is null; absolute numbers are tracked
round-over-round.

Measured attribution (--profile-steps) is ON by default so BENCH rounds
report xplane-measured device time, not just cost-model estimates; opt
out with --no-profile-steps. The GPT-2 config carries a
`flops_accounting` block pinning down why hw_flops_util
can sit below mfu (Pallas custom-call flops are invisible to XLA
cost_analysis).
"""
import json
import os
import tempfile
import time

# the gpt2_decode tp_decode/disagg A/B blocks need >=2 devices; on the
# CPU bench box fake them via the host-platform device count. Must land
# in XLA_FLAGS before the first jax import anywhere in this process —
# inert on a real TPU backend (the flag only affects the host platform)
# and respects an operator-provided count.
if "--xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=2")

WARMUP = 3
ITERS = 40  # long chain amortizes per-dispatch host latency

# --scale full|ci (env PADDLE_TPU_BENCH_SCALE): "full" is the TPU bench
# box configuration every BENCH round before r06 ran; "ci" shrinks the
# model/batch dims and iteration counts to what a CPU dev box can measure
# in minutes, WITHOUT changing what is measured (same models, same fused
# paths, same attribution/probe blocks). Scaled rounds record
# "scale": "ci" per config + round so the gate and readers can never
# mistake them for full-scale numbers.
_SCALE = os.environ.get("PADDLE_TPU_BENCH_SCALE", "full")


def _scaled(full, ci):
    return ci if _SCALE == "ci" else full

# --profile-steps N: after each config's timed run, capture N extra steps
# in a jax.profiler session (profiler/xplane.py) so the BENCH JSON reports
# MEASURED device time (device_src="xplane") next to the cost-model
# estimates, per config and per eager op. DEFAULT ON for BENCH rounds
# (ROADMAP item 1c: r06+ reports measured, not cost-model, attribution) —
# opt out with --no-profile-steps / --profile-steps 0 /
# PADDLE_TPU_BENCH_PROFILE_STEPS=0.
try:
    DEFAULT_PROFILE_STEPS = int(os.environ.get(
        "PADDLE_TPU_BENCH_PROFILE_STEPS", "3"))
except ValueError:  # malformed env must degrade, never kill the round
    DEFAULT_PROFILE_STEPS = 3
_PROFILE_STEPS = 0
_PROFILE_RESULTS = {}

# one metric, one definition (ROADMAP item 1a, VERDICT r5 "hw_flops_util
# 0.42 < MFU 0.485 is odd"): `mfu` — analytic model FLOPs (6*N*tokens +
# attention term) over peak — is THE headline utilization metric.
# `hw_flops_util` divides XLA cost_analysis flops by peak, and
# cost_analysis CANNOT see into Pallas custom calls: with the fused
# flash-attention path active, the attention fwd+bwd flops (~13% of GPT-2
# model flops at s1024) simply vanish from the numerator, which is exactly
# the r05 0.42-vs-0.485 gap. `flops_accounting` in each affected config
# shows both numerators and `hw_flops_util_incl_pallas` (cost-analysis
# flops + analytic flops of the active Pallas kernels) for the
# apples-to-apples comparison.
FLOPS_NOTE = ("mfu (analytic model FLOPs / peak) is the headline "
              "utilization metric; hw_flops_util uses XLA cost-analysis "
              "flops, which exclude Pallas custom-call kernels (flash "
              "attention) — hw_flops_util < mfu whenever the fused "
              "kernels are active, not a perf regression. "
              "hw_flops_util_incl_pallas adds the analytic kernel flops "
              "back to the cost-analysis count.")


def _profile_root() -> str:
    return os.environ.get(
        "PADDLE_TPU_PROFILE_DIR",
        os.path.join(tempfile.gettempdir(), f"bench_profile_{os.getpid()}"))

# hbm_gb_per_step / hw_flops_util provenance (VERDICT r5 Weak #6): they come
# from compiled.cost_analysis(), not hardware counters — say so in the JSON
ESTIMATES_NOTE = ("hbm_gb_per_step and hw_flops_util are XLA cost-analysis "
                  "ESTIMATES (upper bound, cache-oblivious), not measured "
                  "hardware counters")


def _peak_flops() -> float:
    """bf16 peak of this run's chip, from the one peaks table keyed by
    device_kind (profiler/device_time.PEAKS). A device without a row
    raises: a utilization against an assumed peak is a made-up number."""
    from paddle_tpu.profiler import device_time
    return device_time.device_peaks().bf16_flops


_INIT_HUNG = False  # set when the backend-init probe timed out (see main)

# step-window records (profiler/monitor.py schema) from every timed run this
# process executed; folded into the output under observability.step_records
_STEP_RECORDS = []

# sentinel-overhead measurement (health on vs off on the GPT-2 config);
# folded into the output under observability.health
_HEALTH_BLOCK = {}


def health_overhead_probe(make_step, batch, iters=10, warmup=2):
    """Measure the in-graph health sentinel's step-wall overhead.

    `make_step(health: bool)` builds a fresh TrainStep for the same model;
    both variants are timed through `TrainStep.__call__` (so both pay the
    identical Python dispatch) for `iters` steps. The health=True loop
    pays the sentinel's real production cost: the in-graph reductions plus
    one tiny per-step device->host fetch. Returns the bench
    `observability.health` block (validated by tools/check_bench_result)."""
    from paddle_tpu.profiler import health as _health
    times = {}
    probe = None
    for label, on in (("off", False), ("on", True)):
        step = make_step(on)
        if on:
            probe = step._health_probe
        loss = None
        for _ in range(warmup):
            loss = step(*batch)
        if loss is not None:
            # drain async warmup dispatches BEFORE opening the window —
            # their device tail would inflate both measurements and
            # deflate the relative overhead the acceptance gate reads
            float(loss)
        t0 = time.perf_counter()
        for _ in range(iters):
            loss = step(*batch)
        float(loss)  # device sync closes the timed window
        times[label] = 1000.0 * (time.perf_counter() - t0) / iters
    off, on = times["off"], times["on"]
    stats = _health.last_stats() or {}
    sentinel = {
        "loss": _finite_or_none(stats.get("loss")),
        "grad_norm": _finite_or_none(stats.get("grad_norm")),
        "update_ratio": _finite_or_none(stats.get("update_ratio")),
        "nonfinite": bool(stats.get("nonfinite", False)),
    }
    return {
        "step_ms_off": round(off, 3),
        "step_ms_on": round(on, 3),
        "overhead_frac": round((on - off) / off, 4) if off > 0 else None,
        "interval": _health.interval(),
        "groups": len(probe.group_names) if probe is not None else None,
        "sentinel": sentinel,
        "note": ("health on/off timed through TrainStep.__call__ on the "
                 "same model; 'on' includes the in-graph sentinel "
                 "reductions and the per-step stats-vector fetch"),
    }


def _finite_or_none(v):
    try:
        v = float(v)
    except (TypeError, ValueError):
        return None
    return v if v == v and v not in (float("inf"), float("-inf")) else None


def _observability_snapshot():
    """Metrics-registry snapshot + retrace summary + step records +
    compile attribution + device-vs-host split + recent structured events,
    folded into the bench JSON so each round's perf line carries its own
    observability data (PR 2, extended in the fleet-observability PR).
    Never raises — the bench must stay unkillable."""
    out = {}
    try:
        from paddle_tpu.profiler import metrics as _metrics
        _metrics.update_device_memory_gauges()
        out["metrics"] = _metrics.default_registry().snapshot()
    except Exception as e:
        out["metrics_error"] = f"{type(e).__name__}: {e}"
    try:
        from paddle_tpu.profiler.watchdog import get_watchdog
        wd = get_watchdog()
        out["retraces_total"] = wd.total_retraces()
        out["retrace_events"] = [e.to_dict() for e in list(wd.events)[-10:]]
    except Exception as e:
        out["retrace_error"] = f"{type(e).__name__}: {e}"
    try:
        # XLA compile cost per entry point (jax.monitoring feed): the
        # relaunch/cold-start story in numbers
        from paddle_tpu.profiler import compile_watch
        out["compile_attribution"] = compile_watch.summary()
    except Exception as e:
        out["compile_error"] = f"{type(e).__name__}: {e}"
    try:
        out["device_time"] = _device_time_probe()
    except Exception as e:
        out["device_time_error"] = f"{type(e).__name__}: {e}"
    if _HEALTH_BLOCK:
        out["health"] = dict(_HEALTH_BLOCK)
    try:
        from paddle_tpu.profiler import events as _events
        out["events_tail"] = _events.recent(20)
    except Exception as e:
        out["events_error"] = f"{type(e).__name__}: {e}"
    out["step_records"] = list(_STEP_RECORDS)[-10:]
    return out


def _device_time_probe():
    """Per-op host-dispatch vs device-execution split on a handful of
    representative eager ops (profiler/device_time.py). On CPU (and by
    default on TPU) device times are roofline ESTIMATES from the cost
    model and labeled so; `PADDLE_TPU_DEVICE_TIME=sync` measures real
    completion at the price of serialized dispatch; under --profile-steps
    the probe runs inside an xplane capture session, so rows carry
    MEASURED trace-correlated device time (src="xplane") and the
    correlation block reports the measured-vs-estimate delta per op."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.profiler import device_time
    from paddle_tpu.profiler.recorder import get_recorder
    from paddle_tpu.profiler.utils import RecordEvent

    rng = np.random.default_rng(0)
    a = paddle.to_tensor(rng.normal(size=(256, 256)).astype("float32"))
    b = paddle.to_tensor(rng.normal(size=(256, 256)).astype("float32"))

    def run_ops():
        for _ in range(3):  # first pass compiles; later passes are steady
            # one span to a pass, closed once the result is back on the
            # host: its device work lies inside it, which asynchronous
            # dispatch does not promise for the per-op spans
            with RecordEvent("probe_pass"):
                c = paddle.matmul(a, b)
                d = paddle.nn.functional.softmax(c)
                (d + c).mean().numpy()

    correlation = None
    if _PROFILE_STEPS > 0:
        from paddle_tpu.profiler import xplane
        sess = xplane.CaptureSession(
            os.path.join(_profile_root(), "eager_probe"))
        sess.start()
        try:
            run_ops()
        finally:
            summary = sess.stop(steps=3)
        rows = summary["device_time"]["rows"]
        correlation = summary.get("correlation")
    else:
        rec = get_recorder()
        was = rec.enabled
        rec.clear()
        rec.enabled = True
        try:
            run_ops()
        finally:
            rec.enabled = was
        rows = device_time.split_rows(rec.collect())
    platform, peak_flops, peak_bw = device_time.platform_peaks()
    mode = ("xplane" if any(r.get("src") == "xplane" for r in rows)
            else "measured" if device_time.sync_mode() else "estimate")
    out = {
        "rows": rows,
        "mode": mode,
        "platform": platform,
        "note": ("host_ms is dispatch latency; device_ms is roofline-"
                 "estimated from cost-model flops/bytes at peaks "
                 f"({peak_flops:.3g} FLOP/s, {peak_bw:.3g} B/s) unless "
                 "mode=measured (PADDLE_TPU_DEVICE_TIME=sync) or "
                 "mode=xplane (--profile-steps trace correlation)"),
    }
    if correlation is not None:
        out["correlation"] = correlation
    return out


def _profile_compiled_steps(label, run_step, flops_per_step):
    """Capture `_PROFILE_STEPS` invocations of an already-compiled train
    step in a jax.profiler session: each step runs inside a
    `RecordEvent("train_step")` span (synced before the span closes), so
    xplane correlation yields the MEASURED per-step device lane-time next
    to the cost-model estimate. Stores a compact result under
    `_PROFILE_RESULTS[label]`; never raises (the bench must finish)."""
    from paddle_tpu.profiler import xplane
    from paddle_tpu.profiler.utils import RecordEvent
    try:
        sess = xplane.CaptureSession(os.path.join(_profile_root(), label))
        sess.start()
        try:
            for _ in range(_PROFILE_STEPS):
                with RecordEvent("train_step"):
                    run_step()  # syncs internally: device work stays in-span
        finally:
            summary = sess.stop(steps=_PROFILE_STEPS)
        rows = [r for r in summary["device_time"]["rows"]
                if r["op"] == "train_step"]
        measured_ms = rows[0]["device_ms"] / _PROFILE_STEPS if rows else None
        est_ms = (1000.0 * flops_per_step / _peak_flops()) \
            if flops_per_step else None
        _PROFILE_RESULTS[label] = {
            # measured per-segment attribution (attention fwd/bwd, mlp,
            # ln, loss/CE, optimizer, ...) classified from the trace's
            # XLA op metadata — profiler/xplane.segment_breakdown
            "segments": summary.get("segments"),
            "session_dir": summary["session_dir"],
            "status": summary["status"],
            "steps": _PROFILE_STEPS,
            "device_ms_per_step_measured": (round(measured_ms, 3)
                                            if measured_ms else None),
            "device_ms_per_step_cost_model": (round(est_ms, 3)
                                              if est_ms else None),
            "measured_vs_estimate": (round(measured_ms / est_ms, 3)
                                     if measured_ms and est_ms else None),
            "device_src": rows[0]["src"] if rows else None,
            "correlation": summary.get("correlation"),
            "note": ("device_ms_per_step_measured is xplane-trace work-lane "
                     "time per compiled step; cost_model row is the XLA "
                     "cost-analysis FLOPs at the configured peak"),
        }
    except Exception as e:
        _PROFILE_RESULTS[label] = {"error": f"{type(e).__name__}: {e}"}


def _run_config(step, args, iters=None, warmup=None,
                profile_label=None):
    """AOT-compile the TrainStep ONCE, read cost_analysis from the same
    executable, and time by invoking it directly (no second jit compile).

    Returns (sec_per_step, final_loss, flops, bytes_accessed). With
    --profile-steps and a `profile_label`, a bounded xplane capture of the
    same executable follows the timed loop (measured device time per
    config in the JSON)."""
    import jax.numpy as jnp
    from paddle_tpu.framework import random as random_mod

    if iters is None:
        iters = _scaled(ITERS, 8)
    if warmup is None:
        warmup = _scaled(WARMUP, 1)
    rng = random_mod.default_generator().split()
    lr = jnp.asarray(step.optimizer.get_lr(), jnp.float32)
    arrs = [a.data for a in args]
    compiled = step._step.lower(step.params, step.buffers, step.opt_state,
                                rng, lr, 1, *arrs).compile()
    flops = nbytes = None
    try:
        an = compiled.cost_analysis()
        if isinstance(an, list):
            an = an[0]
        flops, nbytes = an.get("flops"), an.get("bytes accessed")
    except Exception:
        pass
    params, buffers, opt_state = step.params, step.buffers, step.opt_state
    # t is a traced scalar arg of the lowered executable: thread the real
    # step counter so Adam/AdamW bias correction follows a genuine
    # trajectory instead of freezing at t=1 (ADVICE r2)
    t = 0
    for _ in range(warmup):
        t += 1
        # [:4] tolerates the health-armed step's extra sentinel output
        # (PADDLE_TPU_HEALTH=1 while benching)
        loss, params, buffers, opt_state = compiled(
            params, buffers, opt_state, rng, lr, t, *arrs)[:4]
    float(loss)  # sync
    try:
        from paddle_tpu.profiler.watchdog import get_watchdog
        retrace0 = get_watchdog().total_retraces()
    except Exception:
        retrace0 = None
    try:
        from paddle_tpu.profiler import server as _obs_server
    except Exception:
        _obs_server = None
    t0 = time.perf_counter()
    for _ in range(iters):
        t += 1
        loss, params, buffers, opt_state = compiled(
            params, buffers, opt_state, rng, lr, t, *arrs)[:4]
        if _obs_server is not None:
            _obs_server.note_step(t)  # /healthz liveness while benching
    final_loss = float(loss)  # device sync
    dt = time.perf_counter() - t0
    # one step-window observability record per timed run (PR 2 schema)
    try:
        from paddle_tpu.profiler.monitor import make_step_record
        from paddle_tpu.profiler.watchdog import get_watchdog
        batch = (int(arrs[0].shape[0])
                 if arrs and getattr(arrs[0], "ndim", 0) else None)
        _STEP_RECORDS.append(make_step_record(
            step=iters, window_steps=iters, window_time_s=dt,
            samples=batch * iters if batch else None,
            flops_per_step=flops, peak_flops=_peak_flops(),
            retraces=(get_watchdog().total_retraces() - retrace0
                      if retrace0 is not None else 0)))
    except Exception:
        pass
    if profile_label and _PROFILE_STEPS > 0:
        state = {"t": t, "params": params, "buffers": buffers,
                 "opt_state": opt_state}

        def run_step():
            state["t"] += 1
            loss, state["params"], state["buffers"], state["opt_state"] = \
                compiled(state["params"], state["buffers"],
                         state["opt_state"], rng, lr, state["t"], *arrs)[:4]
            float(loss)  # sync inside the caller's RecordEvent span
        _profile_compiled_steps(profile_label, run_step, flops)
    return dt / iters, final_loss, flops, nbytes


def _platform() -> str:
    """Backend platform recorded per config and round so the gate can
    refuse cross-platform throughput comparisons (a CPU dev-box round vs
    a TPU driver round is not a regression, it is incomparable)."""
    try:
        import jax
        return jax.devices()[0].platform
    except Exception:
        return "unknown"


def _program_audit_block(reports_fn):
    """Static program audit of this config's compiled executables
    (paddle_tpu.analysis: trace + lower only, nothing runs) — aggregate
    counts + the findings themselves, so a bench round records whether
    the headline programs are hazard-clean on the box that produced the
    numbers. `reports_fn` -> list[AuditReport]. Never raises."""
    try:
        reports = reports_fn()
        counts = {"info": 0, "low": 0, "medium": 0, "high": 0}
        for r in reports:
            for sev, n in r.counts().items():
                counts[sev] += n
        return {
            "counts": counts,
            "clean_high": counts["high"] == 0,
            "reports": [r.to_dict(max_findings=8) for r in reports],
        }
    except Exception as e:
        return {"error": f"{type(e).__name__}: {e}"}


def bench_gpt2():
    import numpy as np
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu import optimizer
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models.gpt import GPT, GPTConfig
    from paddle_tpu.nn import functional as F

    B, L = _scaled((8, 1024), (2, 256))
    paddle.seed(0)
    cfg = GPTConfig.gpt2_small()
    cfg.max_position_embeddings = L
    cfg.dropout = 0.0
    cfg.attn_dropout = 0.0
    model = GPT(cfg)
    opt = optimizer.AdamW(learning_rate=1e-4, parameters=model.parameters(),
                          weight_decay=0.01)
    # O2 mixed precision: fp32 master weights + Adam state, bf16 compute —
    # the production TPU training configuration (no loss scaling needed)
    step = TrainStep(model, F.cross_entropy, opt, amp_dtype=jnp.bfloat16)
    rng = np.random.default_rng(0)
    ids = paddle.to_tensor(
        rng.integers(0, cfg.vocab_size, (B, L)).astype("int32"))
    labels = paddle.to_tensor(
        rng.integers(0, cfg.vocab_size, (B, L)).astype("int32"))
    from paddle_tpu.ops.pallas import flash_attention as _fa
    fa_pallas0 = _fa._stats["pallas"]
    sec, loss, flops, nbytes = _run_config(step, (ids, labels),
                                           profile_label="gpt2_small")
    # did this config's trace actually take the fused Pallas attention
    # path? (decides whether its flops are missing from cost_analysis)
    fa_pallas = _fa._stats["pallas"] > fa_pallas0
    # sentinel overhead (ISSUE 10 acceptance: <=2% step wall on this
    # config): same model, health on vs off, short __call__-timed loops
    try:
        def mk(health):
            o = optimizer.AdamW(learning_rate=1e-4,
                                parameters=model.parameters(),
                                weight_decay=0.01)
            return TrainStep(model, F.cross_entropy, o,
                             amp_dtype=jnp.bfloat16, health=health)
        _HEALTH_BLOCK.update(health_overhead_probe(
            mk, (ids, labels), iters=_scaled(10, 4),
            warmup=_scaled(2, 1)))
    except Exception as e:
        _HEALTH_BLOCK.update({"error": f"{type(e).__name__}: {e}"})
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    # model-FLOPs MFU: 6*N per token (fwd+bwd) + attention 12*L*D_model*T
    attn_flops = 12 * cfg.num_layers * B * L * L * cfg.hidden_size
    model_flops = 6 * n_params * B * L + attn_flops
    pallas_flops = attn_flops if fa_pallas else 0
    return {
        "name": f"gpt2-small-124M b{B} s{L} bf16+fp32-master",
        "platform": _platform(),
        "scale": _SCALE,
        "program_audit": _program_audit_block(
            lambda: [step.audit(ids, labels)]),
        "tokens_per_sec_chip": round(B * L / sec, 1),
        "samples_per_sec_chip": round(B / sec, 3),
        "step_time_ms": round(1000 * sec, 2),
        "final_loss": round(loss, 4),
        "mfu": round(model_flops / sec / _peak_flops(), 4),
        "hw_flops_util": (round(flops / sec / _peak_flops(), 4)
                          if flops else None),
        "flops_accounting": {
            "model_flops_per_step": model_flops,
            "xla_cost_flops_per_step": flops,
            "pallas_attn_flops_per_step": pallas_flops,
            "hw_flops_util_incl_pallas": (
                round((flops + pallas_flops) / sec / _peak_flops(), 4)
                if flops else None),
            "note": FLOPS_NOTE,
        },
        "hbm_gb_per_step": round(nbytes / 1e9, 2) if nbytes else None,
        "estimates_note": ESTIMATES_NOTE,
    }


def _conv_fusion_micro_ab(B=128, dtype_bytes=2):
    """Per-shape HBM-bytes accounting for the fused conv+BN chain on the
    ResNet-50 bottleneck 1x1 tails — the `flops_accounting` pattern
    applied to bytes: the COMPOSED side is measured from XLA
    cost_analysis of the matmul+stats+normalize chain (custom-call-free,
    so the estimate sees every pass, including the statistics read the
    fusion eliminates); the FUSED side is the kernel's analytic traffic
    (read x+w, write y + two (C,) stat vectors, then the elementwise
    apply's read y / write out) — cost_analysis cannot see inside Pallas
    custom calls, which is exactly why the composed/analytic pairing is
    the honest comparison. Never raises."""
    import jax
    import jax.numpy as jnp

    # (hw, Cin, Cout) of the bottleneck conv3 tails, ResNet-50 at 224px
    shapes = [(56, 64, 256), (28, 128, 512), (14, 256, 1024),
              (7, 512, 2048)]
    dt = jnp.bfloat16 if dtype_bytes == 2 else jnp.float32
    rows, tot_comp, tot_fused = [], 0, 0
    for hw_, cin, cout in shapes:
        try:
            R = B * hw_ * hw_

            def chain(x, w, g, b):
                y = jnp.dot(x, w, preferred_element_type=jnp.float32) \
                    .astype(dt)
                mean = jnp.mean(y, axis=0, dtype=jnp.float32)
                var = jnp.mean(
                    jnp.square(y.astype(jnp.float32)), axis=0) - mean ** 2
                out = (y.astype(jnp.float32) - mean) \
                    * jax.lax.rsqrt(var + 1e-5) * g + b
                return jnp.maximum(out, 0.0).astype(dt)

            args = (jax.ShapeDtypeStruct((R, cin), dt),
                    jax.ShapeDtypeStruct((cin, cout), dt),
                    jax.ShapeDtypeStruct((cout,), jnp.float32),
                    jax.ShapeDtypeStruct((cout,), jnp.float32))
            an = jax.jit(chain).lower(*args).compile().cost_analysis()
            if isinstance(an, list):
                an = an[0]
            composed = an.get("bytes accessed")
            # fused: conv kernel reads x + w, writes y + 2x(C,) f32 sums;
            # apply kernel reads y (+ per-channel consts), writes out
            fused = (R * cin + cin * cout + 2 * R * cout) * dtype_bytes \
                + (R * cout) * dtype_bytes + 10 * cout * 4
            # minimum-pass roofline of the composed chain (perfect XLA
            # fusion assumed): fused + the one full statistics read of y
            # the epilogue fusion eliminates — savings are computed vs
            # THIS conservative model; the raw cost-analysis column
            # (cache-oblivious, counts unfused elementwise passes) is
            # kept as context, not as the denominator
            composed_model = fused + R * cout * dtype_bytes
            if composed:
                rows.append({
                    "shape": f"b{B}x{hw_}x{hw_} {cin}->{cout}",
                    "composed_gb_cost_analysis": round(composed / 1e9, 3),
                    "composed_gb_model": round(composed_model / 1e9, 3),
                    "fused_gb_model": round(fused / 1e9, 3),
                    "pct_saved": round(
                        100 * (1 - fused / composed_model), 1),
                })
                tot_comp += composed_model
                tot_fused += fused
        except Exception:
            continue
    out = {"rows": rows, "note": (
        "fused side: analytic kernel traffic (stats computed in the conv "
        "epilogue — no separate full-activation statistics read); "
        "composed_gb_model: the same + that one statistics read "
        "(minimum-pass roofline, perfect-fusion assumption); pct_saved "
        "is fused vs composed_gb_model (conservative); "
        "composed_gb_cost_analysis is XLA's cache-oblivious estimate of "
        "the custom-call-free chain, kept as context")}
    if tot_comp:
        out["total_pct_saved"] = round(100 * (1 - tot_fused / tot_comp), 1)
    return out


def _paged_vs_dense_ab(model, ctxs, page_size, n_tokens=8, dense_iters=3):
    """Per-token decode cost, paged vs cacheless, at growing context.

    Paged side: ONE ServingEngine (one compiled decode executable over a
    fixed page-pool shape) decodes `n_tokens` after prefilling a
    `ctx`-token prompt — per-token wall from the engine's decode-phase
    clock (prefill + compiles excluded). Dense side: one jitted FULL
    forward over the `ctx`-token sequence (what a cacheless decoder pays
    for every token at that context), timed after its own warmup. The
    acceptance read: paged stays ~flat as ctx grows, dense grows with
    it. Never raises."""
    import jax
    import numpy as np
    from paddle_tpu.inference.serving import ServingEngine
    from paddle_tpu.jit import functionalize

    rng = np.random.default_rng(7)
    vocab = model.cfg.vocab_size
    max_len = max(ctxs) + n_tokens + 1
    eng = ServingEngine(model, max_batch=1, max_len=max_len,
                        page_size=page_size, name="paged_ab")
    # warm the decode executable (and one prefill bucket) out of the clock
    eng.submit(rng.integers(1, vocab, (8,)).tolist(), max_new_tokens=2)
    eng.run_until_idle()
    apply_fn, params, buffers = functionalize(model)
    dense_jit = jax.jit(lambda p, b, x: apply_fn(p, b, None, x)[0])
    rows = []
    for ctx in ctxs:
        prompt = rng.integers(1, vocab, (ctx,)).tolist()
        w0, t0 = eng.stats["decode_wall_s"], eng.stats["decode_tokens"]
        eng.submit(prompt, max_new_tokens=n_tokens)
        eng.run_until_idle()
        dw = eng.stats["decode_wall_s"] - w0
        dt = eng.stats["decode_tokens"] - t0
        paged_ms = 1000.0 * dw / max(dt, 1)
        import jax.numpy as jnp
        jnp_ids = jnp.asarray(np.asarray([prompt], np.int32))
        jax.block_until_ready(dense_jit(params, buffers, jnp_ids))  # compile
        td = time.perf_counter()
        for _ in range(dense_iters):
            jax.block_until_ready(dense_jit(params, buffers, jnp_ids))
        dense_ms = 1000.0 * (time.perf_counter() - td) / dense_iters
        rows.append({"ctx": int(ctx),
                     "paged_ms_per_token": round(paged_ms, 3),
                     "dense_ms_per_token": round(dense_ms, 3)})
    out = {"rows": rows, "decode_tokens_per_ctx": n_tokens,
           "note": ("paged: one fixed decode executable over the page "
                    "pool, per-token wall at the given prefilled "
                    "context; dense: one jitted full forward over the "
                    "ctx-token sequence = the cacheless cost of ONE "
                    "token at that context")}
    if len(rows) >= 2 and rows[0]["paged_ms_per_token"] > 0 \
            and rows[0]["dense_ms_per_token"] > 0:
        out["paged_growth"] = round(rows[-1]["paged_ms_per_token"]
                                    / rows[0]["paged_ms_per_token"], 3)
        out["dense_growth"] = round(rows[-1]["dense_ms_per_token"]
                                    / rows[0]["dense_ms_per_token"], 3)
        if rows[-1]["paged_ms_per_token"] > 0:
            out["speedup_at_max_ctx"] = round(
                rows[-1]["dense_ms_per_token"]
                / rows[-1]["paged_ms_per_token"], 3)
    return out


def _fused_vs_eager_ab(model, prompts, max_batch, max_len, page_size,
                       n_tokens):
    """The serving-v2 headline A/B: the SAME greedy traffic through the
    single-dispatch fused decode step vs the per-op eager path (identical
    math — the engines must produce identical tokens), per-token decode
    wall from each engine's own stats."""
    from paddle_tpu.inference.serving import ServingEngine

    out = {"decode_tokens_per_mode": len(prompts) * n_tokens}
    tokens = {}
    for mode in ("fused", "eager"):
        eng = ServingEngine(model, max_batch=max_batch, max_len=max_len,
                            page_size=page_size, name=f"ab_{mode}",
                            decode_mode=mode)
        # warm compile/trace out of the clock (the eager path traces
        # per-op abstract evals on first use too)
        eng.submit(prompts[0][:4] or [1], max_new_tokens=2)
        eng.run_until_idle()
        w0, t0 = eng.stats["decode_wall_s"], eng.stats["decode_tokens"]
        reqs = [eng.submit(p, max_new_tokens=n_tokens) for p in prompts]
        eng.run_until_idle()
        dw = eng.stats["decode_wall_s"] - w0
        dt = eng.stats["decode_tokens"] - t0
        out[f"{mode}_ms_per_token"] = round(1000.0 * dw / max(dt, 1), 3)
        tokens[mode] = [r.result(5) for r in reqs]
    out["identical_tokens"] = tokens["fused"] == tokens["eager"]
    if out["eager_ms_per_token"] and out["fused_ms_per_token"]:
        out["speedup"] = round(out["eager_ms_per_token"]
                               / out["fused_ms_per_token"], 3)
    out["note"] = ("same greedy prompts through decode_mode=fused (ONE "
                   "donated executable per lane bucket) vs eager (per-op "
                   "dispatch of the identical step fn); "
                   "identical_tokens is the bit-parity check")
    return out


def _shared_prefix_ab(model, max_batch, max_len, page_size, n_requests,
                      prefix_len, n_tokens):
    """Copy-on-write shared-prefix A/B: the parallel-sampling shape —
    n_requests with the IDENTICAL prompt and distinct sampling seeds,
    admitted with prefix sharing on vs off. The win is PAGE-POOL
    OCCUPANCY (the on side's free-page watermark stays high because the
    prompt KV is resident once and forked), and the prompt length is
    deliberately NOT page-aligned so every sharer's first divergent
    decode write lands on the shared tail page and exercises the
    copy-on-write fork (cow_copies)."""
    import numpy as np
    from paddle_tpu.inference.serving import SamplingParams, ServingEngine

    rng = np.random.default_rng(3)
    vocab = model.cfg.vocab_size
    if prefix_len % page_size == 0:
        prefix_len -= 2  # keep a partial tail page (see docstring)
    common = rng.integers(1, vocab, (prefix_len,)).tolist()
    out = {"requests": n_requests, "prefix_tokens": prefix_len}
    for label, share in (("on", True), ("off", False)):
        eng = ServingEngine(model, max_batch=max_batch, max_len=max_len,
                            page_size=page_size, name=f"shp_{label}",
                            share_prefix=share)
        reqs = [eng.submit(common, max_new_tokens=n_tokens,
                           sampling=SamplingParams(temperature=0.8,
                                                   seed=1000 + i))
                for i in range(n_requests)]
        eng.run_until_idle()
        for r in reqs:
            r.result(5)
        st = eng.stats
        out[label] = {
            "min_free_pages": int(st["min_free_pages"]),
            "prefix_hit_tokens": int(st["prefix_hit_tokens"]),
            "shared_admissions": int(st["shared_admissions"]),
            "cow_copies": int(st["cow_copies"]),
            "preemptions": int(st["preemptions"]),
            "completed": int(st["completed"]),
        }
        leak = eng.allocator.outstanding()
        out[label]["leaked_pages"] = len(leak)
    out["pages_saved_at_watermark"] = (out["on"]["min_free_pages"]
                                       - out["off"]["min_free_pages"])
    out["note"] = ("identical prompt x n_requests with distinct sampling "
                   "seeds (parallel sampling), shared-prefix CoW admission "
                   "on vs off; pages_saved_at_watermark = extra free pages "
                   "at the deepest point = extra admission headroom; "
                   "cow_copies counts divergent-write page forks")
    return out


def _tp_decode_ab(model, prompts, max_batch, max_len, page_size,
                  n_tokens):
    """Tensor-parallel decode A/B: the SAME greedy traffic through the
    single-chip fused engine vs a 2-way ``Mesh(("tp",))`` engine (paged
    KV pools + attention heads sharded over the head axis, block tables
    host-side). The claim is capacity, not speed — per-device KV bytes
    halve at the same TPOT — so the gate pins `identical_tokens` (TP is
    a layout change, never a math change) and reports the per-link
    collective bytes of the sharded decode program."""
    import jax
    import numpy as np
    from jax.sharding import Mesh
    from paddle_tpu.inference.serving import ServingEngine

    if len(jax.devices()) < 2:
        return {"skipped": "needs >=2 devices"}
    out = {"decode_tokens_per_mode": len(prompts) * n_tokens}
    tokens = {}
    for mode in ("single", "tp"):
        mesh = (Mesh(np.array(jax.devices()[:2]), ("tp",))
                if mode == "tp" else None)
        eng = ServingEngine(model, max_batch=max_batch, max_len=max_len,
                            page_size=page_size, name=f"tpab_{mode}",
                            mesh=mesh)
        eng.submit(prompts[0][:4] or [1], max_new_tokens=2)  # warm
        eng.run_until_idle()
        w0, t0 = eng.stats["decode_wall_s"], eng.stats["decode_tokens"]
        reqs = [eng.submit(p, max_new_tokens=n_tokens) for p in prompts]
        eng.run_until_idle()
        dw = eng.stats["decode_wall_s"] - w0
        dt = eng.stats["decode_tokens"] - t0
        out[f"{mode}_ms_per_token"] = round(1000.0 * dw / max(dt, 1), 3)
        tokens[mode] = [r.result(5) for r in reqs]
        if mode == "tp":
            out["tp_degree"] = eng.tp_degree()
            try:
                link = eng.audit(emit=False)[-1]
                out["collective_bytes_by_link"] = dict(link.link_bytes)
            except Exception as e:
                out["collective_bytes_by_link"] = {
                    "error": f"{type(e).__name__}: {e}"}
    out["identical_tokens"] = tokens["single"] == tokens["tp"]
    if out["single_ms_per_token"] and out["tp_ms_per_token"]:
        out["tpot_ratio"] = round(out["tp_ms_per_token"]
                                  / out["single_ms_per_token"], 3)
    out["note"] = ("same greedy prompts through the single-chip fused "
                   "engine vs the head-sharded 2-way TP mesh engine; "
                   "identical_tokens is the bit-parity check, tpot_ratio "
                   "~1.0 means the model could be tp_degree x larger at "
                   "the same TPOT (per-device KV bytes / tp_degree)")
    return out


def _disagg_ab(model, prompts, max_batch, max_len, page_size, n_tokens):
    """Disaggregated prefill/decode A/B: the SAME greedy traffic through
    the co-located engine vs the two-stage pipeline (prefill workers on
    their own devices producing KV pages into the handoff queue, the
    decode engine draining it inside its own step). The claim is
    interference isolation — decode TPOT stops paying for prefill
    bubbles — pinned again by `identical_tokens` (the handoff is a page
    move, never a math change) plus the handoff-plane counters."""
    from paddle_tpu.inference.disagg import DisaggPipeline
    from paddle_tpu.inference.serving import ServingEngine

    out = {"decode_tokens_per_mode": len(prompts) * n_tokens}
    tokens = {}
    for mode in ("colocated", "disagg"):
        eng = ServingEngine(model, max_batch=max_batch, max_len=max_len,
                            page_size=page_size, name=f"dab_{mode}")
        pipe = DisaggPipeline(eng, num_workers=1) if mode == "disagg" \
            else None
        submit = pipe.submit if pipe is not None else eng.submit
        drain = (pipe.run_until_idle if pipe is not None
                 else eng.run_until_idle)
        # warm compiles out of the clock: one prompt per distinct
        # pow2 handoff bucket the timed traffic will hit, so the
        # per-bucket inject/extract executables all exist before the
        # timer starts (same warm set for both modes — the engines'
        # lane/prefill compiles stay comparable)
        from paddle_tpu.inference.disagg import _pow2_pad
        seen_buckets = set()
        for p in sorted(prompts, key=len):
            b = _pow2_pad(-(-(len(p) + 1) // page_size))
            if b in seen_buckets:
                continue
            seen_buckets.add(b)
            submit(p, max_new_tokens=2)
        drain()
        w0, t0 = eng.stats["decode_wall_s"], eng.stats["decode_tokens"]
        reqs = [submit(p, max_new_tokens=n_tokens) for p in prompts]
        drain()
        dw = eng.stats["decode_wall_s"] - w0
        dt = eng.stats["decode_tokens"] - t0
        out[f"{mode}_ms_per_token"] = round(1000.0 * dw / max(dt, 1), 3)
        tokens[mode] = [r.result(5) for r in reqs]
        ttfts = [r.ttft_s for r in reqs if r.ttft_s is not None]
        if ttfts:
            out[f"{mode}_ttft_p50_ms"] = round(
                1000.0 * sorted(ttfts)[len(ttfts) // 2], 3)
        if mode == "disagg":
            st = pipe.status()
            out["handoffs"] = int(st["handoffs"])
            out["prefill_workers"] = int(st["stages"]["prefill"]["workers"])
            out["worker_prefills"] = int(st["worker_prefills"])
            out["decode_prefills"] = int(eng.stats["prefills"])
            pipe.close()
    out["identical_tokens"] = tokens["colocated"] == tokens["disagg"]
    if out["colocated_ms_per_token"] and out["disagg_ms_per_token"]:
        out["tpot_ratio"] = round(out["disagg_ms_per_token"]
                                  / out["colocated_ms_per_token"], 3)
    out["note"] = ("same greedy prompts through the co-located engine vs "
                   "the disaggregated prefill/decode pipeline (KV-page "
                   "handoff); identical_tokens is the bit-parity check; "
                   "decode_prefills==0 proves every prefill ran on a "
                   "prefill worker, not the decode engine")
    return out


def bench_gpt2_decode():
    """Autoregressive-decode serving bench: hundreds of concurrent
    simulated streams through the continuous-batching engine
    (inference/serving.py) over the paged KV cache — tokens/s/chip,
    p50/p99 TTFT/TPOT, goodput, and the paged-vs-dense, fused-vs-eager,
    shared-prefix-on/off, tp-decode and disagg A/Bs. The decode
    analogue of the train-step configs."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.inference.serving import ServingEngine
    from paddle_tpu.models.gpt import GPT, GPTConfig

    paddle.seed(0)
    if _SCALE == "ci":
        cfg = GPTConfig(vocab_size=8192, max_position_embeddings=512,
                        hidden_size=128, num_layers=2, num_heads=4,
                        dropout=0.0, attn_dropout=0.0)
        max_batch, max_len, page_size = 4, 160, 8
        streams, max_new = 24, 10
        prompt_lo, prompt_hi = 6, 48
        ab_ctxs, ab_tokens = (32, 64, 128), 6
        fve_streams, fve_tokens = 6, 6
        shp_requests, shp_prefix, shp_tokens = 8, 32, 4
        tpd_streams, tpd_tokens = 4, 6
        dis_streams, dis_tokens = 4, 6
    else:
        cfg = GPTConfig.gpt2_small()
        cfg.dropout = cfg.attn_dropout = 0.0
        max_batch, max_len, page_size = 32, 1024, 16
        streams, max_new = 512, 64
        prompt_lo, prompt_hi = 32, 512
        ab_ctxs, ab_tokens = (128, 512, 960), 16
        fve_streams, fve_tokens = 64, 16
        shp_requests, shp_prefix, shp_tokens = 64, 256, 8
        tpd_streams, tpd_tokens = 16, 16
        dis_streams, dis_tokens = 16, 16
    model = GPT(cfg)
    model.eval()
    eng = ServingEngine(model, max_batch=max_batch, max_len=max_len,
                        page_size=page_size, name="gpt2_decode")
    rng = np.random.default_rng(0)
    reqs = []
    t0 = time.perf_counter()
    for _ in range(streams):
        plen = int(rng.integers(prompt_lo, prompt_hi))
        reqs.append(eng.submit(
            rng.integers(1, cfg.vocab_size, (plen,)).tolist(),
            max_new_tokens=max_new))
    eng.run_until_idle(max_iterations=streams * (max_new + 4) + 1000)
    wall = time.perf_counter() - t0
    ttfts = [r.ttft_s for r in reqs if r.ttft_s is not None]
    tpots = [r.tpot_s for r in reqs if r.tpot_s is not None]
    qwaits = [r.admitted_ts - r.submitted_ts for r in reqs
              if r.admitted_ts is not None]
    goodput = sum(len(r.generated) for r in reqs)
    st = eng.status()["stats"]

    def _pct(vals, q):
        return round(float(np.percentile(vals, q)), 4) if vals else None

    ab = {}
    try:
        ab = _paged_vs_dense_ab(model, ab_ctxs, page_size,
                                n_tokens=ab_tokens)
    except Exception as e:
        ab = {"error": f"{type(e).__name__}: {e}"}
    try:
        fve_prompts = [rng.integers(1, cfg.vocab_size,
                                    (int(rng.integers(prompt_lo,
                                                      prompt_hi)),)).tolist()
                       for _ in range(fve_streams)]
        fused_vs_eager = _fused_vs_eager_ab(
            model, fve_prompts, max_batch, max_len, page_size,
            n_tokens=fve_tokens)
    except Exception as e:
        fused_vs_eager = {"error": f"{type(e).__name__}: {e}"}
    try:
        shared_prefix = _shared_prefix_ab(
            model, max_batch, max_len, page_size,
            n_requests=shp_requests, prefix_len=shp_prefix,
            n_tokens=shp_tokens)
    except Exception as e:
        shared_prefix = {"error": f"{type(e).__name__}: {e}"}
    try:
        tpd_prompts = [rng.integers(1, cfg.vocab_size,
                                    (int(rng.integers(prompt_lo,
                                                      prompt_hi)),)).tolist()
                       for _ in range(tpd_streams)]
        tp_decode = _tp_decode_ab(model, tpd_prompts, max_batch, max_len,
                                  page_size, n_tokens=tpd_tokens)
    except Exception as e:
        tp_decode = {"error": f"{type(e).__name__}: {e}"}
    try:
        dis_prompts = [rng.integers(1, cfg.vocab_size,
                                    (int(rng.integers(prompt_lo,
                                                      prompt_hi)),)).tolist()
                       for _ in range(dis_streams)]
        disagg = _disagg_ab(model, dis_prompts, max_batch, max_len,
                            page_size, n_tokens=dis_tokens)
    except Exception as e:
        disagg = {"error": f"{type(e).__name__}: {e}"}
    # serving metric families from the live registry, scoped to this
    # config's observability block (check_bench_result validates them).
    # Snapshotted AFTER the A/B probes so the handoff/per-stage families
    # the disagg pipeline populates land in the same artifact.
    obs = {}
    try:
        from paddle_tpu.profiler import metrics as _metrics
        snap = _metrics.default_registry().snapshot()
        obs["metrics"] = {k: v for k, v in snap.items()
                          if k.startswith(("serving_", "slo_"))}
    except Exception as e:
        obs["metrics_error"] = f"{type(e).__name__}: {e}"
    # request-scoped trace + SLO-window blocks (profiler/reqtrace.py /
    # profiler/slo.py — the /requests and /slo endpoint payloads), so a
    # BENCH round carries per-phase latency attribution
    try:
        obs["reqtrace"] = eng.requests_snapshot(n=min(streams, 50))
    except Exception as e:
        obs["reqtrace"] = {"error": f"{type(e).__name__}: {e}"}
    try:
        obs["slo"] = eng.slo.snapshot()
    except Exception as e:
        obs["slo"] = {"error": f"{type(e).__name__}: {e}"}
    return {
        "name": (f"gpt-decode {cfg.num_layers}L-h{cfg.hidden_size} "
                 f"continuous batching b{max_batch} x {streams} streams "
                 f"(paged KV, page={page_size}, max_len={max_len})"),
        "platform": _platform(),
        "scale": _SCALE,
        "streams": streams,
        "max_new_tokens": max_new,
        "tokens_per_sec_chip": round(goodput / wall, 1),
        "decode_tokens_per_sec": (
            round(st["decode_tokens"] / st["decode_wall_s"], 1)
            if st["decode_wall_s"] else None),
        "goodput_tokens": int(goodput),
        "completed": int(st["completed"]),
        "preemptions": int(st["preemptions"]),
        "batch_occupancy_mean": (
            round(st["decode_tokens"] / max(st["iterations"], 1), 2)),
        "serving": {
            "ttft_s": {"p50": _pct(ttfts, 50), "p99": _pct(ttfts, 99)},
            "tpot_s": {"p50": _pct(tpots, 50), "p99": _pct(tpots, 99)},
            "queue_wait_s": {"p50": _pct(qwaits, 50),
                             "p99": _pct(qwaits, 99)},
            "wall_s": round(wall, 2),
            "prefill_buckets": eng.status()["prefill_buckets"],
            "note": ("TTFT includes queue wait + bucketed prefill (and, "
                     "for early requests, one-time executable compiles); "
                     "TPOT is per finished request, first->last token"),
        },
        "paged_vs_dense": ab,
        "fused_vs_eager": fused_vs_eager,
        "shared_prefix": shared_prefix,
        "tp_decode": tp_decode,
        "disagg": disagg,
        "program_audit": _program_audit_block(lambda: eng.audit()),
        "observability": obs,
    }


def bench_resnet50(B=None, hw=None, depth=50, probe_iters=None):
    """Synthetic-ImageNet ResNet train step (BASELINE.md primary metric).
    The size knobs exist so the harness tests can exercise the full probe/
    compare logic at CPU-feasible shapes; the bench runs the (scale-aware)
    defaults."""
    if B is None:
        B = _scaled(128, 8)
    if hw is None:
        hw = _scaled(224, 64)
    if probe_iters is None:
        probe_iters = _scaled(8, 2)
    import numpy as np
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu import optimizer
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models.resnet import ResNet, BasicBlock, BottleneckBlock
    from paddle_tpu.nn import functional as F

    rng = np.random.default_rng(0)
    img_np = rng.normal(size=(B, 3, hw, hw)).astype("float32")
    imgs = {"NCHW": paddle.to_tensor(img_np),
            "NHWC": paddle.to_tensor(
                np.ascontiguousarray(img_np.transpose(0, 2, 3, 1)))}
    labels = paddle.to_tensor(rng.integers(0, 1000, (B,)).astype("int32"))

    def build(rc, df, fused, fused_conv=True):
        paddle.seed(0)
        block = BottleneckBlock if depth >= 50 else BasicBlock
        model = ResNet(block, depth, recompute=rc, data_format=df,
                       fused_bn=fused, fused_conv_bn=fused_conv)
        opt = optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                                 parameters=model.parameters())
        return TrainStep(model, F.cross_entropy, opt,
                         amp_dtype=jnp.bfloat16)

    # autotune over (remat x data_format) for the FUSED-BN path (reference
    # phi/kernels/autotune/ pattern), plus unfused reference probes at both
    # layouts — the fused-vs-unfused delta is the r6 headline (the Pallas
    # fused BN(+add)+ReLU family, this round's kernel work). Each probe also
    # keeps its executable's cost-analysis bytes so the HBM reduction is
    # measured in the same run it is claimed for.
    probes, probe_errs = {}, {}
    variants = [(rc, df, True) for rc in (False, True)
                for df in ("NCHW", "NHWC")]
    variants += [(False, df, False) for df in ("NCHW", "NHWC")]
    for rc, df, fused in variants:
        try:
            sec_p, _, _, nbytes_p = _run_config(
                build(rc, df, fused), (imgs[df], labels), iters=probe_iters,
                warmup=2)
            probes[(rc, df, fused)] = (sec_p, nbytes_p)
        except Exception as e:  # record, don't swallow: if ALL variants
            probe_errs[(rc, df, fused)] = f"{type(e).__name__}: {e}"
    fused_probes = {k: v for k, v in probes.items() if k[2]}
    if not fused_probes:
        raise RuntimeError(f"all resnet probe variants failed: {probe_errs}")
    best_rc, best_df, _ = min(fused_probes,
                              key=lambda k: fused_probes[k][0])
    from paddle_tpu.ops.pallas import fused_conv_bn as _fcb
    fcb_stats0 = dict(_fcb._stats)
    step = build(best_rc, best_df, fused=True)
    sec, loss, flops, nbytes = _run_config(step, (imgs[best_df], labels),
                                           profile_label="resnet50")
    fcb_engaged = {k: _fcb._stats[k] - fcb_stats0.get(k, 0)
                   for k in _fcb._stats}
    # conv-fusion A/B probe (the r06 headline knob): the main timed run
    # above IS the on side (fused_conv defaults True there — re-building
    # it would only pay a second identical multi-minute XLA compile);
    # the off side runs fused_conv_bn=False at the SAME iters/warmup so
    # the probe-vs-probe ratio carries no amortization bias, with
    # cost-analysis bytes kept so the HBM-bytes/step reduction is
    # measured in-round
    conv_fusion = {"enabled": True,
                   "kernel_stats": fcb_engaged,
                   "engaged": fcb_engaged.get("pallas_fwd", 0) > 0
                   or fcb_engaged.get("xla_fwd", 0) > 0,
                   "micro_ab": _conv_fusion_micro_ab(B=B)}
    try:
        sec_cf_on, nbytes_cf_on = sec, nbytes
        sec_cf_off, _, _, nbytes_cf_off = _run_config(
            build(best_rc, best_df, True, fused_conv=False),
            (imgs[best_df], labels))
        conv_fusion.update({
            "probe_ms_on": round(1000 * sec_cf_on, 2),
            "probe_ms_off": round(1000 * sec_cf_off, 2),
            "speedup_vs_off": round(sec_cf_off / sec_cf_on, 3),
            "hbm_gb_per_step_on": (round(nbytes_cf_on / 1e9, 2)
                                   if nbytes_cf_on else None),
            "hbm_gb_per_step_off": (round(nbytes_cf_off / 1e9, 2)
                                    if nbytes_cf_off else None),
            "hbm_pct_saved": (round(100.0 * (1.0 - nbytes_cf_on
                                             / nbytes_cf_off), 1)
                              if nbytes_cf_on and nbytes_cf_off else None),
            "note": ("fused_conv_bn=True folds the BN statistics pass "
                     "into the 1x1-conv Pallas kernel "
                     "(ops/pallas/fused_conv_bn.py) on eligible shapes; "
                     "probe-vs-probe at the winning layout/remat. On "
                     "platforms where no shape is eligible (CPU) both "
                     "sides compile the same program and the deltas "
                     "read ~0 — `engaged` says whether the kernel ran."),
        })
    except Exception as e:
        conv_fusion["error"] = f"{type(e).__name__}: {e}"
    # unfused comparison at the winning layout/remat (compiled in this same
    # run; probe-length timing is enough for the ratio)
    unfused = probes.get((best_rc, best_df, False))
    if unfused is None:
        try:
            sec_u, _, _, nbytes_u = _run_config(
                build(best_rc, best_df, False), (imgs[best_df], labels),
                iters=probe_iters, warmup=2)
            unfused = (sec_u, nbytes_u)
        except Exception as e:
            probe_errs[(best_rc, best_df, False)] = f"{type(e).__name__}: {e}"
    hbm_unfused = unfused[1] if unfused else None
    # ResNet-50 fwd = 4.09 GFLOP per 224x224 image; train = fwd + ~2x bwd
    model_flops = 3 * 4.09e9 * B * (hw / 224.0) ** 2
    out = {
        "name": (f"resnet{depth} b{B} {hw}x{hw} bf16 {best_df} fused-BN "
                 "(synthetic ImageNet"
                 + (", per-stage remat" if best_rc else "") + ")"),
        "platform": _platform(),
        "scale": _SCALE,
        "conv_fusion": conv_fusion,
        "program_audit": _program_audit_block(
            lambda: [step.audit(imgs[best_df], labels)]),
        "samples_per_sec_chip": round(B / sec, 1),
        "step_time_ms": round(1000 * sec, 2),
        "final_loss": round(loss, 4),
        "mfu": round(model_flops / sec / _peak_flops(), 4),
        "hw_flops_util": (round(flops / sec / _peak_flops(), 4)
                          if flops else None),
        "hbm_gb_per_step": round(nbytes / 1e9, 2) if nbytes else None,
        "estimates_note": ESTIMATES_NOTE,
        "probe_ms": {
            f"{'fused' if fu else 'unfused'},remat={rc},{df}":
                round(1000 * t, 1)
            for (rc, df, fu), (t, _) in sorted(probes.items(),
                                               key=lambda kv: kv[1][0])},
        "note": ("fused Pallas BN(+add)+ReLU train kernels "
                 "(ops/pallas/fused_bn.py) replace the unfused BN chain "
                 "whose ~9 full-activation HBM passes pinned model-MFU near "
                 "0.15 (r5 analysis); unfused probes kept for the delta."),
    }
    if probe_errs:
        out["probe_errors"] = {f"remat={rc},{df},fused={fu}": err
                               for (rc, df, fu), err in probe_errs.items()}
    if nbytes and hbm_unfused:
        out["hbm_gb_per_step_unfused"] = round(hbm_unfused / 1e9, 2)
        out["hbm_pct_saved_vs_unfused"] = round(
            100.0 * (1.0 - nbytes / hbm_unfused), 1)
    fused_probe = probes.get((best_rc, best_df, True))
    if unfused and fused_probe:
        # probe-vs-probe at the same config: identical iters/warmup on both
        # sides, so amortization bias doesn't inflate the headline ratio
        out["fused_speedup_vs_unfused"] = round(
            unfused[0] / fused_probe[0], 3)
    return out


def bench_bert_base():
    import numpy as np
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu import optimizer
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models.bert import Bert, BertConfig
    from paddle_tpu.nn import functional as F
    from paddle_tpu import nn

    # ERNIE/BERT-Base seq128 (BASELINE.md primary metric). b256 saturates
    # the chip (sweep r5: b32 0.25 / b128 0.58 / b256 0.60 / b512 0.28 MFU);
    # dropout=0 matches the GPT flagship convention — with dropout the step
    # is mask-RNG-bound, which the rbg default PRNG already halves.
    B, L = _scaled((256, 128), (8, 64))
    paddle.seed(0)
    cfg = BertConfig.base()
    cfg.max_position_embeddings = max(cfg.max_position_embeddings, L)
    for attr in ("dropout", "hidden_dropout", "attn_dropout",
                 "hidden_dropout_prob", "attention_probs_dropout_prob"):
        if hasattr(cfg, attr):
            setattr(cfg, attr, 0.0)

    class BertCls(nn.Layer):
        def __init__(self):
            super().__init__()
            self.bert = Bert(cfg)
            self.head = nn.Linear(cfg.hidden_size, 2)

        def forward(self, ids):
            _, pooled = self.bert(ids)
            return self.head(pooled)

    model = BertCls()
    opt = optimizer.AdamW(learning_rate=1e-4,
                          parameters=model.parameters())
    step = TrainStep(model, F.cross_entropy, opt, amp_dtype=jnp.bfloat16)
    rng = np.random.default_rng(0)
    ids = paddle.to_tensor(
        rng.integers(0, cfg.vocab_size, (B, L)).astype("int32"))
    labels = paddle.to_tensor(rng.integers(0, 2, (B,)).astype("int32"))
    sec, loss, flops, nbytes = _run_config(step, (ids, labels),
                                           profile_label="bert_base_seq128")
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    model_flops = (6 * n_params * B * L
                   + 12 * cfg.num_layers * B * L * L * cfg.hidden_size)
    return {
        "name": f"bert-base seq{L} b{B} bf16 dropout0 (ERNIE-Base class)",
        "platform": _platform(),
        "scale": _SCALE,
        "program_audit": _program_audit_block(
            lambda: [step.audit(ids, labels)]),
        "samples_per_sec_chip": round(B / sec, 1),
        "step_time_ms": round(1000 * sec, 2),
        "final_loss": round(loss, 4),
        "mfu": round(model_flops / sec / _peak_flops(), 4),
        "hw_flops_util": (round(flops / sec / _peak_flops(), 4)
                          if flops else None),
        "hbm_gb_per_step": round(nbytes / 1e9, 2) if nbytes else None,
        "estimates_note": ESTIMATES_NOTE,
    }


def bench_wide_deep_ps():
    """Wide&Deep over the native parameter server (BASELINE.md row 4).

    Runs in a CPU-forced subprocess: PS-mode trainers are host-CPU
    workers in the reference too (`HogwildWorker`), and the eager PS loop
    on the chip would measure per-op dispatch latency, not the sparse
    path."""
    import json as _json
    import os
    import subprocess
    import sys

    # the child never needs the chip this process holds: JAX_PLATFORMS in
    # its environment is enough. It asserts the platform it got and emits
    # it in the JSON, so a regression here cannot be silent.
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    code = ("import bench, json; "
            "print('WDJSON'+json.dumps(bench._wide_deep_ps_body()))")
    proc = subprocess.run([sys.executable, "-c", code],
                          cwd=os.path.dirname(os.path.abspath(__file__)),
                          env=env,
                          capture_output=True, text=True, timeout=900)
    if proc.returncode != 0:
        # a crash during teardown (e.g. a PS shutdown regression) must not
        # masquerade as a clean run even if the metrics line was flushed
        raise RuntimeError(f"wide&deep bench subprocess rc="
                           f"{proc.returncode}: {proc.stderr[-800:]}")
    for line in proc.stdout.splitlines():
        if line.startswith("WDJSON"):
            return _json.loads(line[len("WDJSON"):])
    raise RuntimeError(f"wide&deep bench subprocess printed no metrics: "
                       f"{proc.stderr[-800:]}")


def _wide_deep_ps_body():
    import jax
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import nn, optimizer
    from paddle_tpu.distributed.ps import PSServer, PSClient
    from paddle_tpu.models.wide_deep import WideDeep

    platform = jax.devices()[0].platform
    assert platform == "cpu", (
        f"PS trainer bench must run on host CPU, got {platform!r}: the "
        "CPU-forcing failed and the number would measure chip dispatch")
    B, SLOTS, VOCAB = 512, 8, 1_000_000
    server = PSServer(0)
    client = PSClient([server.endpoint])
    try:
        paddle.seed(0)
        model = WideDeep(num_slots=SLOTS, embedding_dim=16, dense_dim=13,
                         hidden=64, client=client)
        opt = optimizer.Adam(learning_rate=1e-3,
                             parameters=model.parameters())
        crit = nn.BCEWithLogitsLoss()
        rng = np.random.default_rng(0)

        def batch():
            ids = paddle.to_tensor(
                rng.integers(0, VOCAB, (B, SLOTS)).astype(np.int64))
            dense = paddle.to_tensor(
                rng.normal(size=(B, 13)).astype(np.float32))
            labels = paddle.to_tensor(
                (rng.random((B, 1)) > 0.5).astype(np.float32))
            return ids, dense, labels

        data = [batch() for _ in range(8)]
        for ids, dense, labels in data[:2]:  # warmup
            loss = crit(model(ids, dense), labels)
            loss.backward(); opt.step(); opt.clear_grad()
        t0 = time.perf_counter()
        iters = 20
        for i in range(iters):
            ids, dense, labels = data[i % len(data)]
            loss = crit(model(ids, dense), labels)
            loss.backward(); opt.step(); opt.clear_grad()
        final = float(loss)
        dt = time.perf_counter() - t0
        # PS-relevant metric families from THIS subprocess's registry (the
        # parent's global snapshot can't see them)
        obs = {}
        try:
            from paddle_tpu.profiler import metrics as _metrics
            snap = _metrics.default_registry().snapshot()
            obs["metrics"] = {k: v for k, v in snap.items()
                              if k.startswith(("retry_", "fault_", "ps_",
                                               "heter_", "embed_cache_"))}
        except Exception as e:
            obs["metrics_error"] = f"{type(e).__name__}: {e}"
        return {
            "name": f"wide&deep sparse-PS b{B} x {SLOTS} slots "
                    f"(1M-feasign space, native PS, CPU trainer)",
            "examples_per_sec": round(B * iters / dt, 1),
            "step_time_ms": round(1000 * dt / iters, 2),
            "final_loss": round(final, 4),
            "platform": platform,
            "observability": obs,
        }
    finally:
        client.stop_servers()


def bench_wide_deep_ps_tpu():
    """Wide&Deep with the heterogeneous split: native PS owns the sparse
    tables on host, ONE compiled step runs the dense net fwd+bwd+update on
    the chip (SURVEY §7 "host PS + TPU dense path"; reference heter_ps/).
    Runs in the main (TPU) process — this config is the point: the dense
    path on the accelerator, unlike bench_wide_deep_ps's all-CPU trainer.

    PR-4 shape: mode="pipelined" prefetches the next batch's route/unique/
    pull/H2D on a background stage while the chip executes the current
    step, and the device-side hot-row cache serves repeat feasigns with an
    on-chip gather (gradients absorbed on-chip, written back on eviction/
    flush). A short async-mode probe (the r05 configuration) rides along
    for the speedup ratio, and the per-step stage breakdown lands under
    this config's `observability.heter_breakdown`."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import nn, optimizer
    from paddle_tpu.distributed.ps import PSServer, PSClient
    from paddle_tpu.distributed.ps.heter import HeterPSTrainStep
    from paddle_tpu.models.wide_deep import WideDeep

    B, SLOTS, VOCAB = 512, 8, 1_000_000
    CACHE_ROWS = 1 << 15  # holds the whole repeating working set (~32k/table)
    server = PSServer(0)
    client = PSClient([server.endpoint])
    try:
        paddle.seed(0)
        model = WideDeep(num_slots=SLOTS, embedding_dim=16, dense_dim=13,
                         hidden=64, client=client)
        crit = nn.BCEWithLogitsLoss()
        rng = np.random.default_rng(0)

        def batch():
            ids = paddle.to_tensor(
                rng.integers(0, VOCAB, (B, SLOTS)).astype(np.int64))
            dense = paddle.to_tensor(
                rng.normal(size=(B, 13)).astype(np.float32))
            labels = paddle.to_tensor(
                (rng.random((B, 1)) > 0.5).astype(np.float32))
            return ids, dense, labels

        data = [batch() for _ in range(8)]

        # -- async-mode probe (the r05 configuration) for the ratio -------
        probe_iters = 10
        opt_a = optimizer.Adam(learning_rate=1e-3,
                               parameters=model.parameters())
        step_a = HeterPSTrainStep(model, lambda o, y: crit(o, y), opt_a,
                                  mode="async")
        try:
            for ids, dense, labels in data[:2]:
                step_a(ids, dense, labels)
            ta = time.perf_counter()
            for i in range(probe_iters):
                step_a(*data[i % len(data)])
            step_a.flush()
            async_ms = 1000 * (time.perf_counter() - ta) / probe_iters
        finally:
            step_a.close()

        # -- pipelined + hot-row cache (the headline) ---------------------
        opt = optimizer.Adam(learning_rate=1e-3,
                             parameters=model.parameters())
        step = HeterPSTrainStep(model, lambda o, y: crit(o, y), opt,
                                mode="pipelined",
                                cache_capacity=CACHE_ROWS)
        iters = 30
        try:
            # warmup: pass 1 compiles the miss-heavy shapes and fills the
            # cache; pass 2 compiles the steady-state all-hit shapes so the
            # timed window measures the pipeline, not XLA
            for b in data + data:
                step(*b)
            # drain the push worker before touching stage_totals: a still-
            # running warmup push would race the reset (and leak its time
            # into the timed window)
            step.flush()
            for tot in step.stage_totals:
                step.stage_totals[tot] = 0.0 if tot != "steps" else 0
            t0 = time.perf_counter()
            for i in range(iters):
                loss = step(*data[i % len(data)])
                if i + 1 < iters:  # no dead prefetch after the last step
                    step.prefetch(*data[(i + 1) % len(data)])
            step.flush()
            dt = time.perf_counter() - t0
            final = float(loss)
            st = dict(step.stage_totals)
            # compute estimate: a few fully-synced steps (no prefetch is
            # outstanding — the timed loop stopped prefetching before its
            # last step and flush() discards stragglers anyway)
            sync_iters = 5
            ts = time.perf_counter()
            for i in range(iters, iters + sync_iters):
                float(step(*data[i % len(data)]))
            synced_ms = 1000 * (time.perf_counter() - ts) / sync_iters
        finally:
            # join the workers BEFORE stop_servers: an in-flight push
            # racing server shutdown can wedge interpreter exit
            step.close()

        n = max(st["steps"], 1)
        route_ms = 1000 * st["route_s"] / n
        pull_ms = 1000 * st["pull_s"] / n
        put_ms = 1000 * st["put_s"] / n
        push_ms = 1000 * st["push_s"] / n
        wall_ms = 1000 * dt / iters
        sparse_host_ms = route_ms + pull_ms + put_ms
        compute_ms_est = max(0.0, synced_ms - sparse_host_ms)
        hidden_ms = min(sparse_host_ms,
                        max(0.0, sparse_host_ms + compute_ms_est - wall_ms))
        overlap = (hidden_ms / sparse_host_ms) if sparse_host_ms > 0 else 1.0
        caches = list(step.caches.values())
        hits = sum(c.stats["hit"] for c in caches)
        misses = sum(c.stats["miss"] for c in caches)
        breakdown = {
            "route_ms": round(route_ms, 3),
            "pull_ms": round(pull_ms, 3),
            "h2d_ms": round(put_ms, 3),
            "push_ms": round(push_ms, 3),
            "step_wall_ms": round(wall_ms, 3),
            "synced_step_ms": round(synced_ms, 3),
            "compute_ms_est": round(compute_ms_est, 3),
            "sparse_host_ms": round(sparse_host_ms, 3),
            # fraction of host sparse-path time (route+pull+H2D) hidden
            # under on-chip compute; push runs on its own worker thread and
            # is off the critical path by construction
            "pull_overlap_frac": round(overlap, 3),
            "note": ("host-timer derived; compute_ms_est = synced-step "
                     "wall minus host sparse stages (estimate)"),
        }
        cache_stats = {
            "capacity_rows_per_table": CACHE_ROWS,
            "hits": hits, "misses": misses,
            "hit_rate": round(hits / max(hits + misses, 1), 4),
            "evictions": sum(c.stats["eviction"] for c in caches),
            "writebacks": sum(c.stats["writeback"] for c in caches),
        }
        return {
            "name": f"wide&deep heter-PS b{B} x {SLOTS} slots "
                    f"(1M-feasign space, native host PS + compiled "
                    f"on-chip dense step, pipelined prefetch + device "
                    f"hot-row cache)",
            "examples_per_sec": round(B * iters / dt, 1),
            "step_time_ms": round(wall_ms, 2),
            "final_loss": round(final, 4),
            "platform": _platform(),
            "scale": _SCALE,
            "async_probe_step_ms": round(async_ms, 2),
            "pipelined_speedup_vs_async": round(async_ms / wall_ms, 3)
            if wall_ms else None,
            "observability": {
                "heter_breakdown": breakdown,
                "embed_cache": cache_stats,
            },
        }
    finally:
        client.stop_servers()


def _init_backend_with_retry(tries: int = 3, probe_timeout: float = 180.0):
    """Initialize the jax backend, retrying with backoff.

    A chip belongs to one process: if another process holds it, init
    either RAISES or HANGS forever — so the probe runs in a daemon thread
    with a deadline; on hang we give up and report, instead of blocking
    until the driver kills us with no JSON emitted. Returns None on
    success, else the last error string.
    """
    import threading

    err = None
    for i in range(tries):
        box = {}

        def probe():
            try:
                import jax
                jax.devices()
                box["ok"] = True
            except Exception as e:
                box["err"] = f"{type(e).__name__}: {e}"

        th = threading.Thread(target=probe, daemon=True)
        th.start()
        th.join(probe_timeout)
        if box.get("ok"):
            return None
        if th.is_alive():
            # hung C call: unkillable; report and let main() exit hard
            global _INIT_HUNG
            _INIT_HUNG = True
            return (f"backend init hung >{probe_timeout:.0f}s "
                    "(is another process holding the chip?)")
        err = box.get("err", "unknown init failure")
        # jax caches a failed init; clear cached backends before retry
        import jax.extend.backend
        jax.extend.backend.clear_backends()
        if i < tries - 1:
            time.sleep(10 * (i + 1))
    return err


def main(argv=None):
    """argv defaults to NO arguments — programmatic callers (the harness
    tests) run the default bench; the CLI passes sys.argv[1:] itself.
    Returns the exit code: non-zero when the backend did not initialise or
    the flagship config raised (the JSON line, with its `error`, is
    printed either way)."""
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[1])
    ap.add_argument("--profile-steps", type=int, default=None, metavar="N",
                    help="after each config's timed run, capture N extra "
                         "steps in a jax.profiler session and report "
                         "measured (xplane-correlated) device time next "
                         "to the cost-model estimates (DEFAULT ON: "
                         f"{DEFAULT_PROFILE_STEPS} steps; 0 disables)")
    ap.add_argument("--no-profile-steps", action="store_true",
                    help="opt out of the default-on measured-attribution "
                         "capture (equivalent to --profile-steps 0)")
    ap.add_argument("--scale", choices=("full", "ci"), default=None,
                    help="'full' = the TPU bench-box config every round "
                         "before r06 ran (default); 'ci' = CPU-feasible "
                         "dims/iters, same models and probe blocks, "
                         "recorded as scale=ci per config (env "
                         "PADDLE_TPU_BENCH_SCALE)")
    args = ap.parse_args(argv or [])
    global _PROFILE_STEPS, _SCALE
    if args.scale is not None:
        _SCALE = args.scale
    if args.no_profile_steps:
        _PROFILE_STEPS = 0
    elif args.profile_steps is None:
        _PROFILE_STEPS = max(0, DEFAULT_PROFILE_STEPS)
    else:
        _PROFILE_STEPS = max(0, int(args.profile_steps))
    result = {
        "metric": "gpt2-small-124M train tokens/sec/chip "
                  "(b8 x s1024, bf16 compute + fp32 master, fused step)",
        "value": None,
        "unit": "tokens/sec/chip",
        "vs_baseline": None,
        "platform": None,  # filled after backend init
        "configs": {},
        "note": "reference publishes no in-repo baseline "
                "(BASELINE.json published:{}); peak for MFU = "
                "the device_kind row of profiler/device_time.PEAKS; "
                + ESTIMATES_NOTE,
    }
    configs = result["configs"]
    try:
        from paddle_tpu.profiler import server as _obs_server
        _obs_server.maybe_start_server()  # PADDLE_TPU_METRICS_PORT opt-in
    except Exception:
        pass
    init_err = _init_backend_with_retry()
    if init_err is not None:
        result["error"] = f"jax backend init failed after retries: {init_err}"
        print(json.dumps(result))
        if _INIT_HUNG:
            # a hung init probe leaves an unkillable daemon thread holding
            # the backend lock — exit hard so the JSON (already flushed) is
            # the process's last word instead of a shutdown deadlock
            import sys
            sys.stdout.flush()
            os._exit(1)
        return 1
    result["platform"] = _platform()
    # before the first compile: one fixed compile-cache directory unless
    # the environment placed it
    from paddle_tpu.framework.flags import place_caches
    place_caches(os.path.dirname(os.path.abspath(__file__)))
    # EVERY config — including the flagship — inside the guard: one failure
    # must not sink the whole bench (the round-3 lesson).
    for fn, key in ((bench_gpt2, "gpt2_small"),
                    (bench_gpt2_decode, "gpt2_decode"),
                    (bench_resnet50, "resnet50"),
                    (bench_bert_base, "bert_base_seq128"),
                    (bench_wide_deep_ps, "wide_deep_ps"),
                    (bench_wide_deep_ps_tpu, "wide_deep_ps_tpu")):
        try:
            configs[key] = fn()
        except Exception as e:
            import traceback
            configs[key] = {"error": f"{type(e).__name__}: {e}",
                            "traceback": traceback.format_exc(limit=6)}
    # measured-device-time capture results per config (--profile-steps)
    for key, prof in _PROFILE_RESULTS.items():
        if key in configs and isinstance(configs[key], dict):
            configs[key]["profile"] = prof
    gpt = configs.get("gpt2_small", {})
    if "tokens_per_sec_chip" in gpt:
        result["value"] = gpt["tokens_per_sec_chip"]
        result["step_time_ms"] = gpt["step_time_ms"]
        result["mfu"] = gpt["mfu"]
    else:
        result["error"] = ("flagship gpt2 config failed: "
                           + str(gpt.get("error", "missing")))
    result["observability"] = _observability_snapshot()
    print(json.dumps(result))
    return 1 if "error" in result else 0


if __name__ == "__main__":
    import sys
    sys.exit(main(sys.argv[1:]))
