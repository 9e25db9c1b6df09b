"""The bench harness always prints its one JSON line, and fails LOUDLY:

- backend-init failure → one JSON line with an `error` field, exit code 1;
- any single non-flagship config raising → structured per-config error,
  others intact, exit code 0;
- flagship failure → JSON still printed, `value: null` + `error`, exit
  code 1.
"""
import importlib.util
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _no_cache_placement(monkeypatch):
    """bench.main() points the compile cache at <repo>/.jax_cache before
    its first compile; a test process must not be repointed."""
    from paddle_tpu.framework import flags
    monkeypatch.setattr(flags, "place_caches", lambda checkout: None)


def _load_bench():
    spec = importlib.util.spec_from_file_location(
        "bench_under_test", os.path.join(REPO, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run_main(bench, capsys, rc=0):
    assert bench.main() == rc
    out = capsys.readouterr().out.strip().splitlines()
    assert len(out) == 1, f"bench must print exactly ONE line, got {out}"
    return json.loads(out[0])


def test_backend_init_failure_emits_error_json(capsys, monkeypatch):
    bench = _load_bench()
    monkeypatch.setattr(bench, "_init_backend_with_retry",
                        lambda: "RuntimeError: TPU is wedged")
    rec = _run_main(bench, capsys, rc=1)
    assert "TPU is wedged" in rec["error"]
    assert rec["value"] is None
    assert rec["metric"]  # schema intact for the driver

def test_one_config_failure_does_not_sink_others(capsys, monkeypatch):
    bench = _load_bench()
    monkeypatch.setattr(bench, "_init_backend_with_retry", lambda: None)
    monkeypatch.setattr(bench, "bench_gpt2", lambda: {
        "tokens_per_sec_chip": 123.0, "step_time_ms": 1.0, "mfu": 0.5})
    monkeypatch.setattr(bench, "bench_resnet50",
                        lambda: (_ for _ in ()).throw(RuntimeError("boom")))
    for name in ("bench_gpt2_decode", "bench_bert_base",
                 "bench_wide_deep_ps", "bench_wide_deep_ps_tpu"):
        monkeypatch.setattr(bench, name, lambda: {"ok": 1})
    rec = _run_main(bench, capsys)
    assert rec["value"] == 123.0
    assert "boom" in rec["configs"]["resnet50"]["error"]
    bert = rec["configs"]["bert_base_seq128"]
    assert bert["ok"] == 1
    from tools import check_bench_result as gate
    assert not gate.validate_observability(rec)
    assert "error" not in rec


def test_flagship_failure_still_prints_json(capsys, monkeypatch):
    bench = _load_bench()
    monkeypatch.setattr(bench, "_init_backend_with_retry", lambda: None)
    for name in ("bench_gpt2", "bench_gpt2_decode", "bench_resnet50",
                 "bench_bert_base", "bench_wide_deep_ps",
                 "bench_wide_deep_ps_tpu"):
        monkeypatch.setattr(
            bench, name,
            lambda: (_ for _ in ()).throw(RuntimeError("all dead")))
    rec = _run_main(bench, capsys, rc=1)
    assert rec["value"] is None
    assert "flagship" in rec["error"]
    assert "all dead" in rec["configs"]["gpt2_small"]["error"]


def test_bench_json_includes_observability_snapshot(capsys, monkeypatch,
                                                   peaks_row_for_this_device):
    """PR 2: the bench line must carry the metrics snapshot + retrace
    summary + schema-valid step records under `observability`."""
    from paddle_tpu.profiler.monitor import (make_step_record,
                                             validate_step_record)
    bench = _load_bench()
    monkeypatch.setattr(bench, "_init_backend_with_retry", lambda: None)
    monkeypatch.setattr(bench, "bench_gpt2", lambda: {
        "tokens_per_sec_chip": 1.0, "step_time_ms": 1.0, "mfu": 0.5})
    for name in ("bench_gpt2_decode", "bench_resnet50", "bench_bert_base",
                 "bench_wide_deep_ps", "bench_wide_deep_ps_tpu"):
        monkeypatch.setattr(bench, name, lambda: {"ok": 1})
    # a timed run would have appended one of these (schema from monitor.py)
    bench._STEP_RECORDS.append(make_step_record(
        step=40, window_steps=40, window_time_s=2.0, samples=320,
        flops_per_step=1e12, peak_flops=197e12, retraces=0))
    rec = _run_main(bench, capsys)
    obs = rec["observability"]
    assert isinstance(obs["metrics"], dict)
    # counter families registered at import are in the snapshot even on CPU
    assert "op_calls_total" in obs["metrics"]
    assert "collective_bytes_total" in obs["metrics"]
    assert "jit_retraces_total" in obs["metrics"]
    assert isinstance(obs["retraces_total"], int)
    assert obs["step_records"], "step records must be folded in"
    for sr in obs["step_records"]:
        validate_step_record(sr)
    assert sr["ips"] == 160.0  # 320 samples / 2 s
    # fleet-observability PR: compile attribution + device split + events
    from paddle_tpu.profiler.events import validate_event
    assert isinstance(obs["compile_attribution"], dict)
    for entry, stats in obs["compile_attribution"].items():
        assert stats["count"] >= 1 and stats["seconds"] >= 0
    # --profile-steps is default-ON (ROADMAP 1c), so the eager probe runs
    # under an xplane capture unless opted out
    assert obs["device_time"]["mode"] in ("estimate", "measured", "xplane")
    assert obs["device_time"]["rows"], "device-time probe produced no rows"
    for ev in obs["events_tail"]:
        validate_event(ev)


def test_run_config_emits_step_record(monkeypatch, peaks_row_for_this_device):
    """bench._run_config appends a schema-valid step record per timed run
    (exercised with a stub compiled step — no device needed)."""
    from paddle_tpu.profiler.monitor import validate_step_record
    bench = _load_bench()
    import jax.numpy as jnp

    class _Opt:
        def get_lr(self):
            return 0.1

    class _Compiled:
        def cost_analysis(self):
            return {"flops": 2e9, "bytes accessed": 1e6}

        def __call__(self, params, buffers, opt_state, rng, lr, t, *arrs):
            return jnp.zeros(()), params, buffers, opt_state

    class _Lowered:
        def compile(self):
            return _Compiled()

    class _Step:
        optimizer = _Opt()
        params, buffers, opt_state = {}, {}, {}

        class _S:
            @staticmethod
            def lower(*a, **kw):
                return _Lowered()
        _step = _S()

    class _Arg:
        data = jnp.ones((4, 8), jnp.float32)

    n0 = len(bench._STEP_RECORDS)
    sec, loss, flops, nbytes = bench._run_config(
        _Step(), (_Arg(),), iters=3, warmup=1)
    assert flops == 2e9 and loss == 0.0
    assert len(bench._STEP_RECORDS) == n0 + 1
    sr = bench._STEP_RECORDS[-1]
    validate_step_record(sr)
    assert sr["window_steps"] == 3
    assert sr["samples"] == 12  # batch 4 x 3 iters
    assert sr["flops_per_step_est"] == 2e9


def test_import_paddle_tpu_does_not_init_backend():
    """`import paddle_tpu` must never touch the jax backend: a subprocess
    that merely imports the package must not bind (or hang on) the TPU.
    Round-3 root cause: framework/random.py built a PRNGKey at import."""
    import subprocess
    code = (
        "import paddle_tpu\n"
        "from jax._src import xla_bridge as xb\n"
        "assert not getattr(xb, '_backends', None), 'backend initialized'\n"
        "print('LAZY_OK')\n")
    env = dict(os.environ, PYTHONPATH=REPO)
    env.pop("JAX_PLATFORMS", None)  # the real-world (driver) condition
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0 and "LAZY_OK" in r.stdout, r.stderr[-2000:]


def test_profile_steps_captures_compiled_run(monkeypatch, tmp_path,
                                             peaks_row_for_this_device):
    """--profile-steps: _run_config with a profile label runs a bounded
    xplane capture of the compiled step and records a measured-vs-estimate
    result under _PROFILE_RESULTS (stub executable, CPU-fast)."""
    bench = _load_bench()
    import jax.numpy as jnp

    class _Opt:
        def get_lr(self):
            return 0.1

    class _Compiled:
        def cost_analysis(self):
            return {"flops": 2e9, "bytes accessed": 1e6}

        def __call__(self, params, buffers, opt_state, rng, lr, t, *arrs):
            # enough real jax work for the trace to hold backend events
            x = jnp.ones((64, 64)) @ jnp.ones((64, 64))
            return x.sum() * 0.0, params, buffers, opt_state

    class _Lowered:
        def compile(self):
            return _Compiled()

    class _Step:
        optimizer = _Opt()
        params, buffers, opt_state = {}, {}, {}

        class _S:
            @staticmethod
            def lower(*a, **kw):
                return _Lowered()
        _step = _S()

    class _Arg:
        data = jnp.ones((4, 8), jnp.float32)

    monkeypatch.setenv("PADDLE_TPU_PROFILE_DIR", str(tmp_path))
    monkeypatch.setattr(bench, "_PROFILE_STEPS", 2)
    bench._run_config(_Step(), (_Arg(),), iters=2, warmup=1,
                      profile_label="stub_cfg")
    prof = bench._PROFILE_RESULTS["stub_cfg"]
    assert "error" not in prof, prof
    assert prof["status"] == "complete"
    assert prof["steps"] == 2
    assert prof["device_ms_per_step_cost_model"] is not None
    # the capture correlated the train_step span from the real trace
    assert prof["correlation"]["spans"] >= 2
    assert os.path.isdir(prof["session_dir"])


def test_main_rejects_unknown_args_only_from_cli():
    """bench.main() with no argv must ignore the caller's sys.argv (the
    harness tests run under pytest whose flags argparse would reject)."""
    bench = _load_bench()
    import argparse
    old = sys.argv
    sys.argv = ["bench.py", "--definitely-not-a-bench-flag"]
    try:
        # only reaches argparse: init is stubbed to fail fast
        bench._init_backend_with_retry = lambda: "stop here"
        bench.main()  # must not SystemExit on pytest-style argv
    finally:
        sys.argv = old


def test_device_time_probe_xplane_mode(monkeypatch, tmp_path,
                                       peaks_row_for_this_device):
    """With --profile-steps set, the bench's eager device-time probe runs
    inside a capture session: rows carry src="xplane" and the correlation
    block reports measured device time. What a capture promises is the
    span that waits for its result (`probe_pass`): an eager op's own span
    closes when the op is enqueued, and whether its work overlaps it is
    up to asynchronous dispatch, so of the ops only the annotations are
    asserted."""
    from paddle_tpu.profiler import xplane
    bench = _load_bench()
    monkeypatch.setenv("PADDLE_TPU_PROFILE_DIR", str(tmp_path))
    monkeypatch.setattr(bench, "_PROFILE_STEPS", 1)
    probe = bench._device_time_probe()
    assert probe["mode"] == "xplane", probe
    assert any(r["src"] == "xplane" for r in probe["rows"])
    assert probe["correlation"]["correlated"] >= 1
    by_op = {r["op"]: r for r in probe["correlation"]["by_op"]}
    assert by_op["probe_pass"]["calls"] == 3
    assert by_op["probe_pass"]["xplane_ms"] > 0
    trace = xplane.load_trace(xplane.find_trace_file(
        os.path.join(bench._profile_root(), "eager_probe")))
    names = [e.get("name") for e in trace["traceEvents"]]
    assert names.count("matmul") == 3 and names.count("probe_pass") == 3


import pytest


@pytest.mark.slow  # compiles 8 small resnet TrainStep variants (~2 min)
# fast-sibling: test_resnet_conv_fusion_block_shape validates the block
# contract without the full probe sweep
def test_bench_resnet50_emits_conv_fusion_block():
    """The r06 conv-fusion A/B probe rides bench_resnet50 at CPU-feasible
    shapes and validates against the gate."""
    bench = _load_bench()
    cfg = bench.bench_resnet50(B=4, hw=32, depth=18, probe_iters=2)
    cf = cfg["conv_fusion"]
    assert cf["enabled"] is True
    assert isinstance(cf.get("engaged"), bool)
    assert cf["probe_ms_on"] > 0 and cf["probe_ms_off"] > 0
    assert cfg["platform"] == "cpu"
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import check_bench_result as gate
    doc = {"configs": {"resnet50": cfg}}
    assert [p for p in gate.validate_observability(doc)
            if "conv_fusion" in p] == []


def test_resnet_conv_fusion_block_shape():
    """Fast sibling: the emitted block's field contract (no probe sweep)."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import check_bench_result as gate
    block = {"enabled": True, "engaged": False,
             "kernel_stats": {"pallas_fwd": 0, "xla_fwd": 0,
                              "pallas_bwd": 0, "xla_bwd": 0},
             "probe_ms_on": 10.0, "probe_ms_off": 11.0,
             "speedup_vs_off": 1.1, "hbm_gb_per_step_on": 1.0,
             "hbm_gb_per_step_off": 1.2, "hbm_pct_saved": 16.7,
             "note": "x"}
    doc = {"configs": {"resnet50": {"samples_per_sec_chip": 1.0,
                                    "conv_fusion": block}}}
    assert gate.validate_observability(doc) == []
