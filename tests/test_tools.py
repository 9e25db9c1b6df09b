"""Repo tools (reference `tools/CrossStackProfiler/` + the op-benchmark CI
gate `tools/check_op_benchmark_result.py`): trace merging with per-rank
lanes and clock alignment, the cross-rank op summary, and the bench
regression gate against real BENCH_r*.json artifacts."""
import json
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

REPO = str(pathlib.Path(__file__).resolve().parent.parent)
sys.path.insert(0, os.path.join(REPO, "tools"))

import check_bench_result as gate  # noqa: E402
import cross_stack_profiler as csp  # noqa: E402


def _trace(events):
    return {"traceEvents": [
        {"name": n, "ph": "X", "cat": "op", "ts": ts, "dur": d,
         "pid": 1234, "tid": 0} for n, ts, d in events]}


class TestCrossStackProfiler:
    def test_merge_assigns_rank_lanes_and_aligns(self, tmp_path):
        (tmp_path / "rank_0.json").write_text(json.dumps(
            _trace([("matmul", 1000.0, 5.0)])))
        (tmp_path / "rank_1.json").write_text(json.dumps(
            _trace([("matmul", 9000.0, 7.0)])))  # different host clock
        traces = csp.load_rank_traces(str(tmp_path))
        merged = csp.merge_traces(traces, align=True)
        xs = [e for e in merged["traceEvents"] if e.get("ph") == "X"]
        assert {e["pid"] for e in xs} == {0, 1}
        assert all(e["ts"] == 0.0 for e in xs)  # aligned to rank t0
        names = [e for e in merged["traceEvents"]
                 if e.get("ph") == "M" and e["name"] == "process_name"]
        assert {m["args"]["name"] for m in names} == {"rank 0", "rank 1"}

    def test_op_summary_aggregates_across_ranks(self):
        traces = {0: _trace([("conv", 0, 10.0), ("conv", 20, 30.0)]),
                  1: _trace([("conv", 0, 20.0), ("relu", 5, 1.0)])}
        rows = csp.op_summary(traces)
        conv = next(r for r in rows if r["name"] == "conv")
        assert conv["calls"] == 3
        assert conv["total_us"] == pytest.approx(60.0)
        assert conv["max_us"] == pytest.approx(30.0)
        assert conv["by_rank"] == {0: 40.0, 1: 20.0}
        assert rows[0]["name"] == "conv"  # sorted by total desc

    def test_cli_end_to_end(self, tmp_path):
        d = tmp_path / "traces"
        d.mkdir()
        (d / "worker_0.json").write_text(json.dumps(
            _trace([("step", 0, 100.0)])))
        out = tmp_path / "merged.json"
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools",
                                          "cross_stack_profiler.py"),
             "--trace_dir", str(d), "--out", str(out), "--summary"],
            capture_output=True, text=True, timeout=120)
        assert r.returncode == 0, r.stderr
        assert out.exists()
        assert "step" in r.stdout

    def test_merges_real_profiler_export(self, tmp_path):
        """End-to-end with the actual paddle_tpu profiler output format."""
        import paddle_tpu as paddle
        from paddle_tpu import profiler as P
        prof = P.Profiler()
        prof.start()
        with P.RecordEvent("span_a"):
            paddle.to_tensor(np.ones(4)) * 2
        prof.stop()
        f0 = str(tmp_path / "rank_0.json")
        prof.export(f0)
        traces = csp.load_rank_traces([f0])
        rows = csp.op_summary(traces)
        assert any(r["name"] == "span_a" for r in rows)


class TestBenchGate:
    BASE = {"configs": {
        "gpt": {"tokens_per_sec_chip": 100000.0},
        "resnet": {"samples_per_sec_chip": 2000.0},
        "ps": {"examples_per_sec": 10000.0}}}

    def test_ok_and_improved(self):
        cur = {"configs": {
            "gpt": {"tokens_per_sec_chip": 101000.0},
            "resnet": {"samples_per_sec_chip": 2500.0},
            "ps": {"examples_per_sec": 9900.0}}}
        rows = gate.compare(self.BASE, cur, 0.05)
        by = {r[0]: r[5] for r in rows}
        assert by == {"gpt": "ok", "resnet": "improved", "ps": "ok"}

    def test_regression_detected(self):
        cur = {"configs": {
            "gpt": {"tokens_per_sec_chip": 80000.0},
            "resnet": {"samples_per_sec_chip": 2000.0},
            "ps": {"examples_per_sec": 10000.0}}}
        rows = gate.compare(self.BASE, cur, 0.05)
        assert ("gpt", "tokens_per_sec_chip", 100000.0, 80000.0, -0.2,
                "regressed") in rows

    def test_same_metric_enforced(self):
        """Current config reporting a DIFFERENT (higher-priority) metric
        must read as missing, not compared across units."""
        cur = {"configs": {
            "gpt": {"tokens_per_sec_chip": 100000.0},
            "resnet": {"tokens_per_sec_chip": 500000.0},  # unit switch
            "ps": {"examples_per_sec": 10000.0}}}
        rows = gate.compare(self.BASE, cur, 0.05)
        by = {r[0]: r[5] for r in rows}
        assert by["resnet"] == "missing"

    def test_zero_baseline_unusable(self):
        base = {"configs": {"gpt": {"tokens_per_sec_chip": 0.0}}}
        cur = {"configs": {"gpt": {"tokens_per_sec_chip": 1.0}}}
        rows = gate.compare(base, cur, 0.05)
        assert rows[0][5] == "missing"

    def test_duplicate_rank_files_rejected(self, tmp_path):
        (tmp_path / "rank_0.json").write_text(json.dumps(_trace([])))
        (tmp_path / "worker_0.json").write_text(json.dumps(_trace([])))
        with pytest.raises(ValueError, match="rank 0"):
            csp.load_rank_traces(str(tmp_path))

    def test_missing_config_fails(self):
        cur = {"configs": {"gpt": {"tokens_per_sec_chip": 100000.0}}}
        rows = gate.compare(self.BASE, cur, 0.05)
        assert any(r[5] == "missing" for r in rows)


class TestObservabilitySchemaGate:
    """check_bench_result.py validates `observability` sections against the
    step-record and event schemas (fleet-observability satellite)."""

    @staticmethod
    def _good_doc():
        import time as _time
        from paddle_tpu.profiler.monitor import make_step_record
        return {
            "configs": {"gpt": {"tokens_per_sec_chip": 100000.0}},
            "observability": {
                "step_records": [make_step_record(
                    step=10, window_steps=10, window_time_s=1.0)],
                "events_tail": [{"ts": _time.time(), "kind": "retrace",
                                 "host": "trainer-0", "severity": "info"}],
            },
        }

    def test_valid_observability_passes(self):
        doc = self._good_doc()
        assert gate.validate_observability(doc) == []

    def test_bad_step_record_and_event_named(self):
        doc = self._good_doc()
        doc["observability"]["step_records"][0].pop("ts")
        doc["observability"]["events_tail"][0]["kind"] = "Not Legal"
        problems = gate.validate_observability(doc)
        assert len(problems) == 2
        assert any("step_records[0]" in p and "ts" in p for p in problems)
        assert any("events_tail[0]" in p and "kind" in p for p in problems)

    def test_per_config_blocks_validated(self):
        doc = self._good_doc()
        doc["configs"]["gpt"]["observability"] = {
            "step_records": [{"bogus": True}]}
        problems = gate.validate_observability(doc)
        assert any("configs.gpt.observability" in p for p in problems)

    def test_missing_observability_is_fine(self):
        assert gate.validate_observability(
            {"configs": {"gpt": {"tokens_per_sec_chip": 1.0}}}) == []

    def test_gate_fails_on_schema_violation(self, tmp_path, capsys):
        base = tmp_path / "base.json"
        cur = tmp_path / "cur.json"
        base.write_text(json.dumps(self._good_doc()))
        bad = self._good_doc()
        bad["observability"]["events_tail"][0].pop("host")
        cur.write_text(json.dumps(bad))
        rc = gate.main(["--baseline", str(base), "--current", str(cur)])
        assert rc == 1
        out = capsys.readouterr().out
        assert "observability schema violations" in out
        # --no-obs-check restores the old perf-only gate
        assert gate.main(["--baseline", str(base), "--current", str(cur),
                          "--no-obs-check"]) == 0


class TestAsyncCheckpointMetricsGate:
    """checkpoint_async_* families in an observability metrics snapshot
    must be the right kind with a consistent shape (sharded-checkpoint
    satellite)."""

    @staticmethod
    def _doc_with_metrics(metrics):
        doc = TestObservabilitySchemaGate._good_doc()
        doc["observability"]["metrics"] = metrics
        return doc

    @staticmethod
    def _good_metrics():
        return {
            "checkpoint_async_pending": {
                "kind": "gauge", "help": "h",
                "values": [{"labels": {}, "value": 0.0}]},
            "checkpoint_async_bytes": {
                "kind": "counter", "help": "h",
                "values": [{"labels": {}, "value": 1024.0}]},
            "checkpoint_async_seconds": {
                "kind": "histogram", "help": "h",
                "values": [{"labels": {},
                            "buckets": {"0.1": 1, "+Inf": 2},
                            "sum": 0.5, "count": 2}]},
        }

    def test_live_registry_snapshot_validates(self):
        # the REAL families registered by sharded_checkpoint must pass
        import paddle_tpu.distributed.sharded_checkpoint  # noqa: F401
        from paddle_tpu.profiler.metrics import default_registry
        snap = default_registry().snapshot()
        assert set(_k for _k in snap if _k.startswith("checkpoint_async")) \
            == {"checkpoint_async_pending", "checkpoint_async_bytes",
                "checkpoint_async_seconds"}
        doc = self._doc_with_metrics(snap)
        assert gate.validate_observability(doc) == []

    def test_good_families_pass(self):
        assert gate.validate_observability(
            self._doc_with_metrics(self._good_metrics())) == []

    def test_wrong_kind_named(self):
        m = self._good_metrics()
        m["checkpoint_async_pending"]["kind"] = "counter"
        problems = gate.validate_observability(self._doc_with_metrics(m))
        assert any("checkpoint_async_pending" in p and "gauge" in p
                   for p in problems)

    def test_inconsistent_histogram_named(self):
        m = self._good_metrics()
        m["checkpoint_async_seconds"]["values"][0]["buckets"]["+Inf"] = 99
        problems = gate.validate_observability(self._doc_with_metrics(m))
        assert any("checkpoint_async_seconds" in p and "inconsistent" in p
                   for p in problems)

    def test_negative_value_and_unknown_family_named(self):
        m = self._good_metrics()
        m["checkpoint_async_bytes"]["values"][0]["value"] = -1
        m["checkpoint_async_queue"] = {"kind": "gauge", "values": []}
        problems = gate.validate_observability(self._doc_with_metrics(m))
        assert any("checkpoint_async_bytes" in p for p in problems)
        assert any("checkpoint_async_queue" in p and "unknown" in p
                   for p in problems)

    def test_other_families_ignored(self):
        doc = self._doc_with_metrics(
            {"op_calls_total": {"kind": "counter", "values": "garbage"}})
        assert gate.validate_observability(doc) == []

    def test_malformed_values_reported_not_crash(self):
        for bad in ("garbage", [1, 2], [{"value": 1}, "x"]):
            m = {"checkpoint_async_pending": {"kind": "gauge",
                                             "values": bad}}
            problems = gate.validate_observability(self._doc_with_metrics(m))
            assert any("checkpoint_async_pending" in p for p in problems), \
                f"values={bad!r} did not produce a named violation"


class TestXplaneLaneMerge:
    """cross_stack_profiler --xplane_dir: each rank's backend work lanes
    interleave under its host lane, clock-shifted to the shared zero."""

    @staticmethod
    def _xplane_doc():
        return {"traceEvents": [
            {"ph": "M", "name": "process_name", "pid": 9,
             "args": {"name": "/host:CPU"}},
            {"ph": "M", "name": "thread_name", "pid": 9, "tid": 1,
             "args": {"name": "python"}},
            {"ph": "X", "name": "$frame", "ts": 5000.0, "dur": 100.0,
             "pid": 9, "tid": 1},
            {"ph": "X", "name": "dot.3", "ts": 5010.0, "dur": 40.0,
             "pid": 9, "tid": 2},
            {"ph": "X", "name": "fusion.1", "ts": 5060.0, "dur": 20.0,
             "pid": 9, "tid": 2},
            {"ph": "X", "name": "ThreadpoolListener::StartRegion",
             "ts": 5000.0, "dur": 500.0, "pid": 9, "tid": 2},
        ]}

    def test_device_lanes_interleave_under_rank(self, tmp_path):
        host = {0: _trace([("train_step", 1000.0, 50.0)])}
        merged = csp.merge_traces(
            host, align=True, xplane={0: self._xplane_doc()["traceEvents"]})
        evs = merged["traceEvents"]
        work = [e for e in evs if e.get("ph") == "X"
                and e["name"] in ("dot.3", "fusion.1")]
        assert len(work) == 2
        assert all(e["pid"] == 0 for e in work), "device lane not re-homed"
        # clock shifted: first work event at 0, second keeps its offset
        assert min(e["ts"] for e in work) == 0.0
        assert max(e["ts"] for e in work) == pytest.approx(50.0)
        # infra markers stay out; synthetic thread is labeled xplane:
        assert not any(e.get("name", "").startswith("ThreadpoolListener")
                       for e in evs)
        tnames = [e["args"]["name"] for e in evs
                  if e.get("ph") == "M" and e["name"] == "thread_name"]
        assert any(t.startswith("xplane:") for t in tnames)
        assert merged["metadata"]["xplane_ranks"] == [0]

    def test_load_xplane_dir_files_and_session_dirs(self, tmp_path):
        import gzip
        d = tmp_path / "xp"
        d.mkdir()
        (d / "rank_0.trace.json.gz").write_bytes(
            gzip.compress(json.dumps(self._xplane_doc()).encode()))
        sess = d / "rank_1" / "plugins" / "profile" / "2026_01_01"
        sess.mkdir(parents=True)
        (sess / "host.trace.json.gz").write_bytes(
            gzip.compress(json.dumps(self._xplane_doc()).encode()))
        by_rank = csp.load_xplane_dir(str(d))
        assert set(by_rank) == {0, 1}
        assert any(e.get("name") == "dot.3" for e in by_rank[0])

    def test_cli_with_xplane_dir(self, tmp_path):
        td = tmp_path / "traces"
        td.mkdir()
        (td / "rank_0.json").write_text(json.dumps(
            _trace([("step", 0, 100.0)])))
        xd = tmp_path / "xp"
        xd.mkdir()
        (xd / "rank_0.json").write_text(json.dumps(self._xplane_doc()))
        out = tmp_path / "merged.json"
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools",
                                          "cross_stack_profiler.py"),
             "--trace_dir", str(td), "--out", str(out),
             "--xplane_dir", str(xd)],
            capture_output=True, text=True, timeout=120)
        assert r.returncode == 0, r.stderr
        doc = json.load(open(out))
        assert any(e.get("name") == "dot.3" for e in doc["traceEvents"])
        assert "1 xplane device traces" in r.stdout


class TestObsTailDiagnoseAndFollow:
    @staticmethod
    def _diag_event(step=40, dominant="data_wait"):
        return {"ts": 1722700000.0, "kind": "step_diagnosis",
                "host": "trainer-0", "severity": "info", "wall_s": 2.0,
                "steps": 20, "step": step, "dominant": dominant,
                "dominant_frac": 0.55,
                "terms": {"data_wait": 1.1, "host_dispatch": 0.3,
                          "device_compute": 0.0, "unattributed": 0.6}}

    def test_diagnose_renders_breakdown(self, tmp_path, capsys):
        import obs_tail
        path = tmp_path / "ev.jsonl"
        with open(path, "w") as f:
            f.write(json.dumps(self._diag_event()) + "\n")
            f.write(json.dumps({"ts": 1.0, "kind": "retrace",
                                "host": "trainer-0"}) + "\n")
        rc = obs_tail.main([str(path), "--diagnose"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "dominant=data_wait (55% of wall)" in out
        assert "data_wait=1100.0ms" in out
        assert "step 40" in out
        assert "retrace" not in out  # --diagnose implies the kind filter

    def test_diagnose_respects_explicit_kind(self, tmp_path, capsys):
        import obs_tail
        path = tmp_path / "ev.jsonl"
        with open(path, "w") as f:
            f.write(json.dumps({"ts": 1.0, "kind": "retrace",
                                "host": "h"}) + "\n")
        rc = obs_tail.main([str(path), "--diagnose", "--kind", "retrace"])
        assert rc == 0
        assert "retrace" in capsys.readouterr().out

    def test_follow_for_is_time_bounded(self, tmp_path, capsys):
        """Satellite: --follow gets direct (and bounded) coverage — events
        appended while following are printed, and --follow-for returns."""
        import threading as _threading
        import time as _time
        import obs_tail
        path = tmp_path / "ev.jsonl"
        with open(path, "w") as f:
            f.write(json.dumps({"ts": 1.0, "kind": "retrace",
                                "host": "h", "seq": 0}) + "\n")

        def append_later():
            _time.sleep(0.4)
            with open(path, "a") as f:
                f.write(json.dumps({"ts": 2.0, "kind": "retrace",
                                    "host": "h", "seq": 1}) + "\n")

        th = _threading.Thread(target=append_later)
        th.start()
        t0 = _time.monotonic()
        rc = obs_tail.main([str(path), "--follow", "--follow-for", "1.2",
                            "--json"])
        took = _time.monotonic() - t0
        th.join()
        assert rc == 0
        assert took < 5.0, "follow-for did not bound the tail"
        lines = [json.loads(l) for l in
                 capsys.readouterr().out.strip().splitlines()]
        assert [l["seq"] for l in lines] == [0, 1]


class TestDeviceTimeAndMemoryGate:
    """check_bench_result: device_time provenance (incl. the new
    device_src="xplane") and device_memory_* family validation."""

    @staticmethod
    def _doc(dt=None, metrics=None):
        obs = {}
        if dt is not None:
            obs["device_time"] = dt
        if metrics is not None:
            obs["metrics"] = metrics
        return {"configs": {}, "observability": obs}

    def test_xplane_src_and_mode_valid(self):
        dt = {"mode": "xplane",
              "rows": [{"op": "matmul", "calls": 3, "host_ms": 1.0,
                        "device_ms": 0.5, "src": "xplane"},
                       {"op": "softmax", "calls": 3, "host_ms": 1.0,
                        "device_ms": 0.2, "src": "estimate"}]}
        assert gate.validate_observability(self._doc(dt=dt)) == []

    def test_unknown_src_and_mode_fail(self):
        dt = {"mode": "vibes",
              "rows": [{"op": "matmul", "calls": 1, "host_ms": 1.0,
                        "device_ms": 0.5, "src": "guessed"}]}
        problems = gate.validate_observability(self._doc(dt=dt))
        assert any("mode" in p and "vibes" in p for p in problems)
        assert any("src" in p and "guessed" in p for p in problems)

    def test_malformed_rows_named(self):
        dt = {"rows": [{"op": "", "calls": -1, "host_ms": "x",
                        "device_ms": 0.1, "src": "estimate"}, "junk"]}
        problems = gate.validate_observability(self._doc(dt=dt))
        assert any(".op" in p for p in problems)
        assert any(".calls" in p for p in problems)
        assert any(".host_ms" in p for p in problems)
        assert any("rows[1]" in p for p in problems)

    def test_device_memory_families(self):
        good = {"device_memory_bytes_in_use": {
            "kind": "gauge", "help": "by device",
            "values": [{"labels": {"device": "cpu:0"}, "value": 1024}]}}
        assert gate.validate_observability(self._doc(metrics=good)) == []
        bad = {"device_memory_peak_bytes": {
            "kind": "counter", "help": "",
            "values": [{"labels": {}, "value": -5}]}}
        problems = gate.validate_observability(self._doc(metrics=bad))
        assert any("expected gauge" in p for p in problems)
        missing = {"device_memory_peak_bytes": {
            "kind": "gauge", "help": "",
            "values": [{"labels": {}, "value": -5}]}}
        problems = gate.validate_observability(self._doc(metrics=missing))
        assert any("non-negative" in p for p in problems)
        assert any("'device' label" in p for p in problems)

    def test_real_capture_summary_device_time_validates(self, tmp_path):
        """A real CaptureSession summary's device_time block passes the
        gate with src=xplane rows (the BENCH_r06 shape)."""
        import numpy as np
        import paddle_tpu as paddle
        from paddle_tpu.profiler import xplane
        sess = xplane.CaptureSession(str(tmp_path / "gate"))
        sess.start()
        try:
            a = paddle.to_tensor(np.ones((64, 64), np.float32))
            paddle.matmul(a, a)
        finally:
            summary = sess.stop(steps=1)
        assert gate.validate_observability(
            self._doc(dt=summary["device_time"])) == []


class TestHealthGate:
    """check_bench_result: the bench `observability.health` block and the
    `health_*`/`amp_*` metric families (training-health PR)."""

    @staticmethod
    def _doc(health=None, metrics=None):
        doc = {"configs": {"gpt": {"tokens_per_sec_chip": 1.0}},
               "observability": {}}
        if health is not None:
            doc["observability"]["health"] = health
        if metrics is not None:
            doc["observability"]["metrics"] = metrics
        return doc

    @staticmethod
    def _good_block():
        return {"step_ms_off": 10.0, "step_ms_on": 10.1,
                "overhead_frac": 0.01, "interval": 1, "groups": 13,
                "sentinel": {"loss": 2.5, "grad_norm": 1.0,
                             "update_ratio": 0.001, "nonfinite": False},
                "note": "probe"}

    @staticmethod
    def _good_metrics():
        return {
            "health_loss": {"kind": "gauge", "help": "",
                            "values": [{"labels": {}, "value": -0.5}]},
            "health_layer_grad_norm": {
                "kind": "gauge", "help": "",
                "values": [{"labels": {"group": "fc1"}, "value": 2.0}]},
            "health_nonfinite_total": {
                "kind": "counter", "help": "",
                "values": [{"labels": {"src": "sentinel"}, "value": 1}]},
            "amp_found_inf_total": {"kind": "counter", "help": "",
                                    "values": [{"labels": {}, "value": 2}]},
            "amp_loss_scale": {"kind": "gauge", "help": "",
                               "values": [{"labels": {}, "value": 32768.0}]},
            "fleet_health_status": {
                "kind": "gauge", "help": "",
                "values": [{"labels": {"host": "t0"}, "value": 2}]},
        }

    def test_good_block_and_metrics_pass(self):
        assert gate.validate_observability(
            self._doc(self._good_block(), self._good_metrics())) == []

    def test_failed_probe_reports_itself(self):
        assert gate.validate_observability(
            self._doc({"error": "TimeoutError: slow box"})) == []

    def test_bad_overhead_and_negative_ms_named(self):
        h = self._good_block()
        h["overhead_frac"] = -2.0
        h["step_ms_on"] = -1.0
        problems = gate.validate_observability(self._doc(h))
        assert any("overhead_frac" in p for p in problems)
        assert any("step_ms_on" in p for p in problems)

    def test_bad_sentinel_named(self):
        h = self._good_block()
        h["sentinel"]["nonfinite"] = "yes"
        h["sentinel"]["grad_norm"] = "big"
        problems = gate.validate_observability(self._doc(h))
        assert any("nonfinite" in p for p in problems)
        assert any("grad_norm" in p for p in problems)

    def test_wrong_kind_and_unknown_family_named(self):
        m = self._good_metrics()
        m["health_nonfinite_total"]["kind"] = "gauge"
        m["health_surprise_total"] = {"kind": "counter", "values": []}
        problems = gate.validate_observability(self._doc(metrics=m))
        assert any("health_nonfinite_total" in p and "counter" in p
                   for p in problems)
        assert any("health_surprise_total" in p and "unknown" in p
                   for p in problems)

    def test_missing_label_and_nonfinite_value_named(self):
        m = self._good_metrics()
        m["health_layer_grad_norm"]["values"][0]["labels"] = {}
        m["health_loss"]["values"][0]["value"] = float("nan")
        problems = gate.validate_observability(self._doc(metrics=m))
        assert any("'group' label" in p for p in problems)
        assert any("health_loss" in p and "finite" in p for p in problems)

    def test_negative_counter_named(self):
        m = self._good_metrics()
        m["amp_found_inf_total"]["values"][0]["value"] = -1
        problems = gate.validate_observability(self._doc(metrics=m))
        assert any("amp_found_inf_total" in p and "negative" in p
                   for p in problems)

    def test_live_registry_snapshot_validates(self):
        """Real registry series seeded by the health plane pass the gate."""
        from paddle_tpu.profiler import health
        from paddle_tpu.profiler.metrics import default_registry
        health.reset()
        health.record_step_stats(
            {"loss": 1.5, "nonfinite": False, "grad_norm": 2.0,
             "update_ratio": 0.01, "group_grad_norms": {"fc1": 2.0}},
            step=1)
        snap = default_registry().snapshot()
        assert gate.validate_observability(self._doc(metrics=snap)) == []

    def test_bench_probe_block_validates(self):
        """bench.health_overhead_probe output passes the gate on a tiny
        model (the BENCH_r06 shape)."""
        import paddle_tpu as paddle
        from paddle_tpu import nn, optimizer
        from paddle_tpu.jit import TrainStep
        from paddle_tpu.nn import functional as F
        sys.path.insert(0, REPO)
        try:
            import bench
        finally:
            sys.path.remove(REPO)
        paddle.seed(0)
        net = nn.Linear(8, 4)
        x = paddle.to_tensor(np.ones((4, 8), np.float32))
        y = paddle.to_tensor(np.array([0, 1, 2, 3], np.int64))

        def mk(on):
            opt = optimizer.SGD(learning_rate=0.01,
                                parameters=net.parameters())
            return TrainStep(net, F.cross_entropy, opt, health=on)

        block = bench.health_overhead_probe(mk, (x, y), iters=3, warmup=1)
        assert block["groups"] == 1
        assert block["sentinel"]["nonfinite"] is False
        assert gate.validate_observability(self._doc(block)) == []


class TestObsTailHealth:
    """obs_tail --health: filter + operator rendering of the numerics
    plane's events."""

    @staticmethod
    def _write(tmp_path):
        path = tmp_path / "ev.jsonl"
        recs = [
            {"ts": 10.0, "kind": "retrace", "host": "t0", "name": "mm"},
            {"ts": 11.0, "kind": "tensor_health", "host": "t0",
             "severity": "error", "src": "sentinel", "step": 40,
             "bad_groups": ["blocks.3"]},
            {"ts": 12.0, "kind": "tensor_health", "host": "t0",
             "severity": "error", "src": "eager", "op": "matmul",
             "layer": "blocks.3.attn", "bad_kind": "nan",
             "shape": [8, 64], "dtype": "float32", "output_index": 0},
            {"ts": 13.0, "kind": "health_alert", "host": "t0",
             "severity": "warn", "signal": "grad_explosion",
             "grad_norm": 1e9, "step": 41},
            {"ts": 14.0, "kind": "health_rollback", "host": "t0",
             "severity": "warn", "reason": "nonfinite", "step": 42,
             "restored_step": 35, "rollbacks": 1},
            {"ts": 15.0, "kind": "fleet_health", "host": "t0",
             "severity": "error", "unhealthy": "trainer-1",
             "status": "diverged"},
        ]
        with open(path, "w") as f:
            for r in recs:
                f.write(json.dumps(r) + "\n")
        return str(path)

    def test_health_filters_and_renders(self, tmp_path, capsys):
        import obs_tail
        rc = obs_tail.main([self._write(tmp_path), "--health"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "retrace" not in out          # filtered to health kinds
        assert "nan in blocks.3.attn op=matmul" in out
        assert "blocks.3" in out             # sentinel bad_groups
        assert "grad_explosion" in out
        assert "restored checkpoint step 35" in out
        assert "host trainer-1 went diverged" in out

    def test_health_respects_explicit_kind(self, tmp_path, capsys):
        import obs_tail
        rc = obs_tail.main([self._write(tmp_path), "--health",
                            "--kind", "health_rollback"])
        out = capsys.readouterr().out
        assert rc == 0
        lines = [l for l in out.splitlines() if l.strip()]
        assert len(lines) == 1 and "restored checkpoint" in lines[0]

    def test_health_with_diagnose_combines(self, tmp_path, capsys):
        import obs_tail
        path = tmp_path / "ev.jsonl"
        with open(path, "w") as f:
            f.write(json.dumps({"ts": 1.0, "kind": "health_alert",
                                "host": "t0", "signal": "loss_spike"}) + "\n")
            f.write(json.dumps({"ts": 2.0, "kind": "step_diagnosis",
                                "host": "t0", "wall_s": 1.0, "steps": 5,
                                "dominant": "data_wait",
                                "dominant_frac": 0.5,
                                "terms": {"data_wait": 0.5}}) + "\n")
        rc = obs_tail.main([str(path), "--health", "--diagnose"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "loss_spike" in out
        assert "dominant=data_wait" in out


class TestObsTailErrorPaths:
    def test_unreadable_file_exits_2(self, tmp_path, capsys):
        import obs_tail
        path = tmp_path / "ev.jsonl"
        path.write_text("{}\n")
        os.chmod(path, 0)
        try:
            if os.access(path, os.R_OK):
                pytest.skip("running as root: chmod 0 still readable")
            assert obs_tail.main([str(path)]) == 2
            assert "obs_tail:" in capsys.readouterr().err
        finally:
            os.chmod(path, 0o644)

    def test_follow_backlog_has_no_gap(self, tmp_path, capsys):
        """Events appended between backlog render and tail start must not
        be dropped: follow() reads the backlog through the SAME handle it
        tails."""
        import obs_tail
        path = tmp_path / "ev.jsonl"
        with open(path, "w") as f:
            for i in range(3):
                f.write(json.dumps({"ts": float(i), "kind": "retrace",
                                    "host": "h", "seq": i}) + "\n")

        real_parse = obs_tail.parse_lines
        appended = {"done": False}

        def racing_parse(lines):
            # first call = the backlog parse; append an event right after
            # the backlog lines were read but before the tail loop starts
            out = real_parse(lines)
            if not appended["done"]:
                appended["done"] = True
                with open(path, "a") as f:
                    f.write(json.dumps({"ts": 9.0, "kind": "retrace",
                                        "host": "h", "seq": 3}) + "\n")
            return out

        obs_tail.parse_lines = racing_parse
        try:
            rc = obs_tail.main([str(path), "--follow", "--follow-for",
                                "1.0", "--json"])
        finally:
            obs_tail.parse_lines = real_parse
        assert rc == 0
        seqs = [json.loads(l)["seq"] for l in
                capsys.readouterr().out.strip().splitlines()]
        assert seqs == [0, 1, 2, 3]  # the racing append is NOT lost


class TestPlatformAwareGate:
    """r06: cross-platform rounds/configs read 'incomparable', never
    'regressed' — a CPU dev-box round vs a TPU driver round is not a
    perf regression. Undeclared-vs-undeclared keeps the old behavior."""

    BASE = {"configs": {
        "gpt": {"tokens_per_sec_chip": 100000.0},
        "ps_cpu": {"examples_per_sec": 30000.0, "platform": "cpu"}}}

    def test_declared_mismatch_is_incomparable(self):
        cur = {"platform": "cpu", "configs": {
            "gpt": {"tokens_per_sec_chip": 50.0, "platform": "cpu"},
            "ps_cpu": {"examples_per_sec": 3000.0, "platform": "cpu"}}}
        rows = gate.compare(self.BASE, cur, 0.05,
                            baseline_platform="tpu")
        by = {r[0]: r[5] for r in rows}
        # round platforms differ -> EVERY row incomparable, including the
        # all-CPU PS config (it ran on a different HOST)
        assert by == {"gpt": "incomparable", "ps_cpu": "incomparable"}

    def test_no_assumption_keeps_status_quo(self):
        cur = {"configs": {
            "gpt": {"tokens_per_sec_chip": 50.0},
            "ps_cpu": {"examples_per_sec": 30000.0}}}
        rows = gate.compare(self.BASE, cur, 0.05)
        by = {r[0]: r[5] for r in rows}
        assert by["gpt"] == "regressed"

    def test_incomparable_does_not_fail_cli(self, tmp_path):
        base = tmp_path / "base.json"
        cur = tmp_path / "cur.json"
        base.write_text(json.dumps(self.BASE))
        cur.write_text(json.dumps({"platform": "cpu", "configs": {
            "gpt": {"tokens_per_sec_chip": 50.0, "platform": "cpu"},
            "ps_cpu": {"examples_per_sec": 3000.0, "platform": "cpu"}}}))
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools",
                                          "check_bench_result.py"),
             "--baseline", str(base), "--current", str(cur),
             "--assume-baseline-platform", "tpu"],
            capture_output=True, text=True, timeout=120)
        assert r.returncode == 0, r.stdout + r.stderr
        assert "incomparable" in r.stdout


class TestSegmentsAndConvFusionGate:
    """r06 satellite: the per-segment breakdown block and the conv-fusion
    A/B probe block validate with NAMED violations."""

    @staticmethod
    def _doc(profile=None, conv_fusion=None):
        cfg = {"samples_per_sec_chip": 100.0}
        if profile is not None:
            cfg["profile"] = profile
        if conv_fusion is not None:
            cfg["conv_fusion"] = conv_fusion
        return {"configs": {"resnet50": cfg}}

    def test_valid_segments_pass(self):
        doc = self._doc(profile={"segments": {
            "segments": {
                "attention_fwd": {"device_ms": 1.5, "events": 10,
                                  "frac": 0.5},
                "unattributed": {"device_ms": 1.5, "events": 3,
                                 "frac": 0.5}},
            "total_device_ms": 3.0, "attributed_frac": 0.5}})
        assert gate.validate_observability(doc) == []

    def test_garbled_segments_named(self):
        doc = self._doc(profile={"segments": {
            "segments": {
                "mlp": {"device_ms": -1.0, "events": 2, "frac": 1.7}},
            "total_device_ms": "nope", "attributed_frac": None}})
        probs = gate.validate_observability(doc)
        blob = "\n".join(probs)
        assert "configs.resnet50.profile.segments" in blob
        assert "device_ms" in blob and "frac" in blob \
            and "total_device_ms" in blob

    def test_valid_conv_fusion_passes(self):
        doc = self._doc(conv_fusion={
            "enabled": True, "engaged": False,
            "probe_ms_on": 12.5, "probe_ms_off": 14.0,
            "speedup_vs_off": 1.12, "hbm_gb_per_step_on": 40.0,
            "hbm_gb_per_step_off": 46.0, "hbm_pct_saved": 13.0,
            "kernel_stats": {"pallas_fwd": 0, "xla_fwd": 0}})
        assert gate.validate_observability(doc) == []

    def test_garbled_conv_fusion_named(self):
        doc = self._doc(conv_fusion={
            "enabled": "yes", "probe_ms_on": -3,
            "hbm_pct_saved": 250.0,
            "kernel_stats": {"pallas_fwd": -1}})
        probs = gate.validate_observability(doc)
        blob = "\n".join(probs)
        assert "configs.resnet50.conv_fusion.enabled" in blob
        assert "probe_ms_on" in blob
        assert "hbm_pct_saved" in blob
        assert "kernel_stats" in blob

    def test_probe_error_block_not_gated(self):
        doc = self._doc(conv_fusion={"enabled": True,
                                     "error": "RuntimeError: boom"})
        assert gate.validate_observability(doc) == []

    def test_micro_ab_block_validates(self):
        doc = self._doc(conv_fusion={
            "enabled": True, "engaged": False,
            "micro_ab": {"rows": [
                {"shape": "b128x56x56 64->256",
                 "composed_gb_cost_analysis": 3.8,
                 "composed_gb_model": 0.87, "fused_gb_model": 0.67,
                 "pct_saved": 23.5}],
                "total_pct_saved": 23.5}})
        assert gate.validate_observability(doc) == []
        bad = self._doc(conv_fusion={
            "enabled": True,
            "micro_ab": {"rows": [{"shape": 7, "fused_gb_model": -1,
                                   "pct_saved": 120.0}]}})
        blob = "\n".join(gate.validate_observability(bad))
        assert "micro_ab.rows[0].shape" in blob
        assert "fused_gb_model" in blob and "pct_saved" in blob


class TestScaleAwareGate:
    """Review regression: a scale=ci round must never gate against a
    full-scale baseline even on the SAME platform (bench.py's contract:
    scaled rounds can never be mistaken for full-scale numbers)."""

    def test_scale_mismatch_is_incomparable(self):
        base = {"configs": {"gpt": {"tokens_per_sec_chip": 100000.0,
                                    "platform": "tpu"}}}
        cur = {"configs": {"gpt": {"tokens_per_sec_chip": 50.0,
                                   "platform": "tpu", "scale": "ci"}}}
        rows = gate.compare(base, cur, 0.05)
        assert rows[0][5] == "incomparable"
        # and the reverse direction (full vs ci baseline)
        rows = gate.compare(cur, base, 0.05)
        assert rows[0][5] == "incomparable"

    def test_matching_scales_still_gate(self):
        base = {"configs": {"gpt": {"tokens_per_sec_chip": 100000.0,
                                    "platform": "tpu", "scale": "ci"}}}
        cur = {"configs": {"gpt": {"tokens_per_sec_chip": 80000.0,
                                   "platform": "tpu", "scale": "ci"}}}
        rows = gate.compare(base, cur, 0.05)
        assert rows[0][5] == "regressed"


class TestControllerGate:
    """`controller_*` metric families and `controller_decision` events in
    observability blocks (self-driving fleet satellite): kind/label/shape
    contracts with named violations."""

    @staticmethod
    def _doc(metrics=None, events=None):
        doc = {"configs": {"gpt": {"tokens_per_sec_chip": 1.0}},
               "observability": {}}
        if metrics is not None:
            doc["observability"]["metrics"] = metrics
        if events is not None:
            doc["observability"]["events_tail"] = events
        return doc

    @staticmethod
    def _decision(**over):
        ev = {"ts": 12.0, "kind": "controller_decision", "host": "sup-0",
              "severity": "warn", "policy": "straggler_evict",
              "action": "evict", "target": "trainer-1",
              "outcome": "applied", "decision": 1, "np": 1,
              "evidence": {"windows": 3, "p50_s": 0.4}, "dry_run": False}
        ev.update(over)
        return ev

    def test_valid_controller_metrics_and_event_pass(self):
        metrics = {
            "controller_decisions_total": {"kind": "counter", "values": [
                {"labels": {"policy": "straggler_evict",
                            "outcome": "applied"}, "value": 1}]},
            "controller_evictions_total": {"kind": "counter", "values": [
                {"labels": {"host": "trainer-1"}, "value": 1}]},
            "controller_relaunch_to_first_step_seconds": {
                "kind": "gauge", "values": [
                    {"labels": {"policy": "straggler_evict"},
                     "value": 2.5}]},
        }
        doc = self._doc(metrics=metrics, events=[self._decision()])
        assert gate.validate_observability(doc) == []

    def test_live_registry_snapshot_passes(self):
        from paddle_tpu.profiler import metrics as metrics_mod
        from paddle_tpu.distributed.fleet import controller as ctl
        ctl._M_DECISIONS.inc(policy="health_rollback", outcome="dry_run")
        ctl._M_ROLLBACKS.inc(host="trainer-0")
        snap = metrics_mod.default_registry().snapshot()
        ctl_fams = {k: v for k, v in snap.items()
                    if k.startswith("controller_")}
        assert ctl_fams
        assert gate.validate_observability(self._doc(metrics=ctl_fams)) == []

    def test_unknown_family_and_wrong_kind_named(self):
        metrics = {
            "controller_bogus_total": {"kind": "counter", "values": []},
            "controller_evictions_total": {"kind": "gauge", "values": []},
        }
        blob = "\n".join(gate.validate_observability(self._doc(
            metrics=metrics)))
        assert "controller_bogus_total" in blob and "unknown" in blob
        assert "controller_evictions_total" in blob and "gauge" in blob

    def test_missing_label_bad_outcome_negative_value_named(self):
        metrics = {
            "controller_decisions_total": {"kind": "counter", "values": [
                {"labels": {"policy": "straggler_evict",
                            "outcome": "exploded"}, "value": 1},
                {"labels": {"outcome": "applied"}, "value": -3},
            ]},
        }
        blob = "\n".join(gate.validate_observability(self._doc(
            metrics=metrics)))
        assert "'exploded'" in blob
        assert "missing the 'policy' label" in blob
        assert "-3" in blob

    def test_decision_event_contract_violations_named(self):
        bad = [
            self._decision(outcome="maybe"),
            self._decision(decision=0),
            self._decision(policy=""),
            self._decision(evidence="not-an-object"),
        ]
        blob = "\n".join(gate.validate_observability(self._doc(events=bad)))
        assert "'maybe'" in blob
        assert "'decision' must be a positive integer" in blob
        assert "'policy' must be a non-empty string" in blob
        assert "'evidence' must be an object" in blob

    def test_non_decision_events_not_held_to_decision_contract(self):
        ev = {"ts": 1.0, "kind": "elastic_restart", "host": "sup-0",
              "severity": "warn", "reason": "controller_evict"}
        assert gate.validate_observability(self._doc(events=[ev])) == []


class TestObsTailController:
    """obs_tail --controller: filter + operator rendering of the fleet
    controller's decision events."""

    @staticmethod
    def _write(tmp_path):
        path = tmp_path / "ev.jsonl"
        recs = [
            {"ts": 10.0, "kind": "retrace", "host": "t0", "name": "mm"},
            {"ts": 11.0, "kind": "controller_decision", "host": "sup-0",
             "severity": "warn", "policy": "straggler_evict",
             "action": "evict", "target": "trainer-1", "outcome": "applied",
             "decision": 1, "np": 1,
             "evidence": {"windows": 3, "p50_s": 0.41,
                          "straggling": ["trainer-1"]}, "dry_run": False},
            {"ts": 12.0, "kind": "controller_decision", "host": "sup-0",
             "severity": "info", "policy": "straggler_evict",
             "action": "relaunch_observed", "outcome": "applied",
             "decision": 1, "relaunch_to_first_step_s": 2.75,
             "dry_run": False},
            {"ts": 13.0, "kind": "controller_decision", "host": "sup-0",
             "severity": "warn", "policy": "health_rollback",
             "action": "rollback", "target": "trainer-0",
             "outcome": "dry_run", "decision": 2, "np": 2,
             "evidence": {"diverged": ["trainer-0"]}, "dry_run": True},
        ]
        with open(path, "w") as f:
            for r in recs:
                f.write(json.dumps(r) + "\n")
        return str(path)

    def test_controller_filters_and_renders(self, tmp_path, capsys):
        import obs_tail
        rc = obs_tail.main([self._write(tmp_path), "--controller"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "retrace" not in out          # filtered to decisions
        assert "straggler_evict" in out
        assert "target=trainer-1" in out and "windows=3" in out
        assert "relaunch→first-step 2.75s" in out
        assert "DRY-RUN" in out              # the dry-run rollback line
        assert "health_rollback" in out

    def test_controller_composes_with_health(self, tmp_path, capsys):
        import obs_tail
        path = tmp_path / "ev.jsonl"
        with open(path, "w") as f:
            f.write(json.dumps({"ts": 1.0, "kind": "health_alert",
                                "host": "t0", "signal": "loss_spike"}) + "\n")
            f.write(json.dumps({"ts": 2.0, "kind": "controller_decision",
                                "host": "sup-0", "policy": "health_rollback",
                                "action": "rollback", "outcome": "applied",
                                "decision": 3}) + "\n")
        rc = obs_tail.main([str(path), "--controller", "--health"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "loss_spike" in out
        assert "health_rollback" in out and "decision #3" in out

    def test_controller_respects_explicit_kind(self, tmp_path, capsys):
        import obs_tail
        rc = obs_tail.main([self._write(tmp_path), "--controller",
                            "--kind", "retrace"])
        out = capsys.readouterr().out
        assert rc == 0
        # explicit --kind composes: retraces AND decisions both stream
        assert "retrace" in out
        assert "straggler_evict" in out


class TestServingGate:
    """`serving_*` metric families + the gpt2_decode config block
    (paged-KV decode satellite): kind/label/shape contracts and the
    TTFT/TPOT/goodput/A/B decode-bench contract, named violations."""

    @staticmethod
    def _doc(cfg=None, metrics=None):
        doc = {"configs": {"gpt2_decode": cfg or
                           {"tokens_per_sec_chip": 50.0}}}
        if metrics is not None:
            doc["observability"] = {"metrics": metrics}
        return doc

    @staticmethod
    def _decode_cfg(**over):
        cfg = {
            "tokens_per_sec_chip": 66.0, "decode_tokens_per_sec": 220.0,
            "goodput_tokens": 240, "streams": 24, "completed": 24,
            "preemptions": 0, "batch_occupancy_mean": 3.9,
            "serving": {"ttft_s": {"p50": 0.4, "p99": 1.2},
                        "tpot_s": {"p50": 0.004, "p99": 0.02},
                        "wall_s": 3.6},
            "paged_vs_dense": {
                "rows": [{"ctx": 32, "paged_ms_per_token": 2.0,
                          "dense_ms_per_token": 2.6},
                         {"ctx": 128, "paged_ms_per_token": 1.9,
                          "dense_ms_per_token": 5.9}],
                "paged_growth": 0.95, "dense_growth": 2.27,
                "speedup_at_max_ctx": 3.1},
        }
        cfg.update(over)
        return cfg

    def test_valid_decode_block_passes(self):
        assert gate.validate_observability(
            self._doc(cfg=self._decode_cfg())) == []

    def test_real_bench_block_passes(self):
        """The ACTUAL bench_gpt2_decode output shape validates (wired via
        a canned copy of its structure — the full bench run is the BENCH
        round's job)."""
        cfg = self._decode_cfg()
        cfg["platform"] = "cpu"
        cfg["scale"] = "ci"
        assert gate.validate_observability(self._doc(cfg=cfg)) == []

    def test_malformed_percentiles_and_rows_named(self):
        cfg = self._decode_cfg()
        cfg["serving"]["ttft_s"]["p99"] = -1.0
        cfg["serving"]["tpot_s"] = "fast"
        cfg["paged_vs_dense"]["rows"][0]["ctx"] = 0
        cfg["paged_vs_dense"]["rows"][1]["dense_ms_per_token"] = None
        cfg["goodput_tokens"] = -5
        blob = "\n".join(gate.validate_observability(self._doc(cfg=cfg)))
        assert "ttft_s.p99" in blob
        assert "tpot_s is not an object" in blob
        assert "rows[0].ctx" in blob
        assert "rows[1].dense_ms_per_token" in blob
        assert "goodput_tokens" in blob

    def test_missing_percentile_families_named(self):
        cfg = self._decode_cfg()
        del cfg["serving"]["ttft_s"]
        blob = "\n".join(gate.validate_observability(self._doc(cfg=cfg)))
        assert "serving.ttft_s is missing" in blob

    def test_error_ab_probe_reports_itself(self):
        cfg = self._decode_cfg(paged_vs_dense={"error": "XlaError: boom"})
        assert gate.validate_observability(self._doc(cfg=cfg)) == []

    @staticmethod
    def _v2_blocks():
        return {
            "fused_vs_eager": {"fused_ms_per_token": 9.0,
                               "eager_ms_per_token": 21.0,
                               "speedup": 2.33, "identical_tokens": True},
            "shared_prefix": {
                "on": {"min_free_pages": 60, "prefix_hit_tokens": 180,
                       "shared_admissions": 6, "cow_copies": 6,
                       "preemptions": 0, "completed": 8,
                       "leaked_pages": 0},
                "off": {"min_free_pages": 51, "prefix_hit_tokens": 0,
                        "shared_admissions": 0, "cow_copies": 0,
                        "preemptions": 0, "completed": 8,
                        "leaked_pages": 0},
            },
        }

    def test_valid_v2_ab_blocks_pass(self):
        cfg = self._decode_cfg(**self._v2_blocks())
        assert gate.validate_observability(self._doc(cfg=cfg)) == []

    def test_fused_eager_token_drift_fails_the_gate(self):
        """fused and eager decode disagreeing on tokens is a correctness
        bug the schema gate must catch, not a perf footnote."""
        blocks = self._v2_blocks()
        blocks["fused_vs_eager"]["identical_tokens"] = False
        blob = "\n".join(gate.validate_observability(
            self._doc(cfg=self._decode_cfg(**blocks))))
        assert "identical_tokens" in blob and "disagreed" in blob

    def test_shared_prefix_leak_and_phantom_hits_named(self):
        blocks = self._v2_blocks()
        blocks["shared_prefix"]["on"]["leaked_pages"] = 2
        blocks["shared_prefix"]["off"]["prefix_hit_tokens"] = 9
        blocks["shared_prefix"]["on"]["cow_copies"] = -1
        blob = "\n".join(gate.validate_observability(
            self._doc(cfg=self._decode_cfg(**blocks))))
        assert "on.leaked_pages" in blob
        assert "off.prefix_hit_tokens" in blob and "disabled" in blob
        assert "on.cow_copies" in blob

    def test_v2_error_blocks_report_themselves(self):
        cfg = self._decode_cfg(
            fused_vs_eager={"error": "XlaError: boom"},
            shared_prefix={"error": "RuntimeError: pool"})
        assert gate.validate_observability(self._doc(cfg=cfg)) == []

    @staticmethod
    def _distributed_blocks():
        return {
            "tp_decode": {"single_ms_per_token": 12.0,
                          "tp_ms_per_token": 12.4, "tp_degree": 2,
                          "tpot_ratio": 1.033, "identical_tokens": True,
                          "collective_bytes_by_link": {"ici": 512.0,
                                                       "dcn": 0.0}},
            "disagg": {"colocated_ms_per_token": 12.0,
                       "disagg_ms_per_token": 12.2, "tpot_ratio": 1.017,
                       "handoffs": 5, "prefill_workers": 1,
                       "decode_prefills": 0, "identical_tokens": True},
        }

    def test_valid_distributed_decode_blocks_pass(self):
        cfg = self._decode_cfg(**self._distributed_blocks())
        assert gate.validate_observability(self._doc(cfg=cfg)) == []

    def test_tp_token_drift_and_bad_degree_named(self):
        """TP is a layout change: token drift vs single-chip is a
        correctness bug, and a tp_degree < 2 means no sharding ran."""
        blocks = self._distributed_blocks()
        blocks["tp_decode"]["identical_tokens"] = False
        blocks["tp_decode"]["tp_degree"] = 1
        blocks["tp_decode"]["collective_bytes_by_link"]["ici"] = -1
        blob = "\n".join(gate.validate_observability(
            self._doc(cfg=self._decode_cfg(**blocks))))
        assert "tp_decode.identical_tokens" in blob and "disagreed" in blob
        assert "tp_decode.tp_degree" in blob
        assert "collective_bytes_by_link.ici" in blob

    def test_disagg_decode_side_prefill_fails_the_gate(self):
        """A nonzero decode-side prefill count means the stages were
        never actually split — the disaggregation claim is void."""
        blocks = self._distributed_blocks()
        blocks["disagg"]["decode_prefills"] = 3
        blocks["disagg"]["handoffs"] = 0
        blob = "\n".join(gate.validate_observability(
            self._doc(cfg=self._decode_cfg(**blocks))))
        assert "decode_prefills" in blob and "ran prefills itself" in blob
        assert "disagg.handoffs" in blob

    def test_distributed_blocks_may_skip_or_error(self):
        """A 1-device box skips the TP A/B; a failed probe reports
        itself — both stay schema-valid."""
        cfg = self._decode_cfg(
            tp_decode={"skipped": "needs >=2 devices"},
            disagg={"error": "RuntimeError: boom"})
        assert gate.validate_observability(self._doc(cfg=cfg)) == []

    def test_handoff_families_and_stage_enum_enforced(self):
        metrics = {
            "serving_handoff_wait_seconds": {
                "kind": "histogram", "values": [
                    {"labels": {"model": "m"},
                     "buckets": {"+Inf": 3}, "sum": 0.01, "count": 3}]},
            "serving_handoff_bytes_total": {
                "kind": "counter", "values": [
                    {"labels": {"model": "m"}, "value": 8192.0}]},
            "serving_handoff_depth": {
                "kind": "gauge", "values": [
                    {"labels": {"model": "m"}, "value": 0}]},
            "serving_stage_occupancy": {
                "kind": "gauge", "values": [
                    {"labels": {"model": "m", "stage": "prefill"},
                     "value": 1}]},
        }
        assert gate.validate_observability(self._doc(metrics=metrics)) == []
        metrics["serving_stage_occupancy"]["values"][0]["labels"][
            "stage"] = "warp"
        blob = "\n".join(gate.validate_observability(
            self._doc(metrics=metrics)))
        assert "stage label" in blob and "warp" in blob

    def test_path_label_value_enum_enforced(self):
        metrics = {
            "serving_ttft_seconds": {"kind": "histogram", "values": [
                {"labels": {"model": "m", "path": "warp"},
                 "buckets": {"+Inf": 1}, "sum": 0.1, "count": 1}]},
        }
        blob = "\n".join(gate.validate_observability(
            self._doc(metrics=metrics)))
        assert "path label" in blob and "warp" in blob

    def test_path_label_optional_for_back_compat(self):
        """Pre-v2 artifacts (BENCH_r07 and earlier) carry no path label
        on the latency histograms — they must keep validating."""
        metrics = {
            "serving_tpot_seconds": {"kind": "histogram", "values": [
                {"labels": {"model": "m"},
                 "buckets": {"+Inf": 2}, "sum": 0.1, "count": 2}]},
        }
        assert gate.validate_observability(
            self._doc(metrics=metrics)) == []

    def test_valid_serving_metrics_pass(self):
        metrics = {
            "serving_queue_depth": {"kind": "gauge", "values": [
                {"labels": {"model": "gpt"}, "value": 2}]},
            "serving_goodput_tokens_total": {"kind": "counter", "values": [
                {"labels": {"model": "gpt"}, "value": 240}]},
            "serving_ttft_seconds": {"kind": "histogram", "values": [
                {"labels": {"model": "gpt"},
                 "buckets": {"0.1": 1, "+Inf": 2}, "sum": 0.6,
                 "count": 2}]},
        }
        assert gate.validate_observability(
            self._doc(metrics=metrics)) == []

    def test_live_registry_serving_snapshot_passes(self):
        from paddle_tpu.profiler import metrics as metrics_mod
        from paddle_tpu.inference import serving as srv
        srv._M_QUEUE.set(1, model="gatetest")
        srv._M_TTFT.observe(0.2, model="gatetest")
        srv._M_TPOT.observe(0.01, model="gatetest")
        srv._M_GOODPUT.inc(10, model="gatetest")
        snap = metrics_mod.default_registry().snapshot()
        fams = {k: v for k, v in snap.items() if k.startswith("serving_")}
        assert fams
        assert gate.validate_observability(self._doc(metrics=fams)) == []

    def test_unknown_family_wrong_kind_missing_label_named(self):
        metrics = {
            "serving_bogus_total": {"kind": "counter", "values": []},
            "serving_queue_depth": {"kind": "counter", "values": []},
            "serving_goodput_tokens_total": {"kind": "counter", "values": [
                {"labels": {}, "value": 3}]},
            "serving_tpot_seconds": {"kind": "histogram", "values": [
                {"labels": {"model": "m"},
                 "buckets": {"+Inf": 5}, "sum": 1.0, "count": 4}]},
        }
        blob = "\n".join(gate.validate_observability(
            self._doc(metrics=metrics)))
        assert "serving_bogus_total" in blob and "unknown" in blob
        assert "serving_queue_depth" in blob and "expected gauge" in blob
        assert "missing the 'model' label" in blob
        assert "inconsistent" in blob  # +Inf 5 != count 4


class TestMetricsDumpServingHistograms:
    """tools/metrics_dump.py renders the serving latency histograms with
    estimated percentiles (the satellite's operator view)."""

    def test_serving_histograms_render_quantiles(self, capsys, tmp_path):
        import metrics_dump
        from paddle_tpu.profiler import metrics as metrics_mod
        reg = metrics_mod.MetricsRegistry()
        h = reg.histogram("serving_ttft_seconds",
                          "ttft by model")
        for v in (0.02, 0.04, 0.06, 0.3, 1.2):
            h.observe(v, model="gpt")
        reg.gauge("serving_queue_depth", "queue by model").set(
            3, model="gpt")
        path = tmp_path / "snap.json"
        path.write_text(json.dumps(reg.snapshot()))
        rc = metrics_dump.main([str(path), "--filter", "serving"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "serving_ttft_seconds [histogram]" in out
        assert "count=5" in out and "p50=" in out and "p99=" in out
        assert "serving_queue_depth [gauge]" in out

    @staticmethod
    def _driver_wrapper(tmp_path):
        """A driver-style wrapper document ({n, cmd, rc, tail, parsed}) of a
        pre-v2 round: serving families carry no `path` label."""
        from paddle_tpu.profiler import metrics as metrics_mod
        reg = metrics_mod.MetricsRegistry()
        ttft = reg.histogram("serving_ttft_seconds", "ttft by model")
        tpot = reg.histogram("serving_tpot_seconds", "tpot by model")
        for v in (0.8, 2.1, 4.7):
            ttft.observe(v, model="gpt2_decode")
            tpot.observe(v / 50, model="gpt2_decode")
        bench = {"metric": "m", "value": 1.0, "configs": {},
                 "observability": {"metrics": reg.snapshot()}}
        path = tmp_path / "BENCH_wrapper.json"
        path.write_text(json.dumps({"n": 1, "cmd": "python bench.py",
                                    "rc": 0, "tail": json.dumps(bench),
                                    "parsed": bench}))
        return str(path)

    def test_driver_bench_wrapper_is_understood(self, capsys, tmp_path):
        """The driver's BENCH_r{N}.json wrapper (bench object under
        `parsed`/`tail`) renders directly — found driving the serving
        satellite: the operator view of a published round's serving
        histograms previously required hand-extracting the tail."""
        import metrics_dump
        path = self._driver_wrapper(tmp_path)
        rc = metrics_dump.main([path, "--filter", "serving_ttft"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "serving_ttft_seconds [histogram]" in out
        assert "p99=" in out

    def test_prom_text_roundtrip_for_serving_families(self):
        import metrics_dump
        from paddle_tpu.profiler import metrics as metrics_mod
        reg = metrics_mod.MetricsRegistry()
        reg.histogram("serving_tpot_seconds", "tpot by model").observe(
            0.01, model="gpt")
        snap = metrics_dump.parse_prometheus_text(reg.to_prometheus_text())
        fam = snap["serving_tpot_seconds"]
        assert fam["kind"] == "histogram"
        assert fam["values"][0]["count"] == 1

    def test_serving_summary_view_splits_by_path(self, capsys, tmp_path):
        """--serving: the SLO summary breaks TTFT/TPOT out per decode
        path (fused vs eager) with quantiles."""
        import metrics_dump
        from paddle_tpu.profiler import metrics as metrics_mod
        reg = metrics_mod.MetricsRegistry()
        ttft = reg.histogram("serving_ttft_seconds",
                             "ttft by model and path")
        tpot = reg.histogram("serving_tpot_seconds",
                             "tpot by model and path")
        for v in (0.02, 0.05, 0.4):
            ttft.observe(v, model="gpt", path="fused")
            tpot.observe(v / 10, model="gpt", path="fused")
        ttft.observe(0.9, model="gpt", path="eager")
        reg.gauge("serving_batch_occupancy", "occ by model").set(
            4, model="gpt")
        path = tmp_path / "snap.json"
        path.write_text(json.dumps(reg.snapshot()))
        rc = metrics_dump.main([str(path), "--serving"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "path=fused" in out and "path=eager" in out
        assert "ttft" in out and "tpot" in out
        assert "p50=" in out and "p99=" in out
        assert "serving_batch_occupancy" in out

    def test_serving_summary_view_on_published_bench(self, capsys, tmp_path):
        """--serving degrades gracefully on a pre-v2 artifact (no path
        label) and still summarizes the families."""
        import metrics_dump
        path = self._driver_wrapper(tmp_path)
        rc = metrics_dump.main([path, "--serving"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "ttft" in out and "serving summary" in out


class TestObsTailServing:
    """obs_tail --serving: filter + operator rendering of the request
    lifecycle events."""

    @staticmethod
    def _write(tmp_path):
        path = tmp_path / "ev.jsonl"
        recs = [
            {"ts": 10.0, "kind": "retrace", "host": "t0", "name": "mm"},
            {"ts": 11.0, "kind": "serving_admission", "host": "t0",
             "model": "gpt", "request": 7, "slot": 2, "prompt_len": 33,
             "bucket": 64, "queue_wait_s": 0.12, "preemptions": 0,
             "free_pages": 90},
            {"ts": 12.0, "kind": "serving_eviction", "host": "t0",
             "severity": "info", "model": "gpt", "request": 7,
             "reason": "eos", "generated": 18, "free_pages": 95},
            {"ts": 13.0, "kind": "serving_eviction", "host": "t0",
             "severity": "warn", "model": "gpt", "request": 9,
             "reason": "preempted", "generated": 4, "free_pages": 10},
        ]
        with open(path, "w") as f:
            for r in recs:
                f.write(json.dumps(r) + "\n")
        return str(path)

    def test_serving_filters_and_renders(self, tmp_path, capsys):
        import obs_tail
        rc = obs_tail.main([self._write(tmp_path), "--serving"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "retrace" not in out              # filtered to lifecycle
        assert "request 7 -> slot 2" in out
        assert "prompt 33 -> bucket 64" in out
        assert "eos after 18 token(s)" in out
        assert "preempted after 4 token(s)" in out

    def test_serving_composes_with_controller(self, tmp_path, capsys):
        import obs_tail
        path = tmp_path / "ev.jsonl"
        with open(path, "w") as f:
            f.write(json.dumps(
                {"ts": 1.0, "kind": "serving_admission", "host": "t0",
                 "request": 1, "slot": 0, "prompt_len": 4, "bucket": 16,
                 "queue_wait_s": 0.0, "free_pages": 3}) + "\n")
            f.write(json.dumps(
                {"ts": 2.0, "kind": "controller_decision", "host": "s0",
                 "policy": "straggler_skip", "action": "skip",
                 "outcome": "applied", "decision": 4}) + "\n")
        rc = obs_tail.main([str(path), "--serving", "--controller"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "request 1 -> slot 0" in out
        assert "straggler_skip" in out and "decision #4" in out


class TestProgramAuditGate:
    """Per-config `program_audit` blocks and `analysis_*` metric families
    (static program auditor, ISSUE 15): shape/label contracts with named
    violations, plus a live-registry roundtrip through an actual audit."""

    @staticmethod
    def _block(**over):
        block = {"counts": {"info": 0, "low": 1, "medium": 0, "high": 0},
                 "clean_high": True,
                 "reports": [{"name": "GPT#1", "entry": "train_step",
                              "counts": {"info": 0, "low": 1, "medium": 0,
                                         "high": 0},
                              "findings": [{"check": "dtype",
                                            "severity": "low",
                                            "code": "silent-upcast",
                                            "message": "m"}]}]}
        block.update(over)
        return block

    def _doc(self, block):
        return {"configs": {"gpt2": {"tokens_per_sec_chip": 1.0,
                                     "program_audit": block}}}

    def test_valid_block_passes(self):
        assert gate.validate_observability(self._doc(self._block())) == []

    def test_error_block_is_legal(self):
        doc = self._doc({"error": "TypeError: boom"})
        assert gate.validate_observability(doc) == []

    def test_clean_high_contradiction_named(self):
        block = self._block(
            counts={"info": 0, "low": 0, "medium": 0, "high": 2},
            clean_high=True)
        probs = gate.validate_observability(self._doc(block))
        assert any("clean_high" in p and "contradicts" in p for p in probs)

    def test_illegal_check_and_severity_named(self):
        block = self._block()
        block["reports"][0]["findings"][0]["check"] = "vibes"
        block["reports"][0]["findings"][0]["severity"] = "fatal"
        probs = gate.validate_observability(self._doc(block))
        assert any("'vibes'" in p for p in probs)
        assert any("'fatal'" in p for p in probs)

    def test_negative_count_named(self):
        block = self._block(
            counts={"info": 0, "low": -1, "medium": 0, "high": 0})
        probs = gate.validate_observability(self._doc(block))
        assert any("counts.low" in p for p in probs)

    def test_analysis_metrics_roundtrip_from_live_registry(self):
        """An actual audit's emitted metrics validate through the gate."""
        import jax
        import jax.numpy as jnp
        from paddle_tpu.analysis import audit_program
        from paddle_tpu.profiler import metrics as metrics_mod

        def step(params, x):
            return jax.tree_util.tree_map(lambda p: p * 0.9, params), \
                x.sum()

        audit_program(step, ({"w": jnp.ones((512, 1024))},
                             jnp.ones((4,))), name="gate_t", emit=True)
        snap = metrics_mod.default_registry().snapshot()
        metrics = {k: v for k, v in snap.items()
                   if k.startswith("analysis_")}
        assert "analysis_findings_total" in metrics
        doc = {"configs": {}, "observability": {"metrics": metrics}}
        assert gate.validate_observability(doc) == []

    def test_unknown_analysis_family_named(self):
        metrics = {"analysis_mystery_total": {
            "kind": "counter", "help": "x",
            "values": [{"labels": {}, "value": 1}]}}
        doc = {"configs": {}, "observability": {"metrics": metrics}}
        probs = gate.validate_observability(doc)
        assert any("analysis_mystery_total" in p and "unknown" in p
                   for p in probs)

    def test_bad_severity_label_named(self):
        metrics = {"analysis_findings_total": {
            "kind": "counter", "help": "x",
            "values": [{"labels": {"check": "dtype",
                                   "severity": "fatal"}, "value": 1}]}}
        doc = {"configs": {}, "observability": {"metrics": metrics}}
        probs = gate.validate_observability(doc)
        assert any("severity" in p and "'fatal'" in p for p in probs)

    def test_obs_tail_analysis_view(self, tmp_path, capsys):
        import obs_tail
        path = tmp_path / "ev.jsonl"
        with open(path, "w") as f:
            f.write(json.dumps(
                {"ts": 1.0, "kind": "analysis_finding", "host": "t0",
                 "severity": "error", "program": "GPT#1",
                 "entry": "train_step", "check": "donation",
                 "code": "undonated-large-input",
                 "finding_severity": "high", "param": "['w']",
                 "message": "big and dead",
                 "fix_hint": "donate it"}) + "\n")
            f.write(json.dumps(
                {"ts": 2.0, "kind": "retrace", "host": "t0",
                 "site": "eager"}) + "\n")
        rc = obs_tail.main([str(path), "--analysis"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "donation/undonated-large-input" in out
        assert "GPT#1[train_step]" in out and "donate it" in out
        assert "retrace" not in out  # filtered to analysis kinds


class TestReqTraceAndSLOGate:
    """`reqtrace`/`slo` observability blocks + `slo_*` metric families:
    the bench gate's request-trace and SLO-window shape contracts."""

    @staticmethod
    def _trace(**over):
        t = {"trace_id": 5, "rid": 3, "model": "gpt",
             "state": "complete", "finish_reason": "eos",
             "preemptions": 1, "decode_iterations": 6,
             "decode_tokens": 6, "shared_tokens": 0, "e2e_s": 0.5,
             "phases": {"queued": 0.1, "prefill": 0.1, "decode": 0.25,
                        "preempted": 0.05},
             "spans": [
                 {"phase": "queued", "start": 0.0, "end": 0.1},
                 {"phase": "prefill", "start": 0.1, "end": 0.15,
                  "bucket": 16, "prompt_tokens": 9},
                 {"phase": "preempted", "start": 0.15, "end": 0.2},
                 {"phase": "prefill", "start": 0.2, "end": 0.25,
                  "bucket": 16, "prompt_tokens": 11, "requeue": True},
                 {"phase": "decode", "start": 0.25, "end": 0.5,
                  "bucket": 2, "path": "fused", "iters": 6},
                 {"phase": "complete", "start": 0.5, "end": 0.5}]}
        t.update(over)
        return t

    def _reqtrace(self, **over):
        rt = {"enabled": True, "model": "gpt", "live": [],
              "completed": [self._trace()], "ring_size": 256,
              "decode_every": 8}
        rt.update(over)
        return rt

    @staticmethod
    def _slo(**over):
        s = {"enabled": True, "model": "gpt", "window": 512,
             "min_samples": 8, "targets": {"ttft": 0.5},
             "signals": {
                 "ttft": {"count": 10, "p50": 0.1, "p95": 0.2,
                          "p99": 0.3},
                 "tpot": {"count": 0, "p50": None, "p95": None,
                          "p99": None}},
             "breached": {}, "status": "ok",
             "stats": {"breaches": 1, "recoveries": 1,
                       "observations": 40}}
        s.update(over)
        return s

    @staticmethod
    def _doc(reqtrace=None, slo=None, metrics=None):
        obs = {}
        if reqtrace is not None:
            obs["reqtrace"] = reqtrace
        if slo is not None:
            obs["slo"] = slo
        if metrics is not None:
            obs["metrics"] = metrics
        return {"observability": obs}

    def test_valid_blocks_pass(self):
        assert gate.validate_observability(self._doc(
            reqtrace=self._reqtrace(), slo=self._slo())) == []

    def test_live_engine_payloads_validate(self):
        """The gate accepts what the engine actually serves: run a tiny
        engine and pipe its /requests + /slo payloads straight in."""
        import tempfile
        from paddle_tpu.framework import flags as flags_mod
        import paddle_tpu as paddle
        from paddle_tpu.inference.serving import ServingEngine
        from paddle_tpu.models.gpt import GPT, GPTConfig
        cache = os.path.join(tempfile.gettempdir(), "pt_serving_ccache")
        os.makedirs(cache, exist_ok=True)
        flags_mod.set_flags({"FLAGS_compile_cache_dir": cache})
        try:
            paddle.seed(0)
            cfg = GPTConfig(vocab_size=512, max_position_embeddings=128,
                            hidden_size=32, num_layers=2, num_heads=2,
                            dropout=0.0, attn_dropout=0.0)
            m = GPT(cfg)
            m.eval()
            eng = ServingEngine(m, max_batch=2, max_len=48, page_size=8,
                                name="gate_live")
            req = eng.submit(list(range(1, 9)), max_new_tokens=3)
            eng.run_until_idle()
            req.result(timeout=10)
            doc = self._doc(reqtrace=eng.requests_snapshot(),
                            slo=eng.slo.snapshot())
            assert gate.validate_observability(doc) == []
        finally:
            flags_mod.set_flags({"FLAGS_compile_cache_dir": ""})

    def test_bad_trace_ids_phase_and_span_named(self):
        t = self._trace(trace_id=0, e2e_s=float("inf"))
        t["phases"]["warmup"] = 0.1
        t["spans"].append({"phase": "decode", "start": 2.0, "end": 1.0})
        probs = gate.validate_observability(self._doc(
            reqtrace=self._reqtrace(completed=[t])))
        text = "\n".join(probs)
        assert "trace_id" in text
        assert "e2e_s" in text
        assert "warmup" in text and "unknown phase" in text
        assert "end 1.0 < start 2.0" in text

    def test_non_monotone_quantiles_named(self):
        s = self._slo()
        s["signals"]["ttft"]["p95"] = 0.05  # p50 0.1 > p95
        probs = gate.validate_observability(self._doc(slo=s))
        assert any("not monotone" in p for p in probs)

    def test_nonfinite_quantile_and_negative_stats_named(self):
        s = self._slo()
        s["signals"]["ttft"]["p99"] = float("nan")
        s["stats"]["breaches"] = -1
        probs = gate.validate_observability(self._doc(slo=s))
        text = "\n".join(probs)
        assert "finite non-negative" in text
        assert "stats.breaches" in text

    def test_unknown_slo_family_and_wrong_kind_named(self):
        metrics = {
            "slo_breach_count": {"kind": "counter", "values": []},
            "slo_breached": {"kind": "counter", "values": []},
            "slo_breaches_total": {
                "kind": "counter",
                "values": [{"labels": {"model": "gpt"}, "value": 1}]},
        }
        probs = gate.validate_observability(self._doc(metrics=metrics))
        text = "\n".join(probs)
        assert "slo_breach_count: unknown slo family" in text
        assert "slo_breached: kind" in text and "expected gauge" in text
        assert "missing the 'signal' label" in text

    def test_error_blocks_report_themselves(self):
        assert gate.validate_observability(self._doc(
            reqtrace={"error": "probe failed"},
            slo={"error": "probe failed"})) == []

    def test_queue_wait_percentiles_in_decode_block(self):
        cfg = {"tokens_per_sec_chip": 50.0,
               "serving": {"ttft_s": {"p50": 0.1, "p99": 0.2},
                           "tpot_s": {"p50": 0.01, "p99": 0.02},
                           "queue_wait_s": {"p50": 0.05, "p99": 0.4}}}
        assert gate.validate_observability(
            {"configs": {"gpt2_decode": cfg}}) == []
        cfg["serving"]["queue_wait_s"]["p99"] = -0.4
        probs = gate.validate_observability(
            {"configs": {"gpt2_decode": cfg}})
        assert any("queue_wait_s" in p for p in probs)


class TestObsTailSLO:
    """--slo: the serving SLO plane view (breach excursions + completed
    request traces) with kind-filter composition."""

    @staticmethod
    def _breach_event():
        return {"ts": 1722700000.0, "kind": "slo_breach", "host": "t0",
                "severity": "warn", "model": "gpt", "signal": "ttft",
                "quantile": "p99", "value": 0.82, "target": 0.5,
                "window": 24}

    @staticmethod
    def _trace_event():
        return {"ts": 1722700001.0, "kind": "request_trace",
                "host": "t0", "severity": "info", "trace_id": 9,
                "rid": 4, "model": "gpt", "finish_reason": "eos",
                "preemptions": 1, "decode_tokens": 16, "e2e_s": 1.25,
                "phases": {"queued": 0.2, "prefill": 0.15,
                           "decode": 0.85, "preempted": 0.05}}

    def test_slo_filters_and_renders(self, tmp_path, capsys):
        import obs_tail
        path = tmp_path / "ev.jsonl"
        with open(path, "w") as f:
            f.write(json.dumps(self._breach_event()) + "\n")
            f.write(json.dumps(self._trace_event()) + "\n")
            f.write(json.dumps({"ts": 1.0, "kind": "retrace",
                                "host": "t0"}) + "\n")
        rc = obs_tail.main([str(path), "--slo"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "ttft p99=820.0ms breached target 500.0ms" in out
        assert "over 24 sample(s)" in out
        assert "re-arms on recovery" in out
        assert "trace 9 request 4 eos e2e 1250.0ms" in out
        assert "preemptions=1" in out
        assert "decode=850.0ms" in out
        assert "retrace" not in out  # --slo implies the kind filter

    def test_slo_composes_with_explicit_kind(self, tmp_path, capsys):
        import obs_tail
        path = tmp_path / "ev.jsonl"
        with open(path, "w") as f:
            f.write(json.dumps(self._breach_event()) + "\n")
            f.write(json.dumps({"ts": 2.0, "kind": "retrace",
                                "host": "t0"}) + "\n")
            f.write(json.dumps({"ts": 3.0, "kind": "xla_compile",
                                "host": "t0"}) + "\n")
        rc = obs_tail.main([str(path), "--slo", "--kind", "retrace"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "slo_breach" in out and "retrace" in out
        assert "xla_compile" not in out


class TestMetricsDumpRequests:
    """--requests: per-request phase breakdowns from a bench artifact,
    a /requests payload file, or the live endpoint."""

    @staticmethod
    def _payload():
        return {
            "enabled": True, "model": "gpt", "ring_size": 256,
            "decode_every": 8,
            "live": [{"trace_id": 7, "rid": 5, "state": "running",
                      "preemptions": 0, "decode_tokens": 3,
                      "phases": {"queued": 0.01, "prefill": 0.04}}],
            "completed": [{"trace_id": 6, "rid": 4,
                           "finish_reason": "eos", "preemptions": 2,
                           "decode_tokens": 8, "e2e_s": 0.9,
                           "phases": {"queued": 0.1, "prefill": 0.2,
                                      "decode": 0.55,
                                      "preempted": 0.05}}],
            "introspection": [
                {"iteration": 41, "active": 3, "lanes": 4,
                 "occupancy": 3, "queue_depth": 2, "free_pages": 11,
                 "used_pages": 20, "cow_shared_pages": 5,
                 "decode_mode": "fused"}],
        }

    def test_requests_view_from_payload_file(self, tmp_path, capsys):
        import metrics_dump
        path = tmp_path / "requests.json"
        path.write_text(json.dumps(self._payload()))
        rc = metrics_dump.main([str(path), "--requests"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "request traces (model gpt, tracer on)" in out
        assert "LIVE trace    7 request    5" in out
        assert "DONE trace    6 request    4 eos" in out
        assert "preempt=2" in out and "e2e=900.0ms" in out
        assert "decode=550.0ms" in out
        assert "pages free/used/shared=11/20/5" in out

    def test_requests_view_from_bench_observability(self, tmp_path,
                                                    capsys):
        import metrics_dump
        path = tmp_path / "bench.json"
        path.write_text(json.dumps(
            {"observability": {"reqtrace": self._payload()}}))
        rc = metrics_dump.main([str(path), "--requests"])
        out = capsys.readouterr().out
        assert rc == 0 and "DONE trace    6" in out

    def test_requests_view_without_traces_reports_it(self, tmp_path,
                                                     capsys):
        import metrics_dump
        path = tmp_path / "bench.json"
        path.write_text(json.dumps(
            {"observability": {"reqtrace": {
                "enabled": True, "model": "gpt", "live": [],
                "completed": []}}}))
        rc = metrics_dump.main([str(path), "--requests"])
        assert rc == 0
        assert "(no traces recorded)" in capsys.readouterr().out

    def test_requests_view_from_live_endpoint(self, capsys):
        from paddle_tpu.profiler.server import ObservabilityServer
        import metrics_dump
        import urllib.request  # noqa: F401  (exercised inside the tool)
        payload = self._payload()

        class _Stub:
            @staticmethod
            def requests_snapshot(n=50):
                return payload
        srv = ObservabilityServer()
        srv.start(0)
        try:
            import paddle_tpu.profiler.server as server_mod
            # the staticmethod object itself: reading the attribute gives
            # the bare function, and putting that back would bind it
            orig = server_mod.ObservabilityServer.__dict__["_engine"]
            server_mod.ObservabilityServer._engine = staticmethod(
                lambda name=None: _Stub())
            try:
                rc = metrics_dump.main(
                    [f"http://127.0.0.1:{srv.port}/requests",
                     "--requests"])
            finally:
                server_mod.ObservabilityServer._engine = orig
        finally:
            srv.stop()
        out = capsys.readouterr().out
        assert rc == 0 and "DONE trace    6" in out
