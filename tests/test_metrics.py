"""Metrics registry (profiler/metrics.py) + tools/metrics_dump.py.

Reference analog: `paddle/fluid/platform/monitor.h` StatRegistry tests —
here the registry is labeled, typed, and exports Prometheus text + JSON.
"""
import json
import os
import sys
import threading

import pytest

from paddle_tpu.profiler import metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))


@pytest.fixture()
def reg():
    return metrics.MetricsRegistry()


class TestCounterGauge:
    def test_counter_inc_and_labels(self, reg):
        c = reg.counter("requests_total", "demo")
        c.inc()
        c.inc(2, op="matmul")
        c.inc(3, op="matmul")
        assert c.value() == 1
        assert c.value(op="matmul") == 5
        assert c.total() == 6

    def test_counter_rejects_negative(self, reg):
        with pytest.raises(ValueError):
            reg.counter("c").inc(-1)

    def test_gauge_set_inc_dec(self, reg):
        g = reg.gauge("mem_bytes")
        g.set(100, device="tpu:0")
        g.inc(50, device="tpu:0")
        g.dec(25, device="tpu:0")
        assert g.value(device="tpu:0") == 125

    def test_get_or_create_and_type_conflict(self, reg):
        c1 = reg.counter("x_total")
        assert reg.counter("x_total") is c1
        with pytest.raises(TypeError):
            reg.gauge("x_total")

    def test_label_order_irrelevant(self, reg):
        c = reg.counter("c_total")
        c.inc(1, a="1", b="2")
        c.inc(1, b="2", a="1")
        assert c.value(a="1", b="2") == 2


class TestHistogram:
    def test_buckets_and_sum(self, reg):
        h = reg.histogram("lat_seconds", buckets=(0.01, 0.1, 1.0))
        for v in (0.005, 0.05, 0.5, 5.0):
            h.observe(v)
        (snap,) = h.snapshot()["values"]
        assert snap["count"] == 4
        assert snap["sum"] == pytest.approx(5.555)
        assert snap["buckets"]["0.01"] == 1      # cumulative
        assert snap["buckets"]["0.1"] == 2
        assert snap["buckets"]["1.0"] == 3
        assert snap["buckets"]["+Inf"] == 4


class TestExporters:
    def test_prometheus_text_format(self, reg):
        reg.counter("ops_total", "op calls").inc(3, op="a\"b\n")
        reg.gauge("hot").set(1.5)
        reg.histogram("h_seconds", buckets=(1.0,)).observe(0.5)
        txt = reg.to_prometheus_text()
        assert '# TYPE paddle_tpu_ops_total counter' in txt
        assert 'paddle_tpu_ops_total{op="a\\"b\\n"} 3.0' in txt
        assert 'paddle_tpu_hot 1.5' in txt
        assert 'paddle_tpu_h_seconds_bucket{le="1.0"} 1' in txt
        assert 'paddle_tpu_h_seconds_count 1' in txt

    def test_prometheus_headers_even_without_series(self, reg):
        reg.counter("quiet_total", "never incremented")
        assert "paddle_tpu_quiet_total" in reg.to_prometheus_text()

    def test_snapshot_json_serializable(self, reg):
        reg.counter("a_total").inc(2, k="v")
        reg.histogram("b_seconds").observe(0.1)
        snap = json.loads(json.dumps(reg.snapshot()))
        assert snap["a_total"]["kind"] == "counter"
        assert snap["a_total"]["values"][0] == {"labels": {"k": "v"},
                                                "value": 2.0}
        assert snap["b_seconds"]["values"][0]["count"] == 1

    def test_reset_keeps_families(self, reg):
        reg.counter("a_total").inc(5)
        reg.reset()
        assert reg.counter("a_total").total() == 0
        assert "a_total" in reg.names()


class TestEnableSwitch:
    def test_set_enabled_roundtrip(self):
        was = metrics.enabled()
        try:
            metrics.set_enabled(False)
            assert not metrics.enabled()
            metrics.set_enabled(True)
            assert metrics.enabled()
        finally:
            metrics.set_enabled(was)


class TestThreadSafety:
    def test_concurrent_increments(self, reg):
        c = reg.counter("t_total")
        n, k = 8, 2000

        def work():
            for _ in range(k):
                c.inc(1, tid="x")

        threads = [threading.Thread(target=work) for _ in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.value(tid="x") == n * k


class TestMetricsDumpTool:
    def _snapshot(self):
        r = metrics.MetricsRegistry()
        r.counter("collective_bytes_total", "bytes").inc(
            4096, kind="all_reduce", link="ici")
        r.histogram("w_seconds").observe(0.2)
        return r.snapshot()

    def test_format_snapshot(self):
        import metrics_dump
        out = metrics_dump.format_snapshot(self._snapshot())
        assert "collective_bytes_total" in out
        assert "kind=all_reduce,link=ici" in out
        assert "4,096" in out
        out2 = metrics_dump.format_snapshot(self._snapshot(), "w_seconds")
        assert "collective_bytes_total" not in out2 and "w_seconds" in out2

    def test_cli_accepts_bench_json(self, tmp_path, capsys):
        import metrics_dump
        bench_doc = {"metric": "x", "value": 1,
                     "observability": {"metrics": self._snapshot()}}
        p = tmp_path / "bench.json"
        p.write_text(json.dumps(bench_doc))
        assert metrics_dump.main([str(p)]) == 0
        assert "collective_bytes_total" in capsys.readouterr().out

    def test_cli_rejects_garbage(self, tmp_path):
        import metrics_dump
        p = tmp_path / "x.json"
        p.write_text("not json at all")
        assert metrics_dump.main([str(p)]) == 2

    def test_histogram_percentile_rendering(self):
        """PR-4: histogram families render p50/p95/p99 estimates from the
        cumulative buckets (the heter pull/push/route latencies)."""
        import metrics_dump
        r = metrics.MetricsRegistry()
        h = r.histogram("heter_pull_seconds")
        for v in [0.001] * 90 + [0.08] * 10:
            h.observe(v, mode="pipelined")
        out = metrics_dump.format_snapshot(r.snapshot())
        assert "p50=" in out and "p95=" in out and "p99=" in out
        # p50 sits in the (0.0005, 0.001] bucket; p95+ in the big one
        assert "mode=pipelined" in out

    def test_hist_quantile_estimator(self):
        import metrics_dump
        buckets = {"0.001": 50, "0.01": 90, "0.1": 100, "+Inf": 100}
        q50 = metrics_dump.hist_quantile(buckets, 0.5)
        q99 = metrics_dump.hist_quantile(buckets, 0.99)
        assert q50 is not None and abs(q50 - 0.001) < 1e-9
        assert q99 is not None and 0.01 < q99 <= 0.1
        assert metrics_dump.hist_quantile({"+Inf": 0}, 0.5) is None


class TestMetricNamingLint:
    """Fleet-observability contract: every registered family is a legal
    Prometheus name and its help string documents the label keys its
    series use — a scraper must never meet an undocumented label."""

    NAME_RE = __import__("re").compile(r"^[a-z][a-z0-9_]*$")

    @staticmethod
    def _import_instrumented_modules():
        # every module that registers metric families at import
        import paddle_tpu  # noqa: F401
        import paddle_tpu.amp  # noqa: F401
        import paddle_tpu.distributed.checkpoint  # noqa: F401
        import paddle_tpu.distributed.collective  # noqa: F401
        import paddle_tpu.distributed.fleet.controller  # noqa: F401
        import paddle_tpu.distributed.fleet.elastic  # noqa: F401
        import paddle_tpu.distributed.fleet.leader  # noqa: F401
        import paddle_tpu.distributed.fleet.telemetry  # noqa: F401
        import paddle_tpu.distributed.ps.cache  # noqa: F401
        import paddle_tpu.distributed.ps.communicator  # noqa: F401
        import paddle_tpu.distributed.ps.heter  # noqa: F401
        import paddle_tpu.fault  # noqa: F401
        import paddle_tpu.inference.disagg  # noqa: F401
        import paddle_tpu.inference.serving  # noqa: F401
        import paddle_tpu.io.dataloader  # noqa: F401
        import paddle_tpu.io.worker  # noqa: F401
        import paddle_tpu.ops._dispatch  # noqa: F401
        import paddle_tpu.profiler.compile_watch  # noqa: F401
        import paddle_tpu.profiler.health  # noqa: F401
        import paddle_tpu.profiler.reqtrace  # noqa: F401
        import paddle_tpu.profiler.slo  # noqa: F401
        import paddle_tpu.profiler.watchdog  # noqa: F401

    def test_family_names_match_prometheus_grammar(self):
        self._import_instrumented_modules()
        reg = metrics.default_registry()
        bad = [n for n in reg.names() if not self.NAME_RE.match(n)]
        assert not bad, f"illegal metric family names: {bad}"

    def test_label_keys_are_documented_in_help(self):
        """Each live series' label keys must appear (case-insensitively)
        in the family's help text. Runs over whatever the session has
        populated so far plus a deterministic seed of the core labeled
        families."""
        self._import_instrumented_modules()
        import numpy as np
        import paddle_tpu as paddle
        from paddle_tpu.profiler import compile_watch
        # deterministic seed: exercise core labeled families
        a = paddle.to_tensor(np.ones((4, 4), np.float32))
        paddle.matmul(a, a)  # op_* counters
        from paddle_tpu.profiler.watchdog import RetraceWatchdog
        wd = RetraceWatchdog()
        wd.observe("eager", "lint_op", [np.zeros((2,), np.float32)])
        compile_watch._on_duration(
            "/jax/core/compile/backend_compile_duration", 0.01)
        # deep-profiling PR families: device-memory gauges (device=),
        # capture counter (status=), collective timing (kind=)
        metrics.sample_device_memory()
        from paddle_tpu.profiler import xplane as _xplane
        _xplane._M_CAPTURES.inc(status="complete")
        from paddle_tpu.distributed import collective as _coll
        _coll._M_COLL_SECONDS.observe(0.001, kind="all_reduce")
        # training-health PR families: sentinel gauges (group=), nonfinite
        # counter (src=), monitor alerts (signal=), fleet status (host=),
        # and the AMP scaler pair
        from paddle_tpu.profiler import health as _health
        _health._M_LAYER_GRAD.set(0.5, group="fc1")
        _health._M_NONFINITE.inc(src="sentinel")
        _health._M_ALERTS.inc(signal="loss_spike")
        _health._M_LOSS.set(1.0)
        _health._M_GRAD_NORM.set(1.0)
        _health._M_UPDATE_RATIO.set(0.01)
        _health._M_ROLLBACK.inc()
        from paddle_tpu.distributed.fleet import telemetry as _tel
        _tel._M_HEALTH.set(0, host="trainer-0")
        import paddle_tpu.amp as _amp
        _amp._M_FOUND_INF.inc()
        _amp._M_LOSS_SCALE.set(32768.0)
        # self-driving fleet controller families: decisions (policy=,
        # outcome=), per-action counters (host=), relaunch-to-first-step
        # gauge (policy=)
        from paddle_tpu.distributed.fleet import controller as _ctl
        _ctl._M_DECISIONS.inc(policy="straggler_evict", outcome="applied")
        _ctl._M_DECISIONS.inc(policy="straggler_skip", outcome="applied")
        _ctl._M_EVICTIONS.inc(host="trainer-1")
        _ctl._M_ROLLBACKS.inc(host="trainer-1")
        _ctl._M_READMISSIONS.inc(host="trainer-1")
        _ctl._M_FIRST_STEP.set(1.5, policy="straggler_evict")
        # HA control plane families: election term gauge, takeovers
        # (reason=), fenced stale actuations (policy=)
        from paddle_tpu.distributed.fleet import leader as _ldr
        _ldr._M_TERM.set(3)
        _ldr._M_TAKEOVERS.inc(reason="lease_expired")
        _ldr._M_FENCED.inc(policy="serving_restart")
        # disaggregated-serving fault-tolerance families: worker
        # respawns + requeues (reason=)
        from paddle_tpu.inference import disagg as _dis
        _dis._M_W_RESTARTS.inc()
        _dis._M_REQUEUE.inc(reason="worker_dead")
        # continuous-batching serving families (model=, latency split by
        # decode path=)
        from paddle_tpu.inference import serving as _srv
        _srv._M_QUEUE.set(2, model="gpt")
        _srv._M_OCC.set(1, model="gpt")
        _srv._M_TTFT.observe(0.05, model="gpt", path="fused")
        _srv._M_TPOT.observe(0.01, model="gpt", path="fused")
        _srv._M_TTFT.observe(0.07, model="gpt", path="eager")
        _srv._M_TPOT.observe(0.02, model="gpt", path="eager")
        _srv._M_GOODPUT.inc(8, model="gpt")
        # self-healing serving families: hot-swap lifecycle (model=,
        # outcome=), swap pause histogram + applied-step gauge (model=),
        # watchdog restarts (model=, reason=), suspension gauge (model=)
        _srv._M_SWAP_TOTAL.inc(1.0, model="gpt", outcome="applied")
        _srv._M_SWAP_PAUSE.observe(0.003, model="gpt")
        _srv._M_SWAP_STEP.set(100, model="gpt")
        _srv._M_RESTARTS.inc(model="gpt", reason="wedged")
        _srv._M_SUSPENDED.set(0, model="gpt")
        # disaggregated prefill/decode handoff plane (model=, per-stage
        # occupancy additionally by stage=)
        _srv._M_HANDOFF_DEPTH.set(1, model="gpt")
        _srv._M_HANDOFF_WAIT.observe(0.004, model="gpt")
        _srv._M_HANDOFF_BYTES.inc(4096, model="gpt")
        _srv._M_STAGE_OCC.set(1, model="gpt", stage="prefill")
        _srv._M_STAGE_OCC.set(2, model="gpt", stage="decode")
        # request-trace lifecycle histograms (model=) + SLO plane
        # families (model=, signal=)
        from paddle_tpu.profiler import reqtrace as _rt
        _rt._M_QWAIT.observe(0.01, model="gpt")
        _rt._M_PREFILL.observe(0.05, model="gpt")
        _rt._M_REQUEUE.observe(0.02, model="gpt")
        from paddle_tpu.profiler import slo as _slo
        _slo._M_BREACHES.inc(model="gpt", signal="ttft")
        _slo._M_BREACHED.set(1, model="gpt", signal="ttft")
        _slo._M_P99.set(0.2, model="gpt", signal="ttft")
        _slo._M_P99.set(0.01, model="gpt", signal="handoff_wait")
        reg = metrics.default_registry()
        problems = []
        for name in reg.names():
            fam = reg.get(name)
            help_lc = fam.help.lower()
            keys = set()
            for v in fam.snapshot()["values"]:
                keys.update(v.get("labels", {}))
            for key in keys:
                if key.lower() not in help_lc:
                    problems.append(f"{name}: label {key!r} not mentioned "
                                    f"in help {fam.help!r}")
        assert not problems, "\n".join(problems)
