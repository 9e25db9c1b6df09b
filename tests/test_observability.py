"""End-to-end runtime observability (PR 2): per-op host tracing through the
eager dispatch, recorder drain-vs-record thread safety, Benchmark timer
degradation paths, scheduler window edges + chrome-trace schema, collective
byte accounting, DataLoader wait wiring, and the ThroughputMonitor step
JSONL.

All CPU-only — the acceptance bar is that a one-step eager train loop under
an active Profiler yields per-op chrome rows, summary op rows, and a
prometheus snapshot carrying op/collective/retrace counters.
"""
import json
import threading
import time

import numpy as np
import pytest

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu import nn, optimizer
from paddle_tpu import profiler as prof
from paddle_tpu.distributed.topology import HybridCommunicateGroup, build_mesh
from paddle_tpu.profiler import metrics
from paddle_tpu.profiler.monitor import (ThroughputMonitor, make_step_record,
                                         validate_step_record)
from paddle_tpu.profiler.recorder import HostSpan, get_recorder, now_ns
from paddle_tpu.profiler.timer import Benchmark
from paddle_tpu.profiler.watchdog import get_watchdog


@pytest.fixture()
def clean_recorder():
    rec = get_recorder()
    rec.clear()
    yield rec
    rec.enabled = False
    rec.clear()


def _one_step_eager_train(steps=1):
    paddle.seed(0)
    net = nn.Sequential(nn.Linear(8, 8), nn.ReLU(), nn.Linear(8, 4))
    opt = optimizer.SGD(parameters=net.parameters(), learning_rate=0.1)
    x = paddle.to_tensor(np.ones((4, 8), np.float32))
    y = paddle.to_tensor(np.zeros((4,), np.int64))
    lossf = nn.CrossEntropyLoss()
    for _ in range(steps):
        loss = lossf(net(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
    return float(loss)


class TestOpLevelTracing:
    """Acceptance: eager train loop under RECORD → op spans + summary rows
    + prometheus counters."""

    def test_train_loop_emits_op_spans_and_counters(self, tmp_path,
                                                    clean_recorder):
        p = prof.Profiler(targets=[prof.ProfilerTarget.CPU])
        p.start()
        _one_step_eager_train()
        p.stop()
        path = p.export(str(tmp_path / "trace.json"))
        data = json.load(open(path))
        op_events = [e for e in data["traceEvents"] if e["cat"] == "Operator"]
        assert op_events, "per-op host spans missing from chrome trace"
        names = {e["name"] for e in op_events}
        assert "linear" in names
        lin = next(e for e in op_events if e["name"] == "linear")
        assert lin["args"]["bytes_est"] > 0
        assert lin["args"]["shapes"][0] == [4, 8]
        assert "float32" in lin["args"]["dtypes"][0]
        # summary has op rows
        report = prof.summary_report(p.statistic_data())
        assert "linear" in report and "backward" in report
        # prometheus snapshot carries op/collective/retrace counter families
        txt = metrics.default_registry().to_prometheus_text()
        assert 'paddle_tpu_op_calls_total{op="linear"}' in txt
        assert "paddle_tpu_collective_bytes_total" in txt
        assert "paddle_tpu_jit_retraces_total" in txt

    def test_no_op_spans_outside_record_window(self, clean_recorder):
        _one_step_eager_train()
        assert get_recorder().collect() == []

    def test_metrics_disabled_skips_counters(self, clean_recorder):
        reg = metrics.default_registry()
        metrics.set_enabled(False)
        try:
            before = reg.counter("op_calls_total").total()
            _one_step_eager_train()
            assert reg.counter("op_calls_total").total() == before
        finally:
            metrics.set_enabled(True)

    def test_op_bytes_counter_accumulates(self):
        reg = metrics.default_registry()
        before = reg.counter("op_bytes_total").value(op="matmul")
        x = paddle.to_tensor(np.ones((8, 8), np.float32))
        with paddle.no_grad():
            (x @ x).numpy()
        # 2 inputs + 1 output of 8x8 f32 = 768 bytes minimum
        assert reg.counter("op_bytes_total").value(op="matmul") >= before + 768

    def test_op_flops_counter_exact_for_matmul(self):
        reg = metrics.default_registry()
        before = reg.counter("op_flops_total").value(op="matmul")
        x = paddle.to_tensor(np.ones((8, 8), np.float32))
        with paddle.no_grad():
            (x @ x).numpy()
        # 2*M*K*N = 2*8*8*8 = 1024 for one matmul
        assert reg.counter("op_flops_total").value(op="matmul") \
            == before + 1024

    def test_ops_under_jit_trace_not_counted(self):
        """An op re-entered during a to_static trace executes per compiled
        run, not per Python call — the eager counters must not gain phantom
        dispatches from tracing (nor from cache-hit replays)."""
        reg = metrics.default_registry()
        st = paddle.jit.to_static(nn.Linear(8, 4))
        x = paddle.to_tensor(np.ones((2, 8), np.float32))
        st(x)  # first call: traces the forward with tracer-backed Tensors
        before = reg.counter("op_calls_total").value(op="linear")
        st(x)  # cache hit: no dispatch at all
        st(paddle.to_tensor(np.ones((5, 8), np.float32)))  # re-trace
        assert reg.counter("op_calls_total").value(op="linear") == before

    def test_memory_gauges_honor_kill_switch(self):
        metrics.set_enabled(False)
        try:
            reg = metrics.MetricsRegistry()
            metrics.update_device_memory_gauges(reg)
            assert "device_bytes_in_use" not in reg.names()
        finally:
            metrics.set_enabled(True)


class TestRecorderConcurrency:
    """Satellite: collect() drains per-thread under the buffer lock — spans
    recorded mid-collect are neither lost nor duplicated."""

    def test_concurrent_record_and_collect(self, clean_recorder):
        rec = clean_recorder
        rec.enabled = True
        n_threads, per_thread = 4, 400
        stop_collect = threading.Event()
        collected, errors = [], []

        def producer(tid):
            try:
                for i in range(per_thread):
                    t = now_ns()
                    rec.push(HostSpan(name=f"rectest_{tid}_{i}", start_ns=t,
                                      end_ns=t + 1,
                                      tid=threading.get_ident()))
            except Exception as e:  # pragma: no cover
                errors.append(e)

        def collector():
            while not stop_collect.is_set():
                collected.extend(rec.collect())

        cth = threading.Thread(target=collector)
        cth.start()
        producers = [threading.Thread(target=producer, args=(t,))
                     for t in range(n_threads)]
        for t in producers:
            t.start()
        for t in producers:
            t.join()
        stop_collect.set()
        cth.join()
        collected.extend(rec.collect())  # final drain
        assert not errors
        # count ONLY this test's spans: enabling the global recorder means a
        # background thread leaked by an earlier test (prefetchers, push
        # workers) may add its own op spans to the shared buffers
        names = [s.name for s in collected if s.name.startswith("rectest_")]
        assert len(names) == n_threads * per_thread, \
            f"lost {n_threads * per_thread - len(names)} spans"
        assert len(set(names)) == len(names), "duplicated spans"

    def test_collect_is_draining(self, clean_recorder):
        rec = clean_recorder
        rec.enabled = True
        t = now_ns()
        rec.push(HostSpan("a", t, t + 1, 0))
        assert [s.name for s in rec.collect()] == ["a"]
        assert rec.collect() == []


class TestBenchmarkTimerAudit:
    """Satellite: ips degrades gracefully — no ZeroDivision on any path."""

    def test_step_without_reader_fetch(self):
        bm = Benchmark()
        bm.begin()
        for _ in range(3):
            bm.step(num_samples=8)
        bm.end()
        info = bm.step_info()
        assert "reader_cost: 0.00000" in info and "ips" in info
        rep = bm.report()
        assert rep["reader_cost_avg_s"] == 0.0 and rep["ips"] > 0

    def test_num_samples_none_falls_back_to_steps_per_sec(self):
        bm = Benchmark()
        bm.begin()
        for _ in range(3):
            bm.step()  # no sample counts at all
        bm.end()
        info = bm.step_info()
        assert "steps/s" in info
        rep = bm.report()
        assert rep["ips"] == 0.0 and rep["steps_per_sec"] > 0
        assert rep["total_samples"] == 0

    def test_fresh_benchmark_all_zero_no_raise(self):
        bm = Benchmark()
        assert bm.step_info() == "reader_cost: 0.00000 s, batch_cost: 0.00000 s"
        rep = bm.report()
        assert rep["ips"] == 0.0 and rep["steps_per_sec"] == 0.0

    def test_step_before_begin_arms_only(self):
        bm = Benchmark()
        bm.step(num_samples=16)  # arms the timer; no window to record yet
        assert bm.batch.count == 0 and bm.total_samples == 0
        bm.step(num_samples=16)
        assert bm.batch.count == 1 and bm.total_samples == 16

    def test_end_without_begin(self):
        bm = Benchmark()
        bm.end()
        assert bm.report()["total_time_s"] == 0.0

    def test_reset(self):
        bm = Benchmark()
        bm.begin()
        bm.step(num_samples=4)
        bm.step(num_samples=4)
        bm.reset()
        assert bm.batch.count == 0 and bm.total_samples == 0
        assert bm.report()["ips"] == 0.0


class TestSchedulerEdges:
    """Satellite: make_scheduler window edges."""

    def test_skip_first_shifts_whole_pattern(self):
        S = prof.ProfilerState
        sch = prof.make_scheduler(closed=0, ready=0, record=2, repeat=1,
                                  skip_first=3)
        assert [sch(i) for i in range(6)] == [
            S.CLOSED, S.CLOSED, S.CLOSED, S.RECORD, S.RECORD_AND_RETURN,
            S.CLOSED]

    def test_single_step_record_and_return(self):
        S = prof.ProfilerState
        sch = prof.make_scheduler(closed=0, ready=0, record=1, repeat=0)
        # record=1 means EVERY step is its window's last -> always R&R
        assert [sch(i) for i in range(3)] == [S.RECORD_AND_RETURN] * 3

    def test_repeat_stops_exactly_after_n_periods(self):
        S = prof.ProfilerState
        sch = prof.make_scheduler(closed=1, ready=1, record=1, repeat=2)
        got = [sch(i) for i in range(7)]
        assert got == [S.CLOSED, S.READY, S.RECORD_AND_RETURN,
                       S.CLOSED, S.READY, S.RECORD_AND_RETURN, S.CLOSED]

    def test_ready_window_does_not_record(self, clean_recorder):
        sch = prof.make_scheduler(closed=0, ready=1, record=1, repeat=1)
        traces = []
        p = prof.Profiler(targets=[prof.ProfilerTarget.CPU], scheduler=sch,
                          on_trace_ready=lambda pr: traces.append(
                              len(pr._spans)))
        p.start()
        with prof.RecordEvent("ready_phase"):
            pass
        p.step()
        with prof.RecordEvent("record_phase"):
            pass
        p.step()
        p.stop()
        assert traces == [1]  # only record_phase landed


class TestChromeTraceSchema:
    """Satellite: export is valid JSON with monotonic ts and distinct tids."""

    def test_schema(self, tmp_path, clean_recorder):
        p = prof.Profiler(targets=[prof.ProfilerTarget.CPU])
        p.start()

        def side_thread():
            with prof.RecordEvent("side_span"):
                time.sleep(0.002)

        th = threading.Thread(target=side_thread)
        th.start()
        with prof.RecordEvent("main_span"):
            time.sleep(0.002)
        th.join()
        p.stop()
        path = p.export(str(tmp_path / "schema.json"))
        data = json.load(open(path))  # valid JSON
        evs = data["traceEvents"]
        assert len(evs) >= 2
        for e in evs:
            assert e["ph"] == "X" and e["dur"] >= 0
            assert isinstance(e["ts"], float) and isinstance(e["tid"], int)
        ts = [e["ts"] for e in evs]
        assert ts == sorted(ts), "ts must be monotonic (sorted by start)"
        assert len({e["tid"] for e in evs}) >= 2, \
            "spans from different threads must keep distinct tids"
        assert data["metadata"]["producer"] == "paddle_tpu.profiler"


class TestCollectiveMetrics:
    def setup_method(self, _):
        mesh = build_mesh({"dp": 8})
        hcg = HybridCommunicateGroup(mesh=mesh)
        dist.set_hybrid_communicate_group(hcg)
        dist.destroy_process_group()
        self.mesh = mesh
        self.group = dist.new_group(axis_name="dp")

    def teardown_method(self, _):
        dist.set_hybrid_communicate_group(None)
        dist.destroy_process_group()

    def test_all_reduce_accounted_as_ici_bytes(self):
        reg = metrics.default_registry()
        calls0 = reg.counter("collective_calls_total").value(
            kind="all_reduce", link="ici")
        bytes0 = reg.counter("collective_bytes_total").value(
            kind="all_reduce", link="ici")
        x = paddle.to_tensor(np.ones((8, 4), np.float32))
        x.data = jax.device_put(x.data, NamedSharding(self.mesh, P("dp")))
        dist.all_reduce(x, group=self.group)
        assert reg.counter("collective_calls_total").value(
            kind="all_reduce", link="ici") == calls0 + 1
        assert reg.counter("collective_bytes_total").value(
            kind="all_reduce", link="ici") == bytes0 + 8 * 4 * 4

    def test_broadcast_and_allgather_kinds(self):
        reg = metrics.default_registry()
        b0 = reg.counter("collective_calls_total").value(
            kind="broadcast", link="ici")
        g0 = reg.counter("collective_calls_total").value(
            kind="all_gather", link="ici")
        x = paddle.to_tensor(np.ones((8,), np.float32))
        dist.broadcast(x, src=0, group=self.group)
        dist.all_gather(None, paddle.to_tensor(np.ones((4,), np.float32)),
                        group=self.group)
        assert reg.counter("collective_calls_total").value(
            kind="broadcast", link="ici") == b0 + 1
        assert reg.counter("collective_calls_total").value(
            kind="all_gather", link="ici") == g0 + 1

    def test_traced_collectives_not_counted(self):
        """An all_reduce on a TRACER (inside shard_map/pjit) must NOT hit
        the counters — it executes per compiled run, not per Python call,
        so counting the trace would be meaningless."""
        from jax import shard_map
        reg = metrics.default_registry()
        before = reg.counter("collective_calls_total").total()

        def f(a):
            return dist.all_reduce(a, group=self.group)

        import jax.numpy as jnp
        arr = jnp.ones((8, 2), jnp.float32)
        shard_map(f, mesh=self.mesh, in_specs=P("dp"), out_specs=P("dp"),
                  check_vma=False)(arr)
        assert reg.counter("collective_calls_total").total() == before


class TestDataLoaderWait:
    def test_reader_wait_feeds_benchmark_and_metrics(self):
        from paddle_tpu.io import DataLoader, Dataset

        class DS(Dataset):
            def __len__(self):
                return 16

            def __getitem__(self, i):
                return np.full((4,), i, np.float32)

        reg = metrics.default_registry()
        bm = prof.benchmark()
        reader_cnt0 = bm.reader.count
        batches0 = reg.counter("dataloader_batches_total").total()
        loader = DataLoader(DS(), batch_size=4, num_workers=0)
        out = list(loader)
        assert len(out) == 4
        assert bm.reader.count == reader_cnt0 + 4
        assert reg.counter("dataloader_batches_total").total() == batches0 + 4
        assert reg.counter("dataloader_wait_seconds_total").total() >= 0


class TestThroughputMonitor:
    def test_records_and_jsonl(self, tmp_path):
        path = str(tmp_path / "steps.jsonl")
        mon = ThroughputMonitor(window=2, jsonl_path=path,
                                samples_per_step=32,
                                flops_per_sample=1e9, peak_flops=1e12)
        mon.on_train_begin()
        mon.on_epoch_begin(0)
        for step in range(5):
            mon.on_train_batch_begin(step)
            time.sleep(0.001)
            mon.on_train_batch_end(step)
        mon.on_epoch_end(0)
        mon.on_train_end()
        # 5 steps, window 2 -> 2 full windows + 1 partial flush
        assert len(mon.records) == 3
        for rec in mon.records:
            validate_step_record(rec)
            assert 0.0 <= rec["data_wait_frac"] <= 1.0
            assert rec["mfu_est"] is not None and rec["mfu_est"] > 0
        assert mon.records[0]["window_steps"] == 2
        assert mon.records[-1]["window_steps"] == 1
        assert mon.records[-1]["step"] == 5
        lines = [json.loads(l) for l in open(path)]
        assert lines == mon.records

    def test_monitor_counts_retraces_in_window(self):
        wd = get_watchdog()
        wd.reset()
        mon = ThroughputMonitor(window=10)
        mon.on_train_begin()
        mon.on_train_batch_begin(0)
        wd.observe("s", "f", [np.ones((2,))])
        wd.observe("s", "f", [np.ones((3,))])  # retrace inside the window
        mon.on_train_batch_end(0)
        mon.on_train_end()
        assert mon.records[-1]["retraces"] == 1
        wd.reset()

    def test_hapi_fit_integration(self):
        """ThroughputMonitor rides Model.fit as a plain callback."""
        from paddle_tpu.io import Dataset

        class DS(Dataset):
            def __len__(self):
                return 8

            def __getitem__(self, i):
                return (np.ones((4,), np.float32),
                        np.array(i % 2, np.int64))

        paddle.seed(0)
        model = paddle.Model(nn.Linear(4, 2))
        model.prepare(optimizer=optimizer.SGD(
            parameters=model.parameters(), learning_rate=0.1),
            loss=nn.CrossEntropyLoss())
        mon = ThroughputMonitor(window=2, samples_per_step=4)
        model.fit(DS(), batch_size=4, epochs=1, verbose=0, callbacks=[mon])
        assert mon.records, "fit must emit at least one step record"
        for rec in mon.records:
            validate_step_record(rec)

    def test_make_step_record_degrades(self):
        rec = make_step_record(step=0, window_steps=0, window_time_s=0.0)
        validate_step_record(rec)
        assert rec["steps_per_sec"] == 0.0 and rec["ips"] is None
        assert rec["mfu_est"] is None and rec["step_time_ms"] == 0.0

    def test_validate_rejects_bad_records(self):
        good = make_step_record(step=1, window_steps=1, window_time_s=0.1)
        bad = dict(good)
        del bad["ts"]
        with pytest.raises(ValueError, match="ts"):
            validate_step_record(bad)
        bad2 = dict(good, extra_key=1)
        with pytest.raises(ValueError, match="extra_key"):
            validate_step_record(bad2)
        bad3 = dict(good, data_wait_frac=1.5)
        with pytest.raises(ValueError, match="data_wait_frac"):
            validate_step_record(bad3)

    def test_step_records_sample_device_memory(self):
        """Per-step device-memory watermarks land in the step record (the
        CPU backend has no memory_stats, so the live-arrays fallback
        feeds them — live tensors exist, so the sample is > 0)."""
        _keepalive = paddle.to_tensor(np.ones((64, 64), np.float32))
        mon = ThroughputMonitor(window=1)
        mon.on_train_begin()
        mon.on_train_batch_begin(0)
        mon.on_train_batch_end(0)
        mon.on_train_end()
        rec = mon.records[-1]
        validate_step_record(rec)
        assert rec["device_mem_bytes"] and rec["device_mem_bytes"] > 0
        assert rec["device_mem_peak_bytes"] >= rec["device_mem_bytes"]


class TestStepDiagnosis:
    """diagnose_window decomposes a window's wall into the registry's cost
    terms, names the dominant one, and emits a step_diagnosis event."""

    def test_dominant_term_from_registry_deltas(self):
        from paddle_tpu.profiler import events as events_mod
        from paddle_tpu.profiler.metrics import default_registry
        from paddle_tpu.profiler.monitor import diag_signals, diagnose_window
        events_mod.default_event_log().clear()
        begin = diag_signals()
        # simulate a compile-bound window: 0.4s of xla_compile_seconds
        default_registry().get("xla_compile_seconds").observe(
            0.4, entry="diag_test", phase="backend_compile")
        rec = diagnose_window(begin, wall_s=0.5, steps=4, step=40)
        assert rec["dominant"] == "compile"
        assert rec["terms"]["compile"] == pytest.approx(0.4)
        assert rec["terms"]["unattributed"] == pytest.approx(0.1)
        assert rec["dominant_frac"] == pytest.approx(0.8)
        assert rec["steps"] == 4 and rec["step"] == 40
        evs = events_mod.recent(10, kind="step_diagnosis")
        assert evs and evs[-1]["dominant"] == "compile"
        events_mod.validate_event(evs[-1])

    def test_unattributed_dominates_idle_window(self):
        from paddle_tpu.profiler.monitor import diag_signals, diagnose_window
        rec = diagnose_window(diag_signals(), wall_s=0.2, steps=1,
                              emit=False)
        assert rec["dominant"] == "unattributed"

    def test_collective_term_fed_by_guarded_collectives(self):
        """The collective_seconds histogram (new in this PR) feeds the
        'collective' diagnosis term for every guarded eager collective."""
        from paddle_tpu.profiler.metrics import default_registry
        from paddle_tpu.profiler.monitor import diag_signals
        begin = diag_signals()
        default_registry().histogram(
            "collective_seconds", "eager collective wall time by "
            "kind").observe(0.05, kind="all_reduce")
        assert diag_signals()["collective"] - begin["collective"] \
            == pytest.approx(0.05)

    def test_monitor_emits_one_diagnosis_per_window(self):
        from paddle_tpu.profiler import events as events_mod
        events_mod.default_event_log().clear()
        mon = ThroughputMonitor(window=2)
        mon.on_train_begin()
        for step in range(4):
            mon.on_train_batch_begin(step)
            mon.on_train_batch_end(step)
        mon.on_train_end()
        assert len(mon.diagnoses) == 2
        assert len(events_mod.recent(20, kind="step_diagnosis")) == 2
        assert all(d["dominant"] for d in mon.diagnoses)

    def test_monitor_diagnose_opt_out(self):
        from paddle_tpu.profiler import events as events_mod
        events_mod.default_event_log().clear()
        mon = ThroughputMonitor(window=1, diagnose=False)
        mon.on_train_begin()
        mon.on_train_batch_begin(0)
        mon.on_train_batch_end(0)
        mon.on_train_end()
        assert not mon.diagnoses
        assert not events_mod.recent(20, kind="step_diagnosis")


class TestDeviceMemorySampling:
    def test_sample_families_and_running_peak(self):
        from paddle_tpu.profiler import metrics as metrics_mod
        big = paddle.to_tensor(np.ones((256, 256), np.float32))
        mem = metrics_mod.sample_device_memory()
        assert mem, "no devices sampled"
        dev, stats = next(iter(mem.items()))
        assert stats["bytes_in_use"] > 0
        assert stats["peak_bytes"] >= stats["bytes_in_use"]
        assert stats["src"] in ("memory_stats", "live_arrays")
        reg = metrics_mod.default_registry()
        assert reg.get("device_memory_bytes_in_use").value(device=dev) \
            == stats["bytes_in_use"]
        peak_before = stats["peak_bytes"]
        del big
        mem2 = metrics_mod.sample_device_memory()
        # the watermark never regresses even when usage drops
        assert mem2[dev]["peak_bytes"] >= peak_before \
            or mem2[dev]["src"] == "memory_stats"

    def test_sample_honors_kill_switch(self):
        from paddle_tpu.profiler import metrics as metrics_mod
        metrics_mod.set_enabled(False)
        try:
            assert metrics_mod.sample_device_memory() == {}
        finally:
            metrics_mod.set_enabled(True)
