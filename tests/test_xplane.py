"""XPlane measured device time (profiler/xplane.py): trace parsing and
lane classification, span correlation (synthetic + live CPU capture),
the armed N-step ProfileCapture state machine with its hard wall-clock
cap, and the persistent-compile-cache flag wiring.
"""
import gzip
import json
import os
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.profiler import device_time, xplane
from paddle_tpu.profiler.recorder import HostSpan, get_recorder
from paddle_tpu.profiler.utils import RecordEvent


def _ev(name, ts, dur, pid=1, tid=1, ph="X", args=None):
    e = {"ph": ph, "name": name, "ts": ts, "dur": dur, "pid": pid,
         "tid": tid}
    if args is not None:
        e["args"] = args
    return e


def _meta(pid, tid=None, name=""):
    if tid is None:
        return {"ph": "M", "name": "process_name", "pid": pid,
                "args": {"name": name}}
    return {"ph": "M", "name": "thread_name", "pid": pid, "tid": tid,
            "args": {"name": name}}


def _synthetic_trace():
    """Host lane (python thread, annotations at known windows) + one work
    lane with overlapping backend events + infra noise."""
    return [
        _meta(1, name="/host:CPU"),
        _meta(1, tid=10, name="python"),
        # annotations: matmul [100, 200), softmax [300, 380)
        _ev("$somefile.py:1 frame", 0, 500, tid=10),
        _ev("matmul", 100, 100, tid=10),
        _ev("softmax", 300, 80, tid=10),
        # work lane: overlaps matmul by 60us, softmax by 40us, plus noise
        _ev("dot.3", 120, 60, tid=20),
        _ev("reduce_fusion.1", 320, 40, tid=20),
        _ev("ThreadpoolListener::StartRegion", 100, 300, tid=20),
        _ev("TaskDispatcher::dispatch", 0, 600, tid=21),
    ]


def _span(name, start_ns, end_ns, device_ns=None, src=None):
    return HostSpan(name=name, start_ns=start_ns, end_ns=end_ns, tid=10,
                    device_ns=device_ns, device_src=src)


class TestParseAndClassify:
    def test_classify_lanes_host_vs_work(self):
        host, work = classified = xplane.classify_lanes(_synthetic_trace())
        assert (1, 10) in host
        assert (1, 20) in work
        # a lane with ONLY infra events is neither host nor work
        assert (1, 21) not in host and (1, 21) not in work

    def test_device_process_is_always_work(self):
        evs = [_meta(7, name="/device:TPU:0"),
               _ev("fusion.9", 0, 10, pid=7, tid=1)]
        host, work = xplane.classify_lanes(evs)
        assert (7, 1) in work and not host

    def test_work_events_filters_infra_and_annotations(self):
        works = xplane.work_events(_synthetic_trace(),
                                   span_names=["matmul", "softmax"])
        assert [e["name"] for e in works] == ["dot.3", "reduce_fusion.1"]

    def test_load_trace_gz_and_plain(self, tmp_path):
        doc = {"traceEvents": _synthetic_trace()}
        plain = tmp_path / "t.json"
        plain.write_text(json.dumps(doc))
        gz = tmp_path / "t.trace.json.gz"
        with gzip.open(gz, "wt") as f:
            json.dump(doc, f)
        assert xplane.load_trace(str(plain)) == doc
        assert xplane.load_trace(str(gz)) == doc

    def test_find_trace_file_session_layout(self, tmp_path):
        d = tmp_path / "plugins" / "profile" / "2026_01_01"
        d.mkdir(parents=True)
        (d / "host.trace.json.gz").write_bytes(gzip.compress(b"{}"))
        found = xplane.find_trace_file(str(tmp_path))
        assert found and found.endswith("host.trace.json.gz")
        assert xplane.find_trace_file(str(tmp_path / "nope")) is None


class TestCorrelate:
    def test_overlap_attribution_and_estimate_delta(self):
        spans = [_span("matmul", 0, 1000, device_ns=50_000, src="estimate"),
                 _span("softmax", 2000, 3000, device_ns=10_000,
                       src="estimate")]
        stats = xplane.correlate(spans, _synthetic_trace())
        assert stats["correlated"] == 2
        # matmul window [100,200) overlaps dot.3 [120,180) -> 60us
        assert spans[0].device_ns == 60_000
        assert spans[0].device_src == "xplane"
        # softmax window [300,380) overlaps reduce_fusion.1 [320,360) -> 40us
        assert spans[1].device_ns == 40_000
        by_op = {r["op"]: r for r in stats["by_op"]}
        assert by_op["matmul"]["est_ms"] == 0.05
        assert by_op["matmul"]["xplane_ms"] == 0.06
        assert by_op["matmul"]["xplane_vs_est"] == 1.2

    def test_unmatched_span_keeps_estimate(self):
        spans = [_span("relu", 0, 1000, device_ns=5_000, src="estimate")]
        stats = xplane.correlate(spans, _synthetic_trace())
        assert stats["correlated"] == 0
        assert spans[0].device_src == "estimate"

    def test_extra_spans_align_from_newest(self):
        # two matmul spans, one annotation: only the NEWEST span matches
        spans = [_span("matmul", 0, 10, device_ns=1, src="estimate"),
                 _span("matmul", 20, 30, device_ns=1, src="estimate")]
        stats = xplane.correlate(spans, _synthetic_trace())
        assert stats["correlated"] == 1
        assert spans[0].device_src == "estimate"
        assert spans[1].device_src == "xplane"

    def test_args_name_match_attributes_regardless_of_overlap(self):
        # TPU metadata path: a work event far outside the window whose
        # args name the op still lands on the annotation
        evs = _synthetic_trace() + [
            _ev("fusion.77", 5000, 25, tid=20, args={"tf_op": "matmul"})]
        spans = [_span("matmul", 0, 1000, device_ns=1, src="estimate")]
        xplane.correlate(spans, evs)
        assert spans[0].device_ns == (60 + 25) * 1000

    def test_split_rows_and_table_show_xplane_src(self):
        spans = [_span("matmul", 0, 1000, device_ns=60_000, src="xplane"),
                 _span("matmul", 0, 1000, device_ns=50_000, src="estimate")]
        rows = device_time.split_rows(spans)
        assert rows[0]["src"] == "xplane"
        from paddle_tpu.profiler.statistic import (StatisticData,
                                                   summary_report)
        table = summary_report(StatisticData(spans))
        assert "Dev(ms)" in table and "xplane" in table


def _eager_pass(a):
    """Eager ops inside a span that waits for the result: the one span
    whose device work is sure to lie inside it (an op's own span closes
    when the op is enqueued; whether its work overlaps it is up to
    asynchronous dispatch)."""
    with RecordEvent("eager_pass"):
        paddle.nn.functional.softmax(paddle.matmul(a, a)).numpy()


class TestCaptureSessionLive:
    def test_capture_correlates_eager_ops_on_cpu(self, tmp_path):
        """The acceptance path: a capture session over real eager ops on
        the CPU backend correlates >= 1 op span to device_src="xplane" and
        the summary table gains the measured Dev(ms) column."""
        sess = xplane.CaptureSession(str(tmp_path / "s1"))
        sess.start()
        try:
            a = paddle.to_tensor(np.ones((96, 96), np.float32))
            for _ in range(3):
                _eager_pass(a)
        finally:
            summary = sess.stop(steps=3)
        assert summary["status"] == "complete"
        corr = summary["correlation"]
        assert corr["correlated"] >= 1, corr
        assert summary["device_time"]["mode"] == "xplane"
        assert any(r["src"] == "xplane"
                   for r in summary["device_time"]["rows"])
        assert "Dev(ms)" in summary["summary_table"]
        assert "xplane" in summary["summary_table"]
        # diagnosis rode along and named a dominant term
        assert summary["diagnosis"]["dominant"]
        # the summary is persisted into the session dir
        on_disk = json.load(open(tmp_path / "s1" / "summary.json"))
        assert on_disk["status"] == "complete"

    def test_profiler_device_window_correlates(self, tmp_path):
        """The classic Profiler's device-trace window (trace_dir + a
        device target) now correlates its spans on stop: summary rows
        carry device_src="xplane" without any /profile involvement."""
        from paddle_tpu.profiler.profiler import Profiler, ProfilerTarget
        p = Profiler(targets=[ProfilerTarget.CPU, ProfilerTarget.GPU],
                     trace_dir=str(tmp_path / "prof"))
        with p:
            a = paddle.to_tensor(np.ones((96, 96), np.float32))
            for _ in range(3):
                _eager_pass(a)
        assert p.xplane_stats is not None
        assert p.xplane_stats["correlated"] >= 1
        assert any(s.device_src == "xplane" for s in p._spans)
        assert not xplane.annotating()  # flag cleared on stop

    def test_capture_refuses_busy_recorder(self, tmp_path):
        rec = get_recorder()
        rec.enabled = True
        try:
            with pytest.raises(xplane.CaptureBusyError):
                xplane.CaptureSession(str(tmp_path / "s2")).start()
        finally:
            rec.enabled = False


class TestProfileCapture:
    def test_arm_step_finalize(self, tmp_path):
        cap = xplane.ProfileCapture()
        ack = cap.arm(2, session_dir=str(tmp_path / "p1"), timeout_s=60)
        assert ack["status"] == "armed"
        a = paddle.to_tensor(np.ones((64, 64), np.float32))
        step = 0
        while cap.state != "idle":
            step += 1
            # the result is read back before the step is noted, so the
            # step's work lies inside its `train_step` span: overlap in
            # time is all `correlate` has, and asynchronous dispatch
            # promises none to a step that does not wait
            paddle.matmul(a, a).numpy()
            cap.on_step(step)
            assert step < 10, "capture never finalized"
        summary = cap.wait(1)
        assert summary["status"] == "complete"
        assert summary["steps"] == 2
        assert (summary["correlation"] or {}).get("correlated", 0) >= 1

    def test_concurrent_arm_is_busy(self, tmp_path):
        cap = xplane.ProfileCapture()
        cap.arm(1, session_dir=str(tmp_path / "p2"), timeout_s=60)
        with pytest.raises(xplane.CaptureBusyError):
            cap.arm(1, session_dir=str(tmp_path / "p3"))
        cap.on_step(1)
        cap.on_step(2)  # finalizes
        assert cap.state == "idle"

    def test_armed_but_stalled_times_out(self, tmp_path):
        """The hard wall-clock cap: a job that never steps cannot hold the
        capture armed forever."""
        cap = xplane.ProfileCapture()
        cap.arm(1, session_dir=str(tmp_path / "p4"), timeout_s=0.2)
        summary = cap.wait(5)
        assert summary["status"] == "timeout"
        assert cap.state == "idle"
        # and the slot is reusable afterwards
        cap.arm(1, session_dir=str(tmp_path / "p5"), timeout_s=60)
        cap.on_step(1)
        cap.on_step(2)
        assert cap.wait(1)["status"] == "complete"

    def test_recording_window_capped_mid_flight(self, tmp_path):
        """A capture whose step flow stalls mid-window is force-finalized
        at the cap with whatever was recorded."""
        cap = xplane.ProfileCapture()
        cap.arm(100, session_dir=str(tmp_path / "p6"), timeout_s=1.0)
        a = paddle.to_tensor(np.ones((32, 32), np.float32))
        paddle.matmul(a, a)
        cap.on_step(1)  # starts recording; steps then stall
        summary = cap.wait(10)
        assert summary["status"] == "timeout"
        assert cap.state == "idle"

    def test_on_step_never_raises_while_idle(self):
        xplane.default_capture().on_step(123)  # no session: cheap no-op

    def test_compiled_loop_gets_train_step_spans(self, tmp_path):
        """A loop whose whole step is ONE compiled executable emits no
        eager op spans — the capture brackets each inter-note interval in
        a synthesized `train_step` span so the production (jit) path still
        yields measured per-step device time."""
        import jax
        import jax.numpy as jnp
        f = jax.jit(lambda x: (x @ x).sum())
        x = jnp.ones((96, 96))
        float(f(x))  # compile outside the capture window
        cap = xplane.ProfileCapture()
        cap.arm(2, session_dir=str(tmp_path / "jit"), timeout_s=60)
        for step in range(1, 5):
            float(f(x))  # compiled-only work, no eager dispatch
            cap.on_step(step)
            if cap.state == "idle":
                break
        summary = cap.wait(10)
        assert summary["status"] == "complete"
        rows = [r for r in summary["device_time"]["rows"]
                if r["op"] == "train_step"]
        assert rows and rows[0]["src"] == "xplane", summary["device_time"]
        assert rows[0]["calls"] == 2
        assert "train_step" in summary["summary_table"]


class TestPeaksTable:
    def test_known_kind_has_a_sourced_row(self):
        row = device_time.device_peaks("TPU v5 lite")
        assert row.bf16_flops == 197e12 and row.hbm_bytes_per_s == 819e9
        assert "Google Cloud" in row.source

    def test_unknown_kind_raises_and_estimates_nothing(self):
        """No default peak: a device off the table gets no roofline, and
        the per-op estimator attributes nothing rather than inventing
        CPU "device time"."""
        device_time.reset_peaks()
        with pytest.raises(device_time.UnknownDeviceError, match="cpu"):
            device_time.device_peaks()
        with pytest.raises(device_time.UnknownDeviceError):
            device_time.platform_peaks()
        assert device_time.attribute([], 1e9, 1e6, 0) == (None, None)

    def test_reset_peaks_reprobes(self, peaks_row_for_this_device):
        plat, flops, bw = device_time.platform_peaks()
        assert (plat, flops, bw) == ("cpu", 100e9, 20e9)
        assert device_time.attribute([], 1e9, 0, 0) == (int(1e7), "estimate")


class TestCompileCacheWiring:
    @pytest.mark.slow  # child-process cache roundtrip; flag plumbing is
    def test_flag_points_jax_at_persistent_cache(self, tmp_path):  # pinned fast elsewhere
        """Satellite: PADDLE_TPU_COMPILE_CACHE_DIR -> jax's persistent
        compilation cache, making xla_compile_cache_events_total count
        real hits/misses (it sat at zero with the cache unwired)."""
        import jax
        import jax.numpy as jnp
        from paddle_tpu.framework import flags as flags_mod
        from paddle_tpu.profiler import metrics as metrics_mod
        cache_dir = str(tmp_path / "ccache")
        os.makedirs(cache_dir)
        ctr = metrics_mod.default_registry().get(
            "xla_compile_cache_events_total")
        before = {k: ctr.value(event=k) for k in ("hit", "miss", "request")}
        flags_mod.set_flags({"FLAGS_compile_cache_dir": cache_dir})
        try:
            assert jax.config.jax_compilation_cache_dir == cache_dir
            f = jax.jit(lambda x: x * 3.0 + 1.0)
            f(jnp.ones((4, 4))).block_until_ready()
            assert os.listdir(cache_dir), "no cache entries written"
            assert ctr.value(event="miss") > before["miss"]
            # same program after dropping jax's in-memory caches: a HIT
            jax.clear_caches()
            f2 = jax.jit(lambda x: x * 3.0 + 1.0)
            f2(jnp.ones((4, 4))).block_until_ready()
            assert ctr.value(event="hit") > before["hit"]
        finally:
            flags_mod.set_flags({"FLAGS_compile_cache_dir": ""})
            assert jax.config.jax_compilation_cache_dir is None


class TestSegmentBreakdown:
    """Measured per-segment attribution (r06): work events classified by
    XLA op-metadata scope tags, fwd/bwd split by autodiff markers,
    unattributed bucket for metadata-free exports."""

    @staticmethod
    def _tpu_style_trace():
        """Device-lane events whose args carry op_name metadata the way
        the TPU TB export does."""
        def dev(name, ts, dur, op_name):
            return _ev(name, ts, dur, pid=5, tid=50,
                       args={"name": op_name})
        return [
            _meta(5, name="/device:TPU:0"),
            _meta(5, tid=50, name="XLA Op"),
            dev("fusion.1", 0, 100,
                "jit(step)/attention/dot_general"),
            dev("fusion.2", 100, 300,
                "jit(step)/transpose(jvp(attention))/dot_general"),
            dev("fusion.3", 400, 80, "jit(step)/mlp/dot_general"),
            dev("fusion.4", 480, 160,
                "jit(step)/transpose(jvp(mlp))/dot_general"),
            dev("fusion.5", 640, 20, "jit(step)/ln/reduce"),
            dev("fusion.6", 660, 30, "jit(step)/loss/reduce"),
            dev("fusion.7", 690, 40, "jit(step)/optimizer/multiply"),
            dev("fusion.8", 730, 25, "jit(step)/embed/gather"),
            dev("custom-call.9", 755, 55, "flash_attention_fwd"),
            dev("fusion.10", 810, 90, "something_opaque"),
            # backward LN spelling: no /ln/ path component, only the
            # autodiff-wrapped scope — must still classify as ln
            dev("fusion.11", 900, 10,
                "jit(step)/transpose(jvp(ln))/reduce"),
        ]

    def test_classification_and_fractions(self):
        out = xplane.segment_breakdown(self._tpu_style_trace())
        seg = out["segments"]
        assert seg["attention_fwd"]["device_ms"] == pytest.approx(0.155)
        assert seg["attention_bwd"]["device_ms"] == pytest.approx(0.3)
        assert seg["mlp_fwd"]["device_ms"] == pytest.approx(0.08)
        assert seg["mlp_bwd"]["device_ms"] == pytest.approx(0.16)
        assert seg["ln"]["events"] == 2  # fwd (/ln/) + bwd (jvp(ln))
        assert seg["ln"]["device_ms"] == pytest.approx(0.03)
        assert seg["loss"]["device_ms"] == pytest.approx(0.03)
        assert seg["optimizer"]["device_ms"] == pytest.approx(0.04)
        assert seg["embed"]["events"] == 1
        assert seg["unattributed"]["device_ms"] == pytest.approx(0.09)
        total = out["total_device_ms"]
        assert total == pytest.approx(0.91)
        assert out["attributed_frac"] == pytest.approx(1 - 0.09 / 0.91,
                                                       abs=1e-4)
        fracs = sum(r["frac"] for r in seg.values())
        assert fracs == pytest.approx(1.0, abs=1e-3)

    def test_metadata_free_trace_is_all_unattributed(self):
        out = xplane.segment_breakdown(_synthetic_trace())
        seg = out["segments"]
        assert set(seg) == {"unattributed"}
        assert out["attributed_frac"] == 0.0

    def test_empty_trace(self):
        out = xplane.segment_breakdown([])
        assert out["segments"] == {}
        assert out["total_device_ms"] == 0.0
        assert out["attributed_frac"] is None
