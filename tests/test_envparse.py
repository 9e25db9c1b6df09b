"""Shared env-knob parse helper (paddle_tpu/utils/envparse.py) + one
regression test per offender the convention lint surfaced: every
consumer that used to detonate with an anonymous int()/float()
ValueError on a garbled PADDLE_TPU_* value now warns (naming the knob)
and uses its documented default instead.
"""
import warnings

import pytest

from paddle_tpu.utils import envparse
from paddle_tpu.utils.envparse import (EnvKnobError, env_bool, env_float,
                                       env_int, env_str)


@pytest.fixture(autouse=True)
def _fresh_warn_state():
    envparse._reset_warned()
    yield
    envparse._reset_warned()


class TestHelper:
    def test_unset_returns_default(self, monkeypatch):
        monkeypatch.delenv("PADDLE_TPU_TEST_K", raising=False)
        assert env_int("PADDLE_TPU_TEST_K", 7) == 7
        assert env_float("PADDLE_TPU_TEST_K", 2.5) == 2.5
        assert env_str("PADDLE_TPU_TEST_K", "d") == "d"
        assert env_bool("PADDLE_TPU_TEST_K", True) is True

    def test_empty_string_counts_as_unset(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_TEST_K", "")
        assert env_int("PADDLE_TPU_TEST_K", 7) == 7
        assert env_str("PADDLE_TPU_TEST_K", "d") == "d"

    def test_valid_values_parse(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_TEST_K", "42")
        assert env_int("PADDLE_TPU_TEST_K", 7) == 42
        assert env_float("PADDLE_TPU_TEST_K", 2.5) == 42.0

    def test_garbled_warns_once_naming_knob_and_default(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_TEST_K", "ten")
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            assert env_int("PADDLE_TPU_TEST_K", 7) == 7
            assert env_int("PADDLE_TPU_TEST_K", 7) == 7  # second: silent
        assert len(w) == 1
        msg = str(w[0].message)
        assert "PADDLE_TPU_TEST_K" in msg and "'ten'" in msg and "7" in msg

    def test_strict_raises_named_error(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_TEST_K", "ten")
        with pytest.raises(EnvKnobError, match="PADDLE_TPU_TEST_K"):
            env_int("PADDLE_TPU_TEST_K", 7, strict=True)
        with pytest.raises(ValueError):  # EnvKnobError IS a ValueError
            env_float("PADDLE_TPU_TEST_K", 7.0, strict=True)

    def test_bool_conventions(self, monkeypatch):
        for off in ("0", "false", "OFF", "No"):
            monkeypatch.setenv("PADDLE_TPU_TEST_K", off)
            assert env_bool("PADDLE_TPU_TEST_K", True) is False
        monkeypatch.setenv("PADDLE_TPU_TEST_K", "1")
        assert env_bool("PADDLE_TPU_TEST_K", False) is True


class TestOffenderRegressions:
    """Each consumer the lint found parsing PADDLE_TPU_* numerics
    directly: garbled value -> default behavior, never a raw
    ValueError."""

    def test_event_buffer(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_EVENT_BUFFER", "lots")
        from paddle_tpu.profiler.events import EventLog
        log = EventLog()  # was: int('lots') ValueError at construction
        assert log._ring.maxlen == 512

    def test_retrace_warn(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_RETRACE_WARN", "many")
        from paddle_tpu.profiler.watchdog import RetraceWatchdog
        wd = RetraceWatchdog()
        assert wd.warn_threshold == 0

    def test_health_interval_and_groups(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_HEALTH_INTERVAL", "x")
        monkeypatch.setenv("PADDLE_TPU_HEALTH_GROUPS", "y")
        from paddle_tpu.profiler import health
        assert health.interval() == 1
        assert health.max_groups() == 32

    def test_profile_timeout(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_PROFILE_TIMEOUT", "forever")
        from paddle_tpu.profiler import xplane
        assert xplane.capture_timeout() == xplane.DEFAULT_CAPTURE_TIMEOUT

    def test_health_stall_sec(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_HEALTH_STALL_SEC", "soon")
        from paddle_tpu.profiler import server
        out = server.liveness()
        assert out["stall_after_s"] == server.DEFAULT_STALL_SEC

    def test_ckpt_barrier_timeouts(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_CKPT_BARRIER_TIMEOUT", "slow")
        monkeypatch.setenv("PADDLE_TPU_CKPT_RESUME_TIMEOUT", "slower")
        from paddle_tpu.distributed.checkpoint import CheckpointCoordinator
        coord = CheckpointCoordinator(store=object(), rank=0, world_size=2)
        assert coord.timeout == 60.0
        assert coord.resume_timeout == 120.0

    def test_digest_window_and_interval(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_DIGEST_WINDOW", "wide")
        monkeypatch.setenv("PADDLE_TPU_DIGEST_INTERVAL", "often")
        from paddle_tpu.distributed.fleet.telemetry import FleetReporter
        rep = FleetReporter(store=None, rank=0)
        assert rep.walls.maxlen == 20
        assert rep.min_interval_s == 0.5

    def test_straggler_factor_and_stale_sec(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_STRAGGLER_FACTOR", "big")
        monkeypatch.setenv("PADDLE_TPU_DIGEST_STALE_SEC", "old")
        from paddle_tpu.distributed.fleet.telemetry import FleetAggregator
        agg = FleetAggregator(store=None, world_size=2)
        assert agg.straggler_factor == 2.0
        assert agg.stale_sec == 120.0

    def test_elastic_restart_num(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_ELASTIC_RESTART_NUM", "zero")
        from paddle_tpu.distributed.fleet.telemetry import FleetReporter
        assert FleetReporter._generation() == 0

    def test_elastic_supervisor_knobs(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_ELASTIC_MAX_RESTARTS", "lots")
        monkeypatch.setenv("PADDLE_TPU_ELASTIC_BACKOFF", "fast")
        monkeypatch.setenv("PADDLE_TPU_ELASTIC_BACKOFF_MAX", "slow")
        monkeypatch.setenv("PADDLE_TPU_ELASTIC_BUDGET_RESET_SEC", "never")
        monkeypatch.setenv("PADDLE_TPU_CONTROLLER_POLL_SEC", "often")
        from paddle_tpu.distributed.fleet.elastic import ElasticSupervisor
        sup = ElasticSupervisor()
        assert sup.max_restarts == 3
        assert sup.backoff == 1.0
        assert sup.backoff_max == 30.0
        assert sup.budget_reset_s == 300.0
        assert sup.cmd_poll == 1.0

    def test_collective_timeout(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_COLLECTIVE_TIMEOUT", "soon")
        from paddle_tpu.distributed.collective import _deadline_seconds
        assert _deadline_seconds() == 0.0

    def test_retry_policy_from_env(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_STORE_RETRIES", "many")
        monkeypatch.setenv("PADDLE_TPU_STORE_BACKOFF", "fast")
        from paddle_tpu.fault.retry import RetryPolicy
        pol = RetryPolicy.from_env("store", max_attempts=5,
                                   base_delay=0.2)
        assert pol.max_attempts == 5
        assert pol.base_delay == 0.2

    def test_supervisor_metrics_port(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_SUPERVISOR_METRICS_PORT", "auto")
        assert env_int("PADDLE_TPU_SUPERVISOR_METRICS_PORT", 8081) == 8081

    def test_ckpt_abort_exit_still_raises_named_error(self, monkeypatch):
        """This knob keeps the PR-5 STRICT contract: construction fails
        with an error NAMING the knob (not mid-training on the first
        aborted save)."""
        monkeypatch.setenv("PADDLE_TPU_CKPT_ABORT_EXIT", "twice")
        from paddle_tpu.hapi.callbacks import FaultTolerantCheckpoint
        with pytest.raises(ValueError, match="PADDLE_TPU_CKPT_ABORT_EXIT"):
            FaultTolerantCheckpoint("/tmp/nonexistent_ckpt_dir")
