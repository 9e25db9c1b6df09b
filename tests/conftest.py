"""Test config: force an 8-device virtual CPU mesh before jax initializes.

Mirrors the reference's test strategy of simulating clusters on localhost
(`/root/reference/python/paddle/fluid/tests/unittests/test_dist_base.py:968`):
distributed tests run on 8 virtual CPU devices via
--xla_force_host_platform_device_count.
"""
import os

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")
if "--xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] += " --xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np
import pytest


def pytest_addoption(parser):
    parser.addoption(
        "--slow", action="store_true", default=False,
        help="also run tests marked slow (multi-process cluster variants, "
             "long convergence runs); default suite skips them to stay "
             "under the CI wall-clock budget")


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running test, needs --slow to run")


def pytest_collection_modifyitems(config, items):
    if config.getoption("--slow"):
        return
    skip = pytest.mark.skip(reason="slow: pass --slow to run")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)


@pytest.fixture(autouse=True)
def _seed_all():
    import paddle_tpu as paddle
    paddle.seed(1234)
    np.random.seed(1234)
    yield
    from paddle_tpu.framework import tape
    tape.reset_tape()


@pytest.fixture
def peaks_row_for_this_device(monkeypatch):
    """The roofline estimator and bench's MFU need a row of published
    peaks for the device (profiler/device_time.PEAKS); the CPU has none,
    by design. Tests of that machinery's STRUCTURE give it a row."""
    from paddle_tpu.profiler import device_time
    monkeypatch.setitem(device_time.PEAKS, jax.devices()[0].device_kind,
                        device_time.Peaks(100e9, 20e9, "test row"))
    device_time.reset_peaks()
    yield
    device_time.reset_peaks()
