"""The reduction from a trace to busy time, top operations and idle gaps:
on a hand-made trace with known answers, and on the small trace recorded
on the chip (tests/data/, PR 24)."""
import gzip
import json
import os

import pytest

from benchmark import tracing

HERE = os.path.dirname(os.path.abspath(__file__))


def test_union_merges_and_clips():
    assert tracing.union([(5, 9), (0, 3), (2, 4), (20, 30)], 1, 25) == [
        [1, 4], [5, 9], [20, 25]]
    assert tracing.union([(0, 1)], 2, 3) == []


def test_known_trace():
    planes = {
        "devices": {"/device:TPU:0": [("fusion.a", 100.0, 50.0),
                                      ("fusion.b", 120.0, 50.0),
                                      ("fusion.a", 300.0, 100.0),
                                      ("before", 0.0, 5.0)]},
        "spans": [("bench.window", 10.0, 490.0),
                  ("bench.x", 10.0, 190.0), ("bench.y", 200.0, 300.0)],
    }
    r = tracing.reduce_planes(planes)
    assert r["window_s"] == pytest.approx(490e-9)
    # [100,170] and [300,400]; the operation before the window is clipped
    assert r["busy_s"] == pytest.approx(170e-9)
    assert r["device_ops"] == [["fusion.a", pytest.approx(150e-9)],
                               ["fusion.b", pytest.approx(50e-9)]]
    # gaps [10,100] -> x; [170,300] -> y (covers 100 of 130); [400,500] -> y
    assert r["idle_gaps"] == [["y", pytest.approx(230e-9)],
                              ["x", pytest.approx(90e-9)]]
    assert r["busy_s"] + sum(g for _, g in r["idle_gaps"]) == pytest.approx(
        r["window_s"])


def test_no_device_plane_gives_nothing():
    assert tracing.reduce_planes({"devices": {}, "spans": []}) == {}
    assert tracing.reduce_planes(
        {"devices": {"/device:TPU:0": []}, "spans": []}) == {}


def test_two_chips_are_averaged():
    planes = {"devices": {"/device:TPU:0": [("op", 0.0, 100.0)],
                          "/device:TPU:1": [("op", 0.0, 50.0)]},
              "spans": [("bench.window", 0.0, 100.0)]}
    r = tracing.reduce_planes(planes)
    assert r["busy_s"] == pytest.approx(75e-9) and r["chips_traced"] == 2
    assert r["device_ops"] == [["op", pytest.approx(75e-9)]]


def test_recorded_chip_trace():
    path = os.path.join(HERE, "data", "train_v5e_trace.json.gz")
    with gzip.open(path, "rt") as f:
        recorded = json.load(f)
    r = tracing.reduce_planes(recorded["planes"])
    want = recorded["expected"]
    assert r["window_s"] == pytest.approx(want["window_s"])
    assert r["busy_s"] == pytest.approx(want["busy_s"])
    assert 0 < r["busy_s"] < r["window_s"]
    assert [n for n, _ in r["device_ops"][:3]] == want["top_ops"]
    assert {n for n, _ in r["idle_gaps"]} <= set(want["span_names"]) | {
        "outside-spans"}
