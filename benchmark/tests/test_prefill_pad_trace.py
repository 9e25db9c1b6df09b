"""The reader of padded prefill rows (benchmark/prefill_pad_trace.py): on
hand-made spans with a known answer, on spans that lack what it reads,
and on the piece of a closed32 chip trace kept in tests/data/ (PR 35),
whose admissions ran the parent's power-of-two buckets."""
import gzip
import json
import os

import pytest

from benchmark import prefill_pad_trace as pp

HERE = os.path.dirname(os.path.abspath(__file__))


def prefill(start, bucket=None, tokens=None, dur=10.0):
    args = {"rid": 1}
    if bucket is not None:
        args["bucket"] = bucket
    if tokens is not None:
        args["prompt_tokens"] = tokens
    return ["pt.engine.prefill", start, dur, "main", args]


def test_share_of_padded_rows_inside_the_window():
    spans = [["bench.window", 100.0, 900.0, "main", {}],
             prefill(50.0, 1024, 1000),        # before the window
             prefill(95.0, 1024, 1000),        # straddles its start
             prefill(200.0, 192, 130),
             prefill(300.0, 192, 192),
             prefill(400.0, 2048, 1537),
             ["pt.engine.step", 200.0, 50.0, "main", {"iteration": 3}],
             prefill(995.0, 128, 1)]           # straddles its end
    got = pp.reduce(spans)
    assert got["prefills"] == 3
    assert got["rows"] == 192 + 192 + 2048
    assert got["padded_rows"] == 62 + 0 + 511
    assert got["by_bucket"] == {"192": [2, 322], "2048": [1, 1537]}
    assert got["prefill_pad_pct"] == pytest.approx(100.0 * 573 / 2432)


def test_without_a_window_the_whole_trace_counts():
    assert pp.reduce([prefill(0.0, 256, 64)])["prefill_pad_pct"] == 75.0


@pytest.mark.parametrize("spans", [
    [],
    [["pt.engine.step", 0.0, 5.0, "main", {}]],
    [prefill(0.0, bucket=256)],        # a program that names no tokens
    [prefill(0.0, tokens=64)],         # or no bucket
], ids=["empty", "no_prefill", "no_tokens", "no_bucket"])
def test_nothing_to_read_gives_none(spans):
    assert pp.reduce(spans) is None


def test_on_a_chip_trace_of_the_parent_s_buckets():
    """23 admissions of closed32 through powers of two from 16 (the
    ladder before PR 38): 30 % of the rows were padding, the pool's own
    30.4 % (PERF.md section 6, PR 38)."""
    with gzip.open(os.path.join(
            HERE, "data", "closed32_v5e_launch_trace.json.gz"), "rt") as f:
        spans = json.load(f)["planes"]["spans"]
    got = pp.reduce(spans)
    assert (got["prefills"], got["rows"], got["padded_rows"]) == (
        23, 7136, 2142)
    assert got["prefill_pad_pct"] == pytest.approx(30.0168, abs=1e-3)
    assert set(got["by_bucket"]) == {"32", "64", "128", "256", "512", "1024"}
