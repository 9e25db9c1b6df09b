"""The reader that joins each launch of the serving engine to its
execution on the device (benchmark/launch_trace.py): on hand-made traces
with known answers for each cause of an idle instant, for a clock that
breaks causality, for the joins it must refuse, and on the piece of a
closed32 chip trace kept in tests/data/ (PR 35)."""
import copy
import gzip
import json
import os

import pytest

from benchmark import harness
from benchmark import launch_trace as lt
from benchmark import program_trace, tracing

from conftest import ROOT

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = "/device:TPU:0"
US = 1e3          # the hand-made traces are written in microseconds
DECODE, PREFILL = "jit__fused_step_fn", "jit__prefill_fn"
METRICS = ("decode_read_tail_ms", "prefill_read_tail_ms",
           "serve_launch_lag_ms", "serve_back_to_back_pct",
           "serve_idle_cause_call_pct", "serve_idle_cause_read_pct",
           "serve_idle_cause_host_pct")


def span(name, start, end, args=None, line="main"):
    return [name, start * US, (end - start) * US, line, dict(args or {})]


def planes(tight=False, run_ids=False):
    """Six engine steps in a window of 10,000 us, seven launches (seq 0-6:
    decode, decode, decode AHEAD, prefill, decode, decode AHEAD, decode
    AHEAD), one operation an execution. The comments of
    `test_each_idle_instant_goes_to_its_cause` walk the idle gaps.
    `tight`: one launch lag and one read tail are zero, so the clock's
    bounds are [0, 0] and a skew is restored exactly. `run_ids`: the
    executions carry run 100-106 and the runtime's enqueue of each, 60 us
    into its call span, is in the trace; else the join is by order."""
    launch1 = 1800 if tight else 1900     # execution 1 starts
    read2 = 3910 if tight else 4000       # the read of launch 2 ends
    spans = [
        span("bench.window", 0, 10000),
        span("pt.engine.step", 50, 1750, {"iteration": 0}),
        span("pt.engine.dispatch", 100, 300,
             {"seq": 0, "iteration": 0, "ahead": 0}),
        span("pt.engine.fetch", 300, 1500, {"seq": 0, "iteration": 0}),
        span("pt.engine.bookkeep", 1500, 1700, {"lanes": 2}),
        # launched and left unread
        span("pt.engine.step", 1790, 2040, {"iteration": 1}),
        span("pt.engine.dispatch", 1800, 2000,
             {"seq": 1, "iteration": 1, "ahead": 0}),
        # dispatched ahead, while execution 1 runs; then reads 1
        span("pt.engine.step", 2050, 3240, {"iteration": 2}),
        span("pt.engine.dispatch", 2100, 2300,
             {"seq": 2, "iteration": 2, "ahead": 1}),
        span("pt.engine.fetch", 2300, 3000, {"seq": 1, "iteration": 1}),
        span("pt.engine.bookkeep", 3000, 3200, {"lanes": 2}),
        # an admission: drains launch 2, prefills, then decodes
        span("pt.engine.step", 3250, 6250, {"iteration": 3}),
        span("pt.engine.admit", 3260, 6000),
        span("pt.engine.fetch", 3300, read2, {"seq": 2, "iteration": 2}),
        span("pt.engine.bookkeep", 4000, 4200, {"lanes": 2}),
        span("pt.engine.prefill", 4200, 6000, {"rid": 7}),
        span("pt.engine.prefill.build", 4200, 4250),
        span("pt.engine.prefill.dispatch", 4250, 4650,
             {"seq": 3, "transfers": 3}),
        span("pt.engine.prefill.fetch", 4650, 5800, {"seq": 3}),
        span("pt.engine.dispatch", 6000, 6200,
             {"seq": 4, "iteration": 3, "ahead": 0}),
        span("pt.engine.step", 6300, 7550, {"iteration": 4}),
        span("pt.engine.dispatch", 6400, 6600,
             {"seq": 5, "iteration": 4, "ahead": 1}),
        span("pt.engine.fetch", 6600, 7300, {"seq": 4, "iteration": 3}),
        span("pt.engine.bookkeep", 7300, 7500, {"lanes": 2}),
        # a last token by length: reads what was in flight and its own
        span("pt.engine.step", 7600, 9650, {"iteration": 5}),
        span("pt.engine.dispatch", 7700, 7900,
             {"seq": 6, "iteration": 5, "ahead": 1}),
        span("pt.engine.fetch", 7900, 8400, {"seq": 5, "iteration": 4}),
        span("pt.engine.bookkeep", 8400, 8500, {"lanes": 2}),
        span("pt.engine.fetch", 8500, 9400, {"seq": 6, "iteration": 5}),
        span("pt.engine.bookkeep", 9400, 9600, {"lanes": 2}),
    ]
    runs = [(DECODE, 200, 1200), (DECODE, launch1, 2900),
            (DECODE, 2910, 3910), (PREFILL, 4500, 5500),
            (DECODE, 6150, 7150), (DECODE, 7160, 8160),
            (DECODE, 8170, 9170)]
    calls = sorted(s[1] for s in spans if s[0] in lt.CALL_SPANS)
    return {"spans": spans, "ops": {},
            "modules": [[p, s * US, (e - s) * US,
                         100 + k if run_ids else None]
                        for k, (p, s, e) in enumerate(runs)],
            "enqueues": [[100 + k, at + 60 * US]
                         for k, at in enumerate(calls)] if run_ids else [],
            "devices": {CHIP: [(f"{p}/fusion.1", s * US, (e - s) * US)
                               for p, s, e in runs]}}


def skewed(p, us):
    """The same trace with the device's line `us` late."""
    p = copy.deepcopy(p)
    p["modules"] = [[n, s + us * US, d, r] for n, s, d, r in p["modules"]]
    p["devices"] = {k: [(n, s + us * US, d) for n, s, d in v]
                    for k, v in p["devices"].items()}
    return p


def causes(r):
    return {c: r[f"serve_idle_cause_{c}_pct"] for c in lt.CAUSES}


def device_idle_pct(p):
    """`serve_device_idle_pct` of the same planes, as `tracing.py` reads
    it."""
    return tracing.idle_pct({"trace": tracing.reduce_planes(
        {"devices": p["devices"], "spans": [s[:3] for s in p["spans"]]})})


def test_every_launch_is_joined_to_its_execution_and_its_read():
    j = lt.join(planes())
    assert [p["seq"] for p in j["programs"]] == list(range(7))
    assert [p["kind"] for p in j["programs"]] == [
        "decode", "decode", "decode", "prefill", "decode", "decode",
        "decode"]
    assert [p["start"] / US for p in j["programs"]] == [
        200, 1900, 2910, 4500, 6150, 7160, 8170]
    assert [p["read_end"] / US for p in j["programs"]] == [
        1500, 3000, 4000, 5800, 7300, 8400, 9400]
    assert [p["prev_end"] and p["prev_end"] / US for p in j["programs"]] \
        == [None, 1200, 2900, 3910, 5500, 7150, 8160]
    assert j["calls"] == j["executions"] == {"decode": 6, "prefill": 1}
    assert set(j["unjoined"].values()) == {0}
    assert j["joined_by"] == "order"
    # an execution 100 us after its call at the least, a read 90 us after
    # its execution at the least: the device's clock is right within those
    assert j["bounds_us"] == pytest.approx([-100, 90])
    assert j["shift_us"] == 0


def test_a_run_identifier_joins_where_the_trace_has_one():
    """The module events' `run_id` is also on the runtime's enqueue event
    on the host's line: an execution belongs to the call span open when
    its run was enqueued. Another program of the chip (a page copy) has
    no span: it is skipped, and is still the execution before the next."""
    p = planes(run_ids=True)
    by_id, by_order = lt.join(p), lt.join(planes())
    assert by_id["joined_by"] == "run_id"
    # nothing starts before its enqueue either: 40 us at the least
    assert by_id["bounds_us"] == pytest.approx([-40, 90])
    assert [q.pop("enqueue") - q["call"] for q in by_id["programs"]] \
        == [60 * US] * 7
    assert all(q.pop("enqueue") is None for q in by_order["programs"])
    assert by_id["programs"] == by_order["programs"]
    assert lt.reduce(p)["enqueue_lag_ms"]["prefill"]["p50"] \
        == pytest.approx(0.060)
    assert lt.reduce(p)["serve_idle_cause_call_pct"] == pytest.approx(6.3)
    p["modules"].append(["jit_cow_copy", 1300 * US, 100 * US, 107])
    p["enqueues"].append([107, 1250 * US])
    p["devices"][CHIP].append(("jit_cow_copy/fusion.2", 1300 * US, 100 * US))
    j = lt.join(p)
    assert [q["seq"] for q in j["programs"]] == list(range(7))
    assert j["programs"][1]["prev_end"] == 1400 * US
    assert j["executions"] == {"decode": 6, "prefill": 1}
    # an enqueue that is not in the trace: its execution stays unjoined,
    # and so does the call it would have been given to by order
    p = planes(run_ids=True)
    p["enqueues"] = p["enqueues"][1:]
    p["spans"] = [s for s in p["spans"]
                  if not (s[0] == "pt.engine.dispatch" and s[4]["seq"] == 0)]
    j = lt.join(p)
    assert [q["seq"] for q in j["programs"]] == [1, 2, 3, 4, 5, 6]
    assert j["unjoined"]["executions_of_calls_before_the_trace"] == 1
    # two executions enqueued under one call span: refused
    p = planes(run_ids=True)
    p["enqueues"][2][1] = p["enqueues"][1][1] + 1
    assert "one to one" in refused(p)


def test_each_idle_instant_goes_to_its_cause():
    r = lt.reduce(planes())
    got = {(c, s): v * 1e6 for c, s, v in r["idle_s_by_cause_and_span"]}
    want = {  # us of idle: the gap it lies in, and why
        # [0, 200): launch 0's call opens at 100
        ("host", program_trace.OUTSIDE): 50 + 40 + 350,
        ("host", "pt.engine.step"): 50 + (50 + 10) + 50,
        ("call", "pt.engine.dispatch"): 100 + 100 + 150,
        # [1200, 1900): the device done at 1200, the host inside the read
        # until 1500, booking until 1700, launch 1's call opens at 1800
        ("read", "pt.engine.fetch"): 300 + 90 + 230,
        ("host", "pt.engine.bookkeep"): 200 + 200 + 200,
        # [2900, 2910), [7150, 7160), [8160, 8170): queued behind the
        # execution before them; the host stands in a read by then
        ("call", "pt.engine.fetch"): 10 + 10 + 10,
        # [3910, 4500): the read of launch 2 until 4000, booking, the
        # prefill's build, its call from 4250
        ("host", "pt.engine.prefill.build"): 50,
        ("call", "pt.engine.prefill.dispatch"): 250,
        # [5500, 6150): the prefill's read until 5800, the admission's
        # own work until the decode call opens at 6000
        ("read", "pt.engine.prefill.fetch"): 300,
        ("host", "pt.engine.prefill"): 200,
        # [9170, 10000): past the last execution the span alone decides
    }
    assert got == pytest.approx(want)
    assert causes(r) == pytest.approx(
        {"call": 6.3, "read": 9.2, "host": 14.5})


def test_the_three_causes_sum_to_the_device_idle_share():
    p = planes()
    r = lt.reduce(p)
    assert sum(causes(r).values()) == pytest.approx(device_idle_pct(p))
    assert r["idle_s"] == pytest.approx(3000e-6)
    # and they partition what the three metrics of PR 25 partition
    by_phase = program_trace.reduce(p)
    assert sum(program_trace.idle_pct(by_phase, g)
               for g in ("launch", "admit", "other")) == pytest.approx(
        sum(causes(r).values()))


def test_per_program_numbers():
    r = lt.reduce(planes())
    # read tails of the decodes: 300, 100, 90, 150, 240, 230 us
    assert r["decode_read_tail_ms"] == pytest.approx(0.190)
    assert r["prefill_read_tail_ms"] == pytest.approx(0.300)
    # launches 0, 1, 3, 4 found the device idle and nothing queued
    assert r["serve_launch_lag_ms"] == pytest.approx(0.125)
    assert r["launch_lag_ms"]["device_idle_at_the_call"]["n"] == 4
    # decodes 2, 5, 6 started 10 us behind the execution before them;
    # 1 and 4 did not; 0 has nothing before it in the trace
    assert r["serve_back_to_back_pct"] == pytest.approx(60.0)
    assert r["serve_ahead_pct"] == pytest.approx(50.0)
    assert r["gap_before_ms"]["decode.ahead1"] == pytest.approx(
        {"n": 3, "p25": 0.010, "p50": 0.010, "p95": 0.010})
    assert r["gap_before_ms"]["decode.ahead0"]["p50"] == pytest.approx(0.675)
    assert r["read_tail_ms"]["decode.ahead1"]["n"] == 3
    assert r["read_tail_ms"]["decode.ahead1"]["p50"] == pytest.approx(0.230)
    assert r["launch_lag_ms"]["prefill"]["p50"] == pytest.approx(0.250)
    assert r["launch_lag_ms"]["decode.ahead1"]["p50"] == pytest.approx(0.760)


@pytest.mark.parametrize("late_us", [1000, -1000])
def test_a_clock_that_breaks_causality_is_shifted_back(late_us):
    """With one launch lag and one read tail of zero the bounds close on
    the skew itself: everything reads as on the right clock."""
    right = lt.reduce(planes(tight=True))
    assert right["bounds_us"] == pytest.approx([0, 0], abs=1e-6)
    assert right["shift_us"] == 0
    r = lt.reduce(skewed(planes(tight=True), late_us))
    assert r["bounds_us"] == pytest.approx([-late_us, -late_us])
    assert r["shift_us"] == pytest.approx(-late_us)
    assert causes(r) == pytest.approx(causes(right))
    for name in METRICS:
        assert r[name] == pytest.approx(right[name]), name


@pytest.mark.parametrize("late_us, bounds, shift", [
    (1000, (-1100, -910), -910), (-1000, (900, 1090), 900),
    (50, (-150, 40), 0)])
def test_the_shift_is_the_least_that_restores_causality(late_us, bounds,
                                                        shift):
    p = skewed(planes(), late_us)
    r = lt.reduce(p)
    assert r["bounds_us"] == pytest.approx(bounds)
    assert r["shift_us"] == pytest.approx(shift)
    # what is left of the skew is inside what the trace cannot exclude
    assert bounds[0] - shift <= 0 <= bounds[1] - shift
    assert all(v is not None for v in (r[m] for m in METRICS))
    assert sum(causes(r).values()) == pytest.approx(
        100 * r["idle_s"] / r["window_s"])


def test_programs_at_the_traces_edges_stay_unjoined():
    """The trace opens with an execution whose call was made before it
    (its read is in the trace) and ends with a call whose execution is
    not: the names still line up, one program further on."""
    p = planes()
    p["spans"] = [s for s in p["spans"]
                  if not (s[0] == "pt.engine.dispatch" and s[4]["seq"] == 0)]
    p["modules"] = p["modules"][:-1]
    j = lt.join(p)
    assert [q["seq"] for q in j["programs"]] == [1, 2, 3, 4, 5]
    assert [q["start"] / US for q in j["programs"]] == [
        1900, 2910, 4500, 6150, 7160]
    assert j["unjoined"] == {
        "executions_of_calls_before_the_trace": 1,
        "executions_after_the_last_call": 0,
        "calls_before_the_first_execution": 0,
        "calls_without_an_execution": 1,
        "reads_of_launches_before_the_trace": 1,
        "calls_without_a_read": 0}
    r = lt.reduce(p)
    assert all(r[m] is not None for m in METRICS)


def refused(p):
    r = lt.reduce(p)
    assert r["reason"] and r["window_s"] == pytest.approx(0.01)
    assert all(r.get(m) is None for m in METRICS)
    return r["reason"]


@pytest.mark.parametrize("run_ids", [False, True])
def test_a_name_sequence_that_does_not_follow_the_calls_is_refused(run_ids):
    p = planes(run_ids=run_ids)
    p["modules"][3][0] = DECODE          # the prefill ran as a decode?
    assert "program names" in refused(p)
    p = planes(run_ids=run_ids)
    p["modules"] = p["modules"][3:]      # three executions lost
    assert "program names" in refused(p)
    p = planes(run_ids=run_ids)
    del p["modules"][3]                  # one lost in the middle
    assert "program names" in refused(p)


def test_a_trace_without_launch_numbers_is_refused():
    """The parent's spans (the driver runs the parent under this PR's
    benchmark files): no `seq`, so no metric, a reason, no exception."""
    p = planes()
    for s in p["spans"]:
        s[4].pop("seq", None)
    assert "carries no `seq`" in refused(p)


def test_launch_numbers_out_of_order_or_read_twice_are_refused():
    p = planes()
    next(s for s in p["spans"] if s[0] == "pt.engine.dispatch"
         and s[4]["seq"] == 4)[4]["seq"] = 9
    assert "not consecutive" in refused(p)
    p = planes()
    p["spans"].append(span("pt.engine.fetch", 9700, 9800, {"seq": 6}))
    assert "read twice" in refused(p)
    p = planes()
    p["spans"] = [s if s[0] != "pt.engine.prefill.fetch" else
                  span("pt.engine.fetch", 4650, 5800, {"seq": 3})
                  for s in p["spans"]]
    assert "read by a decode span" in refused(p)


def test_a_join_that_no_clock_offset_explains_is_refused():
    p = planes()
    # the read of launch 4 closes before its execution even started
    p["spans"] = [s if not (s[0] == "pt.engine.fetch" and s[4]["seq"] == 4)
                  else span("pt.engine.fetch", 6600, 6100, {"seq": 4})
                  for s in p["spans"]]
    assert "no offset" in refused(p)


def test_nothing_to_read_is_none_not_an_error():
    assert lt.reduce({"spans": [], "devices": {}, "modules": [],
                      "enqueues": []}) is None
    # a training trace: a device plane and spans, none of them a call
    assert lt.reduce({"spans": [span("bench.window", 0, 100),
                                span("pt.train.call", 0, 50, {"t": 1})],
                      "devices": {CHIP: [("jit_step/fusion.1", 0.0, 10.0)]},
                      "modules": [["jit_step", 0.0, 10.0, 5]]}) is None


@pytest.mark.parametrize("name", METRICS)
def test_each_metric_file_reads_the_summary_or_leaves_itself_out(
        name, monkeypatch, capsys):
    module = harness.load_module(ROOT, "metrics", name)
    monkeypatch.setattr(lt, "_summary", None)
    monkeypatch.setattr(program_trace, "newest_trace", lambda: "a/b.pb")
    monkeypatch.setattr(lt, "read_file", lambda path: planes())
    assert module.read({}) == pytest.approx(lt.reduce(planes())[name])
    line, = [x for x in capsys.readouterr().out.splitlines()
             if x.startswith("LAUNCHES ")]
    said = json.loads(line[len("LAUNCHES "):])
    assert said["trace"] == "a" and said["bounds_us"] == [-100.0, 90.0]
    assert module.read({}) is not None      # parsed once, said once
    assert "LAUNCHES" not in capsys.readouterr().out
    # the parent's trace: the file leaves itself out
    bare = planes()
    for s in bare["spans"]:
        s[4].pop("seq", None)
    monkeypatch.setattr(lt, "_summary", None)
    monkeypatch.setattr(lt, "read_file", lambda path: bare)
    assert module.read({}) is None
    assert '"reason"' in capsys.readouterr().out
    # no trace at all
    monkeypatch.setattr(lt, "_summary", None)
    monkeypatch.setattr(program_trace, "newest_trace", lambda: None)
    assert module.read({}) is None


def test_modules_of_a_trace_written_here(tmp_path):
    """`read_file` on a real `.xplane.pb` (the CPU's: no device plane, so
    no modules and nothing to reduce)."""
    import jax
    import jax.numpy as jnp
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("pt.engine.dispatch", seq=0):
        jax.jit(lambda x: x + 1)(jnp.ones(4)).block_until_ready()
    jax.profiler.stop_trace()
    import glob
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    got = lt.read_file(path)
    assert got["modules"] == [] and got["devices"] == {}
    # the CPU client's launch events carry a run identifier too
    assert all(isinstance(r, int) and isinstance(at, float)
               for r, at in got["enqueues"])
    assert [s[4] for s in got["spans"]
            if s[0] == "pt.engine.dispatch"] == [{"seq": 0}]
    assert lt.reduce(got) is None


def test_chip_trace_piece_reads_as_recorded():
    """0.25 s of a `gpt2s_serve_closed32` window on a TPU v5 lite (PR 35),
    cut by record_launch_trace.py with its `XLA Modules` executions."""
    path = os.path.join(HERE, "data", "closed32_v5e_launch_trace.json.gz")
    assert os.path.getsize(path) < 250 * 1024
    with gzip.open(path, "rt") as f:
        kept = json.load(f)
    r = lt.reduce(kept["planes"])
    assert "reason" not in r
    for key, want in kept["expected"].items():
        if isinstance(want, (int, float)):
            assert r[key] == pytest.approx(want, rel=1e-9), key
        else:
            assert r[key] == want, key
    assert sum(causes(r).values()) == pytest.approx(
        100 * r["idle_s"] / r["window_s"])
    # the device's line is shifted before the idle instants are taken, so
    # against `serve_device_idle_pct` the piece's two edges may differ by
    # the shift (1.5 ms of 250 here; of 3-4 s in a cell's window)
    assert sum(causes(r).values()) == pytest.approx(
        device_idle_pct(kept["planes"]),
        abs=100 * abs(r["shift_us"]) / 1e6 / r["window_s"])
    assert r["joined_by"] == "run_id" and r["shift_us"] > 1000
    lo, hi = r["bounds_us"]
    assert lo - r["shift_us"] <= 0 <= hi - r["shift_us"]
    # the piece's edges: nothing unjoined but what straddles them
    assert all(v <= lt.EDGE for v in r["unjoined"].values())
