"""The program's own spans (`pt.*`, profiler/utils.RecordEvent) inside
`ServingEngine.step` and `TrainStep.__call__`, read back from real
profiler captures (`jax.profiler.start_trace` + `ProfileData`) taken the
way the benchmark takes its traced window. Names, nesting and arguments
are an interface: `benchmark/program_trace.py` and the per-layer metrics
in `BENCHMARK.json` read them (PERF.md section 3). Presence, nesting and
counts only; durations are judged on the chip.
"""
import glob
import os

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import jit as jit_mod
from paddle_tpu import optimizer
from paddle_tpu.inference import serving
from paddle_tpu.models import GPT, GPTConfig
from paddle_tpu.models.mellum import Mellum, MellumConfig
from paddle_tpu.models.olmo_hybrid import OlmoHybrid, OlmoHybridConfig
from paddle_tpu.nn import functional as F
from paddle_tpu.profiler.recorder import get_recorder
from paddle_tpu.profiler.utils import SPAN_PREFIX, RecordEvent

# span -> (the pt.* span it sits directly inside, its arguments)
ENGINE_SPANS = {
    "pt.engine.submit": (None, {"rid", "prompt_tokens", "queue_depth"}),
    "pt.engine.step": (None, {"iteration"}),
    "pt.engine.admit": ("pt.engine.step", set()),
    "pt.engine.prefill": ("pt.engine.admit", {
        "rid", "trace_id", "bucket", "prompt_tokens", "shared_tokens",
        "requeue", "queue_wait_us"}),
    "pt.engine.prefill.build": ("pt.engine.prefill", set()),
    "pt.engine.prefill.dispatch": ("pt.engine.prefill", {"seq",
                                                         "transfers"}),
    "pt.engine.prefill.fetch": ("pt.engine.prefill", {"seq"}),
    "pt.engine.capacity": ("pt.engine.step", {"active"}),
    "pt.engine.lanes": ("pt.engine.step", {"lanes", "active"}),
    "pt.engine.upload": ("pt.engine.step", {"transfers"}),
    "pt.engine.dispatch": ("pt.engine.step", {"seq", "iteration", "ahead"}),
    "pt.engine.fetch": ("pt.engine.step", {"seq", "iteration"}),
    "pt.engine.bookkeep": ("pt.engine.step", {"lanes"}),
}
TRAIN_SPANS = {
    "pt.train.call": (None, {"t"}),
    "pt.train.prepare": ("pt.train.call", set()),
    "pt.train.dispatch": ("pt.train.call", set()),
    "pt.train.health": ("pt.train.call", set()),
}


class _NoSpan:
    """RecordEvent patched out: what the program does without its spans."""

    def __init__(self, *a, **kw):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def capture(directory, fn):
    """Run `fn` under a trace taken with the benchmark's options and
    return (fn's result, every host event as a dict)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(directory), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(directory), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    events = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            events.extend({"name": e.name, "line": line.name,
                           "start": e.start_ns,
                           "end": e.start_ns + e.duration_ns,
                           "args": dict(e.stats)} for e in line.events)
    return out, events


def parent(span, events):
    """The innermost `pt.*` event of the same thread that encloses `span`."""
    around = [e for e in events
              if e is not span and e["line"] == span["line"]
              and e["name"].startswith(SPAN_PREFIX)
              and e["start"] <= span["start"] and span["end"] <= e["end"]]
    return max(around, key=lambda e: e["start"])["name"] if around else None


def named(events, name):
    return [e for e in events if e["name"] == name]


def serve(directory=None, state_layers=False, window_layers=False):
    """A tiny engine with more requests than lanes, warmed outside the
    trace; returns what the traced (or untraced) round produced."""
    paddle.seed(0)
    model = (OlmoHybrid(OlmoHybridConfig.tiny(1)) if state_layers
             else Mellum(MellumConfig.tiny(vocab_size=1024)) if window_layers
             else GPT(GPTConfig.tiny()))
    model.eval()
    eng = serving.ServingEngine(model, max_batch=2, max_len=64,
                                page_size=8, eos_id=-1)
    rng = np.random.default_rng(0)

    def round_of(n):
        reqs = [eng.submit(rng.integers(1, 1000, (5 + 3 * i,)).tolist(),
                           max_new_tokens=3 + i % 2) for i in range(n)]
        eng.run_until_idle()
        return reqs

    round_of(5)
    before = dict(eng.stats)
    if directory is None:
        reqs, events = round_of(5), []
    else:
        reqs, events = capture(directory, lambda: round_of(5))
    programs = {m.name for exe in jax.devices()[0].client.live_executables()
                for m in exe.hlo_modules()}
    delta = {k: eng.stats[k] - before[k]
             for k in ("iterations", "prefills", "completed",
                       "h2d_transfers", "table_refreshes",
                       "ahead_iterations", "drained_for_length",
                       "launches", "read_wait_s", "step_wall_s")}
    eng.close()
    return {"reqs": reqs, "events": events, "delta": delta,
            "programs": programs}


def train(directory=None, calls=3):
    paddle.seed(0)
    model = GPT(GPTConfig.tiny())
    opt = optimizer.AdamW(parameters=model.parameters(), learning_rate=1e-3)
    step = jit_mod.TrainStep(model, F.cross_entropy, opt, health=True)
    rows = np.random.default_rng(0).integers(1, 1000, (calls + 1, 2, 17))

    def call(i):
        return float(step(paddle.to_tensor(rows[i][:, :-1]),
                          paddle.to_tensor(rows[i][:, 1:])).data)

    first = call(0)  # compiles, outside the trace
    if directory is None:
        return {"losses": [first] + [call(i + 1) for i in range(calls)],
                "events": []}
    losses, events = capture(
        directory, lambda: [call(i + 1) for i in range(calls)])
    return {"losses": [first] + losses, "events": events}


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    return serve(tmp_path_factory.mktemp("serve_trace"))


@pytest.fixture(scope="module")
def served_state(tmp_path_factory):
    return serve(tmp_path_factory.mktemp("serve_state_trace"),
                 state_layers=True)


@pytest.fixture(scope="module")
def served_window(tmp_path_factory):
    return serve(tmp_path_factory.mktemp("serve_window_trace"),
                 window_layers=True)


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    return train(tmp_path_factory.mktemp("train_trace"))


@pytest.mark.parametrize("name", sorted(ENGINE_SPANS))
def test_engine_span_is_in_the_trace_nested_with_its_arguments(served, name):
    inside, args = ENGINE_SPANS[name]
    spans = named(served["events"], name)
    assert spans, f"no {name} in the trace"
    for s in spans:
        assert parent(s, served["events"]) == inside
        assert set(s["args"]) == args
        assert all(isinstance(v, int) for v in s["args"].values())


def test_engine_span_counts_follow_the_engines_counters(served):
    ev, delta = served["events"], served["delta"]
    assert delta["prefills"] == len(served["reqs"]) == 5
    for name in ("pt.engine.submit", "pt.engine.prefill",
                 "pt.engine.prefill.build", "pt.engine.prefill.dispatch",
                 "pt.engine.prefill.fetch"):
        assert len(named(ev, name)) == delta["prefills"], name
    for name in ("pt.engine.lanes", "pt.engine.upload", "pt.engine.dispatch",
                 "pt.engine.fetch", "pt.engine.bookkeep"):
        assert len(named(ev, name)) == delta["iterations"], name
    steps = named(ev, "pt.engine.step")
    assert len(steps) == len(named(ev, "pt.engine.admit")) >= len(
        named(ev, "pt.engine.capacity")) >= delta["iterations"]
    # `iteration` counts decode iterations done before the step
    its = [s["args"]["iteration"] for s in steps]
    assert its == sorted(its) and its[-1] - its[0] == delta["iterations"] - 1
    # PR 32: `ahead` says the iteration before was unread at the dispatch,
    # and every iteration is fetched once, under its own number, in the
    # step that dispatched it or in the next
    ahead = [d["args"]["ahead"] for d in named(ev, "pt.engine.dispatch")]
    assert set(ahead) == {0, 1}
    assert sum(ahead) == delta["ahead_iterations"]
    read = [f["args"]["iteration"] for f in named(ev, "pt.engine.fetch")]
    assert read == list(range(read[0], read[0] + delta["iterations"]))
    late = 0
    for f in named(ev, "pt.engine.fetch"):
        step, = [s for s in steps if s["start"] <= f["start"]
                 and f["end"] <= s["end"]]
        assert f["args"]["iteration"] - step["args"]["iteration"] in (-1, 0)
        late += step["args"]["iteration"] - f["args"]["iteration"]
    assert late == delta["ahead_iterations"]
    # nothing else is named with the program's prefix
    assert {e["name"] for e in ev if e["name"].startswith(SPAN_PREFIX)} \
        == set(ENGINE_SPANS)


# the span that launches a program -> the span that reads its tokens
READ_OF = {"pt.engine.dispatch": "pt.engine.fetch",
           "pt.engine.prefill.dispatch": "pt.engine.prefill.fetch"}


def test_launch_numbers_are_one_series_over_both_programs(served):
    """PR 35: decode iterations and prefills are numbered in one series,
    in the order of their calls, by `stats["launches"]`."""
    ev, delta = served["events"], served["delta"]
    # the round holds admissions, dispatches made ahead and drains
    assert delta["prefills"] and delta["ahead_iterations"] \
        and delta["drained_for_length"]
    calls = sorted((e for e in ev if e["name"] in READ_OF),
                   key=lambda e: e["start"])
    assert len(calls) == delta["launches"] \
        == delta["iterations"] + delta["prefills"]
    seqs = [c["args"]["seq"] for c in calls]
    assert seqs == list(range(seqs[0], seqs[0] + delta["launches"]))
    assert {c["name"] for c in calls} == set(READ_OF)


def test_every_launch_is_read_once_after_its_call_closed(served):
    ev = served["events"]
    reads = [e for e in ev if e["name"] in READ_OF.values()]
    by_seq = {}
    for r in reads:
        assert r["args"]["seq"] not in by_seq, "a launch read twice"
        by_seq[r["args"]["seq"]] = r
    calls = [e for e in ev if e["name"] in READ_OF]
    assert sorted(by_seq) == sorted(c["args"]["seq"] for c in calls)
    for c in calls:
        r = by_seq[c["args"]["seq"]]
        assert r["name"] == READ_OF[c["name"]]
        assert r["start"] >= c["end"]
        if c["name"] == "pt.engine.dispatch":
            # the read names the same launch by both of its numbers
            assert r["args"]["iteration"] == c["args"]["iteration"]
    # an iteration dispatched ahead is read in a later step than its call
    steps = named(ev, "pt.engine.step")
    step_of = lambda e: next(i for i, s in enumerate(steps)  # noqa: E731
                             if s["start"] <= e["start"]
                             and e["end"] <= s["end"])
    behind = sum(step_of(by_seq[c["args"]["seq"]]) - step_of(c)
                 for c in named(ev, "pt.engine.dispatch"))
    assert behind == served["delta"]["ahead_iterations"]


@pytest.mark.parametrize("counter, spans", [
    ("read_wait_s", ("pt.engine.fetch", "pt.engine.prefill.fetch")),
    ("step_wall_s", ("pt.engine.step",))])
def test_host_counters_time_what_their_spans_time(served, counter, spans):
    """The two counters an operator without a trace reads: seconds inside
    the two reads and inside `step()`. The clock calls sit right around
    (the reads) or right inside (the step) the span, so they differ from
    it by an annotation's own cost."""
    timed = [e for e in served["events"] if e["name"] in spans]
    seconds = sum(e["end"] - e["start"] for e in timed) / 1e9
    assert served["delta"][counter] == pytest.approx(
        seconds, rel=0.05, abs=2e-4 * len(timed))
    assert 0 < served["delta"]["read_wait_s"] < served["delta"]["step_wall_s"]


def test_prefill_spans_carry_the_requests_they_admitted(served):
    reqs = {r.rid: r for r in served["reqs"]}
    prefills = named(served["events"], "pt.engine.prefill")
    assert sorted(p["args"]["rid"] for p in prefills) == sorted(reqs)
    for p in prefills:
        a, r = p["args"], reqs[p["args"]["rid"]]
        assert a["trace_id"] == r.trace_id
        assert a["prompt_tokens"] == len(r.prompt)
        assert a["bucket"] >= a["prompt_tokens"]
        assert a["shared_tokens"] == 0 and a["requeue"] == 0
        assert a["queue_wait_us"] == int(
            1e6 * (r.admitted_ts - r.submitted_ts))
    # two lanes, five requests at once: some had to wait for a lane
    assert max(p["args"]["queue_wait_us"] for p in prefills) > min(
        p["args"]["queue_wait_us"] for p in prefills)
    submits = named(served["events"], "pt.engine.submit")
    assert [s["args"]["rid"] for s in submits] == sorted(reqs)
    assert [s["args"]["queue_depth"] for s in submits] == [0, 1, 2, 3, 4]


def test_engine_programs_are_named_as_the_metrics_expect(served):
    """`serve_prefill_device_pct` and the ledger's breakdown find the two
    programs by these names (a chip trace's `XLA Modules` events carry
    the executable's name; the CPU's trace shows the jitted function)."""
    assert {"jit__prefill_fn", "jit__fused_step_fn"} <= served["programs"]
    for call, span in (("PjitFunction(_prefill_fn)",
                        "pt.engine.prefill.dispatch"),
                       ("PjitFunction(_fused_step_fn)",
                        "pt.engine.dispatch")):
        calls = named(served["events"], call)
        assert calls
        assert {parent(c, served["events"]) for c in calls} == {span}


# what the CPU client's host trace shows of the device's side: a program
# launched, and an array handed over (from Python, or as an argument of a
# jitted call)
LAUNCH = "PjRtCpuExecutable::Execute"
TRANSFER = {"DevicePut", "DevicePutWithSharding"}


def inside(span, events, names):
    return [e for e in events if e["name"] in names
            and e["line"] == span["line"]
            and span["start"] <= e["start"] and e["end"] <= span["end"]]


@pytest.mark.parametrize("engine", ["gpt", "state_layers", "window_layers"])
def test_a_step_launches_its_two_programs_and_nothing_else(
        engine, request):
    """PR 30: block tables and lengths are the host's, so inside
    `pt.engine.step` the only executables are one decode program an
    iteration and one prefill program an admission, and the transfers
    are the packed arguments plus at most one table refresh. A window
    layer's ring adds none (PR 33): its table is computed inside the
    programs from the slot and never travels."""
    run = request.getfixturevalue({"gpt": "served",
                                   "state_layers": "served_state",
                                   "window_layers": "served_window"}[engine])
    ev, delta = run["events"], run["delta"]
    steps = named(ev, "pt.engine.step")
    launches = [e for s in steps for e in inside(s, ev, {LAUNCH})]
    assert launches, "the trace shows no launch: another client's names?"
    assert len(launches) == delta["iterations"] + delta["prefills"] \
        == delta["launches"]
    sites = {"pt.engine.dispatch", "pt.engine.prefill.dispatch"}
    assert {parent(e, ev) for e in launches} == sites
    jitted = {e["name"] for s in steps for e in ev
              if e["name"].startswith("PjitFunction(")
              and e["line"] == s["line"]
              and s["start"] <= e["start"] and e["end"] <= s["end"]}
    assert jitted == {"PjitFunction(_prefill_fn)",
                      "PjitFunction(_fused_step_fn)"}

    # transfers: as many as the span says, where the span says
    moved = [e for s in steps for e in inside(s, ev, TRANSFER)]
    said = 0
    for step in steps:
        uploads = inside(step, ev, {"pt.engine.upload"})
        calls = inside(step, ev, {"pt.engine.dispatch"})
        assert len(uploads) == len(calls) <= 1
        for up, call in zip(uploads, calls):
            n = up["args"]["transfers"]
            assert 2 <= n <= 3
            # the tables inside `.upload`, the lane arrays with the call
            assert len(inside(up, ev, TRANSFER)) == n - 2
            assert len(inside(call, ev, TRANSFER)) == 2
            said += n
        for fill in inside(step, ev, {"pt.engine.prefill.dispatch"}):
            n = fill["args"]["transfers"]
            assert 3 <= n <= 4
            assert len(inside(fill, ev, TRANSFER)) == n
            said += n
    assert said == len(moved) == delta["h2d_transfers"]
    assert delta["h2d_transfers"] == (2 * delta["iterations"]
                                      + 3 * delta["prefills"]
                                      + delta["table_refreshes"])


@pytest.mark.parametrize("name", sorted(TRAIN_SPANS))
def test_train_span_is_in_the_trace_nested_with_its_arguments(trained, name):
    inside, args = TRAIN_SPANS[name]
    spans = named(trained["events"], name)
    assert len(spans) == 3, f"{name}: one to a traced call"
    for s in spans:
        assert parent(s, trained["events"]) == inside
        assert set(s["args"]) == args
    if name == "pt.train.call":
        assert [s["args"]["t"] for s in spans] == [2, 3, 4]


def test_train_spans_follow_one_another_inside_the_call(trained):
    ev = trained["events"]
    assert {e["name"] for e in ev if e["name"].startswith(SPAN_PREFIX)} \
        == set(TRAIN_SPANS)
    for call in named(ev, "pt.train.call"):
        inner = sorted((e for e in ev if e["name"] in TRAIN_SPANS
                        and e is not call and call["start"] <= e["start"]
                        and e["end"] <= call["end"]),
                       key=lambda e: e["start"])
        assert [e["name"] for e in inner] == [
            "pt.train.prepare", "pt.train.dispatch", "pt.train.health"]
        assert all(a["end"] <= b["start"] for a, b in zip(inner, inner[1:]))
        jitted = [e for e in named(ev, "PjitFunction(step)")
                  if call["start"] <= e["start"] <= call["end"]]
        assert {parent(e, ev) for e in jitted} == {"pt.train.dispatch"}


def test_health_span_only_when_the_probe_is_on(tmp_path):
    paddle.seed(0)
    model = GPT(GPTConfig.tiny())
    opt = optimizer.AdamW(parameters=model.parameters(), learning_rate=1e-3)
    step = jit_mod.TrainStep(model, F.cross_entropy, opt, health=False)
    ids = paddle.to_tensor(np.ones((2, 16), np.int32))
    step(ids, ids)
    _, ev = capture(tmp_path, lambda: float(step(ids, ids).data))
    assert len(named(ev, "pt.train.call")) == 1
    assert not named(ev, "pt.train.health")


@pytest.mark.parametrize("what", ["tokens", "losses"])
def test_outputs_are_the_same_without_the_spans(served, trained, what,
                                                monkeypatch):
    monkeypatch.setattr(serving, "RecordEvent", _NoSpan)
    monkeypatch.setattr(jit_mod, "RecordEvent", _NoSpan)
    if what == "tokens":
        bare = serve()
        assert [r.generated for r in bare["reqs"]] == [
            r.generated for r in served["reqs"]]
        assert all(len(r.generated) == r.max_new_tokens
                   for r in bare["reqs"])
        # the launch numbers and the two clocks need no span
        for k in ("launches", "iterations", "prefills", "ahead_iterations"):
            assert bare["delta"][k] == served["delta"][k], k
        assert 0 < bare["delta"]["read_wait_s"] < bare["delta"]["step_wall_s"]
    else:
        assert train()["losses"] == trained["losses"]


def test_record_event_annotates_with_the_recorder_off(tmp_path):
    """A trace somebody else started (the benchmark's window, an
    operator's `start_trace`) sees the span and its arguments; the
    program's own recorder, being off, gets nothing."""
    rec = get_recorder()
    assert not rec.enabled
    rec.clear()

    def spans():
        with RecordEvent("pt.test.outer", rid=7, label="x"):
            with RecordEvent("pt.test.inner"):
                pass

    _, ev = capture(tmp_path, spans)
    outer, = named(ev, "pt.test.outer")
    inner, = named(ev, "pt.test.inner")
    assert outer["args"] == {"rid": 7, "label": "x"}
    assert parent(inner, ev) == "pt.test.outer"
    assert rec.collect() == [] and rec.span_stack() == []


def test_record_event_pushes_its_arguments_with_the_recorder_on():
    rec = get_recorder()
    rec.clear()
    rec.enabled = True
    try:
        with RecordEvent("pt.test.args", rid=3):
            pass
        with RecordEvent("pt.test.bare"):
            pass
    finally:
        rec.enabled = False
    spans = {s.name: s for s in rec.collect()}
    assert spans["pt.test.args"].args == {"rid": 3}
    assert spans["pt.test.bare"].args is None


def test_record_event_lets_an_error_from_jax_through():
    with pytest.raises(TypeError):  # a name jax's annotation refuses
        RecordEvent(None).begin()
