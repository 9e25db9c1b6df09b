"""The reader of the program's own spans and scopes
(benchmark/program_trace.py): on hand-made traces with known answers, on
a trace file written here by jax's profiler, and on the piece of a
serving cell's chip trace kept in tests/data/ (PR 25)."""
import gzip
import json
import os
import shutil

import pytest

from benchmark import program_trace as pt
from benchmark import tracing

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = "/device:TPU:0"


def span(name, start, end, args=None, line="main"):
    return [name, float(start), float(end - start), line, args or {}]


def step_planes():
    """Two engine iterations of 100 ns in a window of 300 ns. Device busy
    [60,100] (decode 1), [110,130] (a prefill), [170,200] (decode 2)."""
    spans = [span("bench.window", 0, 300)]
    for t, it in ((0, 0), (100, 1)):
        spans += [
            span("bench.engine_step", t, t + 100),
            span("pt.engine.step", t + 2, t + 98, {"iteration": it}),
            span("pt.engine.admit", t + 4, t + 34),
            span("pt.engine.capacity", t + 36, t + 40, {"active": 2}),
            span("pt.engine.lanes", t + 40, t + 44, {"lanes": 2,
                                                     "active": 2}),
            span("pt.engine.upload", t + 44, t + 50),
            span("pt.engine.dispatch", t + 50, t + 58),
            span("pt.engine.fetch", t + 58, t + 88),
            span("pt.engine.bookkeep", t + 88, t + 96, {"lanes": 2}),
        ]
    spans += [
        span("pt.engine.prefill", 106, 132, {
            "rid": 9, "trace_id": 9, "bucket": 16, "prompt_tokens": 12,
            "shared_tokens": 0, "requeue": 0, "queue_wait_us": 40}),
        span("pt.engine.prefill.build", 106, 108),
        span("pt.engine.prefill.dispatch", 108, 112),
        span("pt.engine.prefill.fetch", 112, 130),
        span("bench.collect", 200, 260),
        span("pt.engine.submit", 262, 270, {"rid": 10, "prompt_tokens": 5,
                                            "queue_depth": 0}),
        span("pt.engine.step", 400, 500, {"iteration": 2}),  # past the window
    ]
    ops = {"jit__fused_step_fn/fusion.1": [
               "jit(_fused_step_fn)/attention/jit(prim)/dot_general:", "a:1"],
           "jit__fused_step_fn/copy.2": ["cache[0][1]:", ""],
           "jit__prefill_fn/fusion.7": [
               "jit(_prefill_fn)/mlp/jit(prim)/dot_general:", "b:2"]}
    devices = {CHIP: [("jit__fused_step_fn/fusion.1", 60.0, 30.0),
                      ("jit__fused_step_fn/copy.2", 90.0, 10.0),
                      ("jit__prefill_fn/fusion.7", 110.0, 20.0),
                      ("jit__fused_step_fn/fusion.1", 170.0, 30.0)]}
    return {"devices": devices, "spans": spans, "ops": ops}


def device_idle_pct(planes):
    """`serve_device_idle_pct` of the same planes, as `tracing.py` (which
    knows spans as name, start, duration) reads it."""
    return tracing.idle_pct({"trace": tracing.reduce_planes(
        {"devices": planes["devices"],
         "spans": [s[:3] for s in planes["spans"]]})})


def test_nest_gives_parents_and_innermost_pieces():
    spans = [(0.0, 100.0, "a"), (10.0, 40.0, "b"), (20.0, 30.0, "c"),
             (50.0, 120.0, "late"), (200.0, 210.0, "top")]
    parents, pieces = pt.nest(spans)
    assert parents == [None, 0, 1, 0, None]
    own = [0.0] * len(spans)
    for s, e, i in pieces:
        own[i] += e - s
    # a: [0,10] + [40,50]; b: 30 less c's 10; `late` is cut to its parent
    assert own == [20.0, 20.0, 10.0, 50.0, 10.0]
    assert sorted(pieces) == [
        (0.0, 10.0, 0), (10.0, 20.0, 1), (20.0, 30.0, 2), (30.0, 40.0, 1),
        (40.0, 50.0, 0), (50.0, 100.0, 3), (200.0, 210.0, 4)]


def test_idle_instants_go_to_the_innermost_span():
    r = pt.reduce(step_planes())
    assert r["window_s"] == pytest.approx(300e-9)
    assert r["busy_s"] == pytest.approx(90e-9)
    assert r["idle_s"] == pytest.approx(210e-9)
    want = {  # ns of idle inside each innermost span, both iterations
        "pt.engine.step": 2 + 2 + 2 + 2,     # its self time while idle
        "pt.engine.admit": 30 + (2 + 2),     # the second less its prefill
        "pt.engine.prefill": 2,              # [130,132], after its fetch
        "pt.engine.prefill.build": 2,
        "pt.engine.prefill.dispatch": 2,     # [108,110]
        "pt.engine.capacity": 8, "pt.engine.lanes": 8,
        "pt.engine.upload": 12, "pt.engine.dispatch": 16,
        "pt.engine.fetch": 2 + 12,           # [58,60] + [158,170]
        "pt.engine.submit": 8,               # both bookkeeps ran busy
        # [0,2], [100,102], [200,262], [270,300]: the benchmark's own loop
        pt.OUTSIDE: 2 + 2 + 62 + 30,
    }
    got = {k: v * 1e9 for k, v in r["idle_s_by_span"].items()}
    assert got == pytest.approx(want)
    assert sum(got.values()) == pytest.approx(210)


def test_the_three_idle_shares_sum_to_the_device_idle_share():
    planes = step_planes()
    r = pt.reduce(planes)
    launch, admit, other = (pt.idle_pct(r, g)
                            for g in ("launch", "admit", "other"))
    assert launch == pytest.approx(100 * 44 / 300)
    assert admit == pytest.approx(100 * 40 / 300)
    assert other == pytest.approx(100 * 126 / 300)
    assert launch + admit + other == pytest.approx(device_idle_pct(planes))
    assert {pt.idle_group(k) for k in r["idle_s_by_span"]} == {
        "launch", "admit", "other"}


def test_span_metrics():
    planes = step_planes()
    planes["spans"] += [
        span("pt.engine.prefill", 272, 280, {"requeue": 1,
                                             "queue_wait_us": 9000}),
        span("pt.engine.prefill", 282, 290, {"requeue": 0,
                                             "queue_wait_us": 60}),
        span("pt.engine.step", 291, 299, {"iteration": 2})]  # idle: no decode
    r = pt.reduce(planes)
    assert r["span_counts"]["pt.engine.step"] == 3
    assert r["span_counts"]["pt.engine.prefill"] == 3
    # first admissions only: 40 and 60 us, the requeued 9000 left out
    assert r["queue_wait_p95_ms"] == pytest.approx(0.059)
    assert r["prefill_ms"] == pytest.approx(8e-6)
    # steps that decoded, less the fetches under them: 96 - 30 and
    # 96 - 30 - 18 (the prefill's fetch); the idle step does not count
    assert r["engine_host_ms"] == pytest.approx((66 + 48) / 2 * 1e-6)
    assert r["self_ms_p50"]["pt.engine.step"] == pytest.approx(6e-6)
    assert "train_prepare_ms" not in r


def test_train_prepare_is_the_call_less_its_dispatch():
    """The lower quartile of it: of five calls the last two wait for the
    device inside `pt.train.prepare`, and do not move the reading."""
    spans = [span("bench.window", 0, 5000)]
    for t, wait, d in ((0, 0, 60), (1000, 0, 70), (2000, 0, 80),
                       (3000, 800, 70), (4000, 800, 70)):
        spans += [span("pt.train.call", t, t + wait + 90, {"t": t}),
                  span("pt.train.prepare", t + 1, t + wait + 9),
                  span("pt.train.dispatch", t + wait + 10,
                       t + wait + 10 + d)]
    r = pt.reduce({"devices": {}, "spans": spans, "ops": {}})
    # call less dispatch: 30, 20, 10, 820, 820 -> lower quartile 20
    assert r["train_prepare_ms"] == pytest.approx(20e-6)
    assert r["span_ms_p25"]["pt.train.prepare"] == pytest.approx(8e-6)
    assert r["span_ms_p50"]["pt.train.prepare"] == pytest.approx(8e-6)
    assert "idle_s" not in r and "scope_attributed_pct" not in r
    assert pt.idle_pct(r, "launch") is None


def test_device_seconds_by_scope_and_program():
    r = pt.reduce(step_planes())
    assert r["device_op_s"] == pytest.approx(90e-9)
    assert r["device_s_by_scope"] == pytest.approx(
        {"attention": 60e-9, "mlp": 20e-9, "no-scope": 10e-9})
    assert r["device_s_by_program"] == pytest.approx(
        {"jit__fused_step_fn": 70e-9, "jit__prefill_fn": 20e-9})
    assert r["unscoped_top"] == pytest.approx(
        {"jit__fused_step_fn/cache[0][1]:": 10e-9})
    assert r["scope_attributed_pct"] == pytest.approx(100 * 80 / 90)
    assert r["prefill_device_pct"] == pytest.approx(100 * 20 / 90)
    assert r["optimizer_device_pct"] == 0.0


@pytest.mark.parametrize("tf_op, scope", [
    ("jit(step)/jvp(attention)/jit(prim)/dot_general:", "attention"),
    ("jit(step)/transpose(jvp(mlp))/jit(prim)/dot_general:", "mlp"),
    ("jit(step)/transpose(jvp(ln))/jit(prim)/rsqrt:", "ln"),
    ("jit(_prefill_fn)/logits/logits/jit(prim)/dot_general:", "logits"),
    ("jit(step)/jvp(loss)/jit(take_along_axis):", "loss"),
    ("jit(_fused_step_fn)/embed/jit(_take):", "embed"),
    ("jit(step)/optimizer/sub", "optimizer"),
    ("sub:", None), ("cache[0][1]:", None), ("", None),
    ("params['blocks.1.mlp.fc.weight']:", None),
    ("jit(step)/jvp(gln)/kernel_name:", None),
])
def test_scope_of_an_op_name_path(tf_op, scope):
    assert pt.scope_of(tf_op) == scope


def test_optimizer_share_accepts_the_source_where_jax_left_no_path():
    planes = {
        "spans": [span("bench.window", 0, 100)],
        "devices": {CHIP: [("jit_step/divide_subtract_fusion", 0.0, 40.0),
                           ("jit_step/fusion.3", 40.0, 20.0),
                           ("jit_step/fusion.9", 60.0, 10.0),
                           ("jit_step/copy.4", 70.0, 10.0)]},
        "ops": {"jit_step/divide_subtract_fusion": [
                    "sub:", "/root/repo/paddle_tpu/optimizer/optimizers.py:89"],
                "jit_step/fusion.3": [
                    "jit(step)/jvp(mlp)/jit(prim)/dot_general:",
                    "/root/repo/paddle_tpu/ops/math.py:246"],
                "jit_step/fusion.9": [
                    "dot_general:", "/root/repo/paddle_tpu/ops/math.py:246"]},
    }
    r = pt.reduce(planes)
    assert r["optimizer_device_pct"] == pytest.approx(50.0)
    assert r["scope_attributed_pct"] == pytest.approx(25.0)
    assert r["unscoped_top"] == pytest.approx({
        "jit_step/sub:": 40e-9, "jit_step/dot_general:": 10e-9,
        "jit_step/no-metadata": 10e-9})


def test_a_trace_without_program_spans_gives_none():
    planes = step_planes()
    planes["spans"] = [s for s in planes["spans"]
                       if not s[0].startswith(pt.SPAN_PREFIX)]
    r = pt.reduce(planes)
    assert all(pt.idle_pct(r, g) is None
               for g in ("launch", "admit", "other"))
    assert not {"queue_wait_p95_ms", "prefill_ms", "engine_host_ms",
                "train_prepare_ms"} & set(r)
    # what the device planes alone say is still read (the parent commit
    # has the scopes, not the spans)
    assert r["idle_s_by_span"] == {pt.OUTSIDE: pytest.approx(210e-9)}
    assert r["prefill_device_pct"] == pytest.approx(100 * 20 / 90)
    assert pt.reduce({"devices": {}, "spans": [], "ops": {}}) is None
    assert pt.idle_pct(None, "launch") is None


# ---- the file's bytes: event metadata by the wire format --------------

def varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def field(number, value):
    if isinstance(value, int):
        return varint(number << 3) + varint(value)
    value = value.encode() if isinstance(value, str) else value
    return varint(number << 3 | 2) + varint(len(value)) + value


def xspace():
    """A device plane with two programs that each have a `fusion.1`, a
    host plane that must be skipped, and fixed-width fields to step
    over."""
    stat_names = {1: "tf_op", 2: "source", 3: "program_id", 4: "flops"}

    def metadata(i, name, **stats):
        body = field(1, i) + field(2, name) + field(4, "display")
        for key, value in stats.items():
            sid = next(k for k, v in stat_names.items() if v == key)
            body += field(5, field(1, sid) + (
                field(3, value) if isinstance(value, int)
                else field(5, value)))
        return field(4, field(1, i) + field(2, body))

    big = 12990650477851687953  # a program id past 2**63
    plane = field(1, 7) + field(2, CHIP)
    plane += field(3, field(1, 1) + field(2, "XLA Ops")
                   + varint(9 << 3 | 1) + b"\0" * 8)      # a line, skipped
    for sid, name in stat_names.items():
        plane += field(5, field(1, sid) + field(2, field(1, sid)
                                                + field(2, name)))
    plane += metadata(1, "jit_step(41)")
    plane += metadata(2, f"jit__prefill_fn({big})")
    plane += metadata(
        3, "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop",
        tf_op="jit(step)/jvp(mlp)/jit(prim)/dot_general:",
        source="/root/repo/paddle_tpu/ops/math.py:246", program_id=41,
        flops=9)
    plane += metadata(4, "%fusion.1 = f32[4]{0} fusion(f32[4]{0} %q)",
                      tf_op="sub:", source="x.py:1", program_id=big)
    plane += metadata(5, "%param.3 = f32[] parameter(3)",
                      tf_op="lr:", source="", program_id=41)
    plane += metadata(6, "%copy.9 = f32[4]{0} copy(%q)", program_id=41)
    host = field(2, "/host:CPU") + metadata(9, "pt.engine.step",
                                            tf_op="never read")
    return field(1, plane) + field(1, host) + varint(4 << 3 | 5) + b"\0" * 4


def test_op_table_reads_the_event_metadata():
    assert pt.op_table(xspace()) == {
        "jit_step/fusion.1": ["jit(step)/jvp(mlp)/jit(prim)/dot_general:",
                              "/root/repo/paddle_tpu/ops/math.py:246"],
        "jit__prefill_fn/fusion.1": ["sub:", "x.py:1"],
        "jit_step/param.3": ["lr:", ""],
    }
    assert pt.op_table(b"") == {}


# ---- a trace file written here by jax's profiler ------------------------

@pytest.fixture
def checkout(tmp_path, monkeypatch):
    """A made-up checkout whose `.bench_trace/cell` holds a real trace of
    two nested annotations, taken as the benchmark takes its window."""
    import jax
    tracer = tracing.Tracer(str(tmp_path / ".bench_trace" / "cell"))
    with tracer.window():
        with jax.profiler.TraceAnnotation("pt.engine.step", iteration=3):
            with jax.profiler.TraceAnnotation("pt.engine.fetch"):
                pass
            with jax.profiler.TraceAnnotation("pt.engine.dispatch"):
                pass
    monkeypatch.setattr(pt, "__file__",
                        str(tmp_path / "benchmark" / "program_trace.py"))
    monkeypatch.setattr(pt, "_summary", None)
    return tmp_path


def test_read_file_takes_spans_with_their_arguments_and_thread(checkout):
    planes = pt.read_file(pt.newest_trace())
    by_name = {s[0]: s for s in planes["spans"]}
    assert set(by_name) == {"bench.window", "pt.engine.step",
                            "pt.engine.fetch", "pt.engine.dispatch"}
    assert by_name["pt.engine.step"][4] == {"iteration": 3}
    assert len({s[3] for s in planes["spans"]}) == 1
    assert planes["devices"] == {} and planes["ops"] == {}
    r = pt.reduce(planes)
    assert r["span_counts"] == {"pt.engine.dispatch": 1,
                                "pt.engine.fetch": 1, "pt.engine.step": 1}
    assert r["engine_host_ms"] == pytest.approx(
        r["span_ms_p50"]["pt.engine.step"]
        - r["span_ms_p50"]["pt.engine.fetch"])


def test_summary_reads_this_process_trace_once_and_says_so(checkout, capsys):
    first = pt.summary()
    assert first["span_counts"]["pt.engine.step"] == 1
    assert pt.summary() is first
    lines = [x for x in capsys.readouterr().out.splitlines()
             if x.startswith("PROGRAM_SPANS ")]
    assert len(lines) == 1
    said = json.loads(lines[0][len("PROGRAM_SPANS "):])
    assert said["span_counts"] == first["span_counts"] and "trace" in said


def test_summary_of_a_checkout_without_a_trace_is_none(checkout, capsys):
    shutil.rmtree(checkout / ".bench_trace")
    assert pt.newest_trace() is None
    assert pt.summary() is None
    assert "PROGRAM_SPANS" not in capsys.readouterr().out


def test_the_metric_files_read_the_summary(checkout):
    from conftest import ROOT
    from benchmark import harness
    read = {name: harness.load_module(ROOT, "metrics", name).read({})
            for name in ("engine_host_ms", "queue_wait_p95_ms", "prefill_ms",
                         "train_prepare_ms", "serve_idle_launch_pct",
                         "serve_idle_admit_pct", "serve_idle_other_pct",
                         "serve_prefill_device_pct",
                         "train_optimizer_device_pct",
                         "train_scope_attributed_pct",
                         "serve_scope_attributed_pct")}
    assert read.pop("engine_host_ms") > 0
    assert set(read.values()) == {None}   # no prefill, no call, no chip


# ---- the recorded piece of a serving cell's chip trace ------------------

@pytest.fixture(scope="module")
def recorded():
    path = os.path.join(HERE, "data", "serve_v5e_program_trace.json.gz")
    with gzip.open(path, "rt") as f:
        return json.load(f)


def test_recorded_chip_trace_reads_as_when_recorded(recorded):
    r = pt.reduce(recorded["planes"])
    for key, want in recorded["expected"].items():
        if want is None:
            assert key not in r, key
        else:
            assert r[key] == pytest.approx(want), key


def test_recorded_chip_trace_holds_the_engines_spans(recorded):
    r = pt.reduce(recorded["planes"])
    counts = r["span_counts"]
    assert {"pt.engine.step", "pt.engine.admit", "pt.engine.capacity",
            "pt.engine.lanes", "pt.engine.upload", "pt.engine.dispatch",
            "pt.engine.fetch", "pt.engine.bookkeep", "pt.engine.prefill",
            "pt.engine.prefill.build", "pt.engine.prefill.dispatch",
            "pt.engine.prefill.fetch", "pt.engine.submit"} <= set(counts)
    assert counts["pt.engine.dispatch"] == counts["pt.engine.fetch"] \
        == counts["pt.engine.bookkeep"] >= 2
    prefills = [s for s in recorded["planes"]["spans"]
                if s[0] == "pt.engine.prefill"]
    assert all({"rid", "queue_wait_us", "requeue", "bucket"} <= set(s[4])
               for s in prefills)
    # the step's children cover it: what has no span of its own (swap,
    # hand-off, the audit gate) is under a tenth of the step
    assert r["self_ms_p50"]["pt.engine.step"] \
        < 0.1 * r["span_ms_p50"]["pt.engine.step"]


def test_recorded_chip_trace_obeys_the_sum_rule(recorded):
    planes = recorded["planes"]
    r = pt.reduce(planes)
    shares = [pt.idle_pct(r, g) for g in ("launch", "admit", "other")]
    assert all(s is not None and s >= 0 for s in shares)
    assert sum(shares) == pytest.approx(device_idle_pct(planes))
    assert sum(r["idle_s_by_span"].values()) == pytest.approx(r["idle_s"])
    assert r["idle_s"] + r["busy_s"] == pytest.approx(r["window_s"])
    assert 0 < r["scope_attributed_pct"] < 100
    assert 0 < r["prefill_device_pct"] < 100
