"""What PR 27 added to the benchmark for `olmo_hybrid_7b`: the family's
arithmetic against the program's own parameter count, the plain reference
against the program at `OlmoHybridConfig.tiny()`, the driver
`serve_closed_state` end to end on the CPU, and the three readers of the
linear-attention scopes on traces with known answers and on the piece of
the cell's chip trace kept in tests/data/."""
import gzip
import json
import os
import shutil

import jax
import numpy as np
import pytest

from conftest import ROOT

import paddle_tpu as paddle
from benchmark import harness, scope_trace
from benchmark.tests import test_harness as base

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = "/device:TPU:0"

with open(os.path.join(ROOT, "benchmark", "configs",
                       "olmo_hybrid_7b.json")) as _f:
    PUBLISHED = json.load(_f)

TINY = {**PUBLISHED, "source": "tests only: OlmoHybridConfig.tiny(2)",
        "vocab_size": 256, "hidden_size": 64, "intermediate_size": 128,
        "num_attention_heads": 2, "num_key_value_heads": 2,
        "max_position_embeddings": 512, "linear_num_key_heads": 2,
        "linear_num_value_heads": 2, "linear_key_head_dim": 8,
        "linear_value_head_dim": 16,
        "assumed": {**PUBLISHED["assumed"], "linear_chunk_size": 16}}


@pytest.fixture(scope="module")
def family():
    return harness.load_module(ROOT, "families", "olmo_hybrid")


# ------------------------------ the arithmetic --------------------------------


def test_parameter_count_is_the_built_models(family):
    paddle.seed(0)
    model = family.build(TINY)
    assert family.all_params(TINY) == model.num_params()
    assert family.weight_bytes(TINY, 4) == 4 * (
        model.num_params() - model.wte.weight.size)
    cache = jax.eval_shape(lambda: model.init_cache(3, 64, page_size=8,
                                                    num_pages=9))
    d = cache.describe()
    assert family.state_bytes_per_slot(TINY, 4) == d["state_bytes_per_slot"]
    assert family.kv_bytes_per_token(TINY, 4) * 8 == d["page_bytes"]


def test_the_published_cut_is_the_issues_arithmetic(family):
    s = family.sizes(PUBLISHED)
    assert s["layer_types"] == ["linear_attention"] * 3 + \
        ["full_attention"] + ["linear_attention"] * 3 + ["full_attention"]
    assert round(family.all_params(PUBLISHED) / 1e6, 1) == 2435.7
    assert family.kv_bytes_per_token(PUBLISHED, 4) == 61440
    assert family.state_bytes_per_slot(PUBLISHED, 4) == \
        6 * (30 * 96 * 192 + 3 * 11520) * 4
    assert family.delta_rule_step_bytes(PUBLISHED, 1) == \
        2 * 30 * 96 * 192 * 4 * 6
    flops, nbytes = family.delta_rule_prefill_work(PUBLISHED, 1000)
    assert flops == 6 * 30 * 96 * 192 * 1000 * 6
    assert nbytes == 6 * 4 * (1000 * 30 * (2 * 96 + 2 * 192) + 30 * 96 * 192)
    # the whole model's 32 layers, from the same functions: the catalog's 7 B
    whole = {**PUBLISHED, "num_hidden_layers": 32}
    assert 7.3e9 < family.all_params(whole) < 7.5e9


def test_reference_forward_agrees_with_the_program(family):
    paddle.seed(3)
    model = family.build(TINY)
    model.eval()
    rng = np.random.default_rng(0)
    # norms are initialised to 1: perturb every vector, or a reference
    # that dropped one would still agree
    for _, p in model.named_parameters():
        if p.data.ndim == 1:
            p.data = p.data + 0.1 * rng.standard_normal(p.shape).astype(
                np.float32)
    params = {k: p.data for k, p in model.named_parameters()}
    ids = rng.integers(0, 256, (2, 48)).astype(np.int32)
    with paddle.no_grad():
        want = np.asarray(model(paddle.to_tensor(ids)).data)
    pos = np.arange(48, dtype=np.int32)
    for row in range(2):
        got = np.asarray(family.reference.logits_at(
            params, ids[row:row + 1], pos, 2))
        # float32 on both sides; the order of summation differs (chunked
        # against per-token), and 8 post-normed layers of random weights
        # amplify that to 1e-4 of logits of size ~2
        np.testing.assert_allclose(got, want[row], rtol=0, atol=2e-3)


# ------------------------- the driver, on the CPU ----------------------------

NEW_FILES = {
    "benchmark/configs/tiny_olmo.json": TINY,
    "benchmark/traffic/tiny_doc.json": {
        "kind": "serve_closed_state", "clients": 4, "pool": 8,
        "prompt_tokens": {"dist": "lognormal", "median": 12, "sigma": 0.5,
                          "min": 4, "max": 30},
        "output_tokens": {"dist": "lognormal", "median": 4, "sigma": 0.5,
                          "min": 2, "max": 8}},
    "benchmark/workloads/tiny_olmo_serve.json": {
        "engine": {"max_batch": 4, "max_len": 64, "page_size": 8,
                   "num_pages": 25},
        "trace_seconds": 0.5, "drain_limit_s": 60, "check_requests": 2,
        "reference_max_tokens": 64, "tolerance": {"logit_gap": 1e-3}},
}


@pytest.fixture(scope="module")
def grown_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("checkout"))
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__",
                                                  ".pytest_cache"))
    for rel, body in NEW_FILES.items():
        with open(os.path.join(root, rel), "w") as f:
            json.dump(body, f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"].append({
        "name": "tiny_olmo", "source": TINY["source"],
        "file": "benchmark/configs/tiny_olmo.json",
        "reduced": TINY["reduced"], "why": "test"})
    manifest["workloads"].append({
        "name": "tiny_olmo_serve", "config": "tiny_olmo",
        "traffic": "tiny_doc", "chips": 1, "why": "test"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if "olmoh7b_serve_closed32" in m.get("workloads", []):
            m["workloads"].append("tiny_olmo_serve")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return root


interpreted = base.interpreted


def test_driver_prints_a_well_formed_line(grown_root, interpreted):
    r = harness.run_cell(grown_root, "tiny_olmo_serve", seed=2 ** 31 + 11,
                         seconds=1.0, trace=False)
    # the cell reports no `ttft_p95_ms` (it spread 17.7 % over six seeds on
    # the chip, PERF.md PR 27), so neither does its stand-in here
    base.well_formed(r, {"serve_tokens_per_s", "setup_s"})
    assert r["correct"] is True, r
    assert r["attempted"] > 0 and r["failed"] == 0


def test_driver_counts_the_state_and_reports_the_cache(grown_root,
                                                       interpreted, family):
    cell = harness.load_cell(grown_root, "tiny_olmo_serve")
    kind = harness.load_module(grown_root, "kinds", "serve_closed_state")
    run = kind.run({
        "root": grown_root, "seed": 5, "seconds": 1.0, "t_process": 0.0,
        "compiles": harness.CompileCounter(), "family": family,
        "tracer": None, **cell})
    assert run["kind"] == "serve_closed_state" and not run["notes"]
    per_slot = family.state_bytes_per_slot(TINY, 4)
    assert run["work"]["decode_bytes"][-1] == \
        2.0 * per_slot * run["counters"]["decode_tokens"]
    assert run["work"]["delta_rule"]["step_bytes"](3) == \
        family.delta_rule_step_bytes(TINY, 3)
    report = run["report"]
    assert report["cache"]["kv_layers"] == 2
    assert report["cache"]["state_layers"] == 6
    assert report["cache"]["state_bytes_per_slot"] == per_slot
    paths = report["kernel_paths"]["linear_attention"]
    assert paths["chunked"] and paths["step"]


def test_driver_traced_leaves_out_what_a_cpu_trace_cannot_say(
        grown_root, interpreted):
    r = harness.run_cell(grown_root, "tiny_olmo_serve", seed=12,
                         seconds=5.0, trace=True)
    # no TPU plane in a CPU trace: the three new readers find nothing to
    # read and are left out, without raising
    base.well_formed(r, {"engine_step_ms", "tpot_p95_ms",
                         "batch_occupancy_pct"})


# ------------------------------- the readers ----------------------------------


def span(name, start, end, args=None):
    return [name, float(start), float(end - start), "main", args or {}]


def planes():
    """A window of 1000 ns with one decode iteration of 3 active lanes
    and one prefill of 40 real tokens. Device: 100 ns of decode under
    `delta_rule`, 20 under `conv`, 30 of a linear layer's projection
    (under `attention` alone), 25 of a copy of the compiler's own, 50 of
    the prefill's scan, 200 of its MLP;
    one more scan operation lies outside the window."""
    step = "jit(_fused_step_fn)/attention/linear/"
    fill = "jit(_prefill_fn)/attention/linear/"
    ops = {
        "jit__fused_step_fn/fusion.1": [
            step + "delta_rule/jit(_step_impl)/mul:", ""],
        "jit__fused_step_fn/fusion.2": [
            step + "conv/jit(_conv_update_impl)/reduce_sum:", ""],
        "jit__fused_step_fn/fusion.3": [
            "jit(_fused_step_fn)/attention/jit(prim)/dot_general:", ""],
        "jit__prefill_fn/while.4": [
            fill + "delta_rule/jit(_chunked_impl)/while:", ""],
        "jit__prefill_fn/fusion.5": [
            "jit(_prefill_fn)/mlp/jit(prim)/dot_general:", ""],
        "jit__fused_step_fn/copy-start.6": ["", ""],
    }
    events = [("jit__fused_step_fn/fusion.1", 100.0, 100.0),
              ("jit__fused_step_fn/fusion.2", 200.0, 20.0),
              ("jit__fused_step_fn/fusion.3", 220.0, 30.0),
              ("jit__fused_step_fn/copy-start.6", 250.0, 25.0),
              ("jit__prefill_fn/while.4", 400.0, 50.0),
              ("jit__prefill_fn/fusion.5", 450.0, 200.0),
              ("jit__prefill_fn/while.4", 1100.0, 50.0)]
    spans = [span("bench.window", 0, 1000),
             span("pt.engine.lanes", 90, 95, {"lanes": 4, "active": 3}),
             span("pt.engine.prefill", 390, 700, {"prompt_tokens": 40,
                                                  "bucket": 64}),
             span("pt.engine.prefill", 1090, 1200, {"prompt_tokens": 9})]
    return {"devices": {CHIP: events}, "spans": spans, "ops": ops}


def test_reduce_sums_by_the_inner_scopes():
    r = scope_trace.reduce(planes())
    assert r["device_op_s"] == pytest.approx(425e-9)
    assert r["linear_s"] == pytest.approx(170e-9)
    assert r["delta_rule_s"] == {
        "jit__fused_step_fn": pytest.approx(100e-9),
        "jit__prefill_fn": pytest.approx(50e-9)}
    assert r["bare_copy_s"] == {"jit__fused_step_fn": pytest.approx(25e-9)}
    assert r["decode_lanes"] == 3 and r["prefill_tokens"] == [40]


def test_a_trace_without_the_scopes_reads_as_nothing():
    """The parent's program has no `attention/linear` scope: the readers
    return None and do not raise."""
    p = planes()
    p["ops"] = {k: [v[0].replace("/linear/", "/").replace("delta_rule/", "")
                    .replace("conv/", ""), v[1]] for k, v in p["ops"].items()}
    assert scope_trace.reduce(p) is None
    assert scope_trace.reduce({"devices": {}, "spans": [], "ops": {}}) is None


@pytest.fixture
def summarised(monkeypatch):
    def use(p):
        monkeypatch.setattr(scope_trace, "_summary",
                            scope_trace.reduce(p) or {})
    return use


def read(name, run):
    return harness.load_module(ROOT, "metrics", name).read(run)


def test_the_three_metrics_on_known_answers(summarised, family):
    summarised(planes())
    import functools
    run = {"device": {"kind": "TPU v5 lite"}, "work": {"delta_rule": {
        "step_bytes": functools.partial(family.delta_rule_step_bytes,
                                        PUBLISHED, dtype_bytes=4),
        "prefill_work": functools.partial(family.delta_rule_prefill_work,
                                          PUBLISHED, dtype_bytes=4)}}}
    assert read("serve_linear_attn_device_pct", run) == pytest.approx(
        100 * 170 / 425)
    least = 3 * 2 * 30 * 96 * 192 * 4 * 6 / 819e9
    assert read("delta_rule_decode_roofline", run) == pytest.approx(
        100 * least / 125e-9)
    flops, nbytes = family.delta_rule_prefill_work(PUBLISHED, 40)
    least = max(flops / 197e12, nbytes / 819e9)
    assert read("delta_rule_prefill_roofline", run) == pytest.approx(
        100 * least / 50e-9)


def test_the_three_metrics_are_left_out_without_the_scopes(summarised):
    summarised({"devices": {}, "spans": [], "ops": {}})
    run = {"device": {"kind": "TPU v5 lite"}, "work": {}}
    for name in ("serve_linear_attn_device_pct", "delta_rule_decode_roofline",
                 "delta_rule_prefill_roofline"):
        assert read(name, run) is None


RECORDED = os.path.join(HERE, "data", "olmoh_v5e_program_trace.json.gz")


@pytest.mark.skipif(not os.path.isfile(RECORDED),
                    reason="no recorded piece of the cell's chip trace")
def test_readers_on_the_recorded_chip_trace():
    """A piece of `olmoh7b_serve_closed32`'s traced window on the chip
    (record_program_trace.py, PR 27): the scopes arrive as the readers
    expect them, and the sums are those read at recording time."""
    with gzip.open(RECORDED, "rt") as f:
        kept = json.load(f)
    r = scope_trace.reduce(kept["planes"])
    want = kept["expected_scopes"]
    assert r["linear_s"] == pytest.approx(want["linear_s"])
    assert r["device_op_s"] == pytest.approx(want["device_op_s"])
    assert r["delta_rule_s"] == pytest.approx(want["delta_rule_s"])
    assert r["bare_copy_s"] == pytest.approx(want["bare_copy_s"])
    assert r["decode_lanes"] == want["decode_lanes"]
    assert r["prefill_tokens"] == want["prefill_tokens"]
    assert set(r["delta_rule_s"]) == {"jit__fused_step_fn",
                                      "jit__prefill_fn"}
    assert 0 < r["linear_s"] < r["device_op_s"]
