"""What PR 31 added to the benchmark for `nemotron3_nano_30b`: the family's
arithmetic against the program's own parameter count and the issue's
numbers, the plain reference against the program at a tiny size, the
driver `serve_closed_moe` end to end on the CPU (its comparison that
leaves near-tie routings out, its counters read at the window's ends), and
the five readers of the Mamba-2 and expert scopes on traces with known
answers and on the piece of the cell's chip trace kept in tests/data/."""
import functools
import gzip
import json
import os
import shutil

import jax
import numpy as np
import pytest

from conftest import ROOT

import paddle_tpu as paddle
from benchmark import harness, nemotron_trace
from benchmark.tests import test_harness as base

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = "/device:TPU:0"
CELL = "nemo3n_serve_closed64"

with open(os.path.join(ROOT, "benchmark", "configs",
                       "nemotron3_nano_30b.json")) as _f:
    PUBLISHED = json.load(_f)

# blocks MEMEM*EM, 4 of 8 experts held, top-2
TINY = {**PUBLISHED, "source": "tests only: NemotronHConfig.tiny()",
        "vocab_size": 256, "hidden_size": 64, "num_hidden_layers": 8,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "max_position_embeddings": 512, "mamba_num_heads": 8,
        "mamba_head_dim": 8, "ssm_state_size": 16, "n_groups": 2,
        "chunk_size": 16, "n_routed_experts": 4, "num_experts_per_tok": 2,
        "moe_intermediate_size": 32,
        "moe_shared_expert_intermediate_size": 64,
        "published": {"n_routed_experts": 8},
        "assumed": {**PUBLISHED["assumed"], "experts_held_first": 2}}


@pytest.fixture(scope="module")
def family():
    return harness.load_module(ROOT, "families", "nemotron_h")


# ------------------------------ the arithmetic --------------------------------


def test_parameter_count_is_the_built_models(family):
    paddle.seed(0)
    model = family.build(TINY)
    assert model.cfg.experts_held == (2, 4)
    assert model.cfg.n_routed_experts == 8
    assert family.all_params(TINY) == model.num_params()
    routed = sum(p.size for k, p in model.named_parameters()
                 if k.endswith((".w1", ".w2")))
    assert family.weight_bytes(TINY, 4) == 4 * (
        model.num_params() - model.wte.weight.size - routed)
    assert family.expert_bytes(TINY, 4) * 4 * 3 == 4 * routed
    cache = jax.eval_shape(lambda: model.init_cache(3, 64, page_size=8,
                                                    num_pages=9))
    d = cache.describe()
    assert family.state_bytes_per_slot(TINY, 4) == d["state_bytes_per_slot"]
    assert family.kv_bytes_per_token(TINY, 4) * 8 == d["page_bytes"]


def test_the_published_cut_is_the_issues_arithmetic(family):
    s = family.sizes(PUBLISHED)
    assert s["pattern"] == "MEMEM*EMEMEM*"
    assert (s["mamba_layers"], s["expert_layers"], s["attention_layers"]) \
        == (6, 5, 2)
    assert (s["experts_routed"], s["experts_held"], s["top_k"]) == (128, 32, 6)
    # the issue's 2,153.4 M here and 31.58 B whole
    assert round(family.all_params(PUBLISHED) / 1e6, 1) == 2153.4
    whole = {**PUBLISHED, **PUBLISHED["published"], "published": {}}
    assert round(family.all_params(whole) / 1e9, 2) == 31.58
    blocks = family._block_params(s)
    assert round(blocks["M"] / 1e6, 2) == 38.74
    assert round(blocks["*"] / 1e6, 2) == 23.40
    assert round(blocks["expert"] / 1e6, 3) == 9.978
    assert family.kv_bytes_per_token(PUBLISHED, 4) == 2 * 2 * 256 * 4
    assert family.state_bytes_per_slot(PUBLISHED, 4) == \
        6 * (64 * 64 * 128 + 3 * 6144) * 4
    assert family.ssm_step_bytes(PUBLISHED, 1) == 2 * 64 * 64 * 128 * 4 * 6
    flops, nbytes = family.ssm_prefill_work(PUBLISHED, 1000)
    assert flops == 6 * 64 * 64 * 128 * 1000 * 6
    assert nbytes == 6 * 4 * (1000 * (2 * 4096 + 2 * 8 * 128 + 64)
                              + 4096 * 128)
    assert family.shared_expert_bytes(PUBLISHED, 4) == 5 * 2 * 2688 * 3712 * 4


def test_every_published_key_is_in_the_file_but_the_reduced():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = json.loads(f.read().splitlines()[55])
    assert row["source_url"] == PUBLISHED["source"]
    differ = {k for k, v in row["config"].items() if PUBLISHED.get(k) != v}
    assert differ == set(PUBLISHED["reduced"])
    assert {k: row["config"][k] for k in differ} == PUBLISHED["published"]


def test_the_counts_are_lower_bounds_of_the_built_program(family):
    """Operations of a prefill by the family against the program's own
    count of its matrix products (`cost_analysis` of the lowered tiny
    model): the family may not count more."""
    paddle.seed(1)
    model = family.build(TINY)
    model.eval()
    params = {k: p.data for k, p in model.named_parameters()}
    from paddle_tpu.jit import _swapped_state

    def forward(params, ids):
        with paddle.no_grad(), _swapped_state(model, params, {}):
            return model(paddle.to_tensor(ids)).data[:, -1]

    tokens = 64
    cost = jax.jit(forward).lower(
        params, np.zeros((1, tokens), np.int32)).compile().cost_analysis()
    assert 0 < family.prefill_flops(TINY, tokens) <= cost["flops"]
    # the whole forward reads at least the weights the family counts
    assert family.weight_bytes(TINY, 4) <= cost["bytes accessed"]


def test_reference_forward_agrees_with_the_program(family):
    paddle.seed(3)
    model = family.build(TINY)
    model.eval()
    rng = np.random.default_rng(0)
    # norms, biases and skips are initialised to constants: perturb every
    # vector, or a reference that dropped one would still agree
    for k, p in model.named_parameters():
        if p.data.ndim == 1 and not k.endswith(("A_log", "dt_bias")):
            p.data = p.data + 0.1 * rng.standard_normal(p.shape).astype(
                np.float32)
    params = {k: p.data for k, p in model.named_parameters()}
    ids = rng.integers(0, 256, (2, 48)).astype(np.int32)
    with paddle.no_grad():
        want = np.asarray(model(paddle.to_tensor(ids)).data)
    pos = np.arange(48, dtype=np.int32)
    spec = family.reference_spec(TINY)
    assert spec["experts_first"] == 2
    for row in range(2):
        got, own, so_far = family.reference.logits_at(
            params, ids[row:row + 1], pos, spec)
        np.testing.assert_allclose(got, want[row], rtol=0, atol=2e-3)
        assert np.all(np.asarray(so_far) <= np.asarray(own))
        assert np.all(np.diff(np.asarray(so_far)) <= 0)


# ------------------------- the driver, on the CPU ----------------------------

NEW_FILES = {
    "benchmark/configs/tiny_nemo.json": TINY,
    "benchmark/traffic/tiny_chat.json": {
        "kind": "serve_closed_moe", "clients": 4, "pool": 8,
        "prompt_tokens": {"dist": "lognormal", "median": 12, "sigma": 0.5,
                          "min": 4, "max": 30},
        "output_tokens": {"dist": "lognormal", "median": 4, "sigma": 0.5,
                          "min": 2, "max": 8}},
    "benchmark/workloads/tiny_nemo_serve.json": {
        "engine": {"max_batch": 4, "max_len": 64, "page_size": 8,
                   "num_pages": 25},
        "trace_seconds": 0.5, "drain_limit_s": 60, "check_requests": 2,
        "reference_max_tokens": 64,
        "tolerance": {"logit_gap": 1e-3, "margin_epsilon": 1e-7,
                      "left_out_share_max": 0.5}},
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
        "name": "tiny_nemo", "source": TINY["source"],
        "file": "benchmark/configs/tiny_nemo.json",
        "reduced": TINY["reduced"], "why": "test"})
    manifest["workloads"].append({
        "name": "tiny_nemo_serve", "config": "tiny_nemo",
        "traffic": "tiny_chat", "chips": 1, "why": "test"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append("tiny_nemo_serve")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return root


interpreted = base.interpreted


def test_the_cell_reports_what_the_issue_lists():
    cell = harness.load_cell(ROOT, CELL)
    assert cell["end_to_end"] == ["serve_tokens_per_s", "setup_s"]
    assert set(cell["per_layer"]) == {
        "engine_step_ms", "tpot_p95_ms", "batch_occupancy_pct",
        "serve_step_roofline", "serve_device_idle_pct", "engine_host_ms",
        "serve_idle_launch_pct", "serve_idle_admit_pct",
        "serve_idle_other_pct", "serve_prefill_device_pct",
        "serve_scope_attributed_pct", "serve_moe_device_pct",
        "serve_ssm_device_pct", "moe_experts_decode_roofline",
        "ssm_decode_roofline", "ssm_prefill_roofline"}
    assert cell["cell"]["engine"] == {"max_batch": 64, "max_len": 2048,
                                      "page_size": 16, "num_pages": 6145}
    mix = cell["traffic"]
    assert (mix["kind"], mix["clients"], mix["pool"]) == (
        "serve_closed_moe", 64, 128)
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 256,
                                    "sigma": 0.8, "min": 32, "max": 1024}
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 192,
                                    "sigma": 0.7, "min": 32, "max": 512}
    from benchmark import traffic_gen
    stream = traffic_gen.RequestStream(mix, 2 ** 31 + 5, 32768)
    assert int(stream.pairs.sum(axis=1).max()) <= 1536
    ids, _ = stream.next()
    assert 0 < min(ids) and max(ids) < 32768


def test_driver_prints_a_well_formed_line(grown_root, interpreted):
    r = harness.run_cell(grown_root, "tiny_nemo_serve", seed=2 ** 31 + 11,
                         seconds=1.0, trace=False)
    base.well_formed(r, {"serve_tokens_per_s", "setup_s"})
    assert r["correct"] is True, r
    assert r["attempted"] > 0 and r["failed"] == 0


def _run_kind(grown_root, family, **tolerance):
    cell = harness.load_cell(grown_root, "tiny_nemo_serve")
    cell["cell"]["tolerance"].update(tolerance)
    kind = harness.load_module(grown_root, "kinds", "serve_closed_moe")
    return kind.run({
        "root": grown_root, "seed": 5, "seconds": 1.0, "t_process": 0.0,
        "compiles": harness.CompileCounter(), "family": family,
        "tracer": None, **cell})


def test_driver_counts_the_experts_the_state_and_reports_the_cache(
        grown_root, interpreted, family):
    run = _run_kind(grown_root, family)
    assert run["kind"] == "serve_closed_moe" and not run["notes"]
    c = run["counters"]
    # 3 expert blocks, top-2 of 8 with 4 held: no decoded token can be
    # counted more than 6 times, and some were routed here
    assert 0 < c["moe_assignments_here"] <= 6 * c["decode_tokens"]
    assert 0 < c["moe_experts_touched"] <= min(
        c["moe_assignments_here"], 3 * 4 * c["iterations"])
    assert c["moe_tokens_max_over_mean"] >= 1.0
    per_slot = family.state_bytes_per_slot(TINY, 4)
    assert run["work"]["decode_bytes"][-1] == (
        family.expert_bytes(TINY, 4) * c["moe_experts_touched"]
        + 2.0 * per_slot * c["decode_tokens"])
    assert run["work"]["moe"] == {
        "experts_touched": c["moe_experts_touched"],
        "expert_bytes": family.expert_bytes(TINY, 4),
        "shared_bytes": family.shared_expert_bytes(TINY, 4),
        "iterations": c["iterations"]}
    assert run["work"]["ssm"]["step_bytes"](3) == \
        family.ssm_step_bytes(TINY, 3)
    report = run["report"]
    assert (report["cache"]["kv_layers"], report["cache"]["state_layers"],
            report["cache"]["cacheless_layers"]) == (1, 4, 3)
    assert report["cache"]["num_kv_heads"] == 2
    assert report["cache"]["state_bytes_per_slot"] == per_slot
    paths = report["kernel_paths"]
    assert paths["ssm"]["chunked"] and paths["ssm"]["step"]
    assert paths["moe"]["route"] and paths["moe"]["ragged_dot"]
    assert report["left_out_share"] == 0.0
    assert report["left_out_positions"][1] > 0


@pytest.mark.parametrize("epsilon,share", [(10.0, 1.0), (0.02, None)],
                         ids=["every_position", "from_the_first_on"])
def test_a_run_that_leaves_out_too_much_is_not_correct(grown_root,
                                                       interpreted, family,
                                                       epsilon, share):
    """An epsilon above every margin leaves every position out: nothing
    is compared, and the run says so. One that some margins are under
    leaves out whole tails of requests, more than the half allowed."""
    run = _run_kind(grown_root, family, margin_epsilon=epsilon)
    left, checked = run["report"]["left_out_positions"]
    assert run["report"]["left_out_share"] == left / checked
    if share is not None:
        assert run["report"]["left_out_share"] == share
    assert 0.5 < run["report"]["left_out_share"] <= 1.0
    assert any("left out" in n for n in run["notes"])


def test_driver_traced_leaves_out_what_a_cpu_trace_cannot_say(
        grown_root, interpreted):
    r = harness.run_cell(grown_root, "tiny_nemo_serve", seed=12,
                         seconds=5.0, trace=True)
    # no TPU plane in a CPU trace: the five new readers find nothing to
    # read and are left out, without raising
    base.well_formed(r, {"engine_step_ms", "tpot_p95_ms",
                         "batch_occupancy_pct"})


# ------------------------------- the readers ----------------------------------


def span(name, start, end, args=None):
    return [name, float(start), float(end - start), "main", args or {}]


def planes():
    """A window of 1000 ns with one decode iteration of 3 active lanes
    and one prefill of 40 real tokens. Device, decode: 100 ns under
    `ssm/scan`, 20 under `ssm/conv`, 30 of a Mamba-2 projection (under
    `attention/ssm` alone), 60 of the grouped product, 15 of the router,
    25 of a copy of the compiler's own, 10 of full attention; prefill: 50
    of the scan, 200 of the experts; one more scan lies outside the
    window."""
    step, fill = "jit(_fused_step_fn)/", "jit(_prefill_fn)/"
    ops = {
        "jit__fused_step_fn/fusion.1": [
            step + "attention/ssm/scan/jit(_step_impl)/mul:", ""],
        "jit__fused_step_fn/fusion.2": [
            step + "attention/ssm/conv/jit(_conv_update_impl)/add:", ""],
        "jit__fused_step_fn/fusion.3": [
            step + "attention/ssm/jit(prim)/dot_general:", ""],
        "jit__fused_step_fn/gmm.4": [
            step + "mlp/moe/experts/jit(_held_impl)/pallas_call:", ""],
        "jit__fused_step_fn/fusion.5": [
            step + "mlp/moe/route/jit(_route_impl)/top_k:", ""],
        "jit__fused_step_fn/copy-start.6": ["", ""],
        "jit__fused_step_fn/paged.7": [
            step + "attention/jit(_paged_attn_grouped_pallas)/x:", ""],
        "jit__prefill_fn/while.8": [
            fill + "attention/ssm/scan/jit(_chunked_impl)/while:", ""],
        "jit__prefill_fn/gmm.9": [
            fill + "mlp/moe/experts/jit(_held_impl)/pallas_call:", ""],
    }
    events = [("jit__fused_step_fn/fusion.1", 100.0, 100.0),
              ("jit__fused_step_fn/fusion.2", 200.0, 20.0),
              ("jit__fused_step_fn/fusion.3", 220.0, 30.0),
              ("jit__fused_step_fn/gmm.4", 250.0, 60.0),
              ("jit__fused_step_fn/fusion.5", 310.0, 15.0),
              ("jit__fused_step_fn/copy-start.6", 325.0, 25.0),
              ("jit__fused_step_fn/paged.7", 350.0, 10.0),
              ("jit__prefill_fn/while.8", 400.0, 50.0),
              ("jit__prefill_fn/gmm.9", 450.0, 200.0),
              ("jit__prefill_fn/while.8", 1100.0, 50.0)]
    spans = [span("bench.window", 0, 1000),
             span("pt.engine.lanes", 90, 95, {"lanes": 4, "active": 3}),
             span("pt.engine.prefill", 390, 700, {"prompt_tokens": 40,
                                                  "bucket": 64}),
             span("pt.engine.prefill", 1090, 1200, {"prompt_tokens": 9})]
    return {"devices": {CHIP: events}, "spans": spans, "ops": ops}


def test_reduce_sums_by_the_inner_scopes():
    r = nemotron_trace.reduce(planes())
    assert r["device_op_s"] == pytest.approx(510e-9)
    assert r["ssm_s"] == pytest.approx(200e-9)
    assert r["moe_s"] == pytest.approx(275e-9)
    assert r["scan_s"] == {"jit__fused_step_fn": pytest.approx(100e-9),
                           "jit__prefill_fn": pytest.approx(50e-9)}
    assert r["moe_program_s"] == {
        "jit__fused_step_fn": pytest.approx(75e-9),
        "jit__prefill_fn": pytest.approx(200e-9)}
    assert r["bare_copy_s"] == {"jit__fused_step_fn": pytest.approx(25e-9)}
    assert (r["decode_lanes"], r["decode_iterations"]) == (3, 1)
    assert r["prefill_tokens"] == [40]


def test_a_trace_without_the_scopes_reads_as_nothing():
    """The parent's programs have neither scope: the readers return None
    and do not raise."""
    p = planes()
    p["ops"] = {k: [v[0].replace("/ssm/", "/").replace("/moe/", "/"), v[1]]
                for k, v in p["ops"].items()}
    assert nemotron_trace.reduce(p) is None
    assert nemotron_trace.reduce({"devices": {}, "spans": [],
                                  "ops": {}}) is None


@pytest.fixture
def summarised(monkeypatch):
    def use(p):
        monkeypatch.setattr(nemotron_trace, "_summary",
                            nemotron_trace.reduce(p) or {})
    return use


def read(name, run):
    return harness.load_module(ROOT, "metrics", name).read(run)


NEW_METRICS = ("serve_moe_device_pct", "serve_ssm_device_pct",
               "moe_experts_decode_roofline", "ssm_decode_roofline",
               "ssm_prefill_roofline")


def test_the_five_metrics_on_known_answers(summarised, family):
    summarised(planes())
    run = {"device": {"kind": "TPU v5 lite"}, "work": {
        "ssm": {"step_bytes": functools.partial(
                    family.ssm_step_bytes, PUBLISHED, dtype_bytes=4),
                "prefill_work": functools.partial(
                    family.ssm_prefill_work, PUBLISHED, dtype_bytes=4)},
        "moe": {"experts_touched": 7, "iterations": 1,
                "expert_bytes": family.expert_bytes(PUBLISHED, 4),
                "shared_bytes": family.shared_expert_bytes(PUBLISHED, 4)}}}
    assert read("serve_moe_device_pct", run) == pytest.approx(100 * 275 / 510)
    assert read("serve_ssm_device_pct", run) == pytest.approx(100 * 200 / 510)
    least = 3 * 2 * 64 * 64 * 128 * 4 * 6 / 819e9
    assert read("ssm_decode_roofline", run) == pytest.approx(
        100 * least / 125e-9)
    flops, nbytes = family.ssm_prefill_work(PUBLISHED, 40)
    least = max(flops / 197e12, nbytes / 819e9)
    assert read("ssm_prefill_roofline", run) == pytest.approx(
        100 * least / 50e-9)
    nbytes = 7 * 2 * 2688 * 1856 * 4 + 5 * 2 * 2688 * 3712 * 4
    assert read("moe_experts_decode_roofline", run) == pytest.approx(
        100 * nbytes / 819e9 / 100e-9)


def test_the_five_metrics_are_left_out_without_the_scopes(summarised):
    summarised({"devices": {}, "spans": [], "ops": {}})
    run = {"device": {"kind": "TPU v5 lite"}, "work": {}}
    for name in NEW_METRICS:
        assert read(name, run) is None


RECORDED = os.path.join(HERE, "data", "nemo3n_v5e_program_trace.json.gz")


@pytest.mark.skipif(not os.path.isfile(RECORDED),
                    reason="no recorded piece of the cell's chip trace")
def test_readers_on_the_recorded_chip_trace(summarised, family):
    """A piece of `nemo3n_serve_closed64`'s traced window on the chip
    (record_program_trace.py, PR 31): the scopes arrive as the readers
    expect them, the sums are those read at recording time, and the
    family's work arithmetic is a lower bound of what the chip took: no
    share passes 100 %."""
    with gzip.open(RECORDED, "rt") as f:
        kept = json.load(f)
    r = nemotron_trace.reduce(kept["planes"])
    want = kept["expected_scopes"]
    for k in ("device_op_s", "ssm_s", "moe_s", "scan_s", "moe_program_s",
              "bare_copy_s"):
        assert r[k] == pytest.approx(want[k]), k
    assert r["decode_lanes"] == want["decode_lanes"]
    assert r["prefill_tokens"] == want["prefill_tokens"]
    assert set(r["scan_s"]) == {"jit__fused_step_fn", "jit__prefill_fn"}
    assert 0 < r["ssm_s"] + r["moe_s"] < r["device_op_s"]
    summarised(kept["planes"])
    run = {"device": {"kind": "TPU v5 lite"}, "work": {
        "ssm": {"step_bytes": functools.partial(
                    family.ssm_step_bytes, PUBLISHED, dtype_bytes=4),
                "prefill_work": functools.partial(
                    family.ssm_prefill_work, PUBLISHED, dtype_bytes=4)},
        # every expert held, every iteration: the most the counter can say
        "moe": {"experts_touched": 5 * 32 * r["decode_iterations"],
                "iterations": r["decode_iterations"],
                "expert_bytes": family.expert_bytes(PUBLISHED, 4),
                "shared_bytes": family.shared_expert_bytes(PUBLISHED, 4)}}}
    for name in NEW_METRICS:
        value = read(name, run)
        assert value is not None and 0 < value < 100, (name, value)
