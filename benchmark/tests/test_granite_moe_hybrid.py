"""What the Granite 4.0-H cell added to the benchmark: the family's
arithmetic against the program's own parameter count and the deployment's
numbers, the plain reference against the program at a tiny size, the
kind `serve_closed_moe` end to end on the CPU for this family, the
cell's metric set, and the reader of the expert layers' `permute` scope
(`moe_permute_pct`) on a trace with known answers and on the piece of the
cell's chip trace kept in tests/data/."""
import gzip
import json
import os
import shutil

import jax
import numpy as np
import pytest

from conftest import ROOT

import paddle_tpu as paddle
from benchmark import harness, permute_trace
from benchmark.tests import test_harness as base

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = "/device:TPU:0"
CELL = "granite4hs_serve_closed64"

with open(os.path.join(ROOT, "benchmark", "configs",
                       "granite4_h_small_32b_a9b.json")) as _f:
    PUBLISHED = json.load(_f)

# layers mamba, attention, mamba (blocks ME*EME), 4 of 8 experts held
# (experts 2-5), top-3
TINY = {**PUBLISHED, "source": "tests only: GraniteMoeHybridConfig.tiny()",
        "vocab_size": 256, "hidden_size": 64, "num_hidden_layers": 3,
        "layer_types": ["mamba", "attention", "mamba"],
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "max_position_embeddings": 512, "mamba_n_heads": 8,
        "mamba_d_head": 8, "mamba_d_state": 16, "mamba_chunk_size": 16,
        "num_local_experts": 4, "num_experts_per_tok": 3,
        "intermediate_size": 32, "shared_intermediate_size": 64,
        "published": {"num_local_experts": 8},
        "assumed": {**PUBLISHED["assumed"], "experts_held_first": 2}}


@pytest.fixture(scope="module")
def family():
    return harness.load_module(ROOT, "families", "granite_moe_hybrid")


# ------------------------------ the arithmetic --------------------------------


def test_parameter_count_is_the_built_models(family):
    paddle.seed(0)
    model = family.build(TINY)
    assert model.cfg.experts_held == (2, 4)
    assert model.cfg.n_routed_experts == 8
    assert model.cfg.hybrid_override_pattern == "ME*EME"
    assert family.all_params(TINY) == model.num_params()
    routed = sum(p.size for k, p in model.named_parameters()
                 if k.endswith((".w1", ".w2")))
    # the tied embedding is the head, read whole by every pass
    assert family.weight_bytes(TINY, 4) == 4 * (model.num_params() - routed)
    assert family.expert_bytes(TINY, 4) * 4 * 3 == 4 * routed
    cache = jax.eval_shape(lambda: model.init_cache(3, 64, page_size=8,
                                                    num_pages=9))
    d = cache.describe()
    assert family.state_bytes_per_slot(TINY, 4) == d["state_bytes_per_slot"]
    assert family.kv_bytes_per_token(TINY, 4) * 8 == d["page_bytes"]


def test_the_tied_embedding_is_drawn_at_the_files_deviation(family):
    """`assumed.embedding_init_std`: the seeded Xavier-uniform draw scaled,
    so the same seed gives the same embedding, at the file's deviation."""
    std = PUBLISHED["assumed"]["embedding_init_std"]
    draws = []
    for _ in range(2):
        paddle.seed(7)
        draws.append(np.asarray(family.build(TINY).wte.weight.data))
    np.testing.assert_array_equal(draws[0], draws[1])
    assert abs(draws[0].std() / std - 1) < 0.05
    assert np.abs(draws[0]).max() <= np.sqrt(3.0) * std * (1 + 1e-6)


def test_the_published_cut_is_the_deployments_arithmetic(family):
    s = family.sizes(PUBLISHED)
    assert s["layer_types"] == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert (s["mamba_layers"], s["attention_layers"]) == (9, 1)
    assert (s["experts_routed"], s["experts_held"], s["top_k"]) == (72, 9, 10)
    assert (s["head_dim"], s["d_inner"], s["mamba_groups"]) == (128, 8192, 1)
    # 2,055.0 M here; 32.2 B whole, 8.8 B of them met by a token
    assert round(family.all_params(PUBLISHED) / 1e6, 1) == 2055.0
    assert round(family.published_params(PUBLISHED) / 1e9, 1) == 32.2
    assert round(family.published_active_params(PUBLISHED) / 1e9, 1) == 8.8
    parts = family._layer_params(s)
    # the published table counts the sublayers' two norms (8,192) apart
    assert round((parts["mamba"] - 4096) / 1e6, 2) == 102.29
    assert round((parts["attention"] - 4096) / 1e6, 2) == 41.94
    assert round(parts["expert"] / 1e6, 3) == 9.437
    # 38.66 MB of state a slot, 8 KiB of K/V a token
    assert family.state_bytes_per_slot(PUBLISHED, 4) == \
        9 * (128 * 64 * 128 + 3 * 8448) * 4
    assert round(family.state_bytes_per_slot(PUBLISHED, 4) / 1e6, 2) == 38.66
    assert family.kv_bytes_per_token(PUBLISHED, 4) == 8192
    assert family.ssm_step_bytes(PUBLISHED, 1) == 2 * 8192 * 128 * 4 * 9
    flops, nbytes = family.ssm_prefill_work(PUBLISHED, 1000)
    assert flops == 6 * 8192 * 128 * 1000 * 9
    assert nbytes == 9 * 4 * (1000 * (2 * 8192 + 2 * 128 + 128)
                              + 8192 * 128)
    assert family.expert_bytes(PUBLISHED, 4) == 3 * 4096 * 768 * 4
    assert family.shared_expert_bytes(PUBLISHED, 4) == \
        10 * 3 * 4096 * 1536 * 4


def test_the_counts_are_lower_bounds_of_the_built_program(family):
    """Operations of a prefill by the family against the program's own
    count (`cost_analysis` of the lowered tiny model): the family may not
    count more."""
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
    assert (spec["embedding_multiplier"], spec["residual_multiplier"],
            spec["attention_multiplier"], spec["logits_scaling"]) == (
        12, 0.22, 0.0078125, 16)
    scale = float(np.abs(want).mean())
    for row in range(2):
        got, own, so_far = family.reference.logits_at(
            params, ids[row:row + 1], pos, spec)
        np.testing.assert_allclose(got, want[row], rtol=0, atol=1e-3 * scale)
        assert np.all(np.asarray(so_far) <= np.asarray(own))
        assert np.all(np.diff(np.asarray(so_far)) <= 0)


# -------------------------- the kind, on the CPU -----------------------------

NEW_FILES = {
    "benchmark/configs/tiny_granite.json": TINY,
    "benchmark/traffic/tiny_chat.json": {
        "kind": "serve_closed_moe", "clients": 4, "pool": 8,
        "prompt_tokens": {"dist": "lognormal", "median": 12, "sigma": 0.5,
                          "min": 4, "max": 30},
        "output_tokens": {"dist": "lognormal", "median": 4, "sigma": 0.5,
                          "min": 2, "max": 8}},
    "benchmark/workloads/tiny_granite_serve.json": {
        "engine": {"max_batch": 4, "max_len": 64, "page_size": 8,
                   "num_pages": 25},
        "trace_seconds": 0.5, "drain_limit_s": 60, "check_requests": 2,
        "reference_max_tokens": 64,
        "tolerance": {"logit_gap": 1e-6, "margin_epsilon": 1e-7,
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
        "name": "tiny_granite", "source": TINY["source"],
        "file": "benchmark/configs/tiny_granite.json",
        "reduced": TINY["reduced"], "why": "test"})
    manifest["workloads"].append({
        "name": "tiny_granite_serve", "config": "tiny_granite",
        "traffic": "tiny_chat", "chips": 1, "why": "test"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append("tiny_granite_serve")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return root


interpreted = base.interpreted


def test_the_cell_reports_its_metric_set_on_the_chat_mix():
    cell = harness.load_cell(ROOT, CELL)
    assert cell["chips"] == 1
    assert cell["end_to_end"] == ["serve_tokens_per_s", "setup_s"]
    assert set(cell["per_layer"]) == {
        "engine_step_ms", "tpot_p95_ms", "batch_occupancy_pct",
        "serve_step_roofline", "serve_device_idle_pct", "engine_host_ms",
        "serve_idle_launch_pct", "serve_idle_admit_pct",
        "serve_idle_other_pct", "serve_prefill_device_pct",
        "serve_scope_attributed_pct", "prefill_pad_pct",
        "serve_moe_device_pct", "serve_ssm_device_pct",
        "moe_experts_decode_roofline", "ssm_decode_roofline",
        "ssm_prefill_roofline", "moe_permute_pct"}
    assert cell["cell"]["engine"] == {"max_batch": 64, "max_len": 2048,
                                      "page_size": 16, "num_pages": 6145}
    mix = cell["traffic"]
    assert (mix["kind"], mix["clients"], mix["pool"]) == (
        "serve_closed_moe", 64, 128)
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 256,
                                    "sigma": 0.8, "min": 32, "max": 1024}
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 192,
                                    "sigma": 0.7, "min": 32, "max": 512}
    # the same mix as the other hybrid's cell
    assert cell["traffic"] == harness.load_cell(
        ROOT, "nemo3n_serve_closed64")["traffic"]
    from benchmark import traffic_gen
    stream = traffic_gen.RequestStream(mix, 2 ** 31 + 5, 12544)
    ids, _ = stream.next()
    assert 0 < min(ids) and max(ids) < 12544


def test_the_kind_prints_a_well_formed_line(grown_root, interpreted):
    r = harness.run_cell(grown_root, "tiny_granite_serve", seed=2 ** 31 + 11,
                         seconds=1.0, trace=False)
    base.well_formed(r, {"serve_tokens_per_s", "setup_s"})
    assert r["correct"] is True, r
    assert r["attempted"] > 0 and r["failed"] == 0


def test_the_kind_counts_the_experts_the_state_and_reports_the_cache(
        grown_root, interpreted, family):
    cell = harness.load_cell(grown_root, "tiny_granite_serve")
    kind = harness.load_module(grown_root, "kinds", "serve_closed_moe")
    run = kind.run({
        "root": grown_root, "seed": 5, "seconds": 1.0, "t_process": 0.0,
        "compiles": harness.CompileCounter(), "family": family,
        "tracer": None, **cell})
    assert not run["notes"], run["notes"]
    c = run["counters"]
    # 3 expert layers, top-3 of 8 with 4 held: no decoded token can be
    # counted more than 9 times, and some were routed here
    assert 0 < c["moe_assignments_here"] <= 9 * c["decode_tokens"]
    assert 0 < c["moe_experts_touched"] <= min(
        c["moe_assignments_here"], 3 * 4 * c["iterations"])
    per_slot = family.state_bytes_per_slot(TINY, 4)
    assert run["work"]["decode_bytes"][-1] == (
        family.expert_bytes(TINY, 4) * c["moe_experts_touched"]
        + 2.0 * per_slot * c["decode_tokens"])
    report = run["report"]
    assert (report["cache"]["kv_layers"], report["cache"]["state_layers"],
            report["cache"]["cacheless_layers"]) == (1, 2, 3)
    assert report["cache"]["state_bytes_per_slot"] == per_slot
    paths = report["kernel_paths"]
    assert paths["ssm"]["chunked"] and paths["ssm"]["step"]
    assert paths["moe"]["softmax_route"] and not paths["moe"]["route"]
    assert report["left_out_positions"][1] > 0


def test_the_kind_sees_a_decode_step_that_leaves_the_state_unchanged(
        grown_root, interpreted, family, monkeypatch):
    """The comparison that decides `correct` reads the logits through the
    state: with every decode step leaving the state as it found it, the
    run comes out not correct by the logit gap."""
    from paddle_tpu.ops import ssm
    step = ssm.ssd_step

    def broken(states, *args, **kwargs):
        return step(states, *args, **kwargs)[0], states
    monkeypatch.setattr(ssm, "ssd_step", broken)
    cell = harness.load_cell(grown_root, "tiny_granite_serve")
    # outputs of 24-32 tokens, so that every checked request decodes
    # through the state for a while
    cell["traffic"] = {**cell["traffic"], "output_tokens": {
        "dist": "lognormal", "median": 28, "sigma": 0.1, "min": 24,
        "max": 32}}
    cell["cell"] = {**cell["cell"], "check_requests": 32}
    kind = harness.load_module(grown_root, "kinds", "serve_closed_moe")
    run = kind.run({
        "root": grown_root, "seed": 2 ** 31 + 13, "seconds": 1.0,
        "t_process": 0.0, "compiles": harness.CompileCounter(),
        "family": family, "tracer": None, **cell})
    assert any("below the reference's best logit" in n
               for n in run["notes"]), run["notes"]


# ------------------------------- the reader -----------------------------------


def span(name, start, end, args=None):
    return [name, float(start), float(end - start), "main", args or {}]


def planes():
    """A window of 1000 ns. Decode: 40 ns of the sort under `permute`,
    60 of the grouped product, 15 of the router, 10 of the return under
    `permute`, 25 of the shared expert, 30 of a Mamba-2 scan; prefill: 50
    of `permute`, 200 of the grouped product; one more `permute` lies
    outside the window."""
    step, fill = "jit(_fused_step_fn)/", "jit(_prefill_fn)/"
    experts = "mlp/moe/experts/jit(_held_impl)/"
    ops = {
        "jit__fused_step_fn/sort.1": [step + experts + "permute/sort:", ""],
        "jit__fused_step_fn/gmm.2": [step + experts + "pallas_call:", ""],
        "jit__fused_step_fn/fusion.3": [
            step + "mlp/moe/route/jit(_softmax_route_impl)/top_k:", ""],
        "jit__fused_step_fn/fusion.4": [
            step + experts + "permute/jit(_take)/gather:", ""],
        "jit__fused_step_fn/fusion.5": [
            step + "mlp/moe/shared/jit(_matmul_exact)/dot_general:", ""],
        "jit__fused_step_fn/fusion.6": [
            step + "attention/ssm/scan/jit(_step_impl)/mul:", ""],
        "jit__prefill_fn/sort.7": [fill + experts + "permute/sort:", ""],
        "jit__prefill_fn/gmm.8": [fill + experts + "pallas_call:", ""],
    }
    events = [("jit__fused_step_fn/sort.1", 100.0, 40.0),
              ("jit__fused_step_fn/gmm.2", 140.0, 60.0),
              ("jit__fused_step_fn/fusion.3", 200.0, 15.0),
              ("jit__fused_step_fn/fusion.4", 215.0, 10.0),
              ("jit__fused_step_fn/fusion.5", 225.0, 25.0),
              ("jit__fused_step_fn/fusion.6", 250.0, 30.0),
              ("jit__prefill_fn/sort.7", 400.0, 50.0),
              ("jit__prefill_fn/gmm.8", 450.0, 200.0),
              ("jit__prefill_fn/sort.7", 1100.0, 50.0)]
    return {"devices": {CHIP: events}, "ops": ops,
            "spans": [span("bench.window", 0, 1000)]}


def test_reduce_sums_the_permute_scope_of_every_program():
    r = permute_trace.reduce(planes())
    assert r["moe_s"] == pytest.approx(400e-9)
    assert r["permute_s"] == pytest.approx(100e-9)


def read(run):
    return harness.load_module(ROOT, "metrics", "moe_permute_pct").read(run)


def test_moe_permute_pct_on_known_answers(monkeypatch):
    monkeypatch.setattr(permute_trace, "_summary",
                        permute_trace.reduce(planes()))
    assert read({}) == pytest.approx(25.0)


@pytest.mark.parametrize("trace", ["no_scope", "empty"])
def test_a_trace_without_the_scope_reads_as_nothing(monkeypatch, trace):
    """The parent's programs have no `permute` scope: the reader returns
    None and does not raise."""
    p = planes()
    if trace == "no_scope":
        p["ops"] = {k: [v[0].replace("permute/", ""), v[1]]
                    for k, v in p["ops"].items()}
    else:
        p = {"devices": {}, "spans": [], "ops": {}}
    assert permute_trace.reduce(p) is None
    monkeypatch.setattr(permute_trace, "_summary",
                        permute_trace.reduce(p) or {})
    assert read({}) is None


RECORDED = os.path.join(HERE, "data", "granite4hs_v5e_program_trace.json.gz")


@pytest.mark.skipif(not os.path.isfile(RECORDED),
                    reason="no recorded piece of the cell's chip trace")
def test_moe_permute_pct_on_the_recorded_chip_trace(monkeypatch):
    """A piece of the cell's traced window on the chip
    (record_program_trace.py): the scope arrives as the reader expects
    it, inside the expert layers, and reads in (0, 100)."""
    with gzip.open(RECORDED, "rt") as f:
        kept = json.load(f)
    r = permute_trace.reduce(kept["planes"])
    assert 0 < r["permute_s"] < r["moe_s"]
    monkeypatch.setattr(permute_trace, "_summary", r)
    assert 0 < read({}) < 100
