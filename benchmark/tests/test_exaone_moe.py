"""What PR 37 added to the benchmark for `kexaone_236b_a23b`: the family's
arithmetic against the program's own parameter count and the issue's
numbers, the plain reference (the model and its MTP module) against the
program at a tiny size, the driver `serve_closed_spec` end to end on the
CPU (the drafts it holds to the reference, the module's counters read at
the window's ends, a lane's K/V counted once), a wrong MTP module that
changes no token and is still not correct, and the three readers of the
`mtp` scope on a trace with known answers."""
import json
import os
import shutil

import jax
import numpy as np
import pytest

from conftest import ROOT

import paddle_tpu as paddle
from benchmark import harness, mtp_trace
from benchmark.tests import test_harness as base

CHIP = "/device:TPU:0"
CELL = "kexaone_serve_closed32_reason"

with open(os.path.join(ROOT, "benchmark", "configs",
                       "kexaone_236b_a23b.json")) as _f:
    PUBLISHED = json.load(_f)

# layers S S S F S (dense, then sparse), a window of 16, 4 of 8 experts
# held (2-5), top-2, a vocabulary small enough that drafts are accepted
TINY = {**PUBLISHED, "source": "tests only: ExaoneMoeConfig.tiny()",
        "vocab_size": 48, "hidden_size": 64, "intermediate_size": 96,
        "num_hidden_layers": 5, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "sliding_window": 16,
        "max_position_embeddings": 512, "num_experts": 4,
        "num_experts_per_tok": 2, "moe_intermediate_size": 32,
        "rope_parameters": {"rope_type": "default", "rope_theta": 10000},
        "published": {"num_experts": 8},
        "assumed": {**PUBLISHED["assumed"], "experts_held_first": 2}}


@pytest.fixture(scope="module")
def family():
    return harness.load_module(ROOT, "families", "exaone_moe")


# ------------------------------ the arithmetic --------------------------------


def test_parameter_count_is_the_built_models(family):
    paddle.seed(0)
    model = family.build(TINY)
    assert model.cfg.experts_held == (2, 4)
    assert model.cfg.num_experts == 8
    assert model.draft_tokens == 1
    assert family.all_params(TINY) == model.num_params()


def test_the_published_cut_is_the_issues_arithmetic(family):
    s = family.sizes(PUBLISHED)
    assert (s["layers"], s["full_layers"], s["window_layers"],
            s["dense_layers"], s["sparse_layers"], s["mtp_layers"]) \
        == (5, 1, 4, 1, 4, 1)
    assert s["layer_types"] == ["sliding_attention"] * 3 \
        + ["full_attention", "sliding_attention"]
    assert (s["experts_routed"], s["experts_held"], s["top_k"],
            s["vocab"], s["window"]) == (128, 8, 8, 19200, 128)
    p = family._parts(s)
    M = 1e6
    assert round(p["attention"] / M, 2) == 113.25
    assert round(p["expert"] / M, 2) == 37.75
    assert round(p["dense_layer"] / M, 1) == 453.0
    assert round(p["sparse_layer"] / M, 1) == 151.8
    assert round((p["sparse_layer"] + 8 * p["expert"]) / M, 1) == 453.8
    assert round(p["ends"] / M, 1) == 235.9
    assert round((p["mtp"] + 8 * p["expert"]) / M, 1) == 529.3
    assert round(family.all_params(PUBLISHED) / M, 1) == 3033.4
    whole, active = family.published_params(PUBLISHED)
    assert round(whole / 1e9, 1) == 236.6 and round(active / 1e9, 1) == 23.7
    # memory by arithmetic: the two paged layers (the full one and the MTP
    # block's) and the four rings
    assert family.kv_bytes_per_token(PUBLISHED, 4) == 2 * 2 * 1024 * 4
    assert family.window_row_bytes(PUBLISHED, 4) == 4 * 2 * 1024 * 4
    mtp = family.mtp_bytes(PUBLISHED, 4)
    assert mtp["kv_token"] == 2 * 1024 * 4
    assert round(mtp["fixed"] / 1e9, 2) == round(
        (75.5e6 + 151.8e6 + 19200 * 6144) * 4 / 1e9, 2)


def test_every_published_key_is_in_the_file_but_the_reduced():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    row = next(r for r in rows if r["name"] == "K-EXAONE-236B-A23B")
    assert PUBLISHED["source"] == row["source_url"]
    reduced = set(PUBLISHED["reduced"])
    assert reduced == {"num_hidden_layers", "num_experts", "vocab_size"}
    for key, value in row["config"].items():
        if key in reduced:
            assert PUBLISHED[key] != value
            assert PUBLISHED["published"][key] == value
        else:
            assert PUBLISHED[key] == value, key
    assert set(PUBLISHED["assumed"]) >= {
        "residual_form", "qk_norm", "full_layers_unrotated",
        "selection_bias", "mtp_form", "mask", "rope", "weights",
        "unread_keys", "experts_held_first"}
    for point in ("residual_form", "qk_norm", "full_layers_unrotated",
                  "selection_bias", "mtp_form"):
        assert "No number of the cell rests on it" in \
            PUBLISHED["assumed"][point]
    assert "16 chips" in PUBLISHED["deployment"]


def test_the_counts_are_lower_bounds_of_the_built_program(family):
    paddle.seed(0)
    model = family.build(TINY)
    model.eval()
    tokens = 64
    ids = np.zeros((1, tokens), np.int32)

    def whole(ids):
        with paddle.no_grad():
            logits, drafts = model(paddle.to_tensor(ids), with_drafts=True)
        return logits.data[:, -1], drafts.data[:, -1]

    cost = jax.jit(whole).lower(ids).compile().cost_analysis()
    pairs = tokens * 2 * 5            # top-2 in four layers and the module
    assert 0 < family.prefill_flops(TINY, tokens) \
        + family.expert_flops(TINY, pairs // 2) <= cost["flops"]
    assert family.weight_bytes(TINY, 4) <= cost["bytes accessed"]


def test_reference_agrees_with_the_program(family):
    paddle.seed(3)
    model = family.build(TINY)
    model.eval()
    params = {k: p.data for k, p in model.named_parameters()}
    spec = family.reference_spec(TINY)
    ids = np.random.default_rng(0).integers(1, 48, (1, 48)).astype(np.int32)
    with paddle.no_grad():
        logits, drafts = model(paddle.to_tensor(ids), with_drafts=True)
    want, margin, so_far = family.reference.logits_at(
        params, ids, np.arange(48), spec)
    np.testing.assert_allclose(np.asarray(logits.data)[0], want, rtol=0,
                               atol=2e-4)
    assert np.all(np.asarray(so_far) <= np.asarray(margin))
    want_drafts, least = family.reference.draft_logits_at(
        params, ids, np.arange(47), spec)
    np.testing.assert_allclose(np.asarray(drafts.data)[0, :47], want_drafts,
                               rtol=0, atol=2e-4)
    # a draft rests on the decoder's routing and on the module's
    assert np.all(np.asarray(least) <= np.asarray(so_far)[:47])
    assert np.all(np.diff(np.asarray(least)) <= 0)


# --------------------------- the driver, on the CPU ---------------------------

NEW_FILES = {
    "benchmark/configs/tiny_exaone.json": TINY,
    "benchmark/traffic/tiny_reason.json": {
        "kind": "serve_closed_spec", "clients": 4, "pool": 8,
        "prompt_tokens": {"dist": "lognormal", "median": 20, "sigma": 0.5,
                          "min": 4, "max": 40},
        "output_tokens": {"dist": "lognormal", "median": 10, "sigma": 0.5,
                          "min": 5, "max": 20}},
    "benchmark/workloads/tiny_exaone_serve.json": {
        "engine": {"max_batch": 4, "max_len": 64, "page_size": 8,
                   "num_pages": 33},
        "trace_seconds": 0.5, "drain_limit_s": 60, "check_requests": 6,
        "check_draft_requests": 12, "check_prompt_tokens": [18, 26],
        "reference_max_tokens": 64,
        "tolerance": {"logit_gap": 1e-3, "margin_epsilon": 1e-7,
                      "left_out_share_max": 0.5, "past_window_min": 4}},
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
        "name": "tiny_exaone", "source": TINY["source"],
        "file": "benchmark/configs/tiny_exaone.json",
        "reduced": TINY["reduced"], "why": "test"})
    manifest["workloads"].append({
        "name": "tiny_exaone_serve", "config": "tiny_exaone",
        "traffic": "tiny_reason", "chips": 1, "why": "test"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append("tiny_exaone_serve")
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
        "moe_experts_decode_roofline", "serve_window_attn_device_pct",
        "window_attn_decode_roofline", "window_attn_prefill_roofline",
        "decode_read_tail_ms", "prefill_read_tail_ms", "serve_launch_lag_ms",
        "serve_back_to_back_pct", "serve_idle_cause_call_pct",
        "serve_idle_cause_read_pct", "serve_idle_cause_host_pct",
        "mtp_accept_pct", "serve_mtp_device_pct", "mtp_draft_roofline"}
    assert cell["chips"] == 1
    assert cell["cell"]["engine"] == {"max_batch": 32, "max_len": 2048,
                                      "page_size": 16, "num_pages": 4097}
    assert cell["cell"]["reference_max_tokens"] == 2048
    assert cell["cell"]["check_draft_requests"] \
        > cell["cell"]["check_requests"]
    for k in ("logit_gap", "margin_epsilon", "left_out_share_max",
              "past_window_min", "why"):
        assert k in cell["cell"]["tolerance"], k
    # what seeded weights do to the cell is in its `why`
    assert "COST" in cell["cell"]["why"]
    mix = cell["traffic"]
    assert (mix["kind"], mix["clients"], mix["pool"]) == (
        "serve_closed_spec", 32, 128)
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 384,
                                    "sigma": 0.7, "min": 64, "max": 1024}
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 640,
                                    "sigma": 0.5, "min": 192, "max": 1024}
    from benchmark import traffic_gen
    stream = traffic_gen.RequestStream(mix, 2 ** 31 + 5, 19200)
    assert int(stream.pairs.sum(axis=1).max()) <= 2048
    ids, _ = stream.next()
    assert 0 < min(ids) and max(ids) < 19200


def test_the_manifest_only_gained():
    """Against the parent's manifest (git's HEAD where the checkout has
    one): nothing that was there changed but `workloads` lists that gained
    the cell at their end."""
    import subprocess
    try:
        parent = json.loads(subprocess.run(
            ["git", "show", "HEAD:BENCHMARK.json"], cwd=ROOT, check=True,
            capture_output=True, text=True).stdout)
    except (subprocess.CalledProcessError, FileNotFoundError):
        pytest.skip("no git history here")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        now = json.load(f)
    if CELL in [w["name"] for w in parent["workloads"]]:
        pytest.skip("HEAD already has the cell")
    for key in ("command", "paths", "run_seconds"):
        assert now[key] == parent[key]
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for old, new in zip(parent[group], now[group]):
            if old != new:
                assert new["workloads"] == old["workloads"] + [CELL]
                assert {k: v for k, v in new.items() if k != "workloads"} \
                    == {k: v for k, v in old.items() if k != "workloads"}
    assert [c["name"] for c in now["configs"][len(parent["configs"]):]] \
        == ["kexaone_236b_a23b"]
    assert [w["name"] for w in now["workloads"][len(parent["workloads"]):]] \
        == [CELL]
    assert [m["name"] for m in now["per_layer"][len(parent["per_layer"]):]] \
        == ["mtp_accept_pct", "serve_mtp_device_pct", "mtp_draft_roofline"]
    assert now["end_to_end"][len(parent["end_to_end"]):] == []


def test_driver_prints_a_well_formed_line(grown_root, interpreted):
    r = harness.run_cell(grown_root, "tiny_exaone_serve", seed=2 ** 31 + 11,
                         seconds=1.0, trace=False)
    base.well_formed(r, {"serve_tokens_per_s", "setup_s"})
    assert r["correct"] is True, r
    assert r["attempted"] > 0 and r["failed"] == 0


def _run_kind(grown_root, family, **changes):
    cell = harness.load_cell(grown_root, "tiny_exaone_serve")
    tolerance = {k: changes.pop(k) for k in list(changes)
                 if k in cell["cell"]["tolerance"]}
    cell["cell"]["tolerance"].update(tolerance)
    cell["cell"].update(changes)
    kind = harness.load_module(grown_root, "kinds", "serve_closed_spec")
    return kind.run({
        "root": grown_root, "seed": 5, "seconds": 1.0, "t_process": 0.0,
        "compiles": harness.CompileCounter(), "family": family,
        "tracer": None, **cell})


def test_driver_holds_the_drafts_and_counts_a_lanes_kv_once(
        grown_root, interpreted, family):
    from paddle_tpu.inference.serving import ServingEngine
    submit = ServingEngine.submit
    run = _run_kind(grown_root, family)
    assert ServingEngine.submit is submit        # the wrappers came off
    assert run["kind"] == "serve_closed_spec" and not run["notes"], \
        run["notes"]
    c = run["counters"]
    # a vocabulary of 48: some drafts are the model's own token, most not
    assert 0 < c["mtp_accepted"] < c["mtp_drafted"]
    # every decode iteration drafts one token for every active lane, and
    # the tokens are the iterations' lanes and the accepted drafts
    assert c["mtp_drafted"] <= c["iterations"] * 4
    assert c["decode_tokens"] <= c["mtp_drafted"] + c["mtp_accepted"]
    assert 0 < c["mtp_experts_touched"] <= 4 * c["iterations"]
    # both rows' reads are in the program's counter, one in the work
    work = run["work"]
    assert work["window"]["rows"] == c["window_rows"] / 2
    assert work["decode_bytes"][-2] == pytest.approx(
        family.expert_bytes(TINY, 4) * c["moe_experts_touched"]
        + family.window_row_bytes(TINY, 4) * c["window_rows"] / 2)
    mtp = family.mtp_bytes(TINY, 4)
    assert work["decode_bytes"][-1] == mtp["expert"] \
        * c["mtp_experts_touched"]
    assert work["mtp"]["bytes"] >= mtp["fixed"] * c["iterations"] \
        + mtp["expert"] * c["mtp_experts_touched"]
    assert work["moe"]["shared_bytes"] == family.shared_expert_bytes(TINY, 4)
    report = run["report"]
    assert (report["cache"]["kv_layers"], report["cache"]["draft_layers"],
            report["cache"]["window_layers"]) == (2, 1, 4)
    assert report["kernel_paths"]["moe"]["route"]
    # the drafts were held to the reference over at least as many
    # positions as the tokens
    left_out, checked = report["left_out_drafts"]
    assert checked >= report["left_out_positions"][1] > 0
    assert left_out == 0 and report["max_draft_gap_vs_reference"] <= 1e-3
    assert report["checked_draft_requests"] >= report["checked_requests"]
    assert report["counted"]["mtp_drafted"] == c["mtp_drafted"]


def test_a_wrong_mtp_module_changes_no_token_and_is_not_correct(
        grown_root, interpreted, family):
    """The module's projection perturbed in the PROGRAM after the build
    (the reference reads the weights the kind hands it, which are the
    perturbed ones' originals): every emitted token is still the main
    model's, and the drafts give the run away."""
    class Perturbed:
        """The family, whose reference sees the module as it was built."""
        def __init__(self):
            self.reference = self
            self.kept = {}

        def __getattr__(self, name):
            return getattr(family, name)

        def build(self, config):
            model = family.build(config)
            w = model.mtp.proj.weight
            self.kept["mtp.proj.weight"] = w.data
            w.data = w.data[::-1]          # another projection altogether
            return model

        def _with_the_original(self, params):
            return {**params, **self.kept}

        def logits_at(self, params, *a, **kw):
            return family.reference.logits_at(
                self._with_the_original(params), *a, **kw)

        def draft_logits_at(self, params, *a, **kw):
            return family.reference.draft_logits_at(
                self._with_the_original(params), *a, **kw)

    run = _run_kind(grown_root, Perturbed())
    assert run["report"]["max_logit_gap_vs_reference"] <= 1e-3
    assert any("a draft sits" in n for n in run["notes"]), run["notes"]
    assert not any("generated token sits" in n for n in run["notes"])


def test_a_run_that_checks_fewer_drafts_than_tokens_is_not_correct(
        grown_root, interpreted, family):
    run = _run_kind(grown_root, family, check_draft_requests=1)
    assert any("at least as many positions" in n for n in run["notes"])


def test_driver_traced_leaves_out_what_a_cpu_trace_cannot_say(
        grown_root, interpreted):
    r = harness.run_cell(grown_root, "tiny_exaone_serve", seed=3,
                         seconds=2.0, trace=True)
    assert r["correct"] is True, r
    got = set(r["metrics"])
    # counters and host clocks read; no TPU plane in a CPU trace, so every
    # reader of the device's operations is left out and none raises
    assert {"mtp_accept_pct", "batch_occupancy_pct", "engine_step_ms",
            "tpot_p95_ms"} <= got
    assert not {"serve_mtp_device_pct", "mtp_draft_roofline"} & got
    assert 0 < r["metrics"]["mtp_accept_pct"]["value"] < 100


# ------------------------ the readers of the `mtp` scope ----------------------


def _planes(decode_scope="jit(_fused_step_fn)/jit(main)/mtp/experts/x",
            prefill_scope="jit(_prefill_fn)/jit(main)/mtp/logits/y"):
    """2 ms of trace: a decode program with 300 us under `mtp` of 800, a
    prefill program with 100 us under it of 400."""
    us = 1000
    events = [("jit__fused_step_fn/a", 0, 500 * us),
              ("jit__fused_step_fn/b", 500 * us, 300 * us),
              ("jit__prefill_fn/c", 1000 * us, 300 * us),
              ("jit__prefill_fn/d", 1300 * us, 100 * us)]
    ops = {"jit__fused_step_fn/a": ["jit(_fused_step_fn)/jit(main)/"
                                    "attention/full/k", ""],
           "jit__fused_step_fn/b": [decode_scope, ""],
           "jit__prefill_fn/c": ["jit(_prefill_fn)/jit(main)/mlp/moe/"
                                 "experts/z", ""],
           "jit__prefill_fn/d": [prefill_scope, ""]}
    return {"spans": [], "devices": {CHIP: events}, "ops": ops}


def test_reduce_sums_by_the_mtp_scope():
    found = mtp_trace.reduce(_planes())
    assert found["device_op_s"] == pytest.approx(1.2e-3)
    assert found["mtp_s"] == {"jit__fused_step_fn": pytest.approx(3e-4),
                              "jit__prefill_fn": pytest.approx(1e-4)}


def test_a_trace_without_the_scope_reads_as_nothing():
    """The parent's programs have no `mtp` scope (`nomtp/` and `mtp_x` are
    no such component): the readers return None and do not raise."""
    assert mtp_trace.reduce(_planes("jit(f)/nomtp/experts", "x/mtp_x/y")) \
        is None
    assert mtp_trace.reduce({"spans": [], "devices": {}, "ops": {}}) is None


@pytest.fixture
def summarised(monkeypatch):
    def put(found):
        monkeypatch.setattr(mtp_trace, "_summary", found or {})
    return put


def test_the_two_trace_metrics_on_known_answers(summarised, family):
    summarised(mtp_trace.reduce(_planes()))
    run = {"device": {"kind": "TPU v5 lite"},
           "work": {"mtp": {"bytes": 819e9 * 1.5e-4}}, "counters": {}}
    read = lambda name: harness.load_module(  # noqa: E731
        ROOT, "metrics", name).read(run)
    assert read("serve_mtp_device_pct") == pytest.approx(100 * 4 / 12)
    assert read("mtp_draft_roofline") == pytest.approx(50.0)
    assert read("mtp_accept_pct") is None
    run["counters"] = {"mtp_drafted": 200, "mtp_accepted": 3}
    assert read("mtp_accept_pct") == pytest.approx(1.5)


def test_the_metrics_are_left_out_without_the_scope(summarised):
    summarised(None)
    run = {"device": {"kind": "TPU v5 lite"}, "work": {}, "counters": {}}
    for name in ("serve_mtp_device_pct", "mtp_draft_roofline",
                 "mtp_accept_pct"):
        assert harness.load_module(ROOT, "metrics", name).read(run) is None
