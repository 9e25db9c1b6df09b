"""What PR 39 added to the benchmark for `deepseek_v2_236b_a21b`: the
family's arithmetic against the program's own parameter count and the
issue's numbers, every published key in the file, the plain reference
against the program at a tiny size, the driver `serve_closed_latent` end
to end on the CPU (the latent rows and expert counters read at the
window's ends), the cell's metric set, and the readers of the
`attention/latent` scope on a trace with known answers."""
import json
import os
import shutil

import jax
import numpy as np
import pytest

from conftest import ROOT

import paddle_tpu as paddle
from benchmark import harness, latent_trace
from benchmark.tests import test_harness as base

CHIP = "/device:TPU:0"
CELL = "dsv2_serve_closed32_longdoc"

with open(os.path.join(ROOT, "benchmark", "configs",
                       "deepseek_v2_236b_a21b.json")) as _f:
    PUBLISHED = json.load(_f)

# a dense layer then three expert layers; 4 heads of 16 + 8 / 16, ranks 24
# and 32; 4 of 16 experts held (4-7), in 4 groups of 4, 2 kept, top-3
TINY = {**PUBLISHED, "source": "tests only: DeepseekV2Config.tiny()",
        "vocab_size": 48, "hidden_size": 64, "intermediate_size": 96,
        "num_hidden_layers": 4, "num_attention_heads": 4,
        "num_key_value_heads": 4, "q_lora_rank": 24, "kv_lora_rank": 32,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "max_position_embeddings": 512, "n_routed_experts": 4,
        "num_experts_per_tok": 3, "n_group": 4, "topk_group": 2,
        "moe_intermediate_size": 16,
        "published": {"n_routed_experts": 16},
        # positions past YaRN's original inside the tiny cell's requests
        "rope_scaling": {**PUBLISHED["rope_scaling"],
                         "original_max_position_embeddings": 24},
        "assumed": {**PUBLISHED["assumed"], "experts_held_first": 4}}


@pytest.fixture(scope="module")
def family():
    return harness.load_module(ROOT, "families", "deepseek_v2")


# ------------------------------ the arithmetic --------------------------------


def test_parameter_count_is_the_built_models(family):
    paddle.seed(0)
    model = family.build(TINY)
    assert model.cfg.experts_held == (4, 4)
    assert model.cfg.n_routed_experts == 16
    assert family.all_params(TINY) == model.num_params()


def test_the_published_cut_is_the_issues_arithmetic(family):
    s = family.sizes(PUBLISHED)
    assert (s["layers"], s["dense_layers"], s["sparse_layers"]) == (5, 1, 4)
    assert (s["experts_routed"], s["experts_held"], s["top_k"], s["n_group"],
            s["topk_group"], s["scale"], s["vocab"]) \
        == (160, 8, 6, 8, 3, 16.0, 12800)
    assert (s["hidden"], s["heads"], s["q_rank"], s["rank"], s["nope"],
            s["rope"], s["v_dim"], s["expert_ffn"], s["shared_experts"],
            s["dense_ffn"]) == (5120, 128, 1536, 512, 128, 64, 128, 1536, 2,
                                12288)
    p = family._parts(s)
    M = 1e6
    assert round(p["attention"] / M, 2) == 149.23
    assert round(p["dense_layer"] / M, 2) == 337.98
    assert round(p["sparse_layer"] / M, 2) == 197.24
    assert round(p["shared"] / M, 2) == 47.19
    assert round(p["expert"] / M, 2) == 23.59
    assert round(p["ends"] / M, 2) == 131.08
    assert round(family.all_params(PUBLISHED) / M, 1) == 2013.0
    whole, active = family.published_params(PUBLISHED)
    assert round(whole / 1e9, 1) == 235.7 and round(active / 1e9, 1) == 21.4
    # the latent rows: 512 + 64 float32 numbers a token and layer
    assert family.kv_bytes_per_token(PUBLISHED, 4) == 576 * 4 * 5
    assert family.latent_decode_flops(PUBLISHED, 1) == 128 * 1088 * 2
    flops, nbytes = family.latent_prefill_work(PUBLISHED, 2)
    assert flops == 5 * 128 * 320 * 2 * 3
    assert nbytes == 5 * 4 * 2 * 128 * (2 * 192 + 2 * 128)


def test_every_published_key_is_in_the_file_but_the_reduced():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    row = next(r for r in rows if r["name"] == "DeepSeek-V2")
    assert PUBLISHED["source"] == row["source_url"]
    reduced = set(PUBLISHED["reduced"])
    assert reduced == {"num_hidden_layers", "n_routed_experts", "vocab_size"}
    for key, value in row["config"].items():
        if key in reduced:
            assert PUBLISHED[key] != value
            assert PUBLISHED["published"][key] == value
        else:
            assert PUBLISHED[key] == value, key
    assert set(PUBLISHED["assumed"]) >= {
        "rope_pairs", "yarn", "weights", "unread_keys", "residual_form",
        "experts_held_first"}
    assert "20 chips" in PUBLISHED["deployment"]
    assert PUBLISHED["serve_dtype"] == "float32"


def test_the_counts_are_lower_bounds_of_the_built_program(family):
    paddle.seed(0)
    model = family.build(TINY)
    model.eval()
    tokens = 64
    ids = np.zeros((1, tokens), np.int32)

    def whole(ids):
        with paddle.no_grad():
            return model(paddle.to_tensor(ids)).data[:, -1]

    cost = jax.jit(whole).lower(ids).compile().cost_analysis()
    pairs = tokens * 3 * 3            # top-3 in three expert layers
    assert 0 < family.prefill_flops(TINY, tokens) \
        + family.expert_flops(TINY, pairs // 4) <= cost["flops"]
    assert family.weight_bytes(TINY, 4) <= cost["bytes accessed"]


def test_reference_agrees_with_the_program(family):
    paddle.seed(3)
    model = family.build(TINY)
    model.eval()
    params = {k: p.data for k, p in model.named_parameters()}
    spec = family.reference_spec(TINY)
    ids = np.random.default_rng(0).integers(1, 48, (1, 40)).astype(np.int32)
    with paddle.no_grad():
        logits = model(paddle.to_tensor(ids))
    want, margin, so_far = family.reference.logits_at(
        params, ids, np.arange(40), spec)
    np.testing.assert_allclose(np.asarray(logits.data)[0], want, rtol=0,
                               atol=2e-4)
    assert np.all(np.asarray(so_far) <= np.asarray(margin))
    assert np.all(np.diff(np.asarray(so_far)) <= 0)


# --------------------------- the driver, on the CPU ---------------------------

with open(os.path.join(ROOT, "benchmark", "workloads", CELL + ".json")) as _f:
    _CELL_TOLERANCE = json.load(_f)["tolerance"]

NEW_FILES = {
    "benchmark/configs/tiny_dsv2.json": TINY,
    "benchmark/traffic/tiny_longdoc.json": {
        "kind": "serve_closed_latent", "clients": 4, "pool": 8,
        "prompt_tokens": {"dist": "lognormal", "median": 24, "sigma": 0.5,
                          "min": 8, "max": 40},
        "output_tokens": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                          "min": 4, "max": 16}},
    "benchmark/workloads/tiny_dsv2_serve.json": {
        "engine": {"max_batch": 4, "max_len": 64, "page_size": 8,
                   "num_pages": 33},
        "trace_seconds": 0.5, "drain_limit_s": 60, "check_requests": 6,
        "reference_max_tokens": 64,
        # the cell's own limits but for epsilon, the CPU's: a near-tie to
        # follow now and then at a tiny size
        "tolerance": {**_CELL_TOLERANCE, "margin_epsilon": 3e-3,
                      "past_original_min": 1}},
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
        "name": "tiny_dsv2", "source": TINY["source"],
        "file": "benchmark/configs/tiny_dsv2.json",
        "reduced": TINY["reduced"], "why": "test"})
    manifest["workloads"].append({
        "name": "tiny_dsv2_serve", "config": "tiny_dsv2",
        "traffic": "tiny_longdoc", "chips": 1, "why": "test"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append("tiny_dsv2_serve")
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
        "moe_experts_decode_roofline", "decode_read_tail_ms",
        "prefill_read_tail_ms", "serve_launch_lag_ms",
        "serve_back_to_back_pct", "serve_idle_cause_call_pct",
        "serve_idle_cause_read_pct", "serve_idle_cause_host_pct",
        "prefill_pad_pct", "serve_latent_attn_device_pct",
        "latent_attn_decode_roofline", "latent_attn_prefill_roofline"}
    assert cell["chips"] == 1
    assert cell["cell"]["engine"] == {"max_batch": 32, "max_len": 8192,
                                      "page_size": 16, "num_pages": 16385}
    for k in ("logit_gap", "margin_epsilon", "left_out_share_max",
              "past_original_min", "why"):
        assert k in cell["cell"]["tolerance"], k
    mix = cell["traffic"]
    assert (mix["kind"], mix["clients"], mix["pool"], mix["order"]) == (
        "serve_closed_latent", 32, 128, "mix")
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 3072,
                                    "sigma": 0.5, "min": 1024, "max": 6144}
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 320,
                                    "sigma": 0.6, "min": 64, "max": 1024}
    from benchmark import traffic_gen
    stream = traffic_gen.RequestStream(mix, 2 ** 31 + 5, 12800)
    longest = int(stream.pairs.sum(axis=1).max())
    assert 4096 < longest <= 7168 <= cell["cell"]["reference_max_tokens"]
    ids, _ = stream.next()
    assert 0 < min(ids) and max(ids) < 12800


def test_every_seed_sends_the_pool_in_the_mixs_order():
    """Under the kind the seed draws the ids and not the order; the
    generator is put back on the way out."""
    from benchmark import traffic_gen
    kind = harness.load_module(ROOT, "kinds", "serve_closed_latent")
    mix = harness.load_cell(ROOT, CELL)["traffic"]
    seeds = (2 ** 31 + 5, 7)
    plain = traffic_gen.RequestStream
    # a mix that does not say `order: mix` keeps the seed's order
    with kind._order_of_the_mix({k: v for k, v in mix.items()
                                 if k != "order"}):
        assert traffic_gen.RequestStream is plain
        e, f = (traffic_gen.RequestStream(mix, s, 12800) for s in seeds)
    assert list(e._order) != list(f._order)
    with kind._order_of_the_mix(mix):
        a, b = (traffic_gen.RequestStream(mix, s, 12800) for s in seeds)
    assert traffic_gen.RequestStream is not type(a)
    assert list(a._order) == list(b._order)
    assert sorted(a._order) == list(range(128))
    ids_a, out_a = a.next()
    ids_b, out_b = b.next()
    assert out_a == out_b and len(ids_a) == len(ids_b) and ids_a != ids_b
    # not the pairing's own shuffle, nor the pool's index order
    rng = np.random.default_rng(128)
    assert list(a._order) != list(rng.permutation(128))
    assert list(a._order) != list(range(128))
    c, d = (traffic_gen.RequestStream(mix, s, 12800) for s in seeds)
    assert list(c._order) != list(d._order)


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
        == ["deepseek_v2_236b_a21b"]
    assert [w["name"] for w in now["workloads"][len(parent["workloads"]):]] \
        == [CELL]
    assert [m["name"] for m in now["per_layer"][len(parent["per_layer"]):]] \
        == ["serve_latent_attn_device_pct", "latent_attn_decode_roofline",
            "latent_attn_prefill_roofline"]
    assert now["end_to_end"][len(parent["end_to_end"]):] == []


def test_driver_prints_a_well_formed_line(grown_root, interpreted):
    r = harness.run_cell(grown_root, "tiny_dsv2_serve", seed=2 ** 31 + 11,
                         seconds=1.0, trace=False)
    base.well_formed(r, {"serve_tokens_per_s", "setup_s"})
    assert r["correct"] is True, r
    assert r["attempted"] > 0 and r["failed"] == 0


def _run_kind(grown_root, family, **changes):
    cell = harness.load_cell(grown_root, "tiny_dsv2_serve")
    tolerance = {k: changes.pop(k) for k in list(changes)
                 if k in cell["cell"]["tolerance"]}
    cell["cell"]["tolerance"].update(tolerance)
    cell["cell"].update(changes)
    kind = harness.load_module(grown_root, "kinds", "serve_closed_latent")
    return kind.run({
        "root": grown_root, "seed": 5, "seconds": 1.0, "t_process": 0.0,
        "compiles": harness.CompileCounter(), "family": family,
        "tracer": None, **cell})


def test_driver_counts_the_latent_rows_and_the_experts(grown_root,
                                                      interpreted, family):
    run = _run_kind(grown_root, family)
    assert not run["notes"], run["notes"]
    counters = run["counters"]
    # every decode iteration's active lanes, their context a layer
    assert counters["latent_rows"] > 0
    assert counters["latent_rows"] % TINY["num_hidden_layers"] == 0
    assert counters["moe_assignments_here"] > 0
    latent = run["work"]["latent"]
    assert latent["rows"] == counters["latent_rows"]
    assert latent["row_bytes"] == (32 + 8) * 4
    assert latent["prefill_work"](10) == family.latent_prefill_work(TINY, 10)
    report = run["report"]
    assert report["cache"]["latent_layers"] == 4
    assert report["cache"]["latent_width"] == 128
    assert report["kernel_paths"]["paged_attention"]["latent_pallas"] > 0
    assert report["kernel_paths"]["moe"]["group_route"] > 0
    # every compared position kept, YaRN's original passed, near-ties met
    # and followed (the tiny cell's epsilon is wide)
    left_out, checked = report["left_out_positions"]
    assert checked > 0 and left_out == 0
    assert report["compared_past_original"] > 0
    assert report["near_ties"]["met"] > 0
    assert report["near_ties"]["reference_forwards"] \
        >= report["checked_requests"]
    assert sum(report["compared_by_bucket"].values()) \
        == report["checked_requests"]


def test_a_wrong_reference_is_not_correct(grown_root, interpreted, family):
    """The reference with the softmax scale's m^2 left out (cos and sin
    already carry 1), at the cell's own `logit_gap`: a compared token sits
    below its best logit, whichever way the near-ties go."""
    class Unscaled:
        def __getattr__(self, name):
            return getattr(family, name)

        def reference_spec(self, config):
            spec = family.reference_spec(config)
            return {**spec, "rope_scaling": {**spec["rope_scaling"],
                                             "mscale_all_dim": 0.0,
                                             "mscale": 0.0}}
    run = _run_kind(grown_root, Unscaled())
    assert any("below the reference's best logit" in n
               and f"(tolerance {_CELL_TOLERANCE['logit_gap']})" in n
               for n in run["notes"]), run["notes"]


def _fake(S, L, held, ties, wrong_unless=None, gap=1.0):
    """A stand-in for the reference's `gaps_at`: margins of 1 but at the
    (layer, position) pairs of `ties`; where the forced choice at every
    pair of `wrong_unless` is not the other one, the positions from the
    first such pair on read `gap`."""
    import numpy as np
    calls = []

    def fn(here, choice):
        calls.append(here.copy())
        margins = np.ones((S, L), np.float32)
        for layer, t in ties:
            margins[layer, t] = 0.0
        gaps = np.zeros((8,), np.float32)
        for layer, t in wrong_unless or ():
            if not here[layer, 0, t]:
                gaps[np.arange(8) + 8 >= t] = gap
        return gaps, margins, np.ones((S, L, held), bool)
    return fn, calls


def _comparison(epsilon=0.5, gap=3e-4):
    kind = harness.load_module(ROOT, "kinds", "serve_closed_latent")
    return kind, kind._Comparison(
        {"margin_epsilon": epsilon, "logit_gap": gap}, original=12)


def test_the_comparison_follows_a_near_tie_the_program_took_the_other_way():
    """Positions 8-15 generated; a near-tie at position 10 in layer 1 (and
    one at 3 in layer 0, in the prompt, which the program took as the
    reference does): the reference's own way puts the tokens from 10 on
    below the best, the other way at 10 keeps them all."""
    import numpy as np
    kind, comparison = _comparison()
    fn, calls = _fake(2, 32, 4, ties=[(0, 3), (1, 10)],
                      wrong_unless=[(1, 10)])
    pos = np.arange(8, 16)
    gaps = comparison.follow(fn, pos, 8, (2, 32, 4))
    assert gaps == [0.0] * 8
    assert (comparison.ties, comparison.other_way) == (2, 1)
    assert comparison.forwards == len(calls) == 2
    assert (comparison.checked, comparison.left_out) == (8, 0)
    assert comparison.past_original == 4          # positions 12-15
    assert calls[1][1, 0, 10] and not calls[1][0, 0, 3]


def test_the_comparison_is_not_fooled_by_a_wrong_program():
    """Whichever way the near-ties go, the positions from 9 on sit below
    the best: the way reported is the one that left the tolerance least,
    and the search spent every way there was."""
    import numpy as np
    kind, comparison = _comparison()
    fn, calls = _fake(2, 32, 4, ties=[(0, 3), (1, 10)],
                      wrong_unless=[(0, 3), (1, 10), (0, 9)])
    gaps = comparison.follow(fn, np.arange(8, 16), 8, (2, 32, 4))
    assert max(gaps) == 1.0
    assert len(calls) == 2     # each way at 3 leaves it at 9, before 10


def test_a_near_tie_past_the_forwards_leaves_the_rest_out(monkeypatch):
    """Where the search cannot afford the other way, what follows the
    near-tie is left out, not compared."""
    import numpy as np
    kind, comparison = _comparison()
    monkeypatch.setattr(kind, "_FORWARDS", 1)
    fn, _ = _fake(2, 32, 4, ties=[(1, 10)], wrong_unless=[(1, 10)])
    gaps = comparison.follow(fn, np.arange(8, 16), 8, (2, 32, 4))
    assert gaps == [0.0, 0.0] and comparison.left_out == 6


def test_driver_traced_leaves_out_what_a_cpu_trace_cannot_say(
        grown_root, interpreted):
    r = harness.run_cell(grown_root, "tiny_dsv2_serve", seed=3,
                         seconds=2.0, trace=True)
    assert r["correct"] is True, r
    got = set(r["metrics"])
    assert {"batch_occupancy_pct", "engine_step_ms", "tpot_p95_ms"} <= got
    assert not {"serve_latent_attn_device_pct", "latent_attn_decode_roofline",
                "latent_attn_prefill_roofline"} & got


# ----------------------- the readers of the `latent` scope --------------------


def _planes(decode_scope="jit(_fused_step_fn)/attention/latent/decode/x",
            flash="_fa_fwd_pallas.3"):
    """2 ms of trace: a decode program with 300 us under `latent/decode`
    and 100 under `latent/absorb` of 900, a prefill program with 200 us of
    the flash forward under `latent/prefill` and 100 of its expansion
    there, of 700; a prefill span of 1,000 tokens."""
    us = 1000
    events = [("jit__fused_step_fn/a", 0, 500 * us),
              ("jit__fused_step_fn/b", 500 * us, 300 * us),
              ("jit__fused_step_fn/e", 800 * us, 100 * us),
              ("jit__prefill_fn/c", 1000 * us, 400 * us),
              (f"jit__prefill_fn/{flash}", 1400 * us, 200 * us),
              ("jit__prefill_fn/f", 1600 * us, 100 * us)]
    ops = {"jit__fused_step_fn/a": ["jit(_fused_step_fn)/mlp/moe/x", ""],
           "jit__fused_step_fn/b": [decode_scope, ""],
           "jit__fused_step_fn/e": ["jit(_fused_step_fn)/attention/latent/"
                                    "absorb/y", ""],
           "jit__prefill_fn/c": ["jit(_prefill_fn)/mlp/moe/experts/z", ""],
           f"jit__prefill_fn/{flash}": [
               "jit(_prefill_fn)/attention/latent/prefill/while/body/"
               "jit(_fa_fwd_pallas)/pallas_call:", ""],
           "jit__prefill_fn/f": ["jit(_prefill_fn)/attention/latent/prefill/"
                                 "dot_general:", ""]}
    spans = [("pt.engine.prefill", 1000 * us, 700 * us, 0,
              {"prompt_tokens": 1000})]
    return {"spans": spans, "devices": {CHIP: events}, "ops": ops}


def test_reduce_sums_by_the_latent_scope():
    found = latent_trace.reduce(_planes())
    assert found["device_op_s"] == pytest.approx(1.6e-3)
    assert found["latent_s"] == {"jit__fused_step_fn": pytest.approx(4e-4),
                                 "jit__prefill_fn": pytest.approx(3e-4)}
    assert found["decode_s"] == {"jit__fused_step_fn": pytest.approx(3e-4)}
    assert found["flash_s"] == {"jit__prefill_fn": pytest.approx(2e-4)}
    assert found["prefill_tokens"] == [1000]


def test_a_trace_without_the_scope_reads_as_nothing():
    """The parent's programs have no `attention/latent` scope: the readers
    return None and do not raise."""
    planes = _planes("jit(f)/attention/full/x")
    for key in list(planes["ops"]):
        planes["ops"][key] = [planes["ops"][key][0].replace("latent", "full"),
                              ""]
    assert latent_trace.reduce(planes) is None
    assert latent_trace.reduce({"spans": [], "devices": {}, "ops": {}}) \
        is None


@pytest.fixture
def summarised(monkeypatch):
    def put(found):
        monkeypatch.setattr(latent_trace, "_summary", found or {})
    return put


def test_the_three_trace_metrics_on_known_answers(summarised, family):
    summarised(latent_trace.reduce(_planes()))
    rows = 1000
    run = {"device": {"kind": "TPU v5 lite"}, "counters": {},
           "work": {"latent": {
               "rows": rows, "row_bytes": 819e9 * 1.5e-4 / rows,
               "decode_flops": 0.0,
               "prefill_work": lambda t: (197e12 * 1e-4 * t / 1000, 0.0)}}}
    read = lambda name: harness.load_module(  # noqa: E731
        ROOT, "metrics", name).read(run)
    assert read("serve_latent_attn_device_pct") == pytest.approx(
        100 * 7 / 16)
    assert read("latent_attn_decode_roofline") == pytest.approx(50.0)
    assert read("latent_attn_prefill_roofline") == pytest.approx(50.0)
    # the operations bound it where they take longer than the bytes
    run["work"]["latent"]["decode_flops"] = 197e12 * 3e-4
    assert read("latent_attn_decode_roofline") == pytest.approx(100.0)


def test_the_metrics_are_left_out_without_the_scope(summarised):
    summarised(None)
    run = {"device": {"kind": "TPU v5 lite"}, "work": {}, "counters": {}}
    for name in ("serve_latent_attn_device_pct",
                 "latent_attn_decode_roofline",
                 "latent_attn_prefill_roofline"):
        assert harness.load_module(ROOT, "metrics", name).read(run) is None
