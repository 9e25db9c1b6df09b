"""Granite 4.0-H (`GraniteMoeHybridConfig`: `models/nemotron_h.py` with
Granite's options): the plain reference the benchmark compares with,
loaded from its one file under `benchmark/reference/`, against the
model's whole-sequence forward and against prefill then decode through
the cache, the shares of a deployment against the uncut expert layer, the
softmax router, the attention multiplier on both attention paths, the tied
head over a slice, one group of state-space heads, and the expert layers'
`permute` scope in the programs of every routed model.

Everything at `GraniteMoeHybridConfig.tiny()` (layers mamba, attention,
mamba; 8 experts top-3, 2 K/V heads for 4 query heads), seeded weights,
on the CPU.
"""
import importlib.util
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.serving import ServingEngine
from paddle_tpu.models import decode_blocks
from paddle_tpu.models.nemotron_h import (GraniteMoeHybridConfig, NemotronH,
                                          NemotronHConfig)
from paddle_tpu.ops import moe, ssm
from paddle_tpu.ops.pallas import paged_attention as pa

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module", autouse=True)
def _shared_compile_cache():
    """As tests/test_serving.py: every engine here compiles the same
    tiny programs; share them through the persistent cache."""
    import tempfile
    from paddle_tpu.framework import flags as flags_mod
    cache = os.path.join(tempfile.gettempdir(), "pt_granite_ccache")
    os.makedirs(cache, exist_ok=True)
    flags_mod.set_flags({"FLAGS_compile_cache_dir": cache})
    yield
    flags_mod.set_flags({"FLAGS_compile_cache_dir": ""})


def _load(folder, name):
    path = os.path.join(ROOT, "benchmark", folder, name + ".py")
    spec = importlib.util.spec_from_file_location(f"{folder}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def reference():
    """The benchmark's plain reference, by its path: no second copy to
    drift."""
    return _load("reference", "granite_moe_hybrid")


_MODELS = {}


def _model(layer_types=("mamba", "attention", "mamba"), held=()):
    key = (tuple(layer_types), held)
    if key not in _MODELS:
        paddle.seed(11)
        m = NemotronH(GraniteMoeHybridConfig.tiny(layer_types,
                                                  experts_held=held))
        m.eval()
        _MODELS[key] = m
    return _MODELS[key]


def _spec(m):
    c = m.cfg
    return {"heads": c.num_attention_heads, "kv_heads": c.num_key_value_heads,
            "head_dim": c.head_dim, "mamba_heads": c.mamba_num_heads,
            "mamba_groups": c.n_groups, "top_k": c.num_experts_per_tok,
            "experts_first": c.experts_held[0], "eps": c.layer_norm_epsilon,
            "embedding_multiplier": c.embedding_multiplier,
            "residual_multiplier": c.residual_multiplier,
            "attention_multiplier": c.attention_multiplier,
            "logits_scaling": c.logits_scaling}


def _params(m):
    return {k: p.data for k, p in m.named_parameters()}


def _ids(n, seed=0, vocab=256):
    return np.random.default_rng(seed).integers(1, vocab, (n,)).tolist()


# ------------------------------- the options ---------------------------------


def test_the_config_maps_layer_types_onto_blocks():
    cfg = GraniteMoeHybridConfig.tiny().as_nemotron_h()
    assert cfg.hybrid_override_pattern == "ME*EME"
    assert (cfg.router, cfg.expert_form, cfg.tie_word_embeddings) == (
        "softmax", "swiglu", True)
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.attention_multiplier, cfg.logits_scaling) == (
        12.0, 0.22, 0.0078125, 16.0)
    assert (cfg.head_dim, cfg.n_groups, cfg.routed_scaling_factor) == (
        16, 1, 1.0)
    whole = GraniteMoeHybridConfig()
    assert [i for i, t in enumerate(whole.layer_types)
            if t == "attention"] == [5, 15, 25, 35]
    # a depth cut keeps the leading layers: one whole period of ten
    cut = GraniteMoeHybridConfig(num_hidden_layers=10).as_nemotron_h()
    assert cut.hybrid_override_pattern == "ME" * 5 + "*E" + "ME" * 4
    assert (cut.d_inner, cut.conv_channels) == (8192, 8448)


def test_nemotron_h_keeps_its_own_defaults():
    cfg = NemotronHConfig.tiny()
    assert (cfg.router, cfg.expert_form, cfg.tie_word_embeddings) == (
        "sigmoid", "relu2", False)
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.attention_multiplier, cfg.logits_scaling) == (
        1.0, 1.0, None, 1.0)
    with pytest.raises(ValueError, match="scales nothing"):
        NemotronHConfig.tiny(router="softmax")
    with pytest.raises(ValueError, match="layer_types"):
        GraniteMoeHybridConfig.tiny(("mamba", "window")).as_nemotron_h()


def test_the_head_is_the_embedding_over_the_slice_divided():
    """No untied head is made; the logits are the final norm's output
    against the embedding's rows, divided by `logits_scaling`."""
    m = _model()
    assert not hasattr(m, "lm_head")
    x = np.random.default_rng(2).normal(size=(3, 64)).astype(np.float32)
    with paddle.no_grad():
        got = np.asarray(m._logits(paddle.to_tensor(x)).data)
    normed = x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-5) \
        * np.asarray(m.norm_f.weight.data)
    want = normed @ np.asarray(m.wte.weight.data).T / 16.0
    assert got.shape == (3, 256)
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-6)


def test_the_softmax_router_weighs_by_a_softmax_over_the_chosen():
    rng = np.random.default_rng(0)
    u = rng.normal(size=(20, 64)).astype(np.float32)
    router = (0.3 * rng.normal(size=(64, 72))).astype(np.float32)
    experts, weights, margin = moe.softmax_route(u, router, top_k=10)
    z = u.astype(np.float64) @ router
    top = np.sort(z, axis=1)[:, ::-1]
    chosen = np.take_along_axis(z, np.asarray(experts), axis=1)
    np.testing.assert_allclose(chosen, top[:, :10], rtol=0, atol=1e-5)
    e = np.exp(chosen - chosen.max(1, keepdims=True))
    np.testing.assert_allclose(weights, e / e.sum(1, keepdims=True),
                               rtol=0, atol=1e-6)
    np.testing.assert_allclose(margin, top[:, 9] - top[:, 10], rtol=0,
                               atol=1e-5)


def test_one_group_norms_the_whole_width_after_the_gate():
    rng = np.random.default_rng(5)
    y, z = rng.normal(size=(2, 3, 64)), rng.normal(size=(2, 3, 64))
    w = rng.normal(size=(64,))
    v = y * z / (1 + np.exp(-z))
    want = v / np.sqrt((v * v).mean(-1, keepdims=True) + 1e-5) * w
    got = ssm.gated_group_rms_norm(
        jnp.asarray(y, jnp.float32), jnp.asarray(z, jnp.float32),
        jnp.asarray(w, jnp.float32), groups=1, epsilon=1e-5)
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-5)


def _per_token(x, delta, A, Bm, Cm, D):
    """The recurrence one token at a time, float64, one group."""
    x, delta, A, Bm, Cm, D = (np.asarray(t, np.float64)
                              for t in (x, delta, A, Bm, Cm, D))
    B, L, H, P = x.shape
    S = np.zeros((B, H, P, Bm.shape[-1]))
    y = np.zeros(x.shape)
    for t in range(L):
        S = (np.exp(delta[:, t] * A)[..., None, None] * S
             + (delta[:, t, :, None] * x[:, t])[..., None]
             * Bm[:, t, 0][:, None, None, :])
        y[:, t] = (S * Cm[:, t, 0][:, None, None, :]).sum(-1) \
            + D[:, None] * x[:, t]
    return y, S


@pytest.mark.parametrize("L", [1, 31, 64, 100])
def test_one_group_of_heads_over_chunks_of_a_ragged_prompt(L):
    """Every head reads the one group's B and C; prompt lengths that are
    not a multiple of the chunk (32 here, 256 in the cell)."""
    rng = np.random.default_rng(L)
    f = np.float32
    x = rng.normal(size=(2, L, 16, 4)).astype(f)
    delta = (0.5 * np.exp(rng.uniform(-2, 0, (2, L, 16)))).astype(f)
    A = -rng.uniform(1, 16, (16,)).astype(f)
    Bm, Cm = (rng.normal(size=(2, L, 1, 8)).astype(f) for _ in range(2))
    D = rng.normal(size=(16,)).astype(f)
    y, S = ssm.ssd_chunked(x, delta, A, Bm, Cm, D, chunk=32)
    want_y, want_S = _per_token(x, delta, A, Bm, Cm, D)
    np.testing.assert_allclose(y, want_y, rtol=0, atol=2e-4)
    np.testing.assert_allclose(S, want_S, rtol=0, atol=2e-4)


# --------------------------- the attention multiplier -------------------------


def test_the_prompts_attention_takes_the_multiplier():
    rng = np.random.default_rng(3)
    q = rng.normal(size=(1, 5, 2, 2, 16)).astype(np.float32)
    k, v = (rng.normal(size=(1, 5, 2, 16)).astype(np.float32)
            for _ in range(2))
    from paddle_tpu.models.nemotron_h import _grouped_causal_attention
    got = _grouped_causal_attention(q, k, v, scale=0.0078125)
    s = np.einsum("bqkgd,bskd->bkgqs", q, k) * 0.0078125
    s = np.where(np.tril(np.ones((5, 5), bool)), s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    np.testing.assert_allclose(got, np.einsum("bkgqs,bskd->bqkgd", p, v),
                               rtol=0, atol=1e-5)
    # None keeps 1/sqrt(D)
    plain = _grouped_causal_attention(q, k, v)
    again = _grouped_causal_attention(q, k, v, scale=1 / math.sqrt(16))
    np.testing.assert_allclose(plain, again, rtol=0, atol=1e-6)


@pytest.mark.parametrize("path", ["xla", "grouped"])
def test_the_paged_decode_takes_the_multiplier(path, monkeypatch):
    """`decode_blocks.paged_decode_attention` hands its scale to the paged
    kernel (grouped, in the interpreter) and to the XLA gather."""
    from paddle_tpu.models.decode_cache import PagedKVCache
    monkeypatch.setattr(pa, "_INTERPRET", path == "grouped")
    rng = np.random.default_rng(7)
    H, Hkv, D, page, pages = 16, 2, 128, 8, 9
    pool = rng.normal(size=(pages, page, Hkv * D)).astype(np.float32)
    bt = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
    ctx = jnp.asarray([13, 20], jnp.int32)
    q = rng.normal(size=(2, H, D)).astype(np.float32)
    k, v = (rng.normal(size=(2, Hkv * D)).astype(np.float32)
            for _ in range(2))

    def cache():
        return PagedKVCache([jnp.asarray(pool)], [jnp.asarray(pool)],
                            bt, ctx, page, H, D, num_kv_heads=Hkv)
    active = jnp.ones((2,), bool)
    before = pa._stats[path]
    got = decode_blocks.paged_decode_attention(cache(), 0, q, k, v, bt, ctx,
                                               active, scale=0.0078125)
    assert pa._stats[path] == before + 1
    c = cache()
    c.k_pages[0], c.v_pages[0] = pa.cache_append(
        c.k_pages[0], c.v_pages[0], k, v, bt, ctx, active)
    want = pa.paged_attention_xla(q, c.k_pages[0], c.v_pages[0], bt,
                                  ctx + 1, scale=0.0078125)
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-5)
    plain = pa.paged_attention_xla(q, c.k_pages[0], c.v_pages[0], bt,
                                   ctx + 1)
    assert float(np.abs(np.asarray(plain) - np.asarray(want)).max()) > 1e-3


# ------------------------------- the reference --------------------------------


def _contiguous_cache(m, slots, max_len, page_size=8):
    cache = m.init_cache(slots, max_len, page_size=page_size)
    pps = cache.pages_per_seq
    cache.block_tables = jnp.asarray(
        1 + np.arange(slots * pps, dtype=np.int32).reshape(slots, pps))
    return cache


def _forward_with_margins(m, ids):
    """The model's forward, and each position's least routing margin over
    its expert blocks, read off what each block returns on the way."""
    margins = []
    experts = [blk.mixer for blk in m.blocks if blk.kind == "E"]
    for mixer in experts:
        def recording(u, active=None, forward=mixer.forward):
            out = forward(u, active)
            margins.append(out[2])
            return out
        mixer.forward = recording
    try:
        logits = m(ids)
    finally:
        for mixer in experts:
            del mixer.forward
    return logits, jnp.min(jnp.stack(margins), axis=0)


@pytest.mark.parametrize("layer_types,held", [
    (("mamba", "attention", "mamba"), ()),
    (("mamba", "mamba", "attention", "mamba"), (2, 4))],
    ids=["tiny", "share"])
class TestAgainstTheReference:
    def test_forward_equals_the_reference(self, layer_types, held,
                                          reference):
        m = _model(layer_types, held)
        ids = np.asarray([_ids(33, seed=5)], np.int32)
        with paddle.no_grad():
            got, margins = _forward_with_margins(m, paddle.to_tensor(ids))
        want, own, _ = reference.logits_at(_params(m), ids, np.arange(33),
                                           _spec(m))
        scale = float(np.abs(want).mean())
        np.testing.assert_allclose(np.asarray(got.data)[0], want, rtol=0,
                                   atol=1e-3 * scale)
        np.testing.assert_allclose(np.asarray(margins)[0], own, rtol=0,
                                   atol=1e-5)

    def test_prefill_then_decode_gives_the_references_logits(
            self, layer_types, held, reference):
        """`forward_prefill` (padded to its bucket) then N
        `forward_decode` steps through the cache, in lane mode with a
        padding lane, against the reference's full forward at every
        generated position: logits, not tokens."""
        m = _model(layer_types, held)
        ids = np.asarray([_ids(40, seed=3)], np.int32)
        want, margin, _ = reference.logits_at(_params(m), ids, np.arange(40),
                                              _spec(m))
        assert float(np.min(margin)) > 1e-5       # no routing near-tie here
        prompt = 27
        bucket = np.zeros((1, 32), np.int32)
        bucket[0, :prompt] = ids[0, :prompt]
        with paddle.no_grad():
            cache = _contiguous_cache(m, 4, 64)
            cache.states = [s + 3.0 for s in cache.states]   # a used slot
            logits, cache = m.forward_prefill(paddle.to_tensor(bucket),
                                              cache, 2, prompt)
            got = [np.asarray(logits.data)[0]]
            for t in range(prompt, 39):
                tokens = np.array([ids[0, t], 0], np.int32)
                logits, cache = m.forward_decode(
                    paddle.to_tensor(tokens), cache,
                    jnp.array([True, False]),
                    slot_map=jnp.array([2, 4], jnp.int32))  # 4: padding
                got.append(np.asarray(logits.data)[0])
        want = np.asarray(want)[26:39]
        np.testing.assert_allclose(np.stack(got), want, rtol=0,
                                   atol=1e-3 * float(np.abs(want).mean()))
        assert int(cache.context_lens[2]) == 39
        # 12 decode steps, one active lane, top-3 in each expert layer
        n_e = len(layer_types)
        counted = np.asarray(cache.counters["moe"])
        if not held:
            assert counted[0] == 12 * n_e * 3
        assert 0 < counted[1] <= counted[0] <= 12 * n_e * 3


def test_the_four_shares_add_up_to_the_uncut_layer(reference):
    """The share test: experts 0-1, 2-3, 4-5, 6-7 of 8, each share
    computed by a layer that is told what it holds, plus the shared
    expert counted once, against the reference's whole layer."""
    def experts_block(held):
        paddle.seed(3)
        return NemotronH(GraniteMoeHybridConfig.tiny(
            ("mamba",), experts_held=held)).blocks[1].mixer
    blocks = {first: experts_block((first, 2)) for first in (0, 2, 4, 6)}
    whole = experts_block(())
    u = np.random.default_rng(1).normal(size=(1, 30, 64)).astype(np.float32)
    p = {"b." + k: v.data for k, v in whole.named_parameters()}
    spec = {"top_k": 3, "experts_first": 0}
    with jax.default_matmul_precision("highest"):
        want, _ = reference._experts(p, "b.", jnp.asarray(u), spec)
        shared = reference._swiglu(u, p["b.shared_up.weight"],
                                   p["b.shared_down.weight"])
    total = np.asarray(shared)
    for first, blk in blocks.items():
        # one router and one shared expert for all four chips; each holds
        # its own two routed experts of the whole layer's eight
        blk.router.data = whole.router.data
        blk.shared_up.weight.data = whole.shared_up.weight.data
        blk.shared_down.weight.data = whole.shared_down.weight.data
        blk.w1.data = whole.w1.data[first:first + 2]
        blk.w2.data = whole.w2.data[first:first + 2]
        with paddle.no_grad():
            out, _, _ = blk(paddle.to_tensor(u))
        total = total + (np.asarray(out.data) - np.asarray(shared))
    np.testing.assert_allclose(total, want, rtol=0, atol=1e-5)


def test_through_the_engine_with_more_requests_than_slots():
    m = _model()
    eng = ServingEngine(m, max_batch=3, max_len=96, page_size=8,
                        name="granite")
    rng = np.random.default_rng(1)
    prompts = [_ids(int(n), seed=i) for i, n in
               enumerate(rng.integers(3, 50, (5,)))]
    reqs = [eng.submit(p, max_new_tokens=int(n))
            for p, n in zip(prompts, rng.integers(2, 6, (5,)))]
    eng.run_until_idle()
    for p, r in zip(prompts, reqs):
        assert r.state == "done", (r.state, r.error)
        seq = list(p)
        with paddle.no_grad():
            for _ in range(r.max_new_tokens):
                ids = np.zeros((1, 128), np.int32)
                ids[0, :len(seq)] = seq
                logits = np.asarray(m(paddle.to_tensor(ids)).data)[0]
                seq.append(int(logits[len(seq) - 1].argmax()))
        assert r.generated == seq[len(p):]
    counted = eng.device_counters()["moe"]
    # all 8 experts held: each decoded token met its top-3 in 3 layers
    assert counted[0] == eng.stats["decode_tokens"] * 3 * 3
    d = eng.cache.describe()
    assert d["layer_kinds"] == ["state", "none", "kv", "none", "state",
                                "none"]
    assert d["state_shape"] == [8, 8, 16]
    eng.close()


# --------------------- the `permute` scope of every routed model -------------

def _routed_models():
    from paddle_tpu.models.deepseek_v2 import DeepseekV2, DeepseekV2Config
    from paddle_tpu.models.exaone_moe import ExaoneMoe, ExaoneMoeConfig
    from paddle_tpu.models.mellum import Mellum, MellumConfig
    return {"nemotron_h": lambda: NemotronH(NemotronHConfig.tiny()),
            "granite": lambda: NemotronH(GraniteMoeHybridConfig.tiny()),
            "mellum": lambda: Mellum(MellumConfig.tiny()),
            "exaone_moe": lambda: ExaoneMoe(ExaoneMoeConfig.tiny()),
            "deepseek_v2": lambda: DeepseekV2(DeepseekV2Config.tiny())}


@pytest.mark.parametrize("name", ["nemotron_h", "granite", "mellum",
                                  "exaone_moe", "deepseek_v2"])
def test_the_permute_scope_stays_under_the_expert_layer(name):
    """In the compiled decode and prefill programs of each routed model,
    every operation under `permute` sits under the caller's `experts`
    scope (`mlp/moe/experts` in an expert layer), so that the device
    seconds of the expert layer and of the `mlp` scope
    (`serve_moe_device_pct`, `serve_scope_attributed_pct`) hold it as
    they held it before the scope was there."""
    from benchmark import program_trace
    paddle.seed(0)
    m = _routed_models()[name]()
    m.eval()
    eng = ServingEngine(m, max_batch=2, max_len=32, page_size=8,
                        name=f"permute_{name}")
    lanes = eng._lane_arrays([])[1:]
    decode = jax.jit(eng._fused_step_fn).lower(
        eng._params, eng._buffers, eng.cache, eng._last_tokens,
        *lanes).compile().as_text()
    prefill = jax.jit(eng._prefill_fn).lower(
        eng._params, eng._buffers, eng.cache, eng._last_tokens,
        np.zeros((1, 16), np.int32), np.array([0, 5, 0, 0, 0, 0], np.int32),
        np.array([0.0, 1.0], np.float32)).compile().as_text()
    eng.close()
    for text in (decode, prefill):
        # the program's own paths (a reduction's inner computation
        # carries a path relative to its call)
        names = {n for n in re.findall(r'op_name="([^"]*)"', text)
                 if n.startswith("jit(")}
        permuted = [n for n in names if "/permute/" in n]
        assert permuted, "no operation under permute"
        for n in permuted:
            # nested inside the caller's `experts` scope (`mlp/moe/experts`,
            # or `mtp/experts` in the drafting module): without it the
            # operation keeps every enclosing scope a reader sums by
            assert "/experts/jit(_held_impl)/permute/" in n, n
            bare = n.replace("/permute/", "/")
            assert program_trace.scope_of(n) == program_trace.scope_of(bare)
            assert ("/mlp/moe/" in n) == ("/mlp/moe/" in bare)
        assert any("/mlp/moe/experts/" in n for n in permuted)
