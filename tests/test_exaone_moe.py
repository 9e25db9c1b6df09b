"""EXAONE-MoE (models/exaone_moe.py): the model and its multi-token-
prediction module against the plain reference the benchmark compares
with (loaded from its one file under `benchmark/reference/`), the shares
of a deployment against the uncut layer, R rows a lane through the pages
and through the ring (`decode_blocks.*_rows_attention`), prefill and
self-drafting decode through the cache, and what the cache says of
itself.

Everything at `ExaoneMoeConfig.tiny()` (layers S S S F S, a dense MLP then
sparse ones, a window of 8, 8 experts top-2 beside a shared one, 2 K/V
heads for 4 query heads, the MTP module), seeded weights, on the CPU.
"""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.framework.tensor import Tensor
from paddle_tpu.inference.serving import ServingEngine
from paddle_tpu.models import decode_blocks
from paddle_tpu.models.decode_cache import DraftingUnsupported
from paddle_tpu.models.exaone_moe import (FULL, SLIDING, ExaoneMoe,
                                          ExaoneMoeConfig)
from paddle_tpu.ops import moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
W = 8                                                 # the tiny window
VOCAB = 64


@pytest.fixture(scope="module", autouse=True)
def _shared_compile_cache():
    """As tests/test_serving.py: every engine here compiles the same
    tiny programs; share them through the persistent cache."""
    import tempfile
    from paddle_tpu.framework import flags as flags_mod
    cache = os.path.join(tempfile.gettempdir(), "pt_exaone_ccache")
    os.makedirs(cache, exist_ok=True)
    flags_mod.set_flags({"FLAGS_compile_cache_dir": cache})
    yield
    flags_mod.set_flags({"FLAGS_compile_cache_dir": ""})


@pytest.fixture(scope="module")
def reference():
    """The benchmark's plain reference, by its path: no second copy to
    drift."""
    path = os.path.join(ROOT, "benchmark", "reference", "exaone_moe.py")
    spec = importlib.util.spec_from_file_location("reference_exaone", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_MODELS = {}


def _model(held=(), **changes):
    key = (held, tuple(sorted(changes.items())))
    if key not in _MODELS:
        paddle.seed(11)
        m = ExaoneMoe(ExaoneMoeConfig.tiny(experts_held=held, **changes))
        m.eval()
        _MODELS[key] = m
    return _MODELS[key]


def _spec(m):
    c = m.cfg
    return {"heads": c.num_attention_heads, "kv_heads": c.num_key_value_heads,
            "head_dim": c.head_dim, "top_k": c.num_experts_per_tok,
            "scale": c.routed_scaling_factor,
            "experts_first": c.experts_held[0], "eps": c.rms_norm_eps,
            "window": c.sliding_window, "layer_types": list(c.layer_types),
            "mlp_layer_types": list(c.mlp_layer_types),
            "mtp_layer_type": c.mtp_layer_types[0],
            "rope_parameters": c.rope_parameters}


def _params(m):
    return {k: p.data for k, p in m.named_parameters()}


def _ids(n, seed=0, vocab=VOCAB):
    return np.random.default_rng(seed).integers(1, vocab, (n,)).tolist()


# ------------------------------- the whole model ------------------------------


@pytest.mark.parametrize("held", [(), (2, 4)])
def test_forward_and_the_mtp_module_equal_the_reference(held, reference):
    m = _model(held)
    ids = np.asarray(_ids(40, seed=3), np.int32)
    with paddle.no_grad():
        logits, drafts = m(paddle.to_tensor(ids[None]), with_drafts=True)
    want, hid, margin = reference.forward(_params(m), ids, _spec(m))
    np.testing.assert_allclose(np.asarray(logits.data)[0], want, rtol=0,
                               atol=2e-4)
    # row i of the module reads token i + 1: the last row has none
    shifted = np.concatenate([ids[1:], [0]]).astype(np.int32)
    want_drafts, mtp_margin = reference.mtp_forward(_params(m), hid, shifted,
                                                    _spec(m))
    np.testing.assert_allclose(np.asarray(drafts.data)[0, :-1],
                               np.asarray(want_drafts)[:-1], rtol=0,
                               atol=2e-4)
    # the dense layer routes nothing; every sparse one gives a margin
    assert np.all(np.isfinite(margin)) and np.all(np.isfinite(mtp_margin))


def test_the_parameter_names_are_the_references(reference):
    names = set(_params(_model()))
    assert {"wte.weight", "lm_head.weight", "norm_f.weight",
            "blocks.0.mlp.gate_up.weight", "blocks.0.mlp.down.weight",
            "blocks.1.moe.router", "blocks.1.moe.e_score_correction_bias",
            "blocks.1.moe.w_gate_up", "blocks.1.moe.w_down",
            "blocks.1.moe.shared.gate_up.weight",
            "blocks.3.attn.q_norm.weight", "mtp.proj.weight",
            "mtp.embed_norm.weight", "mtp.hidden_norm.weight",
            "mtp.norm_f.weight", "mtp.block.moe.router",
            "mtp.block.attn.o_proj.weight"} <= names
    assert not any(k.startswith("blocks.0.moe") for k in names)


def test_rotation_is_on_the_sliding_layers_only():
    m = _model()
    assert [b.attn.rope_kind for b in m.blocks] == [
        "default", "default", "default", None, "default"]
    assert [b.attn.window for b in m.blocks] == [W, W, W, None, W]
    assert m.mtp.block.attn.rope_kind is None
    assert m.mtp.block.attn.window is None


def test_the_sixteenth_shares_add_up_to_the_uncut_layer(reference):
    """Every chip of a layer's 16 computes the router whole, its own
    experts' part and (here: all of them) the shared expert; the routed
    parts of the shares plus the shared expert ONCE are the uncut layer of
    the reference."""
    cfg = ExaoneMoeConfig.tiny(num_experts=16, num_experts_per_tok=4)
    paddle.seed(5)
    whole = ExaoneMoe(cfg)
    blk = whole.blocks[1].moe
    rng = np.random.default_rng(0)
    u = Tensor(jnp.asarray(rng.standard_normal((1, 24, 64)), jnp.float32))
    p = {"router": blk.router.data,
         "e_score_correction_bias": blk.e_score_correction_bias.data,
         "w_gate_up": blk.w_gate_up.data, "w_down": blk.w_down.data,
         "shared.gate_up.weight": blk.shared.gate_up.weight.data,
         "shared.down.weight": blk.shared.down.weight.data}
    spec = {"top_k": 4, "experts_first": 0, "scale": 2.5}
    with jax.default_matmul_precision("highest"):
        want, _, _ = reference._experts(p, "", u.data, spec)
        shared = reference._swiglu(u.data, p["shared.gate_up.weight"],
                                   p["shared.down.weight"])
    total = jnp.zeros_like(want)
    touched = 0
    for first in range(16):
        blk.first = first
        full_gu, full_d = blk.w_gate_up, blk.w_down
        blk.w_gate_up = Tensor(full_gu.data[first:first + 1])
        blk.w_down = Tensor(full_d.data[first:first + 1])
        try:
            out, counters = blk(u)
        finally:
            blk.w_gate_up, blk.w_down = full_gu, full_d
        total = total + (out.data - shared)          # the routed part
        touched += int(counters[0])
    blk.first = 0
    np.testing.assert_allclose(total + shared, want, rtol=0, atol=2e-5)
    assert touched == 24 * 4                 # every assignment, once


# ------------------------- R rows a lane over the cache -----------------------


def _cache(m, B=2, max_len=48, page=8):
    cache = m.init_cache(B, max_len, page_size=page)
    pages = max_len // page
    cache.block_tables = jnp.asarray(
        1 + np.arange(B * pages, dtype=np.int32).reshape(B, pages))
    return cache


def test_paged_rows_equal_one_row_at_a_time():
    m = _model()
    rng = np.random.default_rng(1)
    r = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    ctx = jnp.asarray([13, 7], jnp.int32)
    active = jnp.asarray([True, True])
    q, k, v = r(2, 2, 4, 16), r(2, 2, 32), r(2, 2, 32)
    filled = [r(*m.init_cache(2, 48, page_size=8).k_pages[0].shape)
              for _ in range(2)]

    def fresh():
        c = _cache(m)
        # copies: the eager append donates the pools it is given
        c.k_pages[0], c.v_pages[0] = (jnp.array(x, copy=True)
                                      for x in filled)
        return c

    both = fresh()
    got = decode_blocks.paged_rows_attention(both, 0, q, k, v,
                                             both.block_tables, ctx, active)
    one = fresh()
    want = jnp.stack([decode_blocks.paged_decode_attention(
        one, 0, q[:, i], k[:, i], v[:, i], one.block_tables, ctx + i, active)
        for i in range(2)], axis=1)
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)
    np.testing.assert_array_equal(both.k_pages[0], one.k_pages[0])


def test_a_rejected_drafts_ring_row_is_rewritten_before_it_is_read(reference):
    """A sliding layer at the wrap: position `ctx + 1` takes the row that
    position `ctx + 1 - W` holds. Query 0 (at `ctx`) still sees that
    position, so the rows are written and attended one at a time; and when
    the draft at `ctx + 1` is rejected, the next call writes `ctx + 1`
    again before any query reads the row, while the position it displaced
    is outside every later query's window."""
    m = _model()
    rng = np.random.default_rng(2)
    T, H, Hkv, D = 30, 4, 2, 16
    r = lambda *s: np.asarray(rng.standard_normal(s), np.float32)  # noqa: E731
    q, k, v = r(T, H, D), r(T, Hkv * D), r(T, Hkv * D)
    junk_k, junk_v = r(T, Hkv * D), r(T, Hkv * D)     # rejected drafts' rows

    def plain(t):
        lo = max(0, t - W + 1)
        kk = k[lo:t + 1].reshape(-1, Hkv, D).repeat(H // Hkv, axis=1)
        vv = v[lo:t + 1].reshape(-1, Hkv, D).repeat(H // Hkv, axis=1)
        s = np.einsum("hd,thd->ht", q[t], kk) / np.sqrt(D)
        s = np.exp(s - s.max(-1, keepdims=True))
        return np.einsum("ht,thd->hd", s / s.sum(-1, keepdims=True), vv)

    cache = _cache(m, B=1)
    slots, active = jnp.asarray([0], jnp.int32), jnp.asarray([True])
    # every iteration: the real row at ctx, then a draft at ctx + 1 that
    # is REJECTED (junk K/V), so the next iteration starts at ctx + 1
    for ctx in range(T - 1):
        pair = lambda a, b: jnp.asarray(np.stack([a, b])[None])  # noqa: E731
        out = decode_blocks.ring_rows_attention(
            cache, 0, pair(q[ctx], q[ctx + 1]),
            pair(k[ctx], junk_k[ctx + 1]), pair(v[ctx], junk_v[ctx + 1]),
            slots, jnp.asarray([ctx], jnp.int32), active)
        np.testing.assert_allclose(out[0, 0], plain(ctx), rtol=0, atol=2e-5,
                                   err_msg=f"context {ctx}")


def test_prefill_then_self_drafting_decode_equals_the_reference(reference):
    """The protocol the engine drives, by hand for two lanes at different
    laps of their rings: prefill (the MTP module over the prompt), then
    iterations of `forward_verify` / `draft_decode` / `accept_drafts` with
    greedy tokens. Every row-0 logit equals the reference's at its
    position, every draft logit `mtp_forward`'s, whether or not the draft
    before was accepted; both branches are taken."""
    m = _model()
    params, spec = _params(m), _spec(m)
    prompts = [_ids(5, seed=1), _ids(19, seed=2)]
    cache = _cache(m, B=2, max_len=64)
    seqs, last, draft = [], [], []
    with paddle.no_grad():
        for slot, p in enumerate(prompts):
            ids = np.zeros((1, 32), np.int32)
            ids[0, :len(p)] = p
            logits, cache, hid = m.forward_prefill(
                paddle.to_tensor(ids), cache, slot, len(p), with_hidden=True)
            tok = jnp.argmax(logits.data, -1).astype(jnp.int32)
            guess, cache = m.draft_prefill(hid, paddle.to_tensor(ids), tok,
                                           cache, slot, len(p))
            seqs.append(list(p) + [int(tok[0])])
            last.append(int(tok[0]))
            draft.append(int(jnp.argmax(guess.data[0])))
    rows, guesses, accepted_any, rejected_any = [], [], 0, 0
    active = jnp.asarray([True, True])

    @jax.jit
    def iteration(cache, pair):
        """What the engine's verify step does, greedy, with the logits."""
        with paddle.no_grad():
            logits, hid, cache = m.forward_verify(Tensor(pair), cache, active)
            sampled = jnp.argmax(logits.data, -1).astype(jnp.int32)
            ok = pair[:, 1] == sampled[:, 0]
            more, cache = m.draft_decode(hid, Tensor(sampled), cache, active)
            cache = m.accept_drafts(cache, ok, active)
        return logits.data, sampled, ok, more.data, cache

    for _ in range(26):
        ctx = np.asarray(cache.context_lens)
        logits, sampled, ok, more, cache = iteration(
            cache, jnp.asarray(np.stack([last, draft], 1), jnp.int32))
        logits, sampled, ok, more = (np.asarray(x) for x in
                                     (logits, sampled, ok, more))
        for b in range(2):
            a = bool(ok[b])
            accepted_any += a
            rejected_any += not a
            for r in range(1 + a):
                rows.append((b, ctx[b] + r, logits[b, r]))
                guesses.append((b, ctx[b] + r, more[b, r]))
                seqs[b].append(int(sampled[b, r]))
            last[b] = int(sampled[b, int(a)])
            draft[b] = int(more[b, int(a)].argmax())
        np.testing.assert_array_equal(np.asarray(cache.context_lens),
                                      ctx + 1 + ok)
    assert accepted_any and rejected_any
    counted = {k: np.asarray(v) for k, v in cache.counters.items()}
    assert counted["mtp"].tolist() == [52, accepted_any]
    for b in range(2):
        seq = np.asarray(seqs[b], np.int32)
        want, hid, _ = reference.forward(params, seq, spec)
        shifted = np.concatenate([seq[1:], [0]]).astype(np.int32)
        want_mtp, _ = reference.mtp_forward(params, hid, shifted, spec)
        for lane, t, got in rows:
            if lane == b:
                np.testing.assert_allclose(got, want[t], rtol=0, atol=3e-4,
                                           err_msg=f"lane {b} position {t}")
        for lane, t, got in guesses:
            if lane == b:
                np.testing.assert_allclose(got, want_mtp[t], rtol=0,
                                           atol=3e-4,
                                           err_msg=f"lane {b} draft at {t}")
        # the tokens are plain greedy decoding's: each is the argmax of
        # the reference's logits at the position before it
        L = len(prompts[b])
        assert seq[L:].tolist() \
            == np.asarray(want).argmax(-1)[L - 1:-1].tolist()
    # and `generate_plain` is that, a full forward a token
    assert seqs[0][5:9] == reference.generate_plain(params, prompts[0], 4,
                                                    spec)


def test_without_the_module_the_model_is_stepped_one_token(reference):
    """`num_nextn_predict_layers` 0: no module, no drafting protocol, the
    plain one-token step through the same engine."""
    m = _model(num_nextn_predict_layers=0)
    assert m.draft_tokens == 0 and not hasattr(m, "mtp")
    eng = ServingEngine(m, max_batch=2, max_len=48, page_size=8,
                        name="exaone_plain")
    assert eng._last_tokens.shape == (3,)
    prompts = [_ids(6, seed=4), _ids(17, seed=5)]
    reqs = [eng.submit(p, max_new_tokens=9) for p in prompts]
    eng.run_until_idle()
    for p, r in zip(prompts, reqs):
        seq = np.asarray(p + r.generated, np.int32)
        want, _, _ = reference.forward(_params(m), seq, _spec(m))
        assert r.generated == np.asarray(want).argmax(-1)[len(p) - 1:-1] \
            .tolist()
        assert r.drafts == []
    assert eng.stats["draft_tokens"] == 0
    assert eng.cache.describe()["draft_layers"] == 0
    assert "mtp" not in eng.device_counters()
    eng.close()


# --------------------------------- the cache ----------------------------------


def test_the_cache_counts_the_modules_pool():
    m = _model()
    eng = ServingEngine(m, max_batch=2, max_len=32, page_size=8,
                        name="exaone_desc")
    d = eng.cache.describe()
    assert d["layer_kinds"] == ["kv_window"] * 3 + ["kv", "kv_window", "kv"]
    assert (d["kv_layers"], d["draft_layers"], d["window_layers"]) \
        == (2, 1, 4)
    # K and V of 2 heads of 16, for the full layer and the MTP block
    pool = 9 * 8 * 32 * 4
    assert d["pool_bytes"] == eng.cache.pool_bytes() \
        == 2 * 2 * pool + d["window_bytes"]
    assert d["page_bytes"] == 2 * 2 * 8 * 32 * 4
    assert eng.stats["draft_layers"] == 1
    assert eng.cache_snapshot()["pages"]["layers"] == 2
    assert set(eng.device_counters()) == {"moe", "moe_prefill", "mtp",
                                          "mtp_moe", "window_rows"}
    eng.close()


def test_tensor_parallel_decode_refuses_by_name():
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    with pytest.raises(DraftingUnsupported,
                       match=r"tensor-parallel.*drafts 1 token"):
        ServingEngine(_model(), max_batch=2, max_len=32, page_size=8,
                      mesh=mesh)


def test_disaggregated_prefill_refuses_by_name():
    from paddle_tpu.inference.disagg import DisaggPipeline
    eng = ServingEngine(_model(), max_batch=2, max_len=32, page_size=8,
                        name="exaone_disagg")
    with pytest.raises(DraftingUnsupported,
                       match=r"DisaggPipeline.*standing draft"):
        DisaggPipeline(eng)
    eng.close()


@pytest.mark.parametrize("changes,match", [
    ({"mtp_layer_types": (SLIDING,)}, "mtp_layer_types"),
    ({"num_nextn_predict_layers": 2,
      "mtp_layer_types": (FULL, FULL)}, "num_nextn_predict_layers"),
    ({"scoring_func": "softmax"}, "scoring_func"),
    ({"mlp_layer_types": ("sparse",) * 5}, "first_k_dense_replace"),
    ({"n_group": 8, "topk_group": 4}, "n_group"),
])
def test_what_is_not_implemented_is_refused_by_name(changes, match):
    with pytest.raises(ValueError, match=match):
        ExaoneMoeConfig.tiny(**changes)


# ----------------------------------- scopes -----------------------------------


class TestScopes:
    @pytest.fixture(scope="class")
    def lowered(self):
        m = _model()
        eng = ServingEngine(m, max_batch=2, max_len=32, page_size=8,
                            name="exaone_scopes")
        before = dict(moe._stats)
        lanes = eng._lane_arrays([])[1:]
        decode = jax.jit(eng._fused_step_fn).lower(
            eng._params, eng._buffers, eng.cache, eng._last_tokens,
            *lanes).as_text(debug_info=True)
        prefill = jax.jit(eng._prefill_fn).lower(
            eng._params, eng._buffers, eng.cache, eng._last_tokens,
            np.zeros((1, 16), np.int32),
            np.array([0, 5, 0, 0, 0, 0], np.int32),
            np.array([0.0, 1.0], np.float32)).as_text(debug_info=True)
        traced = {n: moe._stats[n] - before[n] for n in before}
        eng.close()
        return {"decode": decode, "prefill": prefill, "traced": traced}

    @pytest.mark.parametrize("program", ["decode", "prefill"])
    @pytest.mark.parametrize("scope", [
        "attention/rope", "attention/window", "attention/full",
        "mlp/moe/route", "mlp/moe/experts", "mlp/moe/shared",
        "mlp/dense_mlp", "mtp/project", "mtp/attention/full", "mtp/route",
        "mtp/experts", "mtp/shared", "mtp/logits"])
    def test_scope_is_in_the_lowered_program(self, lowered, program, scope):
        assert scope + "/" in lowered[program]

    def test_the_verify_step_has_its_scope(self, lowered):
        assert "spec_verify/" in lowered["decode"]
        assert "spec_verify/" not in lowered["prefill"]

    def test_the_modules_experts_are_outside_the_decoders_scope(self,
                                                                lowered):
        for program in ("decode", "prefill"):
            assert "mtp/mlp/" not in lowered[program]

    def test_the_form_each_program_traced(self, lowered):
        # four sparse layers and the module's block in each program
        assert lowered["traced"]["route"] == 10
        assert lowered["traced"]["softmax_route"] == 0
        assert lowered["traced"]["ragged_dot"] == 10
