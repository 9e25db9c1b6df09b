"""Mellum (models/mellum.py): the softmax router and the SwiGLU form of the
dropless expert layer (ops/moe.py) against NumPy and a loop over the
experts, the shares of a deployment against the uncut layer, the ring of a
sliding-window layer beside the full layers' pages through the cache and
through `ServingEngine`, and the plain reference the benchmark compares
with, loaded from its one file under `benchmark/reference/`.

Everything at `MellumConfig.tiny()` (layers S S S F, a window of 8, 8
experts top-2, 2 K/V heads for 4 query heads), seeded weights, on the CPU.
"""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.serving import ServingEngine
from paddle_tpu.models.decode_cache import WindowLayersUnsupported
from paddle_tpu.models.mellum import FULL, SLIDING, Mellum, MellumConfig
from paddle_tpu.ops import moe, rope
from paddle_tpu.ops.pallas import flash_attention as fa

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
W = 8                                                 # the tiny window


@pytest.fixture(scope="module", autouse=True)
def _shared_compile_cache():
    """As tests/test_serving.py: every engine here compiles the same
    tiny programs; share them through the persistent cache."""
    import tempfile
    from paddle_tpu.framework import flags as flags_mod
    cache = os.path.join(tempfile.gettempdir(), "pt_mellum_ccache")
    os.makedirs(cache, exist_ok=True)
    flags_mod.set_flags({"FLAGS_compile_cache_dir": cache})
    yield
    flags_mod.set_flags({"FLAGS_compile_cache_dir": ""})


@pytest.fixture(scope="module")
def reference():
    """The benchmark's plain reference, by its path: no second copy to
    drift."""
    path = os.path.join(ROOT, "benchmark", "reference", "mellum.py")
    spec = importlib.util.spec_from_file_location("reference_mellum", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_MODELS = {}


def _model(held=(), layers=(SLIDING, SLIDING, SLIDING, FULL)):
    key = (held, layers)
    if key not in _MODELS:
        paddle.seed(11)
        m = Mellum(MellumConfig.tiny(layers, experts_held=held))
        m.eval()
        _MODELS[key] = m
    return _MODELS[key]


def _spec(m):
    c = m.cfg
    return {"heads": c.num_attention_heads, "kv_heads": c.num_key_value_heads,
            "head_dim": c.head_dim, "top_k": c.num_experts_per_tok,
            "experts_first": c.experts_held[0], "eps": c.rms_norm_eps,
            "window": c.sliding_window, "layer_types": list(c.layer_types),
            "rope_parameters": c.rope_parameters}


def _params(m):
    return {k: p.data for k, p in m.named_parameters()}


def _ids(n, seed=0, vocab=256):
    return np.random.default_rng(seed).integers(1, vocab, (n,)).tolist()


# ------------------------------ the expert layer ------------------------------


def _expert_inputs(T=20, h=64, f=32, E=8, k=2, seed=0):
    rng = np.random.default_rng(seed)
    r = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    return r(T, h), r(h, E) * 0.3, r(E, 2 * f, h) * 0.1, r(E, f, h) * 0.1, k


def _swiglu_loop(u, experts, weights, w1, w2, first, held, active=None):
    u, w1, w2 = (np.asarray(x, np.float64) for x in (u, w1, w2))
    f = w2.shape[1]
    out = np.zeros_like(u)
    for t in range(u.shape[0]):
        if active is not None and not active[t]:
            continue
        for e, w in zip(np.asarray(experts)[t], np.asarray(weights)[t]):
            if first <= e < first + held:
                gu = w1[e - first] @ u[t]
                g, up = gu[:f], gu[f:]
                out[t] += w * ((g / (1 + np.exp(-g)) * up) @ w2[e - first])
    return out


class TestSoftmaxRoutedSwiGLUExperts:
    def test_the_router_is_a_softmax_renormalised_over_the_chosen(self):
        u, router, _, _, k = _expert_inputs()
        experts, weights, margin = moe.softmax_route(u, router, top_k=k)
        z = np.asarray(u, np.float64) @ np.asarray(router, np.float64)
        p = np.exp(z - z.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        order = np.argsort(-z, axis=-1)
        np.testing.assert_array_equal(np.asarray(experts), order[:, :k])
        chosen = np.take_along_axis(p, order[:, :k], -1)
        np.testing.assert_allclose(np.asarray(weights),
                                   chosen / chosen.sum(-1, keepdims=True),
                                   rtol=1e-5)
        np.testing.assert_allclose(np.asarray(weights).sum(-1), 1.0,
                                   rtol=1e-6)
        # the margin is taken on the logits, which the softmax squeezes
        sorted_z = np.take_along_axis(z, order, -1)
        np.testing.assert_allclose(np.asarray(margin),
                                   sorted_z[:, k - 1] - sorted_z[:, k],
                                   atol=1e-5)

    @pytest.mark.parametrize("interpret", [False, True],
                             ids=["ragged_dot", "megablox"])
    @pytest.mark.parametrize("case", ["all", "share", "padding"])
    def test_equals_a_loop_over_the_experts(self, case, interpret,
                                            monkeypatch):
        monkeypatch.setattr(moe, "_INTERPRET", interpret)
        u, router, w1, w2, k = _expert_inputs(T=24)
        experts, weights, _ = moe.softmax_route(u, router, top_k=k)
        first, held = (2, 4) if case == "share" else (0, 8)
        active = None
        if case == "padding":
            active = np.arange(24) % 5 != 0
        got, counters = moe.held_experts(
            u, experts, weights, w1[first:first + held],
            w2[first:first + held], first=first, form="swiglu",
            active=None if active is None else jnp.asarray(active))
        want = _swiglu_loop(u, experts, weights, w1[first:first + held],
                            w2[first:first + held], first, held, active)
        np.testing.assert_allclose(np.asarray(got), want, atol=2e-4)
        here = (np.asarray(experts) >= first) \
            & (np.asarray(experts) < first + held)
        if active is not None:
            here &= active[:, None]
        assert int(counters[0]) == here.sum()
        assert moe._stats["gmm" if interpret else "ragged_dot"] > 0

    def test_another_form_is_refused_by_name(self):
        u, router, w1, w2, k = _expert_inputs()
        experts, weights, _ = moe.softmax_route(u, router, top_k=k)
        with pytest.raises(ValueError, match="geglu"):
            moe.held_experts(u, experts, weights, w1, w2, form="geglu")

    def test_the_two_shares_add_up_to_the_uncut_layer(self, reference):
        """Rank 0 holds experts 0-3, rank 1 experts 4-7 of one layer, both
        route over all 8: their partial outputs sum to what the reference
        gives for the layer with every expert (there is no shared expert
        to count once)."""
        whole = _model()
        blk = whole.blocks[1]
        u = jnp.asarray(np.random.default_rng(5).standard_normal(
            (1, 30, 64)), jnp.float32)
        p = {"m." + k: v.data for k, v in blk.moe.named_parameters()}
        want, _, _ = reference._experts(
            p, "m.", u, {"top_k": 2, "experts_first": 0})
        total = 0.0
        for first in (0, 4):
            experts, weights, _ = moe.softmax_route(
                u[0], blk.moe.router.data, top_k=2)
            part, _ = moe.held_experts(
                u[0], experts, weights,
                blk.moe.w_gate_up.data[first:first + 4],
                blk.moe.w_down.data[first:first + 4], first=first,
                form="swiglu")
            assert float(jnp.abs(part).max()) > 0
            total = total + part
        np.testing.assert_allclose(np.asarray(total), np.asarray(want)[0],
                                   atol=2e-5)


# --------------------------------- the model ---------------------------------


def _contiguous_cache(m, slots, max_len, page_size=8):
    cache = m.init_cache(slots, max_len, page_size=page_size)
    pps = cache.pages_per_seq
    cache.block_tables = jnp.asarray(
        1 + np.arange(slots * pps, dtype=np.int32).reshape(slots, pps))
    return cache


def _prefill(m, cache, slot, ids, bucket, filler=0):
    """`ids` padded to `bucket` with `filler` ids: bucket padding that is
    NOT zeros, so that a padded position reaching a ring would show."""
    row = np.full((1, bucket), filler, np.int32)
    row[0, :len(ids)] = ids
    with paddle.no_grad():
        logits, cache = m.forward_prefill(paddle.to_tensor(row), cache, slot,
                                          len(ids))
    return np.asarray(logits.data)[0], cache


@pytest.mark.parametrize("held", [(), (2, 4)], ids=["all", "share"])
class TestCachePath:
    def test_forward_equals_the_reference(self, held, reference):
        m = _model(held)
        ids = np.asarray([_ids(33, seed=5)], np.int32)
        with paddle.no_grad():
            got = np.asarray(m(paddle.to_tensor(ids)).data)[0]
        want, margin, _ = reference.logits_at(_params(m), ids,
                                              np.arange(33), _spec(m))
        assert float(np.min(margin)) > 1e-5       # no routing near-tie here
        np.testing.assert_allclose(got, want, rtol=0, atol=2e-3)

    def test_lanes_at_different_laps_of_their_rings(self, held, reference):
        """Prompts shorter than (5), equal to (8) and longer than the
        window (21, in a bucket of 32 whose padding carries live ids:
        a position at or past `length` must not reach the ring, though
        its row `t mod 8` holds a live token), then 19 decode iterations
        of the three lanes and a padding lane: contexts to 40, the rings
        at laps 0-2, 1-3 and 2-5 in the same iterations. Logits against
        the reference's full forward of each lane's own sequence."""
        m = _model(held)
        lengths, steps = (5, 8, 21), 19
        seqs = [np.asarray(_ids(n + steps, seed=20 + i), np.int32)
                for i, n in enumerate(lengths)]
        wants = [reference.logits_at(
            _params(m), s[None], np.arange(len(s)), _spec(m))
            for s in seqs]
        assert min(float(np.min(w[1])) for w in wants) > 1e-5
        cache = _contiguous_cache(m, 4, 64)
        # slot 3 stands for the slot a padding lane's clamped index names
        _, cache = _prefill(m, cache, 3, _ids(11, seed=9), 16, filler=7)
        kept = [np.asarray(r) for r in cache.window_k]
        got = [[] for _ in lengths]
        for slot, (n, s) in enumerate(zip(lengths, seqs)):
            first, cache = _prefill(m, cache, slot, s[:n],
                                    {5: 16, 8: 16, 21: 32}[n], filler=9)
            got[slot].append(first)
        slot_map = jnp.asarray([0, 1, 2, 4], jnp.int32)       # 4: padding
        active = jnp.asarray([True, True, True, False])
        with paddle.no_grad():
            for t in range(steps - 1):
                tokens = np.array([s[n + t] for n, s in zip(lengths, seqs)]
                                  + [0], np.int32)
                logits, cache = m.forward_decode(
                    paddle.to_tensor(tokens), cache, active,
                    slot_map=slot_map)
                for lane in range(3):
                    got[lane].append(np.asarray(logits.data)[lane])
        for n, rows, (want, _, _) in zip(lengths, got, wants):
            np.testing.assert_allclose(
                np.stack(rows), np.asarray(want)[n - 1:n - 1 + steps],
                rtol=0, atol=2e-3)
        np.testing.assert_array_equal(
            np.asarray(cache.context_lens), [5 + 18, 8 + 18, 21 + 18, 11])
        # the padding lane's clamped slot (3) kept its rings, row for row
        for before, ring in zip(kept, cache.window_k):
            np.testing.assert_array_equal(before[1 + 3:], np.asarray(
                ring)[1 + 3:])
        # each decoded token attended over min(context, 8) ring rows
        rows = sum(min(n + t + 1, W) for n in lengths
                   for t in range(steps - 1))
        assert int(cache.counters["window_rows"][0]) == rows

    def test_a_slot_reused_by_a_shorter_prompt(self, held, reference):
        """A prompt of 3 tokens in the slot a sequence of 30 left: the
        ring is rewritten whole, so nothing of the old sequence is read
        while the new one is still inside the ring's first lap."""
        m = _model(held)
        cache = _contiguous_cache(m, 2, 64)
        _, cache = _prefill(m, cache, 1, _ids(30, seed=40), 32)
        seq = np.asarray(_ids(3 + 7, seed=41), np.int32)
        want, _, _ = reference.logits_at(_params(m), seq[None],
                                         np.arange(10), _spec(m))
        first, cache = _prefill(m, cache, 1, seq[:3], 16, filler=5)
        got = [first]
        with paddle.no_grad():
            for t in range(3, 9):
                logits, cache = m.forward_decode(
                    paddle.to_tensor(np.array([seq[t]], np.int32)), cache,
                    jnp.asarray([True]), slot_map=jnp.asarray([1]))
                got.append(np.asarray(logits.data)[0])
        np.testing.assert_allclose(np.stack(got), np.asarray(want)[2:9],
                                   rtol=0, atol=2e-3)

    def test_every_slot_mode_equals_lane_mode(self, held):
        """`slot_map=None`: lane b is slot b, the ring table is every
        slot's own."""
        m = _model(held)
        ids = _ids(13, seed=50)
        out = []
        for lanes in (None, jnp.asarray([0, 1], jnp.int32)):
            cache = _contiguous_cache(m, 2, 32)
            _, cache = _prefill(m, cache, 1, ids, 16)
            with paddle.no_grad():
                logits, cache = m.forward_decode(
                    paddle.to_tensor(np.array([0, 77], np.int32)), cache,
                    jnp.asarray([False, True]), slot_map=lanes)
            out.append(np.asarray(logits.data)[1])
        np.testing.assert_allclose(out[0], out[1], rtol=0, atol=1e-6)


def test_a_window_that_is_no_whole_number_of_pages_is_refused():
    with pytest.raises(ValueError, match="whole number of pages"):
        _model().init_cache(2, 32, page_size=16)


def _greedy(m, prompt, n):
    """What `generate_dense` gives, from ONE forward over the padded
    sequence (causal: padding cannot reach an earlier position)."""
    seq = list(prompt)
    with paddle.no_grad():
        for _ in range(n):
            ids = np.zeros((1, 128), np.int32)
            ids[0, :len(seq)] = seq
            logits = np.asarray(m(paddle.to_tensor(ids)).data)[0]
            seq.append(int(logits[len(seq) - 1].argmax()))
    return seq[len(prompt):]


def _check(m, prompts, reqs):
    for p, r in zip(prompts, reqs):
        assert r.state == "done", (r.state, r.error)
        assert r.generated == _greedy(m, p, r.max_new_tokens)


class TestThroughTheEngine:
    def test_more_requests_than_slots_and_padding_lanes(self):
        """Continuous batching with 7 requests over 3 slots: slots are
        reused by shorter and longer prompts (a prefill rewrites the
        ring), and with 3 active the 4-lane program runs with a padding
        lane. The scheduler knows nothing of the rings."""
        m = _model()
        eng = ServingEngine(m, max_batch=3, max_len=96, page_size=8,
                            name="mellum")
        rng = np.random.default_rng(1)
        prompts = [_ids(int(n), seed=i)
                   for i, n in enumerate([3, 8, 9, 50, 20, 7, 33])]
        reqs = [eng.submit(p, max_new_tokens=int(n))
                for p, n in zip(prompts, rng.integers(2, 14, (7,)))]
        eng.run_until_idle()
        _check(m, prompts, reqs)
        assert eng.stats["prefills"] == 7
        counted = eng.device_counters()
        # every decoded token met its top-2 in each of the four layers
        # (all 8 experts are held), every prompt token too
        assert counted["moe"][0] == eng.stats["decode_tokens"] * 4 * 2
        assert counted["moe_prefill"][0] == sum(map(len, prompts)) * 4 * 2
        assert 0 < counted["window_rows"][0] \
            <= eng.stats["decode_tokens"] * W
        eng.close()

    def test_a_preempted_request_resumes_with_the_same_tokens(self):
        """A pool too small for both sequences: the youngest is
        preempted and prefilled again with what it had generated, which
        rewrites its rings."""
        m = _model()
        eng = ServingEngine(m, max_batch=2, max_len=40, page_size=8,
                            num_pages=6, name="mellum_pre")
        prompts = [_ids(14, seed=31), _ids(14, seed=32)]
        reqs = [eng.submit(p, max_new_tokens=12) for p in prompts]
        eng.run_until_idle()
        assert eng.stats["preemptions"] >= 1
        _check(m, prompts, reqs)
        eng.close()

    def test_a_shared_prefix_gives_the_tokens_of_an_unshared_one(self):
        """A prefix hit masks the FULL layers' scatter only; the prompt is
        computed whole and the ring rewritten."""
        m = _model()
        base = _ids(24, seed=5)
        prompts = [base + _ids(5, seed=6), base + _ids(7, seed=7),
                   base + _ids(3, seed=8)]
        tokens, hits = [], []
        for share in (False, True):
            eng = ServingEngine(m, max_batch=2, max_len=64, page_size=8,
                                name=f"mellum_share{int(share)}",
                                share_prefix=share)
            reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
            eng.run_until_idle()
            tokens.append([r.generated for r in reqs])
            hits.append(eng.stats["prefix_hit_tokens"])
            eng.close()
        assert hits[0] == 0 and hits[1] >= 24
        assert tokens[0] == tokens[1]
        _check(m, prompts[:1], reqs[:1])

    def test_the_cache_describes_both_groups(self):
        m = _model()
        eng = ServingEngine(m, max_batch=2, max_len=32, page_size=8,
                            name="mellum_desc")
        d = eng.cache.describe()
        assert d["layer_kinds"] == ["kv_window"] * 3 + ["kv"]
        assert (d["kv_layers"], d["window_layers"], d["state_layers"],
                d["cacheless_layers"]) == (1, 3, 0, 0)
        assert (d["num_heads"], d["num_kv_heads"], d["window"]) == (4, 2, W)
        # K and V of 2 heads of 16: pages for the full layer, 1 + 2 slots
        # x 1 page of ring for a sliding one
        assert eng.cache.k_pages[0].shape == (9, 8, 32)
        assert eng.cache.window_k[0].shape == (3, 8, 32)
        ring = 3 * 8 * 32 * 4
        assert d["window_bytes"] == eng.cache.window_bytes() == 3 * 2 * ring
        assert d["pool_bytes"] == eng.cache.pool_bytes() \
            == 2 * 9 * 8 * 32 * 4 + d["window_bytes"]
        # a page of the allocator costs the full layers' bytes alone
        assert d["page_bytes"] == 2 * 8 * 32 * 4
        assert eng.pool_bytes() == eng.cache.pool_bytes()
        snap = eng.cache_snapshot()
        assert snap["window"] == {"layers": 3, "tokens": W, "slots": 2,
                                  "bytes": d["window_bytes"]}
        assert snap["pages"]["bytes"] == d["pool_bytes"] - d["window_bytes"]
        assert eng.stats["window_layers"] == 3
        eng.close()

    def test_admission_and_growth_count_the_full_layers_pages_only(self):
        """max_len 64 at pages of 8: a request of 20 + 12 tokens needs 4
        pages of the full layer whatever the rings hold, and a budget buys
        pages after the rings' fixed cost."""
        m = _model()
        eng = ServingEngine(m, max_batch=2, max_len=64, page_size=8,
                            name="mellum_pages")
        total = eng.allocator.free_pages
        assert total == eng.cache.num_pages - 1 == 16
        req = eng.submit(_ids(20, seed=3), max_new_tokens=12)
        eng.step()
        assert total - eng.allocator.free_pages == 3       # 20 tokens
        eng.run_until_idle()
        assert req.state == "done"
        assert total - eng.stats["min_free_pages"] == 4    # 32 tokens
        assert eng.allocator.free_pages == total
        eng.close()
        page = eng.cache.describe()["page_bytes"]
        capped = ServingEngine(
            m, max_batch=2, max_len=64, page_size=8, name="mellum_budget",
            mem_budget_bytes=eng.cache.window_bytes() + 5 * page)
        assert capped.cache.num_pages == 5
        assert capped.cache.window_bytes() == eng.cache.window_bytes()
        capped.close()

    def test_tensor_parallel_decode_refuses_by_name(self):
        from jax.sharding import Mesh
        mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
        with pytest.raises(WindowLayersUnsupported,
                           match=r"tensor-parallel.*3 window rings"):
            ServingEngine(_model(), max_batch=2, max_len=32, page_size=8,
                          mesh=mesh)

    def test_disaggregated_prefill_refuses_by_name(self):
        from paddle_tpu.inference.disagg import DisaggPipeline
        eng = ServingEngine(_model(), max_batch=2, max_len=32, page_size=8,
                            name="mellum_disagg")
        with pytest.raises(
                WindowLayersUnsupported,
                match=r"DisaggPipeline.*3 window rings, 1 paged K/V"):
            DisaggPipeline(eng)
        eng.close()


# ----------------------------------- scopes -----------------------------------


class TestScopes:
    @pytest.fixture(scope="class")
    def lowered(self):
        m = _model()
        eng = ServingEngine(m, max_batch=2, max_len=32, page_size=8,
                            name="mellum_scopes")
        mods = (("rope", rope), ("moe", moe), ("flash", fa))
        before = {k: dict(mod._stats) for k, mod in mods}
        lanes = eng._lane_arrays([])[1:]
        decode = jax.jit(eng._fused_step_fn).lower(
            eng._params, eng._buffers, eng.cache, eng._last_tokens,
            *lanes).as_text(debug_info=True)
        prefill = jax.jit(eng._prefill_fn).lower(
            eng._params, eng._buffers, eng.cache,
            np.zeros((1, 16), np.int32),
            np.array([0, 5, 0, 0, 0, 0], np.int32),
            np.array([0.0, 1.0], np.float32)).as_text(debug_info=True)
        traced = {k: {n: mod._stats[n] - before[k][n] for n in before[k]}
                  for k, mod in mods}
        eng.close()
        return {"decode": decode, "prefill": prefill, "traced": traced}

    @pytest.mark.parametrize("program", ["decode", "prefill"])
    @pytest.mark.parametrize("scope", [
        "attention/rope", "attention/window", "attention/full",
        "mlp/moe/route", "mlp/moe/experts"])
    def test_scope_is_in_the_lowered_program(self, lowered, program, scope):
        assert scope + "/" in lowered[program]

    def test_the_form_each_program_traced(self, lowered):
        # three sliding layers and a full one in each of the two programs
        assert lowered["traced"]["rope"] == {"default": 6, "yarn": 2}
        assert lowered["traced"]["moe"]["softmax_route"] == 8
        assert lowered["traced"]["moe"]["route"] == 0
        assert lowered["traced"]["moe"]["ragged_dot"] == 8
        # the prefill program's four attentions, three with a window
        assert lowered["traced"]["flash"]["window"] == 3
        assert lowered["traced"]["flash"]["xla"] == 4
