"""Nemotron-H (models/nemotron_h.py): Mamba-2's chunked scan and one-token
step (ops/ssm.py) against the per-token recurrence, the dropless expert
layer (ops/moe.py) against a dense loop over the experts and the shares
of a deployment against the uncut layer, grouped K/V heads in the paged
pools, the cache of three kinds through `ServingEngine`, and the plain
reference the benchmark compares with, loaded from its one file under
`benchmark/reference/`.

Everything at `NemotronHConfig.tiny()` (blocks `MEM*E`, 8 experts top-2,
2 K/V heads for 4 query heads), seeded weights, on the CPU.
"""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.serving import ServingEngine
from paddle_tpu.models.decode_cache import StateLayersUnsupported
from paddle_tpu.models.nemotron_h import NemotronH, NemotronHConfig
from paddle_tpu.ops import moe, ssm
from paddle_tpu.ops.pallas import paged_attention as pa

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module", autouse=True)
def _shared_compile_cache():
    """As tests/test_serving.py: every engine here compiles the same
    tiny programs; share them through the persistent cache."""
    import tempfile
    from paddle_tpu.framework import flags as flags_mod
    cache = os.path.join(tempfile.gettempdir(), "pt_nemotron_ccache")
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
    return _load("reference", "nemotron_h")


_MODELS = {}


def _model(pattern: str = "MEM*E", held=()):
    key = (pattern, held)
    if key not in _MODELS:
        paddle.seed(11)
        m = NemotronH(NemotronHConfig.tiny(pattern, experts_held=held))
        m.eval()
        _MODELS[key] = m
    return _MODELS[key]


def _spec(m):
    c = m.cfg
    return {"heads": c.num_attention_heads, "kv_heads": c.num_key_value_heads,
            "head_dim": c.head_dim, "mamba_heads": c.mamba_num_heads,
            "mamba_groups": c.n_groups, "top_k": c.num_experts_per_tok,
            "routed_scale": c.routed_scaling_factor,
            "experts_first": c.experts_held[0], "eps": c.layer_norm_epsilon}


def _params(m):
    return {k: p.data for k, p in m.named_parameters()}


def _ids(n, seed=0, vocab=256):
    return np.random.default_rng(seed).integers(1, vocab, (n,)).tolist()


# ----------------------------- the recurrence ------------------------------


def _per_token(x, delta, A, Bm, Cm, D, state=None):
    """The recurrence one token at a time, float64."""
    x, delta, A, Bm, Cm, D = (np.asarray(t, np.float64)
                              for t in (x, delta, A, Bm, Cm, D))
    B, L, H, P = x.shape
    G, N = Bm.shape[-2:]
    S = (np.zeros((B, H, P, N)) if state is None
         else np.asarray(state, np.float64))
    y = np.zeros(x.shape)
    for t in range(L):
        Bh = np.repeat(Bm[:, t], H // G, axis=1)
        Ch = np.repeat(Cm[:, t], H // G, axis=1)
        S = (np.exp(delta[:, t] * A)[..., None, None] * S
             + (delta[:, t, :, None] * x[:, t])[..., None] * Bh[:, :, None])
        y[:, t] = (S * Ch[:, :, None]).sum(-1) + D[:, None] * x[:, t]
    return y, S


def _inputs(L, step, seed=0, B=2, H=8, P=4, G=2, N=16):
    rng = np.random.default_rng(seed)
    f = np.float32
    return (rng.normal(size=(B, L, H, P)).astype(f),
            (step * np.exp(rng.uniform(-2, 0, (B, L, H)))).astype(f),
            -rng.uniform(1, 16, (H,)).astype(f),
            rng.normal(size=(B, L, G, N)).astype(f),
            rng.normal(size=(B, L, G, N)).astype(f),
            rng.normal(size=(H,)).astype(f))


class TestMamba2:
    @pytest.mark.parametrize("step", [0.01, 1.0], ids=["weak", "strong"])
    @pytest.mark.parametrize("L", [1, 5, 16, 37, 70])
    def test_chunked_equals_per_token(self, L, step):
        """Across chunk boundaries (chunks of 16) and a ragged last
        chunk."""
        x, delta, A, Bm, Cm, D = _inputs(L, step)
        y, S = ssm.ssd_chunked(x, delta, A, Bm, Cm, D, chunk=16)
        want_y, want_S = _per_token(x, delta, A, Bm, Cm, D)
        np.testing.assert_allclose(y, want_y, rtol=0, atol=2e-4)
        np.testing.assert_allclose(S, want_S, rtol=0, atol=2e-4)

    @pytest.mark.parametrize("step", [0.01, 1.0], ids=["weak", "strong"])
    def test_a_carried_in_state_and_steps_continue_a_chunked_prefix(self,
                                                                    step):
        x, delta, A, Bm, Cm, D = _inputs(50, step, seed=1)
        want_y, want_S = _per_token(x, delta, A, Bm, Cm, D)
        cut = lambda lo, hi: (x[:, lo:hi], delta[:, lo:hi], A,  # noqa: E731
                              Bm[:, lo:hi], Cm[:, lo:hi], D)
        y1, S = ssm.ssd_chunked(*cut(0, 20), chunk=16)
        y2, S = ssm.ssd_chunked(*cut(20, 41), initial_state=S, chunk=16)
        ys = [y1, y2]
        for t in range(41, 50):
            y, S = ssm.ssd_step(S, x[:, t], delta[:, t], A, Bm[:, t],
                                Cm[:, t], D)
            ys.append(y[:, None])
        np.testing.assert_allclose(np.concatenate(ys, 1), want_y, rtol=0,
                                   atol=2e-4)
        np.testing.assert_allclose(S, want_S, rtol=0, atol=2e-4)

    def test_positions_past_length_leave_the_state_alone(self):
        x, delta, A, Bm, Cm, D = _inputs(40, 0.5, seed=2)
        _, S = ssm.ssd_chunked(x, delta, A, Bm, Cm, D,
                               length=jnp.array([13, 40]), chunk=16)
        _, S13 = ssm.ssd_chunked(x[:1, :13], delta[:1, :13], A, Bm[:1, :13],
                                 Cm[:1, :13], D, chunk=16)
        np.testing.assert_allclose(S[0], S13[0], rtol=0, atol=1e-5)

    def test_an_inactive_row_keeps_its_state(self):
        x, delta, A, Bm, Cm, D = _inputs(1, 0.5, seed=3)
        S0 = np.random.default_rng(4).normal(size=(2, 8, 4, 16)) \
            .astype(np.float32)
        _, S = ssm.ssd_step(jnp.asarray(S0), x[:, 0], delta[:, 0], A,
                            Bm[:, 0], Cm[:, 0], D,
                            active=jnp.array([True, False]))
        assert np.array_equal(S[1], S0[1]) and not np.array_equal(S[0], S0[0])

    def test_the_gate_comes_before_the_group_norm(self):
        rng = np.random.default_rng(5)
        y, z = rng.normal(size=(2, 3, 32)), rng.normal(size=(2, 3, 32))
        w = rng.normal(size=(32,))
        v = (y * z / (1 + np.exp(-z))).reshape(2, 3, 4, 8)
        want = (v / np.sqrt((v * v).mean(-1, keepdims=True) + 1e-5)) \
            .reshape(2, 3, 32) * w
        got = ssm.gated_group_rms_norm(
            jnp.asarray(y, jnp.float32), jnp.asarray(z, jnp.float32),
            jnp.asarray(w, jnp.float32), groups=4, epsilon=1e-5)
        np.testing.assert_allclose(got, want, rtol=0, atol=1e-5)


# ------------------------------ the expert layer -----------------------------


def _expert_inputs(T=20, h=64, f=32, E=8, k=2, seed=0):
    rng = np.random.default_rng(seed)
    f32 = np.float32
    u = rng.normal(size=(T, h)).astype(f32)
    router = (0.2 * rng.normal(size=(h, E))).astype(f32)
    bias = rng.uniform(-0.05, 0.05, (E,)).astype(f32)
    w1 = (0.1 * rng.normal(size=(E, f, h))).astype(f32)
    w2 = (0.1 * rng.normal(size=(E, f, h))).astype(f32)
    experts, weights, _ = moe.sigmoid_route(u, router, bias, top_k=k,
                                            scale=2.5)
    return u, router, bias, w1, w2, np.asarray(experts), np.asarray(weights)


def _dense_loop(u, experts, weights, w1, w2, first, held, active=None):
    """Every token against every one of its experts that is held, one
    at a time, float64."""
    y = np.zeros(u.shape)
    for t in range(u.shape[0]):
        if active is not None and not active[t]:
            continue
        for j in range(experts.shape[1]):
            e = int(experts[t, j])
            if first <= e < first + held:
                mid = np.maximum(u[t].astype(np.float64) @ w1[e].T, 0) ** 2
                y[t] += float(weights[t, j]) * (mid @ w2[e])
    return y


class TestDroplessExperts:
    def test_the_router_normalises_over_all_chosen_and_the_bias_only_selects(
            self):
        u, router, bias, *_ = _expert_inputs()
        bias = bias.copy()
        bias[3] = 10.0                       # always chosen, by the bias alone
        experts, weights, margin = moe.sigmoid_route(u, router, bias,
                                                     top_k=2, scale=2.5)
        s = 1 / (1 + np.exp(-(u.astype(np.float64) @ router)))
        assert (np.asarray(experts) == 3).any(axis=1).all()
        chosen = np.take_along_axis(s, np.asarray(experts), axis=1)
        np.testing.assert_allclose(
            weights, 2.5 * chosen / chosen.sum(1, keepdims=True), rtol=1e-5)
        ranked = np.sort(s + bias, axis=1)
        np.testing.assert_allclose(margin, ranked[:, -2] - ranked[:, -3],
                                   rtol=0, atol=1e-6)

    @pytest.mark.parametrize("interpret", [False, True],
                             ids=["ragged_dot", "megablox"])
    @pytest.mark.parametrize("case", ["routed", "all_to_one", "lanes_off"])
    def test_equals_a_dense_loop_over_the_experts(self, case, interpret,
                                                  monkeypatch):
        """Seeded routing; forced imbalance (every token to expert 1, so
        expert 0 has none and nothing is dropped); padding lanes."""
        monkeypatch.setattr(moe, "_INTERPRET", interpret)
        u, _, _, w1, w2, experts, weights = _expert_inputs()
        active = None
        if case == "all_to_one":
            experts = np.stack([np.ones(20, np.int32),
                                np.full(20, 5, np.int32)], 1)
        elif case == "lanes_off":
            active = np.arange(20) % 3 != 0
        path = "gmm" if interpret else "ragged_dot"
        before = moe._stats[path]
        y, counters = moe.held_experts(
            u, jnp.asarray(experts), jnp.asarray(weights), w1[:4], w2[:4],
            first=0, active=None if active is None else jnp.asarray(active))
        assert moe._stats[path] == before + 1
        np.testing.assert_allclose(
            y, _dense_loop(u, experts, weights, w1, w2, 0, 4, active),
            rtol=0, atol=1e-5)
        here = (experts < 4) & (True if active is None else active[:, None])
        sizes = np.bincount(experts[here], minlength=4)[:4]
        assert counters.tolist() == [here.sum(), (sizes > 0).sum(),
                                     sizes.max()]
        if case == "all_to_one":
            assert counters.tolist() == [20, 1, 20]

    def test_the_four_shares_add_up_to_the_uncut_layer(self, reference):
        """The share test: experts 0-1, 2-3, 4-5, 6-7 of 8, each share
        computed by a block that is told what it holds, plus the shared
        expert counted once, against the reference's whole layer."""
        paddle.seed(3)
        blocks = {first: NemotronH(NemotronHConfig.tiny(
            "E", experts_held=(first, 2))).blocks[0].mixer
            for first in (0, 2, 4, 6)}
        paddle.seed(3)
        whole = NemotronH(NemotronHConfig.tiny("E")).blocks[0].mixer
        u = np.random.default_rng(1).normal(size=(1, 30, 64)) \
            .astype(np.float32)
        p = {"b." + k: v.data for k, v in whole.named_parameters()}
        spec = {"top_k": 2, "routed_scale": 2.5, "experts_first": 0}
        with jax.default_matmul_precision("highest"):
            want, _ = reference._experts(p, "b.", jnp.asarray(u), spec)
            shared = reference._relu2(u @ p["b.shared_up.weight"]) \
                @ p["b.shared_down.weight"]
        total = np.asarray(shared)
        for first, blk in blocks.items():
            # one router and one shared expert for all four chips; each
            # holds its own two routed experts of the whole layer's eight
            for name in ("router", "e_score_correction_bias"):
                getattr(blk, name).data = getattr(whole, name).data
            blk.shared_up.weight.data = whole.shared_up.weight.data
            blk.shared_down.weight.data = whole.shared_down.weight.data
            blk.w1.data = whole.w1.data[first:first + 2]
            blk.w2.data = whole.w2.data[first:first + 2]
            with paddle.no_grad():
                out, _, _ = blk(paddle.to_tensor(u))
            total = total + (np.asarray(out.data) - np.asarray(shared))
        np.testing.assert_allclose(total, want, rtol=0, atol=1e-4)


# ------------------------------ grouped K/V heads -----------------------------


class TestGroupedKVHeads:
    @pytest.mark.parametrize("path", ["xla", "grouped"])
    @pytest.mark.parametrize("heads,kv_heads,D", [(4, 2, 16), (32, 2, 128),
                                                  (6, 1, 64)])
    def test_paged_attention_reads_each_kv_head_once(self, heads, kv_heads,
                                                     D, path, monkeypatch):
        """Pools of `kv_heads` folded heads, through the XLA gather and
        through the grouped kernel (in the Pallas interpreter), against
        `paged_attention_xla` over pools with K/V repeated for every
        query head. One sequence is idle and one ends inside a page;
        the longest ends in the kernel's second group of 8 pages, which
        runs past the 12 of a row of the block table."""
        monkeypatch.setattr(pa, "_INTERPRET", path == "grouped")
        rng = np.random.default_rng(heads)
        B, pages, page = 3, 37, 8
        q = rng.normal(size=(B, heads, D)).astype(np.float32)
        k = rng.normal(size=(pages, page, kv_heads, D)).astype(np.float32)
        v = rng.normal(size=(pages, page, kv_heads, D)).astype(np.float32)
        bt = jnp.asarray(rng.permutation(np.arange(1, 37))
                         .reshape(3, 12).astype(np.int32))
        ctx = jnp.array([13, 0, 85], jnp.int32)
        before = pa._stats[path]
        got = pa.paged_attention(q, k.reshape(pages, page, -1),
                                 v.reshape(pages, page, -1), bt, ctx)
        assert pa._stats[path] == before + 1
        rep = heads // kv_heads
        want = pa.paged_attention_xla(
            q, np.repeat(k, rep, axis=2).reshape(pages, page, -1),
            np.repeat(v, rep, axis=2).reshape(pages, page, -1), bt, ctx)
        np.testing.assert_allclose(got, want, rtol=0, atol=1e-5)
        assert float(np.abs(got[1]).max()) == 0.0       # the idle slot

    def test_the_scatters_write_kv_heads_wide_rows(self):
        rng = np.random.default_rng(0)
        pool = jnp.zeros((5, 8, 32), jnp.float32)         # 2 heads of 16
        bt = jnp.array([[1, 2], [3, 4]], jnp.int32)
        seq = rng.normal(size=(11, 32)).astype(np.float32)
        k, v = pa.prefill_append(pool, pool, seq, 2 * seq, bt[1], 11)
        np.testing.assert_array_equal(
            np.asarray(k)[[3, 4]].reshape(16, 32)[:11], seq)
        new = rng.normal(size=(2, 32)).astype(np.float32)
        k, v = pa.cache_append(k, v, new, new, bt,
                               jnp.array([0, 11], jnp.int32))
        np.testing.assert_array_equal(np.asarray(k)[4, 3], new[1])
        np.testing.assert_array_equal(np.asarray(v)[1, 0], new[0])


# --------------------------------- the model ---------------------------------


def _contiguous_cache(m, slots, max_len, page_size=8):
    cache = m.init_cache(slots, max_len, page_size=page_size)
    pps = cache.pages_per_seq
    cache.block_tables = jnp.asarray(
        1 + np.arange(slots * pps, dtype=np.int32).reshape(slots, pps))
    return cache


@pytest.mark.parametrize("pattern,held", [
    ("MEM*E", ()), ("MEMEM*EMEMEM*", (0, 2))], ids=["tiny", "stage_share"])
class TestCachePath:
    def test_prefill_then_decode_gives_the_references_logits(
            self, pattern, held, reference):
        """`forward_prefill` (padded to its bucket) then N
        `forward_decode` steps through the cache, in lane mode with a
        padding lane, against the reference's full forward at every
        generated position: logits, not tokens."""
        m = _model(pattern, held)
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
        np.testing.assert_allclose(np.stack(got), np.asarray(want)[26:39],
                                   rtol=0, atol=2e-3)
        assert int(cache.context_lens[2]) == 39
        # the padding lane's clamped slot (3) kept what it held
        assert float(jnp.abs(cache.states[0][3] - 3.0).max()) == 0.0
        # 12 decode steps, one active lane: each expert block counted its
        # top-2 assignments that fell on experts held here
        n_e = pattern.count("E")
        counted = np.asarray(cache.counters["moe"])
        if not held:
            assert counted[0] == 12 * n_e * 2
        assert 0 < counted[1] <= counted[0] <= 12 * n_e * 2

    def test_forward_equals_the_reference(self, pattern, held, reference):
        m = _model(pattern, held)
        ids = np.asarray([_ids(33, seed=5)], np.int32)
        with paddle.no_grad():
            got = np.asarray(m(paddle.to_tensor(ids)).data)[0]
        want, _, _ = reference.logits_at(_params(m), ids, np.arange(33),
                                         _spec(m))
        np.testing.assert_allclose(got, want, rtol=0, atol=2e-3)


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
        reused (a new request's prefill must overwrite the old state),
        and with 3 active the 4-lane program runs with a padding lane."""
        m = _model()
        eng = ServingEngine(m, max_batch=3, max_len=96, page_size=8,
                            name="nemo")
        rng = np.random.default_rng(1)
        prompts = [_ids(int(n), seed=i) for i, n in
                   enumerate(rng.integers(3, 50, (7,)))]
        reqs = [eng.submit(p, max_new_tokens=int(n))
                for p, n in zip(prompts, rng.integers(2, 7, (7,)))]
        eng.run_until_idle()
        _check(m, prompts, reqs)
        assert eng.stats["prefills"] == 7
        counted = eng.device_counters()["moe"]
        # every decoded token met its top-2 in each of the two expert
        # blocks (all 8 experts are held), and nothing else was counted
        assert counted[0] == eng.stats["decode_tokens"] * 2 * 2
        eng.close()

    def test_a_preempted_request_resumes_with_the_same_tokens(self):
        """A pool too small for both sequences: the youngest is
        preempted and prefilled again with what it had generated, which
        rebuilds its state-space state."""
        m = _model()
        eng = ServingEngine(m, max_batch=2, max_len=40, page_size=8,
                            num_pages=6, name="nemo_pre")
        prompts = [_ids(14, seed=31), _ids(14, seed=32)]
        reqs = [eng.submit(p, max_new_tokens=12) for p in prompts]
        eng.run_until_idle()
        assert eng.stats["preemptions"] >= 1
        _check(m, prompts, reqs)
        eng.close()

    def test_the_cache_describes_three_kinds(self):
        m = _model()
        eng = ServingEngine(m, max_batch=2, max_len=32, page_size=8,
                            name="nemo_desc")
        d = eng.cache.describe()
        assert d["layer_kinds"] == ["state", "none", "state", "kv", "none"]
        assert (d["kv_layers"], d["state_layers"], d["cacheless_layers"]) \
            == (1, 2, 2)
        assert (d["num_heads"], d["num_kv_heads"]) == (4, 2)
        assert d["state_shape"] == [8, 8, 16]
        assert d["conv_state_shape"] == [3, 8 * 8 + 2 * 2 * 16]
        # K and V of 2 heads of 16, never repeated for the 4 query heads
        assert eng.cache.k_pages[0].shape == (9, 8, 32)
        assert eng.pool_bytes() == eng.cache.pool_bytes() \
            + eng.cache.state_bytes()
        eng.close()

    def test_tensor_parallel_decode_refuses_by_name(self):
        from jax.sharding import Mesh
        mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
        with pytest.raises(StateLayersUnsupported, match="tensor-parallel"):
            ServingEngine(_model(), max_batch=2, max_len=32, page_size=8,
                          mesh=mesh)

    def test_disaggregated_prefill_refuses_by_name(self):
        from paddle_tpu.inference.disagg import DisaggPipeline
        eng = ServingEngine(_model(), max_batch=2, max_len=32, page_size=8,
                            name="nemo_disagg")
        with pytest.raises(StateLayersUnsupported,
                           match=r"DisaggPipeline.*2 state, 1 paged K/V"):
            DisaggPipeline(eng)
        eng.close()


# ----------------------------------- scopes -----------------------------------


class TestScopes:
    @pytest.fixture(scope="class")
    def lowered(self):
        m = _model()
        eng = ServingEngine(m, max_batch=2, max_len=32, page_size=8,
                            name="nemo_scopes")
        before = {k: dict(mod._stats) for k, mod in
                  (("ssm", ssm), ("moe", moe))}
        lanes = eng._lane_arrays([])[1:]
        decode = jax.jit(eng._fused_step_fn).lower(
            eng._params, eng._buffers, eng.cache, eng._last_tokens,
            *lanes).as_text(
                debug_info=True)
        prefill = jax.jit(eng._prefill_fn).lower(
            eng._params, eng._buffers, eng.cache,
            np.zeros((1, 16), np.int32),
            np.array([0, 5, 0, 0, 0, 0], np.int32),
            np.array([0.0, 1.0], np.float32)).as_text(debug_info=True)
        traced = {k: {n: mod._stats[n] - before[k][n] for n in before[k]}
                  for k, mod in (("ssm", ssm), ("moe", moe))}
        eng.close()
        return {"decode": decode, "prefill": prefill, "traced": traced}

    @pytest.mark.parametrize("program", ["decode", "prefill"])
    @pytest.mark.parametrize("scope", [
        "attention/ssm/conv", "attention/ssm/scan", "mlp/moe/route",
        "mlp/moe/experts", "mlp/moe/shared"])
    def test_scope_is_in_the_lowered_program(self, lowered, program, scope):
        assert scope + "/" in lowered[program]

    def test_the_form_each_program_traced(self, lowered):
        # two Mamba-2 and two expert blocks in each of the two programs
        assert lowered["traced"]["ssm"] == {"chunked": 2, "step": 2}
        assert lowered["traced"]["moe"]["route"] == 4
        assert lowered["traced"]["moe"]["ragged_dot"] == 4
