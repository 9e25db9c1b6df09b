"""Olmo-Hybrid (models/olmo_hybrid.py): the gated delta rule's chunked
scan and one-token step (ops/linear_attention.py) against the per-token
recurrence, the three things a recurrence needs that attention forgave
(bucket padding, padding lanes, reused slots), the cache of two kinds
through `ServingEngine`, and the plain reference the benchmark compares
with, loaded from its one file under `benchmark/reference/`.

Everything at `OlmoHybridConfig.tiny()` (one period, 4 layers) or two
periods (8 layers, the benchmark's cut), seeded weights, on the CPU.
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
from paddle_tpu.models.gpt import GPT, GPTConfig
from paddle_tpu.models.olmo_hybrid import OlmoHybrid, OlmoHybridConfig
from paddle_tpu.ops import linear_attention as la

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module", autouse=True)
def _shared_compile_cache():
    """As tests/test_serving.py: every engine here compiles the same
    tiny programs; share them through the persistent cache."""
    import tempfile
    from paddle_tpu.framework import flags as flags_mod
    cache = os.path.join(tempfile.gettempdir(), "pt_olmo_ccache")
    os.makedirs(cache, exist_ok=True)
    flags_mod.set_flags({"FLAGS_compile_cache_dir": cache})
    yield
    flags_mod.set_flags({"FLAGS_compile_cache_dir": ""})


@pytest.fixture(scope="module")
def reference():
    """The benchmark's plain reference, by its path (as
    `benchmark/harness.load_module` loads it): no second copy to drift."""
    path = os.path.join(ROOT, "benchmark", "reference", "olmo_hybrid.py")
    spec = importlib.util.spec_from_file_location("reference_olmo_hybrid",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_MODELS = {}


def _model(periods: int = 1):
    if periods not in _MODELS:
        paddle.seed(7 + periods)
        m = OlmoHybrid(OlmoHybridConfig.tiny(periods))
        m.eval()
        _MODELS[periods] = m
    return _MODELS[periods]


def _ids(n, seed=0, vocab=256):
    return np.random.default_rng(seed).integers(1, vocab, (n,)).tolist()


# ----------------------------- the recurrence ------------------------------


def _per_token(q, k, v, g, beta, state=None):
    """The recurrence one token at a time, float64."""
    q, k = (np.asarray(x, np.float64) for x in la._qk(q, k))
    v, g, beta = (np.asarray(x, np.float64) for x in (v, g, beta))
    B, L, H, dk = q.shape
    S = (np.zeros((B, H, dk, v.shape[-1])) if state is None
         else np.asarray(state, np.float64))
    o = np.zeros(v.shape)
    for t in range(L):
        S = np.exp(g[:, t])[..., None, None] * S
        u = beta[:, t][..., None] * (
            v[:, t] - np.einsum("bhk,bhkv->bhv", k[:, t], S))
        S = S + k[:, t][..., :, None] * u[..., None, :]
        o[:, t] = np.einsum("bhk,bhkv->bhv", q[:, t], S)
    return o, S


def _inputs(L, decay, seed=0, B=2, H=3, dk=8, dv=16):
    rng = np.random.default_rng(seed)
    f = np.float32
    q = rng.normal(size=(B, L, H, dk)).astype(f)
    k = rng.normal(size=(B, L, H, dk)).astype(f)
    k[:, ::3] = k[:, :1]            # repeated keys: the solve's hard case
    v = rng.normal(size=(B, L, H, dv)).astype(f)
    g = (-decay * rng.uniform(size=(B, L, H))).astype(f)
    beta = (2.0 * rng.uniform(size=(B, L, H))).astype(f)
    beta[:, ::2] = 1.999            # negative eigenvalues allowed
    return q, k, v, g, beta


class TestGatedDeltaRule:
    # lengths that are no multiple of the chunk (16), below and above it
    @pytest.mark.parametrize("decay", [0.01, 20.0], ids=["weak", "strong"])
    @pytest.mark.parametrize("L", [1, 5, 16, 37, 70])
    def test_chunked_equals_per_token(self, L, decay):
        q, k, v, g, beta = _inputs(L, decay)
        o, S = la.gated_delta_rule_chunked(q, k, v, g, beta, chunk=16)
        want_o, want_S = _per_token(q, k, v, g, beta)
        # float32 against float64: rounding alone
        np.testing.assert_allclose(o, want_o, rtol=0, atol=2e-5)
        np.testing.assert_allclose(S, want_S, rtol=0, atol=2e-5)

    @pytest.mark.parametrize("decay", [0.01, 20.0], ids=["weak", "strong"])
    def test_steps_continue_a_chunked_prefix(self, decay):
        """One-token steps after a chunked prefix give what a longer
        chunked run gives."""
        q, k, v, g, beta = _inputs(45, decay, seed=1)
        want_o, want_S = la.gated_delta_rule_chunked(q, k, v, g, beta,
                                                     chunk=16)
        cut = 38
        _, S = la.gated_delta_rule_chunked(
            q[:, :cut], k[:, :cut], v[:, :cut], g[:, :cut], beta[:, :cut],
            chunk=16)
        for t in range(cut, 45):
            o, S = la.gated_delta_rule_step(S, q[:, t], k[:, t], v[:, t],
                                            g[:, t], beta[:, t])
            np.testing.assert_allclose(o, want_o[:, t], rtol=0, atol=2e-5)
        np.testing.assert_allclose(S, want_S, rtol=0, atol=2e-5)

    def test_positions_past_length_leave_the_state_alone(self):
        q, k, v, g, beta = _inputs(40, 1.0, seed=2)
        length = np.array([23, 40], np.int32)
        _, S = la.gated_delta_rule_chunked(q, k, v, g, beta, length=length,
                                           chunk=16)
        for row, n in enumerate(length):
            sl = slice(row, row + 1)
            _, want = la.gated_delta_rule_chunked(
                q[sl, :n], k[sl, :n], v[sl, :n], g[sl, :n], beta[sl, :n],
                chunk=16)
            np.testing.assert_allclose(S[sl], want, rtol=0, atol=1e-6)

    def test_an_inactive_row_keeps_its_state(self):
        q, k, v, g, beta = _inputs(1, 1.0, seed=3)
        S = np.random.default_rng(0).normal(size=(2, 3, 8, 16)).astype(
            np.float32)
        _, out = la.gated_delta_rule_step(
            S, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
            active=jnp.array([True, False]))
        assert not np.allclose(out[0], S[0])
        np.testing.assert_array_equal(out[1], S[1])

    def test_conv_update_continues_conv_prefill(self):
        rng = np.random.default_rng(4)
        x = rng.normal(size=(2, 11, 6)).astype(np.float32)
        w = rng.normal(size=(4, 6)).astype(np.float32)
        y, tail = la.causal_conv_prefill(x, w, length=jnp.array([7, 2]))
        y7, tail7 = la.causal_conv_prefill(x[:, :7], w)
        np.testing.assert_allclose(y[:, :7], y7, rtol=0, atol=1e-6)
        # the state is the inputs at length-3 .. length-1, zeros before 0
        np.testing.assert_array_equal(tail[0], x[0, 4:7])
        np.testing.assert_array_equal(tail[1, 0], np.zeros(6, np.float32))
        np.testing.assert_array_equal(tail[1, 1:], x[1, :2])
        step, moved = la.causal_conv_update(tail7, x[:, 7], w)
        np.testing.assert_allclose(step[0], y[0, 7], rtol=0, atol=1e-6)
        np.testing.assert_array_equal(moved[0], x[0, 5:8])


# --------------------------- the model and its cache ------------------------


def _contiguous_cache(m, slots, max_len, page_size=8):
    cache = m.init_cache(slots, max_len, page_size=page_size)
    pps = cache.pages_per_seq
    cache.block_tables = jnp.asarray(
        1 + np.arange(slots * pps, dtype=np.int32).reshape(slots, pps))
    return cache


@pytest.mark.parametrize("periods", [1, 2], ids=["tiny", "two_periods"])
class TestCachePath:
    def test_padded_prompt_gives_the_unpadded_state_and_logits(self, periods):
        """A prompt padded to its bucket leaves, in a slot that held
        another request's state, the state, convolution state and
        last-position logits of the unpadded prompt."""
        m = _model(periods)
        prompt = _ids(21, seed=periods)
        with paddle.no_grad():
            exact = _contiguous_cache(m, 2, 64)
            want, exact = m.forward_prefill(
                paddle.to_tensor(np.asarray([prompt], np.int32)), exact, 1,
                21)
            padded = _contiguous_cache(m, 2, 64)
            padded.states = [s + 3.0 for s in padded.states]
            padded.conv_states = [s - 2.0 for s in padded.conv_states]
            ids = np.asarray([prompt + _ids(11, seed=9)], np.int32)  # junk
            got, padded = m.forward_prefill(paddle.to_tensor(ids), padded, 1,
                                            21)
        np.testing.assert_allclose(got.data, want.data, rtol=0, atol=1e-5)
        for a, b in zip(padded.states + padded.conv_states,
                        exact.states + exact.conv_states):
            np.testing.assert_allclose(a[1], b[1], rtol=0, atol=1e-5)
        # the other slot's rows were not touched
        assert float(padded.states[0][0].min()) == 3.0
        assert float(padded.conv_states[0][0].max()) == -2.0

    def test_prefill_then_decode_gives_the_references_logits(self, periods,
                                                             reference):
        """`forward_prefill` then N `forward_decode` steps through the
        cache, in lane mode with a padding lane, against the reference's
        full forward at every generated position: logits, not tokens."""
        m = _model(periods)
        ids = np.asarray([_ids(40, seed=3 + periods)], np.int32)
        params = {k: p.data for k, p in m.named_parameters()}
        want = np.asarray(reference.logits_at(
            params, ids, np.arange(40), m.cfg.num_attention_heads))
        prompt = 27
        bucket = np.zeros((1, 32), np.int32)
        bucket[0, :prompt] = ids[0, :prompt]
        with paddle.no_grad():
            cache = _contiguous_cache(m, 4, 64)
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
        # float32 on both sides; what differs is the order of summation
        # (chunked against per-token, kernels against matrix products),
        # which 8 post-normed layers of random weights amplify to 1e-4
        # of logits of size ~2
        np.testing.assert_allclose(np.stack(got), want[prompt - 1:39],
                                   rtol=0, atol=2e-3)
        assert int(cache.context_lens[2]) == 39
        # the padding lane's clamped slot (3) kept its zero state
        assert float(jnp.abs(cache.states[0][3]).max()) == 0.0

    def test_forward_equals_the_reference(self, periods, reference):
        m = _model(periods)
        ids = np.asarray([_ids(33, seed=5)], np.int32)
        with paddle.no_grad():
            got = np.asarray(m(paddle.to_tensor(ids)).data)[0]
        params = {k: p.data for k, p in m.named_parameters()}
        want = np.asarray(reference.logits_at(
            params, ids, np.arange(33), m.cfg.num_attention_heads))
        np.testing.assert_allclose(got, want, rtol=0, atol=2e-3)


# ------------------------------ through the engine ---------------------------


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
    def test_generate_dense_is_the_greedy_forward(self):
        m = _model()
        prompt = _ids(9, seed=11)
        with paddle.no_grad():
            out = m.generate_dense(
                paddle.to_tensor(np.asarray([prompt], np.int32)), 3)
        assert np.asarray(out.data)[0, 9:].tolist() == _greedy(m, prompt, 3)

    @pytest.mark.parametrize("periods", [1, 2], ids=["tiny", "two_periods"])
    def test_more_requests_than_slots_and_padding_lanes(self, periods):
        """Continuous batching with 7 requests over 3 slots: slots are
        reused (a new request's prefill must overwrite the old state),
        and with 3 active the 4-lane program runs with a padding lane."""
        m = _model(periods)
        eng = ServingEngine(m, max_batch=3, max_len=96, page_size=8,
                            name=f"olmo{periods}")
        assert eng.decode_buckets == [1, 2, 3]
        rng = np.random.default_rng(periods)
        prompts = [_ids(int(n), seed=i) for i, n in
                   enumerate(rng.integers(3, 50, (7,)))]
        reqs = [eng.submit(p, max_new_tokens=int(n))
                for p, n in zip(prompts, rng.integers(2, 7, (7,)))]
        eng.run_until_idle()
        _check(m, prompts, reqs)
        assert eng.stats["prefills"] == 7
        eng.close()

    def test_lane_buckets_narrower_than_max_batch(self):
        """max_batch 8 with two requests: the 2-lane program, whose
        gather and scatter name slots 0 and 1 among 8."""
        m = _model()
        eng = ServingEngine(m, max_batch=8, max_len=64, page_size=8,
                            name="olmo_lanes")
        prompts = [_ids(12, seed=21), _ids(5, seed=22), _ids(7, seed=23)]
        reqs = [eng.submit(p, max_new_tokens=5) for p in prompts]
        eng.run_until_idle()
        _check(m, prompts, reqs)
        eng.close()

    def test_forced_preemption_rebuilds_the_state(self):
        """A pool too small for both sequences: the youngest is
        preempted and prefilled again with what it had generated, which
        rebuilds its recurrent state."""
        m = _model()
        eng = ServingEngine(m, max_batch=2, max_len=40, page_size=8,
                            num_pages=6, name="olmo_pre")
        prompts = [_ids(14, seed=31), _ids(14, seed=32)]
        reqs = [eng.submit(p, max_new_tokens=12) for p in prompts]
        eng.run_until_idle()
        assert eng.stats["preemptions"] >= 1
        _check(m, prompts, reqs)
        eng.close()

    def test_a_shared_prefix_is_admitted_and_computed_whole(self):
        """The second request forks the first's two full pages; its
        prefill still runs the whole prompt, so its state is its own."""
        m = _model()
        eng = ServingEngine(m, max_batch=2, max_len=64, page_size=8,
                            name="olmo_share")
        head = _ids(16, seed=41)
        prompts = [head + _ids(5, seed=42), head + _ids(9, seed=43)]
        first = eng.submit(prompts[0], max_new_tokens=8)
        eng.step()
        second = eng.submit(prompts[1], max_new_tokens=8)
        eng.run_until_idle()
        assert eng.stats["shared_admissions"] == 1
        assert eng.stats["prefix_hit_tokens"] == 16
        _check(m, prompts, [first, second])
        eng.close()


class TestCacheOfTwoKinds:
    def test_bytes_status_and_audit_report_both_kinds(self):
        m = _model(2)
        eng = ServingEngine(m, max_batch=4, max_len=64, page_size=8,
                            num_pages=20, name="olmo_kinds")
        cache = eng.cache
        assert cache.layer_kinds == ("state",) * 3 + ("kv",) + \
            ("state",) * 3 + ("kv",)
        assert len(cache.k_pages) == len(cache.v_pages) == 2
        assert len(cache.states) == len(cache.conv_states) == 6
        pages = 2 * 2 * 20 * 8 * 64 * 4           # layers, K+V, pool
        per_slot = 6 * (2 * 8 * 16 + 3 * 64) * 4  # state + conv tail
        assert eng.pool_bytes() == pages + 4 * per_slot
        assert {k: eng.stats[k] for k in (
            "kv_layers", "state_layers", "state_bytes_per_slot")} == {
            "kv_layers": 2, "state_layers": 6,
            "state_bytes_per_slot": per_slot}
        for view in (eng.status()["cache"], eng.requests_snapshot()["cache"]):
            assert view["pages"]["layers"] == 2
            assert view["pages"]["total"] == view["pages"]["free"] == 19
            assert view["pages"]["bytes"] == pages
            assert view["state"] == {
                "layers": 6, "shape": [2, 8, 16], "conv_shape": [3, 64],
                "bytes_per_slot": per_slot, "slots": 4, "slots_in_use": 0,
                "bytes": 4 * per_slot}
        decode, prefill = eng.audit(emit=False)
        # counted over a pool's AND a state's shape; the numbers are the
        # CPU compiler's, the chip's are PERF.md's
        assert decode.pool_relayout_copies is not None
        assert prefill.pool_relayout_copies is not None
        # shrink_pool and the budget act on pages, the states are fixed
        assert eng.shrink_pool(0.5) == 9
        assert eng.status()["cache"]["pages"]["parked"] == 9
        eng.close()
        capped = ServingEngine(m, max_batch=4, max_len=64, page_size=8,
                               num_pages=20, name="olmo_budget",
                               mem_budget_bytes=4 * per_slot + pages // 2)
        assert capped.cache.num_pages == 10
        assert capped.pool_bytes() <= 4 * per_slot + pages // 2
        capped.close()

    def test_a_model_of_paged_layers_keeps_its_layout(self):
        """GPT runs through the same cache and engine with no state
        layer: one pool pair per layer."""
        paddle.seed(0)
        m = GPT(GPTConfig.tiny())
        m.eval()
        eng = ServingEngine(m, max_batch=2, max_len=32, page_size=8,
                            name="gpt_kinds")
        assert eng.cache.layer_kinds == ("kv", "kv")
        assert not eng.cache.has_state and eng.cache.state_bytes() == 0
        assert eng.status()["cache"]["state"]["layers"] == 0
        assert eng.stats["state_bytes_per_slot"] == 0
        eng.close()

    def test_tensor_parallel_decode_refuses_state_layers(self):
        from jax.sharding import Mesh
        mesh = Mesh(np.asarray(jax.devices()[:2]), ("tp",))
        with pytest.raises(StateLayersUnsupported) as e:
            ServingEngine(_model(), max_batch=2, max_len=32, page_size=8,
                          mesh=mesh)
        assert str(e.value) == (
            "tensor-parallel decode (ServingEngine(mesh=...)) cannot serve "
            "a model with recurrent-state layers (3 state, 1 paged K/V): "
            "missing protocol: sharding a per-slot recurrent state and its "
            "update over the TP axis (set_tp_mesh covers K/V pools only)")

    def test_disaggregated_prefill_refuses_state_layers(self):
        from paddle_tpu.inference.disagg import DisaggPipeline
        eng = ServingEngine(_model(), max_batch=2, max_len=32, page_size=8,
                            name="olmo_disagg")
        with pytest.raises(StateLayersUnsupported) as e:
            DisaggPipeline(eng)
        assert str(e.value) == (
            "disaggregated prefill/decode (DisaggPipeline) cannot serve a "
            "model with recurrent-state layers (3 state, 1 paged K/V): "
            "missing protocol: a hand-off of the slot's recurrent and "
            "convolution state beside its K/V pages (KVHandoff carries "
            "pages only)")
        assert eng.handoff_source is None     # nothing was armed
        eng.close()


# ------------------------------- scopes ---------------------------------------


class TestScopes:
    """The benchmark's per-layer metrics find the linear layers' device
    operations by these paths (PERF.md section 3)."""

    @pytest.fixture(scope="class")
    def lowered(self):
        m = _model()
        eng = ServingEngine(m, max_batch=2, max_len=32, page_size=8,
                            name="olmo_scopes")
        # the packed arguments of both programs (serving.py: int32 [6, W]
        # and float32 [2, W]; ids, int32 [6], float32 [2])
        W = 2
        lanes_i = np.zeros((6, W), np.int32)
        lanes_i[2] = 1                                  # both lanes active
        lanes_f = np.zeros((2, W), np.float32)
        lanes_f[1] = 1.0                                # top-p
        decode = jax.jit(eng._fused_step_fn).lower(
            eng._params, eng._buffers, eng.cache, eng._last_tokens, lanes_i,
            lanes_f)
        prefill = jax.jit(eng._prefill_fn).lower(
            eng._params, eng._buffers, eng.cache, eng._last_tokens,
            np.zeros((1, 16), np.int32),
            np.array([0, 5, 0, 0, 0, 0], np.int32),     # slot 0, length 5
            np.array([0.0, 1.0], np.float32))
        eng.close()
        return {"decode": decode.as_text(debug_info=True),
                "prefill": prefill.as_text(debug_info=True)}

    @pytest.mark.parametrize("program", ["decode", "prefill"])
    @pytest.mark.parametrize("scope", [
        "attention/linear/delta_rule", "attention/linear/conv",
        "attention/linear/", "mlp", "ln", "embed", "logits"])
    def test_scope_is_in_the_lowered_program(self, lowered, program, scope):
        assert scope in lowered[program], (
            f"no operation of the {program} program carries {scope!r}")

    def test_the_form_each_program_traced(self, lowered):
        assert "triangular_solve" in lowered["prefill"]
        assert "triangular_solve" not in lowered["decode"]

    @pytest.mark.parametrize("program", ["decode", "prefill"])
    def test_products_run_in_three_passes_the_recurrence_at_highest(
            self, lowered, program):
        """Single-pass products put the logits 0.12 from the float32
        reference at the published widths (PERF.md, PR 27): every matrix
        product of the model asks for HIGH, the recurrence's for HIGHEST
        (the lowered text holds each inner `jit` once, so the counts are
        of distinct products, not of calls)."""
        import re
        asked = re.findall(r"precision = \[(\w+), \w+\]", lowered[program])
        assert "HIGH" in asked and "HIGHEST" in asked, set(asked)
        # what is left at the default: the attention kernels' XLA
        # stand-ins on the CPU, two products in the one full layer
        assert asked.count("DEFAULT") == 2, asked
