"""Paged KV-cache decode stack (ops/pallas/paged_attention.py +
models/gpt.py decode path): kernel parity vs the dense gather reference
(Pallas interpreter on CPU) on folded [pages, page, H*D] and 4-D pools,
cache-append semantics (null page, donated eager buffers) against a NumPy
model of the pages, greedy-decode parity paged-vs-cacheless,
and the pools' device layout (compiled ahead of time for a described v5e:
no pool-shaped copies).

fast-sibling: every class here is tier-1 except the timing probe
(TestSuperLinear.test_per_token_cost_flat_vs_dense_slow), whose fast
sibling is test_paged_growth_structure.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.models.gpt import GPT, GPTConfig, PagedKVCache
from paddle_tpu.ops.pallas import paged_attention as pa
from paddle_tpu.ops.pallas import tiling

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def interp(monkeypatch):
    """Kernel under the Pallas interpreter."""
    tiling.reset_compile_checks()
    monkeypatch.setattr(pa, "_INTERPRET", True)
    yield
    tiling.reset_compile_checks()


def _rand_pool(rng, B, H, D, page_size, num_pages, pages_per_seq):
    q = jnp.asarray(rng.normal(size=(B, H, D)).astype(np.float32))
    kp = jnp.asarray(rng.normal(
        size=(num_pages, page_size, H, D)).astype(np.float32))
    vp = jnp.asarray(rng.normal(
        size=(num_pages, page_size, H, D)).astype(np.float32))
    bt = jnp.asarray(rng.integers(
        0, num_pages, (B, pages_per_seq)).astype(np.int32))
    return q, kp, vp, bt


class TestKernelParity:
    def test_pallas_matches_dense_reference(self, interp):
        rng = np.random.default_rng(0)
        q, kp, vp, bt = _rand_pool(rng, 3, 12, 64, 8, 10, 4)
        cl = jnp.asarray(np.array([13, 5, 32], np.int32))
        pa._stats["pallas"] = pa._stats["xla"] = 0
        out = pa.paged_attention(q, kp, vp, bt, cl)
        assert pa._stats["pallas"] == 1, "Pallas path not taken"
        ref = pa.paged_attention_xla(q, kp, vp, bt, cl)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=0, atol=2e-6)

    def test_zero_context_slot_outputs_zero(self, interp):
        """An idle serving slot (ctx=0, block table on the null page)
        must output exactly zero on BOTH impls."""
        rng = np.random.default_rng(1)
        q, kp, vp, bt = _rand_pool(rng, 2, 4, 64, 8, 6, 3)
        cl = jnp.asarray(np.array([0, 17], np.int32))
        out = pa.paged_attention(q, kp, vp, bt, cl)
        ref = pa.paged_attention_xla(q, kp, vp, bt, cl)
        assert np.all(np.asarray(out)[0] == 0.0)
        assert np.all(np.asarray(ref)[0] == 0.0)
        np.testing.assert_allclose(np.asarray(out)[1], np.asarray(ref)[1],
                                   atol=2e-6)

    def test_partial_last_page_is_masked(self, interp):
        """Positions past ctx on the last live page must not contribute:
        poisoning them with huge values changes nothing."""
        rng = np.random.default_rng(2)
        q, kp, vp, bt = _rand_pool(rng, 1, 4, 64, 8, 6, 3)
        cl = jnp.asarray(np.array([11], np.int32))  # page 1 holds 3 live
        out = pa.paged_attention(q, kp, vp, bt, cl)
        last_page = int(np.asarray(bt)[0, 1])
        kp2 = kp.at[last_page, 3:].set(1e4)
        vp2 = vp.at[last_page, 3:].set(1e4)
        out2 = pa.paged_attention(q, kp2, vp2, bt, cl)
        np.testing.assert_allclose(np.asarray(out), np.asarray(out2),
                                   atol=2e-6)

    def test_head_split_configs_agree(self, interp):
        """A head block regroups grid programs only — outputs are
        identical across head-block choices."""
        rng = np.random.default_rng(3)
        q, kp, vp, bt = _rand_pool(rng, 2, 16, 64, 8, 8, 3)
        cl = jnp.asarray(np.array([20, 9], np.int32))
        outs = [
            np.asarray(pa._paged_attn_pallas(q, kp, vp, bt, cl,
                                             1.0 / 8.0, bh, interpret=True))
            for bh in (16, 8)]
        for o in outs[1:]:
            np.testing.assert_array_equal(outs[0], o)

    def test_cpu_without_interpret_takes_xla(self):
        rng = np.random.default_rng(4)
        q, kp, vp, bt = _rand_pool(rng, 1, 2, 32, 4, 4, 2)
        cl = jnp.asarray(np.array([5], np.int32))
        pa._stats["pallas"] = pa._stats["xla"] = 0
        pa.paged_attention(q, kp, vp, bt, cl)
        assert pa._stats["xla"] == 1 and pa._stats["pallas"] == 0


# (H, D): GPT-2 small's, GPT-3 XL's, one TP shard of GPT-2 small's 12 heads
# over 4 devices (192 lanes: a tile and a half), and a tile-exact small one
_FOLDED_SHAPES = [(12, 64), (16, 128), (3, 64), (8, 128)]


class TestFoldedKernel:
    """The kernel reads the FOLDED page block [page, H*D] (PR 26): parity
    with the dense gather reference at the head shapes the engine serves,
    in both storage dtypes."""

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("H,D", _FOLDED_SHAPES)
    def test_folded_pool_matches_reference(self, H, D, dtype):
        rng = np.random.default_rng(H * 1000 + D)
        B, S, P, n = 4, 8, 12, 4
        q, kp, vp, bt = _rand_pool(rng, B, H, D, S, P, n)
        q, kp, vp = (x.astype(dtype) for x in (q, kp, vp))
        # idle row, a context ending mid-page, one on a page edge, a full one
        cl = jnp.asarray(np.array([0, 13, 16, 32], np.int32))
        fold = lambda x: x.reshape(P, S, H * D)  # noqa: E731
        out = pa._paged_attn_pallas(q, fold(kp), fold(vp), bt, cl,
                                    float(1 / np.sqrt(D)), H, interpret=True)
        assert out.shape == (B, H, D) and out.dtype == q.dtype
        ref = pa.paged_attention_xla(
            q.astype(jnp.float32), fold(kp).astype(jnp.float32),
            fold(vp).astype(jnp.float32), bt, cl)
        assert np.all(np.asarray(out.astype(jnp.float32))[0] == 0.0)
        # float32 to rounding of the sums; bfloat16 to its output rounding
        atol = 2e-6 if dtype == "float32" else 1e-2
        np.testing.assert_allclose(np.asarray(out.astype(jnp.float32)),
                                   np.asarray(ref), rtol=0, atol=atol)

    def test_dispatch_counts_the_folded_kernel(self, interp):
        rng = np.random.default_rng(7)
        q, kp, vp, bt = _rand_pool(rng, 2, 12, 64, 8, 10, 4)
        cl = jnp.asarray(np.array([9, 30], np.int32))
        before = dict(pa._stats)
        out = pa.paged_attention(q, kp.reshape(10, 8, 768),
                                 vp.reshape(10, 8, 768), bt, cl)
        assert pa._stats["folded"] == before["folded"] + 1
        assert pa._stats["pallas"] == before["pallas"] + 1
        # a 4-D pool is the same call through a reshape
        np.testing.assert_array_equal(
            np.asarray(out), np.asarray(pa.paged_attention(q, kp, vp, bt, cl)))

    def test_head_size_off_the_lane_grid_takes_xla(self, interp):
        """A head of 96 lanes is neither a fraction nor a multiple of a
        128-lane tile: the gate sends it to the gather, it does not fail."""
        rng = np.random.default_rng(8)
        q, kp, vp, bt = _rand_pool(rng, 1, 2, 96, 8, 4, 2)
        before = dict(pa._stats)
        pa.paged_attention(q, kp, vp, bt, jnp.asarray([5], jnp.int32))
        assert pa._stats["xla"] == before["xla"] + 1
        assert pa._stats["pallas"] == before["pallas"]


def _np_pages(P, S, HD):
    return np.zeros((P, S, HD), np.float32)


class TestFoldedScatter:
    """`cache_append` / `prefill_append` / `cow_copy_pages` on folded pools
    against a NumPy model of the pages."""

    def test_cache_append_matches_numpy_model(self):
        rng = np.random.default_rng(11)
        P, S, H, D, B = 7, 4, 3, 8, 3
        model_k, model_v = _np_pages(P, S, H * D), _np_pages(P, S, H * D)
        kp, vp = jnp.asarray(model_k), jnp.asarray(model_v)
        bt = np.array([[2, 3], [4, 1], [5, 6]], np.int32)
        cl = np.array([5, 2, 0], np.int32)
        active = np.array([True, True, False])
        for step in range(3):
            k_new = rng.normal(size=(B, H * D)).astype(np.float32)
            # (3-D rows too)
            v_new = rng.normal(size=(B, H, D)).astype(np.float32)
            kp, vp = pa.cache_append(kp, vp, jnp.asarray(k_new),
                                     jnp.asarray(v_new), jnp.asarray(bt),
                                     jnp.asarray(cl), jnp.asarray(active))
            for b in range(B):
                page, off = (bt[b, cl[b] // S], cl[b] % S) if active[b] \
                    else (0, 0)
                model_k[page, off] = k_new[b]
                model_v[page, off] = v_new[b].reshape(-1)
            cl = cl + active
        assert kp.shape == (P, S, H * D)
        np.testing.assert_array_equal(np.asarray(kp)[1:], model_k[1:])
        np.testing.assert_array_equal(np.asarray(vp)[1:], model_v[1:])

    @pytest.mark.parametrize("start", [0, 5])
    def test_prefill_append_matches_numpy_model(self, start):
        rng = np.random.default_rng(12)
        P, S, H, D, L, length = 8, 4, 3, 8, 12, 10
        model = rng.normal(size=(P, S, H * D)).astype(np.float32)
        kp, vp = jnp.asarray(model), jnp.asarray(model)
        page_ids = np.array([2, 5, 7, 0], np.int32)
        k_seq = rng.normal(size=(L, H * D)).astype(np.float32)
        kp, vp = pa.prefill_append(kp, vp, jnp.asarray(k_seq),
                                   jnp.asarray(k_seq.reshape(L, H, D)),
                                   jnp.asarray(page_ids), jnp.int32(length),
                                   start=start)
        for i in range(start, length):    # below start: shared pages, kept
            model[page_ids[i // S], i % S] = k_seq[i]
        np.testing.assert_array_equal(np.asarray(kp)[1:], model[1:])
        np.testing.assert_array_equal(np.asarray(vp)[1:], model[1:])

    def test_cow_copy_matches_numpy_model(self):
        rng = np.random.default_rng(13)
        model = [rng.normal(size=(6, 4, 24)).astype(np.float32)
                 for _ in range(4)]
        k, v = pa.cow_copy_pages([jnp.asarray(m) for m in model[:2]],
                                 [jnp.asarray(m) for m in model[2:]], 3, 5)
        for m in model:
            m[5] = m[3]
        for got, want in zip(list(k) + list(v), model):
            np.testing.assert_array_equal(np.asarray(got), want)


class TestCacheAppend:
    def test_append_lands_in_block_table_slot(self):
        page_size = 4
        kp = jnp.zeros((5, page_size, 2, 8), jnp.float32)
        vp = jnp.zeros_like(kp)
        bt = jnp.asarray(np.array([[2, 3], [4, 1]], np.int32))
        cl = jnp.asarray(np.array([5, 2], np.int32))
        k_new = jnp.ones((2, 2, 8), jnp.float32)
        v_new = 2.0 * jnp.ones((2, 2, 8), jnp.float32)
        kp, vp = pa.cache_append(kp, vp, k_new, v_new, bt, cl)
        kp_np = np.array(kp)
        # row 0: ctx 5 -> page bt[0, 1]=3, offset 1
        assert np.all(kp_np[3, 1] == 1.0)
        # row 1: ctx 2 -> page bt[1, 0]=4, offset 2
        assert np.all(kp_np[4, 2] == 1.0)
        assert np.all(np.asarray(vp)[3, 1] == 2.0)
        # nothing else touched
        kp_np[3, 1] = kp_np[4, 2] = 0.0
        assert np.all(kp_np == 0.0)

    def test_inactive_rows_write_only_the_null_page(self):
        page_size = 4
        kp = jnp.zeros((4, page_size, 2, 8), jnp.float32)
        vp = jnp.zeros_like(kp)
        bt = jnp.asarray(np.array([[1, 2], [3, 0]], np.int32))
        cl = jnp.asarray(np.array([0, 1], np.int32))
        active = jnp.asarray(np.array([False, True]))
        k_new = jnp.ones((2, 2, 8), jnp.float32)
        kp, vp = pa.cache_append(kp, vp, k_new, k_new, bt, cl, active)
        kp_np = np.asarray(kp)
        assert np.all(kp_np[3, 1] == 1.0)    # the active row's write
        assert np.all(kp_np[1] == 0.0)       # inactive row's pages clean
        assert np.all(kp_np[2] == 0.0)

    def test_eager_append_donates_the_pool(self):
        """The eager append routes through the donating jit: the passed
        pool buffer is consumed (deleted), not copied per token."""
        kp = jnp.zeros((4, 4, 2, 8), jnp.float32)
        vp = jnp.zeros_like(kp)
        bt = jnp.zeros((1, 2), jnp.int32)
        cl = jnp.zeros((1,), jnp.int32)
        k_new = jnp.ones((1, 2, 8), jnp.float32)
        kp2, vp2 = pa.cache_append(kp, vp, k_new, k_new, bt, cl)
        assert kp2 is not kp
        assert kp.is_deleted(), "pool was copied, not donated"
        assert vp.is_deleted()

    def test_prefill_append_scatter(self):
        page_size = 4
        kp = jnp.zeros((6, page_size, 2, 8), jnp.float32)
        vp = jnp.zeros_like(kp)
        page_ids = jnp.asarray(np.array([2, 5, 0], np.int32))
        L = 9
        k_seq = jnp.broadcast_to(
            jnp.arange(1, L + 1, dtype=jnp.float32)[:, None, None],
            (L, 2, 8))
        kp, vp = pa.prefill_append(kp, vp, k_seq, k_seq, page_ids,
                                   jnp.int32(6))  # only 6 of 9 live
        kp_np = np.asarray(kp)
        assert np.all(kp_np[2, 0] == 1.0) and np.all(kp_np[2, 3] == 4.0)
        assert np.all(kp_np[5, 0] == 5.0) and np.all(kp_np[5, 1] == 6.0)
        # padded positions (7, 8, 9) landed on the null page, not page 5
        assert np.all(kp_np[5, 2:] == 0.0)


@pytest.fixture(scope="module")
def v5e_chip():
    """One described (not attached) v5e chip to compile for. Described
    inside a fixture, never at import: see the on-chip-measurement guide."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here: skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


# the two served configurations' pools and lanes (benchmark/workloads):
# (heads, head size, pool pages, lanes, pages per sequence)
_SERVED = {"gpt2_small": (12, 64, 2049, 32, 64),
           "gpt3_1p3b": (16, 128, 1025, 16, 128)}


class TestPoolLayout:
    """The compiled decode and prefill programs must hold a K/V pool in
    ONE layout from argument to result. Before PR 26 the 4-D pool of
    GPT-2 small had its pages in the lanes by default, and every program
    copied every pool in and out (PERF.md section 5). One layer of each
    program, compiled ahead of time for a described v5e and counted by the
    function `ServingEngine.audit()` uses."""

    @staticmethod
    def _args(chip, H, D, P, B, n, pool_shape):
        def sds(shape, dtype=jnp.float32):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
        pool = sds(pool_shape)
        return pool, dict(
            q=sds((B, H, D)), rows=sds((B, H * D)), bt=sds((B, n), jnp.int32),
            cl=sds((B,), jnp.int32), active=sds((B,), jnp.bool_),
            seq=sds((256, H * D)), page_ids=sds((n,), jnp.int32),
            scalar=sds((), jnp.int32))

    @staticmethod
    def _decode_layer(q, k_new, v_new, kp, vp, bt, cl, active):
        kp, vp = pa._append_impl(kp, vp, k_new, v_new, bt, cl, active)
        out = pa._paged_attn_pallas(q, kp, vp, bt, jnp.where(active, cl + 1, 0),
                                    float(1 / np.sqrt(q.shape[-1])),
                                    q.shape[1])
        return out, kp, vp

    def _reports(self, chip, H, D, P, B, n, pool_shape):
        from paddle_tpu.analysis import pool_relayout_report
        pool, a = self._args(chip, H, D, P, B, n, pool_shape)
        decode = jax.jit(self._decode_layer, donate_argnums=(3, 4)).lower(
            a["q"], a["rows"], a["rows"], pool, pool, a["bt"], a["cl"],
            a["active"]).compile()
        prefill = jax.jit(pa.prefill_append, donate_argnums=(0, 1)).lower(
            pool, pool, a["seq"], a["seq"], a["page_ids"], a["scalar"],
            a["scalar"]).compile()
        return [pool_relayout_report(c, [pool]) for c in (decode, prefill)]

    @pytest.mark.parametrize("config", sorted(_SERVED))
    def test_folded_pool_is_updated_in_place(self, v5e_chip, config):
        H, D, P, B, n = _SERVED[config]
        for rep in self._reports(v5e_chip, H, D, P, B, n, (P, 16, H * D)):
            assert rep["pool_relayout_copies"] == 0, rep
            assert rep["temp_size_in_bytes"] < rep["pool_bytes"], rep

    def test_the_count_sees_the_4d_pool_of_before(self, v5e_chip):
        """The same one-layer programs on GPT-2 small's pool as it was
        stored before, [pages, page, 12, 64]: one copy in and one out."""
        H, D, P, B, n = _SERVED["gpt2_small"]
        for rep in self._reports(v5e_chip, H, D, P, B, n, (P, 16, H, D)):
            assert rep["pool_relayout_copies"] >= 2, rep
            assert rep["temp_size_in_bytes"] > rep["pool_bytes"], rep


class TestGPTDecodeParity:
    """Greedy-token parity: the paged incremental decode must produce
    the SAME tokens as the cacheless full-recompute path (bit-exact on
    this box — both paths run f32 XLA on CPU; TPU tolerance is the
    kernels' documented f32-accumulation ULP)."""

    def _model(self):
        paddle.seed(0)
        cfg = GPTConfig.tiny()
        m = GPT(cfg)
        m.eval()
        return m, cfg

    @pytest.mark.slow  # dense-vs-paged walk; prefill/contract siblings stay fast
    def test_greedy_tokens_match_dense(self):
        m, cfg = self._model()
        rng = np.random.default_rng(0)
        ids = paddle.to_tensor(
            rng.integers(1, cfg.vocab_size, (2, 12)).astype("int32"))
        dense = np.asarray(m.generate_dense(ids, 8).data)
        paged = np.asarray(m.generate_paged(ids, 8, page_size=8).data)
        np.testing.assert_array_equal(dense, paged)

    @pytest.mark.slow  # interpret-mode kernel walk; prefill/contract/bucketed
    def test_greedy_parity_on_pallas_interpret(self, interp):  # stay fast
        """Same parity with the decode attention on the Pallas kernel
        (interpret mode): tokens still match the dense path."""
        m, cfg = self._model()
        rng = np.random.default_rng(1)
        ids = paddle.to_tensor(
            rng.integers(1, cfg.vocab_size, (1, 9)).astype("int32"))
        pa._stats["pallas"] = 0
        paged = np.asarray(m.generate_paged(ids, 6, page_size=8).data)
        assert pa._stats["pallas"] > 0, "decode did not use the kernel"
        dense = np.asarray(m.generate_dense(ids, 6).data)
        np.testing.assert_array_equal(dense, paged)

    def test_zero_new_tokens_matches_dense_contract(self):
        """Review regression: generate_paged(ids, 0) returned [B, L+1]
        (prefill's token appended before the budget check) while
        generate_dense returned [B, L]."""
        m, cfg = self._model()
        rng = np.random.default_rng(9)
        ids = paddle.to_tensor(
            rng.integers(1, cfg.vocab_size, (1, 6)).astype("int32"))
        assert tuple(m.generate_paged(ids, 0).shape) == (1, 6)
        assert tuple(m.generate_dense(ids, 0).shape) == (1, 6)

    def test_prefill_matches_training_forward_logits(self):
        """The prefill's last-position logits equal the training
        forward's — one source of truth for the first generated token."""
        m, cfg = self._model()
        rng = np.random.default_rng(2)
        ids_np = rng.integers(1, cfg.vocab_size, (1, 10)).astype("int32")
        ids = paddle.to_tensor(ids_np)
        full = np.asarray(m(ids).data)[0, -1]
        cache = m.init_cache(1, 32, page_size=8)
        import jax.numpy as jnp2
        cache.block_tables = jnp2.asarray(
            np.arange(1, 5, dtype=np.int32)[None])
        logits, cache = m.forward_prefill(ids, cache, 0, 10)
        np.testing.assert_allclose(np.asarray(logits.data)[0], full,
                                   rtol=1e-5, atol=1e-5)
        assert int(np.asarray(cache.context_lens)[0]) == 10

    def test_bucketed_prefill_padding_is_inert(self):
        """Padding the prompt to a shape bucket must not change the
        prefilled K/V or the last-position logits."""
        m, cfg = self._model()
        rng = np.random.default_rng(3)
        ids_np = rng.integers(1, cfg.vocab_size, (1, 7)).astype("int32")
        padded = np.zeros((1, 16), np.int32)
        padded[:, :7] = ids_np

        def run(arr):
            cache = m.init_cache(1, 32, page_size=8)
            import jax.numpy as jnp2
            cache.block_tables = jnp2.asarray(
                np.arange(1, 5, dtype=np.int32)[None])
            logits, cache = m.forward_prefill(
                paddle.to_tensor(arr), cache, 0, 7)
            return np.asarray(logits.data), \
                np.asarray(cache.k_pages[0])

        lo_a, kp_a = run(ids_np)
        lo_b, kp_b = run(padded)
        np.testing.assert_allclose(lo_a, lo_b, rtol=1e-6, atol=1e-6)
        # real pages identical; page 0 (the null page) is the designated
        # dump for padded positions' K/V and legitimately differs
        np.testing.assert_array_equal(kp_a[1:], kp_b[1:])


class TestSuperLinear:
    """Acceptance: per-token decode cost ~flat as context grows on the
    paged path while the cacheless path grows with context length."""

    def _model(self):
        paddle.seed(0)
        cfg = GPTConfig(vocab_size=2048, max_position_embeddings=512,
                        hidden_size=128, num_layers=2, num_heads=4,
                        dropout=0.0, attn_dropout=0.0)
        m = GPT(cfg)
        m.eval()
        return m

    def test_paged_growth_structure(self):
        """Fast sibling: the A/B probe produces well-formed rows and the
        paged executable is context-INDEPENDENT by construction — the
        decode step compiled once serves every context length (no
        retrace as ctx grows), which is what makes its per-token cost
        flat."""
        import bench
        m = self._model()
        ab = bench._paged_vs_dense_ab(m, (16, 32), page_size=8,
                                      n_tokens=2, dense_iters=1)
        assert [r["ctx"] for r in ab["rows"]] == [16, 32]
        for r in ab["rows"]:
            assert r["paged_ms_per_token"] > 0
            assert r["dense_ms_per_token"] > 0

    @pytest.mark.slow
    def test_per_token_cost_flat_vs_dense_slow(self):
        """The measured acceptance A/B at CI scale: over a 4x context
        growth the dense per-token cost must grow markedly while the
        paged per-token cost stays ~flat (generous margins: CPU wall
        clocks on a busy CI box)."""
        import bench
        m = self._model()
        ab = bench._paged_vs_dense_ab(m, (64, 128, 256), page_size=8,
                                      n_tokens=6, dense_iters=3)
        assert ab["dense_growth"] > 1.4, ab
        assert ab["paged_growth"] < ab["dense_growth"] / 1.3, ab
        assert ab["speedup_at_max_ctx"] > 1.0, ab


# -------------- what PR 31 added, compiled for the chip (this file holds ------
# -------------- the one described topology of the test suite) -----------------


class TestNemotronShapesCompileForTheChip:
    """`nemotron3_nano_30b`'s pools hold 2 K/V heads of 128 for 32 query
    heads, and its expert blocks 32 stacked experts of 2688 x 1856: the
    decode layer must update the pools in place, and the grouped product
    must read the stacked weights where they lie."""

    H, HKV, D, P, B, N = 32, 2, 128, 6145, 64, 128

    @staticmethod
    def _decode_layer(q, k_new, v_new, kp, vp, bt, cl, active):
        kp, vp = pa._append_impl(kp, vp, k_new, v_new, bt, cl, active)
        out = pa.paged_attention_xla(q, kp, vp, bt,
                                     jnp.where(active, cl + 1, 0))
        return out, kp, vp

    def test_grouped_pool_is_updated_in_place(self, v5e_chip):
        from paddle_tpu.analysis import pool_relayout_report

        def sds(shape, dtype=jnp.float32):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)
        pool = sds((self.P, 16, self.HKV * self.D))
        rows = sds((self.B, self.HKV * self.D))
        decode = jax.jit(self._decode_layer, donate_argnums=(3, 4)).lower(
            sds((self.B, self.H, self.D)), rows, rows, pool, pool,
            sds((self.B, self.N), jnp.int32), sds((self.B,), jnp.int32),
            sds((self.B,), jnp.bool_)).compile()
        prefill = jax.jit(pa.prefill_append, donate_argnums=(0, 1)).lower(
            pool, pool, sds((256, self.HKV * self.D)),
            sds((256, self.HKV * self.D)), sds((self.N,), jnp.int32),
            sds((), jnp.int32), sds((), jnp.int32)).compile()
        for compiled in (decode, prefill):
            rep = pool_relayout_report(compiled, [pool])
            assert rep["pool_relayout_copies"] == 0, rep

    @pytest.mark.parametrize("tokens", [64, 512], ids=["decode", "prefill"])
    def test_the_grouped_product_reads_the_stacked_weights_in_place(
            self, v5e_chip, tokens, monkeypatch):
        """`ops/moe.held_experts` at the cell's widths through the
        megablox kernel: it compiles at the tiles `_tiles` picks, and no
        operation of the compiled program has the stacked weights' shape
        but the two parameters (a re-laid out copy would)."""
        from paddle_tpu.ops import moe
        monkeypatch.setattr(moe, "_on_tpu", lambda: True)

        def sds(shape, dtype=jnp.float32):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)
        stacked = sds((32, 1856, 2688))
        compiled = jax.jit(
            lambda u, e, w, w1, w2: moe.held_experts(u, e, w, w1, w2)).lower(
            sds((tokens, 2688)), sds((tokens, 6), jnp.int32),
            sds((tokens, 6)), stacked, stacked).compile()
        text = compiled.as_text()
        assert text.count("tpu_custom_call") >= 2
        made = [line for line in text.splitlines()
                if " = f32[32,1856,2688]" in line
                and " parameter(" not in line]
        assert not made, made[:2]
        assert compiled.memory_analysis().temp_size_in_bytes < 200e6
