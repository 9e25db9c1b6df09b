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
                                             1.0 / 8.0, bh, 2, interpret=True))
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
                                    float(1 / np.sqrt(D)), H, 1,
                                    interpret=True)
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


def _walk_case(P, D, dtype, S=8, n=19):
    """One call that holds every edge of the page walk at `P` pages a
    grid step: contexts of 0 (idle), 1, span - 1, span, span + 1, the
    whole table and a tenth of it, over a table of 19 slots (none of 2, 4,
    8 divides it, so the last group reaches past the table's end). Every
    slot past a lane's last live GROUP points at a page of NaN."""
    rng = np.random.default_rng(100 * P + D)
    H = max(256 // D, 1)
    span, full = P * S, n * S
    ctx = np.array([0, 1, span - 1, span, span + 1, full, full // 10],
                   np.int32)
    B, nan_page = len(ctx), 1
    pages = 2 + B * n
    q = rng.normal(size=(B, H, D)).astype(np.float32)
    kp = rng.normal(size=(pages, S, H * D)).astype(np.float32)
    vp = rng.normal(size=(pages, S, H * D)).astype(np.float32)
    kp[nan_page] = vp[nan_page] = np.nan
    bt = 2 + np.arange(B * n, dtype=np.int32).reshape(B, n)
    for b in range(B):
        bt[b, -(-int(ctx[b]) // span) * P:] = nan_page
    cast = lambda x: jnp.asarray(x).astype(dtype)  # noqa: E731
    return (cast(q), cast(kp), cast(vp), jnp.asarray(bt), jnp.asarray(ctx),
            H, nan_page)


class TestPageWalk:
    """The full-heads kernel's walk (PR 34): P pages a grid step, one
    grid step for every (lane, live page group) and none for a group
    without a live token."""

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("D", [64, 128, 256])
    @pytest.mark.parametrize("P", [1, 2, 4, 8])
    def test_walk_matches_reference_and_reads_no_dead_group(self, P, D,
                                                            dtype):
        q, kp, vp, bt, ctx, H, nan_page = _walk_case(P, D, dtype)
        out = pa._paged_attn_pallas(q, kp, vp, bt, ctx,
                                    float(1 / np.sqrt(D)), H, P,
                                    interpret=True)
        out = np.asarray(out.astype(jnp.float32))
        assert out.dtype == np.float32 and np.all(np.isfinite(out)), \
            "a page past a lane's last live group reached the sum"
        assert np.all(out[0] == 0.0)            # the idle lane: exact zeros
        # the gather reads every slot of the table: give it zeros there
        f32 = lambda x: x.astype(jnp.float32).at[nan_page].set(0.0)  # noqa
        ref = pa.paged_attention_xla(q.astype(jnp.float32), f32(kp), f32(vp),
                                     bt, ctx)
        atol = 2e-6 if dtype == "float32" else 1e-2
        np.testing.assert_allclose(out, np.asarray(ref), rtol=0, atol=atol)

    @pytest.mark.parametrize("ctx,span,n_groups", [
        ([0, 1, 15, 16, 17, 64], 16, 4),       # an idle lane, the edges
        ([700, 70, 7, 2048], 64, 32),          # contexts tenfold apart
        ([0, 0, 0], 128, 16),                  # every lane idle
        ([2048] * 5, 128, 16),                 # every lane full
        ([33], 32, 2),
    ])
    def test_work_list_against_a_plain_loop(self, ctx, span, n_groups):
        lane, group, n_items = pa.page_walk(jnp.asarray(ctx, jnp.int32),
                                            span, n_groups)
        want = [(b, g) for b, c in enumerate(ctx)
                for g in range(max(1, -(-c // span)))]
        assert lane.shape == group.shape == (len(ctx) * n_groups,)
        assert lane.dtype == group.dtype == n_items.dtype == jnp.int32
        n = int(n_items[0])
        assert list(zip(np.asarray(lane)[:n].tolist(),
                        np.asarray(group)[:n].tolist())) == want
        # what is never visited still indexes the table
        assert 0 <= int(lane.min()) and int(lane.max()) < len(ctx)
        assert 0 <= int(group.min()) and int(group.max()) < n_groups
        live, walked = pa.page_group_counts(np.asarray(ctx), span)
        assert walked == n
        assert live == n - sum(c == 0 for c in ctx)

    def test_no_idle_lane_walks_live_groups_only(self):
        """`page_groups_live` of the engine's counter IS the item count
        where every lane holds a token."""
        ctx = np.array([700, 70, 7, 2048, 1, 129], np.int32)
        for span in (16, 32, 64, 128):
            live, walked = pa.page_group_counts(ctx, span)
            n_items = pa.page_walk(jnp.asarray(ctx), span, 2048 // span)[2]
            assert live == walked == int(n_items[0])


def _grouped_case(Hkv, G, n, P, ctx, D=128, S=8, seed=0):
    """q over pools of `Hkv` K/V heads and a table of `n` slots, lane b
    owning pages 2 + b * n onward; every slot past a lane's last live
    GROUP of `P` pages points at a page of NaN."""
    rng = np.random.default_rng(seed + 1000 * Hkv + 10 * G + P)
    ctx = np.asarray(ctx, np.int32)
    B, nan_page = len(ctx), 1
    pages = 2 + B * n
    q = rng.normal(size=(B, Hkv * G, D)).astype(np.float32)
    kp = rng.normal(size=(pages, S, Hkv * D)).astype(np.float32)
    vp = rng.normal(size=(pages, S, Hkv * D)).astype(np.float32)
    kp[nan_page] = vp[nan_page] = np.nan
    bt = 2 + np.arange(B * n, dtype=np.int32).reshape(B, n)
    for b in range(B):
        bt[b, -(-int(ctx[b]) // (P * S)) * P:] = nan_page
    return (jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(bt), jnp.asarray(ctx), nan_page)


def _ragged(span, full):
    """Contexts that hold every edge of the walk in one call: idle lanes
    first, last and between live ones, one token, one under / at / one
    over a group's edge, the table's full length."""
    return [min(c, full) for c in (0, 1, span - 1, 0, span, span + 1, full,
                                   0)]


class TestGroupedWalk:
    """The grouped kernel's walk (PR 36): `page_walk`'s list of (lane,
    live page group) items, P pages a grid step fetched by the kernel's
    own copies, against the XLA gather."""

    # (Hkv, G, table slots, P); pages of 8 tokens
    SHAPES = [(4, 8, 19, 4), (4, 8, 16, 8), (2, 16, 19, 2), (2, 16, 12, 16),
              (1, 8, 9, 1), (2, 2, 8, 4)]

    @pytest.mark.parametrize("traffic", ["ragged", "ring"])
    @pytest.mark.parametrize("Hkv,G,n,P", SHAPES)
    def test_walk_matches_the_gather_and_reads_no_dead_group(
            self, Hkv, G, n, P, traffic):
        S = 8
        ctx = _ragged(P * S, n * S) if traffic == "ragged" else [n * S] * 4
        q, kp, vp, bt, cl, nan_page = _grouped_case(Hkv, G, n, P, ctx)
        scale = float(1 / np.sqrt(q.shape[-1]))
        out = np.asarray(pa._paged_attn_grouped_pallas(
            q, kp, vp, bt, cl, scale, P, interpret=True))
        assert np.all(np.isfinite(out)), \
            "a page past a lane's last live group reached the sum"
        for b, c in enumerate(ctx):
            if c == 0:                          # an idle lane: exact zeros
                assert np.all(out[b] == 0.0), b
        # the gather reads every slot of the table: give it zeros there
        ref = pa._paged_attention_grouped_xla(
            q, kp.at[nan_page].set(0.0), vp.at[nan_page].set(0.0), bt, cl,
            scale)
        np.testing.assert_allclose(out, np.asarray(ref), rtol=0, atol=1e-5)

    @pytest.mark.parametrize("Hkv,G,n,P", SHAPES)
    def test_grid_steps_are_the_counted_groups(self, Hkv, G, n, P,
                                               monkeypatch):
        """The grid's bound IS `page_walk`'s item count over the call's
        own lengths, which `page_group_counts` (the engine's counter)
        gives as `walked`: run un-jitted, the list is concrete."""
        S = 8
        ctx = _ragged(P * S, n * S)
        q, kp, vp, bt, cl, _ = _grouped_case(Hkv, G, n, P, ctx)
        seen = []
        walk = pa.page_walk

        def spy(context_lens, span, n_groups):
            out = walk(context_lens, span, n_groups)
            seen.append((span, n_groups, int(out[2][0])))
            return out

        monkeypatch.setattr(pa, "page_walk", spy)
        pa._paged_attn_grouped_pallas.__wrapped__(
            q, kp, vp, bt, cl, 0.1, P, interpret=True)
        (span, n_groups, items), = seen
        assert (span, n_groups) == (P * S, -(-n // P))
        live, walked = pa.page_group_counts(np.asarray(ctx), span)
        assert items == walked == live + sum(c == 0 for c in ctx)

    def test_bfloat16_pools_take_one_part(self):
        q, kp, vp, bt, cl, nan_page = _grouped_case(
            2, 8, 12, 4, _ragged(32, 96))
        bf = lambda x: x.astype(jnp.bfloat16)  # noqa: E731
        out = pa._paged_attn_grouped_pallas(bf(q), bf(kp), bf(vp), bt, cl,
                                            0.1, 4, interpret=True)
        assert out.dtype == jnp.bfloat16
        ref = pa._paged_attention_grouped_xla(
            bf(q), bf(kp).at[nan_page].set(0.0), bf(vp).at[nan_page].set(0.0),
            bt, cl, 0.1)
        np.testing.assert_allclose(np.asarray(out.astype(jnp.float32)),
                                   np.asarray(ref.astype(jnp.float32)),
                                   rtol=0, atol=1e-2)

    @pytest.mark.parametrize("x", [1.0, -3.1415927, 1e-30, 65504.1, 0.0,
                                   1.0000001, -2.9999998])
    def test_the_parts_are_bfloat16_and_sum_to_the_value(self, x):
        parts = pa._bf16_parts(jnp.full((8, 128), x, jnp.float32))
        assert len(parts) == 3
        for part in parts:
            assert part.dtype == jnp.float32
            np.testing.assert_array_equal(
                np.asarray(part),
                np.asarray(part.astype(jnp.bfloat16).astype(jnp.float32)))
        total = np.asarray(parts[0], np.float64) + np.asarray(
            parts[1], np.float64) + np.asarray(parts[2], np.float64)
        np.testing.assert_array_equal(total, np.float64(np.float32(x)))


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
        out = pa._paged_attn_pallas(
            q, kp, vp, bt, jnp.where(active, cl + 1, 0),
            float(1 / np.sqrt(q.shape[-1])), q.shape[1],
            pa.pages_per_step(kp.shape[2], kp.shape[1], kp.dtype.itemsize,
                               bt.shape[1]))
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

    @pytest.mark.parametrize("config", sorted(_SERVED))
    def test_the_work_list_is_computed_once_a_step(self, v5e_chip, config):
        """Every layer's call computes `page_walk` from the same lengths:
        the compiled step of three layers holds the list's reductions
        (the cumulative sum, the count of lanes behind an item) as often
        as the step of one, and still no copy of pool shape."""
        import re
        from collections import Counter
        from paddle_tpu.analysis import pool_relayout_report
        H, D, P, B, n = _SERVED[config]
        pool, a = self._args(v5e_chip, H, D, P, B, n, (P, 16, H * D))

        def step(layers):
            def run(q, k_new, v_new, pools, bt, cl, active):
                out = []
                for kp, vp in pools:
                    q, kp, vp = self._decode_layer(q, k_new, v_new, kp, vp,
                                                   bt, cl, active)
                    out.append((kp, vp))
                return q, out
            return jax.jit(run, donate_argnums=(3,)).lower(
                a["q"], a["rows"], a["rows"], [(pool, pool)] * layers,
                a["bt"], a["cl"], a["active"]).compile()

        def ops(compiled):
            return Counter(re.findall(r" = \S+ ([a-z\-]+)\(",
                                      compiled.as_text()))

        one, three = step(1), step(3)
        n1, n3 = ops(one), ops(three)
        assert n3["custom-call"] - n1["custom-call"] >= 2   # the kernels
        assert n1["reduce-window"] + n1["reduce"] >= 2, n1
        for op in ("reduce-window", "reduce", "iota", "gather", "sort"):
            assert n3[op] == n1[op], (op, n1[op], n3[op])
        rep = pool_relayout_report(three, [pool])
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


# ------------------ a sliding-window layer's ring (PR 33) --------------------


def _ring_cache(B, W, page, Hkv, D, H):
    from paddle_tpu.models.decode_cache import KV_WINDOW, PagedKVCache
    ring = lambda: jnp.zeros((1 + B * W // page, page, Hkv * D),  # noqa: E731
                             jnp.float32)
    return PagedKVCache([], [], jnp.zeros((B, 1), jnp.int32),
                        jnp.zeros((B,), jnp.int32), page, H, D,
                        layer_kinds=[KV_WINDOW], num_kv_heads=Hkv,
                        window_k=[ring()], window_v=[ring()], window=W)


class TestRingTable:
    """`decode_blocks.ring_*`: the scatters and the grouped kernel at a
    table computed from the slot, `[B, W / page]`, slot b owning pages
    `1 + b * W / page` onward."""

    @pytest.mark.parametrize("path", ["xla", "grouped"])
    def test_32_on_4_heads_over_a_ring_equal_the_dense_reference(
            self, path, monkeypatch):
        """The cell's head grouping (32 query heads on 4 K/V heads of
        128) at a window of 64 in pages of 16: lanes at laps 0, 1 and 3 of
        their rings, one idle, in slots out of order; every lane against
        `paged_attention_xla` over the LAST min(context, 64) tokens it
        was given, repeated for every query head."""
        from paddle_tpu.models import decode_blocks as blocks
        monkeypatch.setattr(pa, "_INTERPRET", path == "grouped")
        H, Hkv, D, W, page, B = 32, 4, 128, 64, 16, 4
        rng = np.random.default_rng(0)
        cache = _ring_cache(B, W, page, Hkv, D, H)
        slots = jnp.asarray([2, 0, 3, 1], jnp.int32)
        ends = [40, 70, 200, 0]                 # context after the last step
        history = [rng.normal(size=(n, 2, Hkv * D)).astype(np.float32)
                   for n in ends]
        for t in range(max(ends)):
            active = jnp.asarray([t < n for n in ends])
            k = np.stack([h[min(t, len(h) - 1), 0] if len(h) else
                          np.zeros(Hkv * D, np.float32) for h in history])
            v = np.stack([h[min(t, len(h) - 1), 1] if len(h) else
                          np.zeros(Hkv * D, np.float32) for h in history])
            ctx = jnp.asarray([min(t, n) for n in ends], jnp.int32)
            q = rng.normal(size=(B, H, D)).astype(np.float32)
            before = pa._stats[path]
            out = blocks.ring_decode_attention(cache, 0, q, k, v, slots,
                                               ctx, active)
            if t not in (39, 69, 199):
                continue
            assert pa._stats[path] == before + 1
            for lane, n in enumerate(ends):
                if t >= n:
                    continue
                seen = history[lane][max(0, t + 1 - W):t + 1]
                pages = -(-len(seen) // page)
                pad = np.zeros((pages * page, 2, Hkv * D), np.float32)
                pad[:len(seen)] = seen
                rep = lambda x: np.repeat(  # noqa: E731
                    x.reshape(pages, page, Hkv, D), H // Hkv, axis=2
                ).reshape(pages, page, H * D)
                want = pa.paged_attention_xla(
                    q[lane:lane + 1], rep(pad[:, 0]), rep(pad[:, 1]),
                    jnp.arange(pages, dtype=jnp.int32)[None],
                    jnp.asarray([len(seen)], jnp.int32))
                np.testing.assert_allclose(out[lane], want[0], rtol=0,
                                           atol=2e-5)
        # the idle lane's slot (1) holds nothing, nor does the null page
        ring = np.asarray(cache.window_k[0])
        assert not ring[1 + 1 * 4:1 + 2 * 4].any() and not ring[0].any()

    @pytest.mark.parametrize("length,bucket", [(5, 16), (64, 64), (70, 128),
                                               (200, 256), (129, 256)])
    def test_prefill_rewrites_the_ring_with_the_prompts_last_window(
            self, length, bucket):
        """Row r takes the last position t < length with t mod 64 == r;
        rows past a short prompt read zero; what the slot held before is
        gone; bucket padding (rows at or past `length`, here nonzero)
        reaches no row; the other slots are untouched."""
        from paddle_tpu.models import decode_blocks as blocks
        W, page, B, width = 64, 16, 3, 32
        cache = _ring_cache(B, W, page, 2, 16, 4)
        cache.window_k[0] = cache.window_k[0] + 7.0
        cache.window_v[0] = cache.window_v[0] + 7.0
        rng = np.random.default_rng(length)
        k = rng.normal(size=(bucket, width)).astype(np.float32)
        v = rng.normal(size=(bucket, width)).astype(np.float32)
        blocks.ring_prefill_write(cache, 0, jnp.asarray(k), jnp.asarray(v),
                                  jnp.int32(1), jnp.int32(length))
        for got, seq in ((cache.window_k[0], k), (cache.window_v[0], v)):
            got = np.asarray(got)
            want = np.zeros((W, width), np.float32)
            for t in range(max(0, length - W), length):
                want[t % W] = seq[t]
            np.testing.assert_array_equal(
                got[1 + 4:1 + 8].reshape(W, width), want)
            assert (got[:1 + 4] == 7.0).all() and (got[1 + 8:] == 7.0).all()

    def test_a_padding_lanes_write_is_dropped(self):
        """The sentinel slot `max_batch` is clamped for the gather and
        its write goes to the null page."""
        from paddle_tpu.models import decode_blocks as blocks
        cache = _ring_cache(2, 16, 8, 2, 16, 4)
        table = np.asarray(blocks.ring_tables(cache,
                                              jnp.asarray([1, 2, 0])))
        np.testing.assert_array_equal(table, [[3, 4], [3, 4], [1, 2]])
        ones = jnp.ones((2, 32), jnp.float32)
        blocks.ring_decode_attention(
            cache, 0, jnp.ones((2, 4, 16), jnp.float32), ones, ones,
            jnp.asarray([0, 2]), jnp.asarray([3, 9]),
            jnp.asarray([True, False]))
        ring = np.asarray(cache.window_k[0])
        assert ring[1, 3].all() and not ring[3:].any()


class TestMellumShapesCompileForTheChip:
    """`mellum2_12b_a2p5b`'s pools hold 4 K/V heads of 128 for 32 query
    heads: 16,385 pages for a full layer, a ring of 1,024 tokens for each
    of 64 slots for a sliding one; its expert layers stack 32 SwiGLU
    experts of 2304 x 896. Both programs' layers must update both pool
    shapes in place, the flash forward kernel must take the window at
    `highest`, and the grouped products must read the stacked weights
    where they lie. Compiled for a described v5e, nothing runs."""

    H, HKV, D, B, W, PAGE, POOL, PER_SEQ = 32, 4, 128, 64, 1024, 16, 16385, 320

    def _cache(self, kp, vp, rk, rv, bt, cl):
        from paddle_tpu.models.decode_cache import (KV, KV_WINDOW,
                                                    PagedKVCache)
        return PagedKVCache([kp], [vp], bt, cl, self.PAGE, self.H, self.D,
                            layer_kinds=[KV, KV_WINDOW],
                            num_kv_heads=self.HKV, window_k=[rk],
                            window_v=[rv], window=self.W)

    def test_both_pool_shapes_are_updated_in_place(self, v5e_chip):
        from paddle_tpu.analysis import pool_relayout_report
        from paddle_tpu.models import decode_blocks as blocks

        def sds(shape, dtype=jnp.float32):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)
        width = self.HKV * self.D
        pool = sds((self.POOL, self.PAGE, width))
        ring = sds((1 + self.B * self.W // self.PAGE, self.PAGE, width))
        scale = float(1 / np.sqrt(self.D))

        def pick(table):
            return pa.grouped_pages_per_step(width, self.PAGE, 4,
                                             table.shape[1])

        def decode(q, k, v, kp, vp, rk, rv, bt, cl, slots, active):
            c = self._cache(kp, vp, rk, rv, bt, cl)
            ctx = jnp.take(cl, slots, mode="clip")
            rows = jnp.take(bt, slots, axis=0, mode="clip")
            table = blocks.ring_tables(c, slots)
            rk, rv = pa._append_impl(rk, rv, k, v, table, ctx % self.W,
                                     active)
            a = pa._paged_attn_grouped_pallas(
                q, rk, rv, table,
                jnp.where(active, jnp.minimum(ctx + 1, self.W), 0), scale,
                pick(table))
            kp, vp = pa._append_impl(kp, vp, k, v, rows, ctx, active)
            b = pa._paged_attn_grouped_pallas(
                q, kp, vp, rows, jnp.where(active, ctx + 1, 0), scale,
                pick(rows))
            return a + b, kp, vp, rk, rv

        def prefill(k, v, kp, vp, rk, rv, bt, cl, slot, length):
            c = self._cache(kp, vp, rk, rv, bt, cl)
            blocks.ring_prefill_write(c, 0, k, v, slot, length)
            blocks.paged_prefill_append(c, 0, k, v,
                                        jnp.take(bt, slot, axis=0), length, 0)
            return c.k_pages[0], c.v_pages[0], c.window_k[0], c.window_v[0]

        tables = (sds((self.B, self.PER_SEQ), jnp.int32),
                  sds((self.B,), jnp.int32))
        rows = sds((self.B, width))
        compiled = [
            jax.jit(decode, donate_argnums=(3, 4, 5, 6)).lower(
                sds((self.B, self.H, self.D)), rows, rows, pool, pool, ring,
                ring, *tables, sds((self.B,), jnp.int32),
                sds((self.B,), jnp.bool_)).compile(),
            jax.jit(prefill, donate_argnums=(2, 3, 4, 5)).lower(
                sds((4096, width)), sds((4096, width)), pool, pool, ring,
                ring, *tables, sds((), jnp.int32),
                sds((), jnp.int32)).compile()]
        for program in compiled:
            rep = pool_relayout_report(program, [pool, ring])
            assert rep["pool_relayout_copies"] == 0, rep
            # the two work lists' reductions hold 2.2 MB (PR 36; 0.2 before)
            assert rep["temp_size_in_bytes"] < 4e6, rep

    def test_each_table_has_one_work_list_a_step(self, v5e_chip,
                                                 monkeypatch):
        """Every full layer's call computes `page_walk` from the same
        lengths and every sliding layer's from `min(context + 1, window)`:
        a decode step of two layers of each kind holds the lists'
        reductions (the cumulative sum, the count of lanes behind an item)
        as often as a step of one of each, and no copy of either pool."""
        import re
        from collections import Counter
        from paddle_tpu.analysis import pool_relayout_report
        from paddle_tpu.models import decode_blocks as blocks

        def sds(shape, dtype=jnp.float32):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)
        width = self.HKV * self.D
        pool = sds((self.POOL, self.PAGE, width))
        ring = sds((1 + self.B * self.W // self.PAGE, self.PAGE, width))
        # the dispatch the models' layers call, as on the chip
        monkeypatch.setattr(pa, "_on_tpu", lambda: True)
        monkeypatch.setattr(pa, "_check_compiles_grouped", lambda *a: None)

        def step(layers):
            def run(q, k, v, pools, rings, bt, cl, slots, active):
                from paddle_tpu.models.decode_cache import (KV, KV_WINDOW,
                                                            PagedKVCache)
                kp, vp = ([p[0] for p in pools], [p[1] for p in pools])
                rk, rv = ([r[0] for r in rings], [r[1] for r in rings])
                c = PagedKVCache(
                    kp, vp, bt, cl, self.PAGE, self.H, self.D,
                    layer_kinds=[KV, KV_WINDOW] * layers,
                    num_kv_heads=self.HKV, window_k=rk, window_v=rv,
                    window=self.W)
                ctx = jnp.take(cl, slots, mode="clip")
                rows = jnp.take(bt, slots, axis=0, mode="clip")
                for i in range(layers):
                    q = q + blocks.paged_decode_attention(c, i, q, k, v, rows,
                                                          ctx, active)
                    q = q + blocks.ring_decode_attention(c, i, q, k, v, slots,
                                                         ctx, active)
                return (q, list(zip(c.k_pages, c.v_pages)),
                        list(zip(c.window_k, c.window_v)))
            rows = sds((self.B, width))
            return jax.jit(run, donate_argnums=(3, 4)).lower(
                sds((self.B, self.H, self.D)), rows, rows,
                [(pool, pool)] * layers, [(ring, ring)] * layers,
                sds((self.B, self.PER_SEQ), jnp.int32),
                sds((self.B,), jnp.int32), sds((self.B,), jnp.int32),
                sds((self.B,), jnp.bool_)).compile()

        def ops(compiled):
            return Counter(re.findall(r" = \S+ ([a-z\-]+)\(",
                                      compiled.as_text()))

        before = pa._stats["grouped"]
        one, two = step(1), step(2)
        assert pa._stats["grouped"] == before + 6
        n1, n2 = ops(one), ops(two)
        assert n1["custom-call"] >= 2 and \
            n2["custom-call"] - n1["custom-call"] >= 2      # the kernels
        assert n1["reduce-window"] + n1["reduce"] >= 4, n1  # two lists
        for op in ("reduce-window", "reduce", "iota", "sort"):
            assert n2[op] == n1[op], (op, n1[op], n2[op])
        rep = pool_relayout_report(two, [pool, ring])
        assert rep["pool_relayout_copies"] == 0, rep

    @pytest.mark.parametrize("window", [1024, None], ids=["band", "causal"])
    @pytest.mark.parametrize("L", [64, 4096])
    def test_the_flash_forward_takes_the_window_at_highest(self, v5e_chip,
                                                           L, window):
        from paddle_tpu.ops.pallas import flash_attention as fa

        def sds(heads):
            return jax.ShapeDtypeStruct((1, L, heads, self.D), jnp.float32,
                                        sharding=v5e_chip)
        compiled = jax.jit(lambda q, k, v: fa._fa_fwd_pallas(
            q, k, v, None, True, float(1 / np.sqrt(self.D)),
            blocks=fa._static_blocks(L, L), window=window,
            precision="highest")[0]).lower(
                sds(self.H), sds(self.HKV), sds(self.HKV)).compile()
        assert "tpu_custom_call" in compiled.as_text()
        # K and V are never repeated: nothing of q's size beside q's own
        # transposes in and out
        assert compiled.memory_analysis().temp_size_in_bytes \
            <= 2.1 * L * self.H * self.D * 4

    @pytest.mark.parametrize("tokens", [64, 4096], ids=["decode", "prefill"])
    def test_the_swiglu_products_read_the_stacked_weights_in_place(
            self, v5e_chip, tokens, monkeypatch):
        from paddle_tpu.ops import moe
        monkeypatch.setattr(moe, "_on_tpu", lambda: True)

        def sds(shape, dtype=jnp.float32):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)
        assert moe._tiles(tokens * 8, 2304, 1792) == (64, 2304, 256)
        assert moe._tiles(tokens * 8, 896, 2304) == (64, 896, 768)
        compiled = jax.jit(
            lambda u, e, w, w1, w2: moe.held_experts(
                u, e, w, w1, w2, form="swiglu")).lower(
            sds((tokens, 2304)), sds((tokens, 8), jnp.int32),
            sds((tokens, 8)), sds((32, 1792, 2304)),
            sds((32, 896, 2304))).compile()
        text = compiled.as_text()
        assert text.count("tpu_custom_call") >= 2
        made = [line for line in text.splitlines()
                if (" = f32[32,1792,2304]" in line
                    or " = f32[32,896,2304]" in line)
                and " parameter(" not in line]
        assert not made, made[:2]
        # rows, the stacked halves and the output of every assignment
        assert compiled.memory_analysis().temp_size_in_bytes \
            < 5 * tokens * 8 * 2304 * 4 + 1e6


# ------------ two rows a lane: a model that verifies a draft (PR 37) ----------


class TestKExaoneShapesCompileForTheChip:
    """`kexaone_236b_a23b`'s pools hold 8 K/V heads of 128 for 64 query
    heads: 4,097 pages for a full layer and for the MTP block, a ring of
    128 tokens for each of 32 slots for a sliding one; a decode iteration
    brings TWO rows a lane (`decode_blocks.*_rows_attention`: 64 lanes of
    the one-query kernel); its expert layers stack 8 SwiGLU experts of
    6144 x 2048. Compiled for a described v5e, nothing runs."""

    H, HKV, D, B, R, W, PAGE, POOL, PER_SEQ = 64, 8, 128, 32, 2, 128, 16, \
        4097, 128

    def test_two_rows_a_lane_update_both_pool_shapes_in_place(
            self, v5e_chip, monkeypatch):
        from paddle_tpu.analysis import pool_relayout_report
        from paddle_tpu.models import decode_blocks as blocks
        from paddle_tpu.models.decode_cache import (KV, KV_WINDOW,
                                                    PagedKVCache)

        def sds(shape, dtype=jnp.float32):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)
        width = self.HKV * self.D
        pool = sds((self.POOL, self.PAGE, width))
        ring = sds((1 + self.B * self.W // self.PAGE, self.PAGE, width))
        # the dispatch the model's layers call, as on the chip
        monkeypatch.setattr(pa, "_on_tpu", lambda: True)
        monkeypatch.setattr(pa, "_check_compiles_grouped", lambda *a: None)

        def step(q, k, v, kp, vp, rk, rv, bt, cl, slots, active):
            c = PagedKVCache([kp], [vp], bt, cl, self.PAGE, self.H, self.D,
                             layer_kinds=[KV, KV_WINDOW],
                             num_kv_heads=self.HKV, window_k=[rk],
                             window_v=[rv], window=self.W)
            ctx = jnp.take(cl, slots, mode="clip")
            rows = jnp.take(bt, slots, axis=0, mode="clip")
            a = blocks.paged_rows_attention(c, 0, q, k, v, rows, ctx, active)
            b = blocks.ring_rows_attention(c, 0, q, k, v, slots, ctx, active)
            return (a + b, c.k_pages[0], c.v_pages[0], c.window_k[0],
                    c.window_v[0])

        before = pa._stats["grouped"]
        rows = sds((self.B, self.R, width))
        compiled = jax.jit(step, donate_argnums=(3, 4, 5, 6)).lower(
            sds((self.B, self.R, self.H, self.D)), rows, rows, pool, pool,
            ring, ring, sds((self.B, self.PER_SEQ), jnp.int32),
            sds((self.B,), jnp.int32), sds((self.B,), jnp.int32),
            sds((self.B,), jnp.bool_)).compile()
        # the pages once at 64 lanes, the ring twice at 32
        assert pa._stats["grouped"] == before + 3
        assert compiled.as_text().count("tpu_custom_call") >= 3
        rep = pool_relayout_report(compiled, [pool, ring])
        assert rep["pool_relayout_copies"] == 0, rep
        assert rep["temp_size_in_bytes"] < 8e6, rep

    @pytest.mark.parametrize("window", [128, None], ids=["band", "causal"])
    @pytest.mark.parametrize("L", [64, 2048])
    def test_the_flash_forward_takes_the_window_at_highest(self, v5e_chip,
                                                           L, window):
        from paddle_tpu.ops.pallas import flash_attention as fa

        def sds(heads):
            return jax.ShapeDtypeStruct((1, L, heads, self.D), jnp.float32,
                                        sharding=v5e_chip)
        compiled = jax.jit(lambda q, k, v: fa._fa_fwd_pallas(
            q, k, v, None, True, float(1 / np.sqrt(self.D)),
            blocks=fa._static_blocks(L, L), window=window,
            precision="highest")[0]).lower(
                sds(self.H), sds(self.HKV), sds(self.HKV)).compile()
        assert "tpu_custom_call" in compiled.as_text()
        assert compiled.memory_analysis().temp_size_in_bytes \
            <= 2.1 * L * self.H * self.D * 4

    @pytest.mark.parametrize("tokens", [64, 2048], ids=["decode", "prefill"])
    def test_the_swiglu_products_read_the_stacked_weights_in_place(
            self, v5e_chip, tokens, monkeypatch):
        from paddle_tpu.ops import moe
        monkeypatch.setattr(moe, "_on_tpu", lambda: True)

        def sds(shape, dtype=jnp.float32):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)
        compiled = jax.jit(
            lambda u, e, w, w1, w2: moe.held_experts(
                u, e, w, w1, w2, form="swiglu")).lower(
            sds((tokens, 6144)), sds((tokens, 8), jnp.int32),
            sds((tokens, 8)), sds((8, 4096, 6144)),
            sds((8, 2048, 6144))).compile()
        text = compiled.as_text()
        assert text.count("tpu_custom_call") >= 2
        made = [line for line in text.splitlines()
                if (" = f32[8,4096,6144]" in line
                    or " = f32[8,2048,6144]" in line)
                and " parameter(" not in line]
        assert not made, made[:2]
        # rows, the stacked halves and the output of every assignment
        assert compiled.memory_analysis().temp_size_in_bytes \
            < 5 * tokens * 8 * 6144 * 4 + 1e6
