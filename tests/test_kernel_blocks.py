"""Block picks of the Pallas kernel families.

Each family maps a shape to its blocks in one function of its own file
(`flash_attention._resolve_flash_blocks`, `layer_norm._block_rows_for`,
`softmax_ce._static_blocks`, `fused_bn._block_rows_for`,
`fused_conv_bn._blocks_for`; `paged_attention` gives one program all the
heads and picks the pages a grid step in `pages_per_step`, PR 34).
`_CELL_PICKS` pins what they return at the shapes the benchmark's
cells trace: a PR that moves a pick changes this table in the open.

The pinned values were read off the parent commit (2d1e18f, PR 28), which
still had the run-time autotuner, with the tuner off as every cell runs it,
before the tuner was deleted (PR 29):

    git archive 2d1e18f | tar -x -C /tmp/parent && cd /tmp/parent
    PADDLE_TPU_AUTOTUNE=0 JAX_PLATFORMS=cpu PYTHONPATH=. python -c '
    import jax, jax.numpy as jnp
    from paddle_tpu.ops.pallas import (flash_attention as fa, layer_norm as ln,
        softmax_ce as ce, paged_attention as pa, fused_bn as bn,
        fused_conv_bn as cbn)
    S = lambda B, L, H, D, dt: jax.ShapeDtypeStruct((B, L, H, D), jnp.dtype(dt))
    q = S(8, 1024, 12, 64, "bfloat16")          # and so on down the table
    print(fa._resolve_flash_blocks(q, q, None, True),  # ((fwd), (bwd)) | None
          1024 * 64 * 4 <= fa._FUSED_BWD_DQ_BYTES,
          ln._block_rows_for(8192, 768, q.dtype),       # where _ln_fwd's gate lets it
          ce._blocks_for(8192, 50304, q.dtype),
          pa._resolve_cfg(jnp.dtype("float32"), 12, 64, 16, 64),  # impl, heads
          bn._block_rows_for(q.dtype, 100352, 512, True),
          cbn._resolve_cfg(q.dtype, 100352, 128, 512, False))'

Forward and backward blocks were equal in every row, `impl` was 1 wherever
the shape gate let a kernel run, and the decode buckets were
`_pow2_buckets(1, max_batch)` (tests/test_serving_v2.py::test_decode_buckets).
"""
import hashlib
import json
import os
import zlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.framework import flags
from paddle_tpu.ops import moe
from paddle_tpu.ops.pallas import tiling
from paddle_tpu.ops.pallas import flash_attention as fa
from paddle_tpu.ops.pallas import fused_bn as fb
from paddle_tpu.ops.pallas import fused_conv_bn as fcb
from paddle_tpu.ops.pallas import layer_norm as ln
from paddle_tpu.ops.pallas import paged_attention as pa
from paddle_tpu.ops.pallas import softmax_ce as sce


class TestKernelParity:
    """`blocks=` / `block_rows=` stay kernel arguments: parity of each
    kernel with itself across block shapes other than the family's pick.

    Row-block extents only regroup rows across programs — every row's math
    is identical, so outputs are BIT-compatible across row-block choices
    (layer_norm, fused_bn, softmax_ce block_n, flash block_q). Reduction-
    walk extents (softmax_ce block_v, flash block_k) change the online-
    accumulation grouping, so those assert tight f32 allclose instead.
    """

    def test_layer_norm_block_rows_bitwise(self):
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(512, 256)).astype("float32"))
        g = jnp.asarray(rng.normal(size=(256,)).astype("float32"))
        b = jnp.asarray(rng.normal(size=(256,)).astype("float32"))
        outs = [ln._ln_fwd_pallas(x, g, b, eps=1e-5, block_rows=br,
                                  interpret=True)
                for br in (256, 128, 512)]
        for o in outs[1:]:
            assert np.array_equal(np.asarray(outs[0]), np.asarray(o))

    def test_fused_bn_block_rows_bitwise(self):
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.normal(size=(512, 128)).astype("float32"))
        k = jnp.asarray(rng.normal(size=(128,)).astype("float32"))
        c = jnp.asarray(rng.normal(size=(128,)).astype("float32"))
        fwd = [fb._bn_act_fwd_pallas(x, None, k, c, act="relu",
                                     has_add=False, interpret=True,
                                     block_rows=br)
               for br in (256, 128)]
        assert np.array_equal(np.asarray(fwd[0]), np.asarray(fwd[1]))
        dx = [fb._bn_bwd_dx_pallas(x, fwd[0], x, k, c, c, act="relu",
                                   has_add=False, interpret=True,
                                   block_rows=br)[0]
              for br in (256, 128)]
        assert np.array_equal(np.asarray(dx[0]), np.asarray(dx[1]))
        # the per-channel reductions accumulate across row blocks — block
        # choice changes the f32 addition grouping, so allclose here
        red = [fb._bn_bwd_reduce_pallas(x, fwd[0], x, k, c, act="relu",
                                        interpret=True, block_rows=br)
               for br in (256, 128)]
        np.testing.assert_allclose(np.asarray(red[0][0]),
                                   np.asarray(red[1][0]), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(red[0][1]),
                                   np.asarray(red[1][1]), rtol=1e-5)

    def test_softmax_ce_block_variants(self):
        rng = np.random.default_rng(2)
        N, V = 128, 4096
        lg = jnp.asarray(rng.normal(size=(N, V)).astype("float32") * 3)
        lb = jnp.asarray(rng.integers(0, V, (N,)).astype("int32"))
        base_nll, base_lse = sce._ce_fwd_pallas(lg, lb, blocks=(128, 2048),
                                                interpret=True)
        # row-block change: bit-compatible
        nll_n, _ = sce._ce_fwd_pallas(lg, lb, blocks=(64, 2048),
                                      interpret=True)
        assert np.array_equal(np.asarray(base_nll), np.asarray(nll_n))
        # vocab-walk change: online-lse grouping differs -> tight allclose
        nll_v, _ = sce._ce_fwd_pallas(lg, lb, blocks=(128, 1024),
                                      interpret=True)
        np.testing.assert_allclose(np.asarray(base_nll),
                                   np.asarray(nll_v), rtol=1e-6, atol=1e-6)
        dn = jnp.ones((N,), jnp.float32)
        dl = [sce._ce_bwd_pallas(lg, lb, base_lse, dn, blocks=bl,
                                 interpret=True)
              for bl in ((128, 2048), (64, 1024))]
        # bwd is one pure per-block pass (no cross-block accumulation):
        # bit-compatible across BOTH block dims
        assert np.array_equal(np.asarray(dl[0]), np.asarray(dl[1]))

    def test_flash_block_variants(self):
        rng = np.random.default_rng(3)
        B, L, H, D = 1, 256, 2, 64
        q = jnp.asarray(rng.normal(size=(B, L, H, D)).astype("float32"))
        k = jnp.asarray(rng.normal(size=(B, L, H, D)).astype("float32"))
        v = jnp.asarray(rng.normal(size=(B, L, H, D)).astype("float32"))
        sc = float(1.0 / np.sqrt(D))
        base, base_lse = fa._fa_fwd_pallas(q, k, v, None, True, sc,
                                           interpret=True, blocks=(128, 128))
        # q-block change: rows regroup only -> bit-compatible
        out_q, _ = fa._fa_fwd_pallas(q, k, v, None, True, sc,
                                     interpret=True, blocks=(64, 128))
        assert np.array_equal(np.asarray(base), np.asarray(out_q))
        # k-block change: online-softmax grouping differs -> allclose
        out_k, _ = fa._fa_fwd_pallas(q, k, v, None, True, sc,
                                     interpret=True, blocks=(128, 256))
        np.testing.assert_allclose(np.asarray(base), np.asarray(out_k),
                                   rtol=1e-5, atol=1e-5)
        do = jnp.asarray(rng.normal(size=(B, L, H, D)).astype("float32"))
        g1 = fa._fa_bwd_fused_pallas(q, k, v, base, base_lse, do, None,
                                     True, sc, interpret=True,
                                     blocks=(128, 128))
        g2 = fa._fa_bwd_fused_pallas(q, k, v, base, base_lse, do, None,
                                     True, sc, interpret=True,
                                     blocks=(64, 256))
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)


# ----------------------- the picks at the cells' shapes ----------------------

def _sds(B, L, H, D, dtype):
    return jax.ShapeDtypeStruct((B, L, H, D), jnp.dtype(dtype))


def _flash_pick(monkeypatch, B, L, H, D, dtype):
    """(blocks | "small", which backward kernel a gradient would run)."""
    q = _sds(B, L, H, D, dtype)
    blocks = fa._resolve_flash_blocks(q, q, None)
    ran = []
    for name in ("_fa_small_bwd_pallas", "_fa_bwd_fused_pallas",
                 "_fa_bwd_pallas"):
        monkeypatch.setattr(
            fa, name, lambda *a, _n=name, **kw: ran.append((_n, kw)))
    fa._bwd_any(q, q, q, q, None, q, None, True, 1.0, False, True, blocks)
    (name, kw), = ran
    assert kw.get("blocks") == blocks  # forward and backward: one pair
    bwd = {"_fa_small_bwd_pallas": "small", "_fa_bwd_fused_pallas": "fused",
           "_fa_bwd_pallas": "split"}[name]
    return ("small" if blocks is None else blocks), bwd


def _paged_pick(monkeypatch, H, D, page_size, n_pages):
    """(head block, pages a grid step) the dispatch hands the kernel, or
    "xla". The kernel and its compile check are stubbed: nothing runs but
    the XLA gather."""
    seen = []
    monkeypatch.setattr(pa, "_INTERPRET", True)
    monkeypatch.setattr(pa, "_check_compiles", lambda *a: None)
    monkeypatch.setattr(pa, "_paged_attn_pallas",
                        lambda q, *a, **kw: seen.append(a[-2:]) or q)
    q = jnp.ones((1, H, D), jnp.float32)
    pool = jnp.ones((2, page_size, H * D), jnp.float32)
    pa.paged_attention(q, pool, pool, jnp.zeros((1, n_pages), jnp.int32),
                       jnp.asarray([3], jnp.int32))
    (pick,) = seen or ["xla"]
    return pick


def _grouped_pick(monkeypatch, H, Hkv, D, page_size, n_pages):
    """Pages a grid step the dispatch hands the grouped kernel (query
    heads on fewer K/V heads), or "xla"; kernel and check stubbed."""
    seen = []
    monkeypatch.setattr(pa, "_INTERPRET", True)
    monkeypatch.setattr(pa, "_check_compiles_grouped", lambda *a: None)
    monkeypatch.setattr(pa, "_paged_attn_grouped_pallas",
                        lambda q, *a, **kw: seen.append(a[-1]) or q)
    q = jnp.ones((1, H, D), jnp.float32)
    pool = jnp.ones((2, page_size, Hkv * D), jnp.float32)
    pa.paged_attention(q, pool, pool, jnp.zeros((1, n_pages), jnp.int32),
                       jnp.asarray([3], jnp.int32))
    (pick,) = seen or ["xla"]
    return pick


_PICK = {
    "flash": _flash_pick,
    "paged_attn": _paged_pick,
    "paged_attn_grouped": _grouped_pick,
    # None: the shape stays on XLA (the unfused composition for conv_bn)
    "layer_norm": lambda mp, R, N: ln._block_rows_for(R, N),
    "softmax_ce": lambda mp, N, V: sce._static_blocks(N, V),
    "fused_bn": lambda mp, R, C: fb._block_rows_for(R, C),
    "conv_bn": lambda mp, R, Cin, Cout: fcb._blocks_for(R, Cin, Cout),
    # the forward-only flash path (window, precision, grouped K/V heads)
    "flash_forward": lambda mp, L: fa._static_blocks(L, L),
    # megablox's (rows, k, n) tiles of an [m, k] x [k, n] grouped product
    "moe_tiles": lambda mp, m, k, n: moe._tiles(m, k, n),
}

# (family, shape, pick). Flash: (B, L, H, D, dtype), causal, no mask, ->
# (blocks | "small", backward kernel). The serving rows are the engines'
# prefill buckets (B=1, float32), on both sides of `_SMALL_MAX_L` = 512
# and of the small path's VMEM bound, which H moves: first the powers of
# two from the first the kernel takes (L >= 64) to max_len, the buckets
# every cell ran until PR 38 (`_pow2_buckets(16, max_len)`; rows kept
# letter for letter: the ladder still holds 256, 512, ... and a test may
# pass any bucket), then, under "the ladder's steps between them", the
# 3/2 steps of `serving._prefill_ladder(max_len)` (768, 1536; 3072 for
# max_len 5120) that PR 38 added to each cell.
# the flash pick at the ladder length the default blocks do not divide:
# at 768 the k tail of (256, 512) beat (256, 384) at five head shapes of
# seven (PERF.md section 6, PR 38, the chip chain), so it stays
FLASH_768 = (256, 512)

_CELL_PICKS = [
    # gpt2s_train_b8s1024: bf16 compute, b8 x s1024
    ("flash", (8, 1024, 12, 64, "bfloat16"), ((256, 512), "fused")),
    ("layer_norm", (8192, 768), 256),
    ("softmax_ce", (8192, 50304), (256, 2048)),
    # gpt2s_serve_closed32: H12 x D64, max_len 1024, page 16
    ("flash", (1, 64, 12, 64, "float32"), ("small", "small")),
    ("flash", (1, 128, 12, 64, "float32"), ("small", "small")),
    ("flash", (1, 256, 12, 64, "float32"), ((256, 256), "fused")),
    ("flash", (1, 512, 12, 64, "float32"), ((256, 512), "fused")),
    ("flash", (1, 1024, 12, 64, "float32"), ((256, 512), "fused")),
    ("layer_norm", (512, 768), 256),      # a prefill bucket
    ("layer_norm", (256, 768), 256),      # the first bucket at the floor
    ("layer_norm", (128, 768), None),     # a prefill bucket under it
    ("layer_norm", (32, 768), None),      # decode, 32 lanes
    ("paged_attn", (12, 64, 16, 64), (12, 8)),
    # gpt3xl_serve_closed16: H16 x D128, max_len 2048
    ("flash", (1, 64, 16, 128, "float32"), ("small", "small")),
    ("flash", (1, 128, 16, 128, "float32"), ((128, 128), "fused")),
    ("flash", (1, 256, 16, 128, "float32"), ((256, 256), "fused")),
    ("flash", (1, 512, 16, 128, "float32"), ((256, 512), "fused")),
    ("flash", (1, 1024, 16, 128, "float32"), ((256, 512), "fused")),
    ("flash", (1, 2048, 16, 128, "float32"), ((256, 512), "fused")),
    ("layer_norm", (2048, 2048), 256),
    ("layer_norm", (16, 2048), None),     # decode, 16 lanes
    ("paged_attn", (16, 128, 16, 128), (16, 4)),
    # the same model's heads over four chips (`decode_step_tp`): the local
    # slice of 4 heads
    ("paged_attn", (4, 128, 16, 128), (4, 8)),
    # olmoh7b_serve_closed32: H30 x D128 in the full-attention layers
    # (RMSNorm, so no layer_norm rows), max_len 2048
    ("flash", (1, 64, 30, 128, "float32"), ((64, 64), "fused")),
    ("flash", (1, 128, 30, 128, "float32"), ((128, 128), "fused")),
    ("flash", (1, 256, 30, 128, "float32"), ((256, 256), "fused")),
    ("flash", (1, 512, 30, 128, "float32"), ((256, 512), "fused")),
    ("flash", (1, 1024, 30, 128, "float32"), ((256, 512), "fused")),
    ("flash", (1, 2048, 30, 128, "float32"), ((256, 512), "fused")),
    ("paged_attn", (30, 128, 16, 128), (30, 2)),
    # nemo3n_serve_closed64: 32 held experts of 2688 x 1856, top-6; the 64
    # lanes' 384 assignments and a prefill bucket's
    ("paged_attn_grouped", (32, 2, 128, 16, 128), 32),
    ("moe_tiles", (384, 2688, 1856), (32, 2688, 128)),
    ("moe_tiles", (384, 1856, 2688), (32, 512, 896)),
    ("moe_tiles", (6 * 512, 2688, 1856), (64, 2688, 128)),
    ("moe_tiles", (6 * 512, 1856, 2688), (64, 512, 896)),
    # mellum2_serve_closed64_code: 32 query heads on 4 K/V heads of 128,
    # prefill buckets to 4096 and max_len 5120 through the forward-only
    # flash path; 32 held SwiGLU experts of 2304 x 896 (gate and up
    # stacked to 1792), top-8
    # (its full layers' table of 320 slots, its sliding layers' ring of 64)
    ("paged_attn_grouped", (32, 4, 128, 16, 320), 32),
    ("paged_attn_grouped", (32, 4, 128, 16, 64), 32),
    ("flash_forward", (64,), (64, 64)),
    ("flash_forward", (256,), (256, 256)),
    ("flash_forward", (1024,), (256, 512)),
    ("flash_forward", (4096,), (256, 512)),
    ("flash_forward", (5120,), (256, 512)),
    ("moe_tiles", (8 * 64, 2304, 1792), (64, 2304, 256)),
    ("moe_tiles", (8 * 64, 896, 2304), (64, 896, 768)),
    ("moe_tiles", (8 * 16, 2304, 1792), (32, 2304, 256)),
    ("moe_tiles", (8 * 4096, 2304, 1792), (64, 2304, 256)),
    ("moe_tiles", (8 * 4096, 896, 2304), (64, 896, 768)),
    # ---- the ladder's steps between the powers of two (PR 38) ----
    # gpt2s_serve_closed32 (max_len 1024): 768
    ("flash", (1, 768, 12, 64, "float32"), (FLASH_768, "fused")),
    ("layer_norm", (768, 768), 256),
    # gpt3xl_serve_closed16 (max_len 2048): and 1536
    ("flash", (1, 768, 16, 128, "float32"), (FLASH_768, "fused")),
    ("flash", (1, 1536, 16, 128, "float32"), ((256, 512), "fused")),
    ("layer_norm", (768, 2048), 256),
    ("layer_norm", (1536, 2048), 256),
    # olmoh7b_serve_closed32 (max_len 2048)
    ("flash", (1, 768, 30, 128, "float32"), (FLASH_768, "fused")),
    ("flash", (1, 1536, 30, 128, "float32"), ((256, 512), "fused")),
    # nemo3n_serve_closed64 (max_len 2048; its prompt attention is the
    # masked product, no flash rows): top-6 of a bucket's rows
    ("moe_tiles", (6 * 768, 2688, 1856), (64, 2688, 128)),
    ("moe_tiles", (6 * 768, 1856, 2688), (64, 512, 896)),
    ("moe_tiles", (6 * 1536, 2688, 1856), (64, 2688, 128)),
    ("moe_tiles", (6 * 1536, 1856, 2688), (64, 512, 896)),
    # mellum2_serve_closed64_code (max_len 5120): and 3072; top-8
    ("flash_forward", (768,), FLASH_768),
    ("flash_forward", (1536,), (256, 512)),
    ("flash_forward", (3072,), (256, 512)),
    ("moe_tiles", (8 * 768, 2304, 1792), (64, 2304, 256)),
    ("moe_tiles", (8 * 768, 896, 2304), (64, 896, 768)),
    ("moe_tiles", (8 * 1536, 2304, 1792), (64, 2304, 256)),
    ("moe_tiles", (8 * 1536, 896, 2304), (64, 896, 768)),
    ("moe_tiles", (8 * 3072, 2304, 1792), (64, 2304, 256)),
    ("moe_tiles", (8 * 3072, 896, 2304), (64, 896, 768)),
    # kexaone_serve_closed32_reason (max_len 2048; 64 on 8 heads through
    # the forward-only path, rows above): 8 held SwiGLU experts of
    # 6144 x 2048 (gate and up stacked to 4096), top-8
    ("moe_tiles", (8 * 256, 6144, 4096), (64, 512, 512)),
    ("moe_tiles", (8 * 768, 6144, 4096), (64, 512, 512)),
    ("moe_tiles", (8 * 768, 2048, 6144), (64, 2048, 256)),
    ("moe_tiles", (8 * 1536, 2048, 6144), (64, 2048, 256)),
    # no cell: a head size off the lane groups takes the XLA gather
    ("paged_attn", (8, 80, 16, 8), "xla"),
    # no cell: a table under the pick's bound, and pools twice as wide
    ("paged_attn_grouped", (32, 4, 128, 16, 8), 8),
    ("paged_attn_grouped", (64, 8, 128, 16, 320), 16),
    # no cell yet (ROADMAP D4): ResNet-50 b128 NHWC bottleneck stages,
    # [N*H*W, C] and the 1x1 convs (R, Cin, Cout)
    ("fused_bn", (128 * 28 * 28, 512), 256),
    ("fused_bn", (128 * 14 * 14, 1024), 256),
    ("fused_bn", (128 * 7 * 7, 2048), 256),
    ("fused_bn", (128 * 56 * 56, 64), None),    # channels off the lanes
    ("conv_bn", (128 * 28 * 28, 128, 512), (256, 256)),
    ("conv_bn", (128 * 14 * 14, 256, 1024), (256, 256)),
    ("conv_bn", (128 * 7 * 7, 512, 2048), (256, 256)),
    ("conv_bn", (128 * 28 * 28, 512, 128), (256, 128)),
]


def _case_id(case):
    family, shape = case[0], case[1]
    return family + "-" + "x".join(str(d) for d in shape)


@pytest.mark.parametrize("family,shape,pick", _CELL_PICKS,
                         ids=[_case_id(c) for c in _CELL_PICKS])
def test_pick_at_cell_shape(family, shape, pick, monkeypatch):
    assert _PICK[family](monkeypatch, *shape) == pick


@pytest.mark.parametrize("H,D,n_pages", [(12, 64, 64), (16, 128, 128),
                                         (4, 128, 128), (30, 128, 128)])
def test_a_full_heads_call_keeps_its_kernel_counters_and_pick(
        H, D, n_pages, monkeypatch):
    """The grouped kernel's own pick (PR 36) moves nothing for a pool that
    holds every query head: the call counts under "folded", never under
    "grouped", and its pages a step are `pages_per_step`'s."""
    before = dict(pa._stats)
    pick = _paged_pick(monkeypatch, H, D, 16, n_pages)
    assert pick == (H, pa.pages_per_step(H * D, 16, 4, n_pages))
    moved = {k: pa._stats[k] - before[k] for k in before}
    assert moved == {**dict.fromkeys(before, 0), "pallas": 1, "folded": 1}


# --------------- the picks run, under the Pallas interpreter -----------------

def _randn(rng, shape, dtype="float32"):
    return jnp.asarray(rng.normal(size=shape).astype(np.float32)
                       ).astype(dtype)


def _run_flash(blocks, L, D):
    """The grid-walk (or small-path) forward and fused backward at
    `blocks`, batch and heads cut to 2 (grid-parallel dims), against the
    XLA composition."""
    rng = np.random.default_rng(L + D)
    q, k, v, do = (_randn(rng, (2, L, 2, D)) for _ in range(4))
    sc = float(1.0 / np.sqrt(D))
    if blocks == "small":
        out, lse = fa._fa_small_fwd_pallas(q, k, v, None, True, sc,
                                           interpret=True)
        grads = fa._fa_small_bwd_pallas(q, k, v, out, lse, do, None, True,
                                        sc, interpret=True)
    else:
        out, lse = fa._fa_fwd_pallas(q, k, v, None, True, sc,
                                     interpret=True, blocks=blocks)
        grads = fa._fa_bwd_fused_pallas(q, k, v, out, lse, do, None, True,
                                        sc, interpret=True, blocks=blocks)
    ref, vjp = jax.vjp(lambda q, k, v: fa.flash_attention_xla(
        q, k, v, causal=True, scale=sc), q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)
    for g, r in zip(grads, vjp(do)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=2e-3, atol=2e-4)


def _run_layer_norm(block_rows, R, N):
    rng = np.random.default_rng(R + N)
    x, g, b = _randn(rng, (R, N)), _randn(rng, (N,)), _randn(rng, (N,))
    out = ln._ln_fwd_pallas(x, g, b, eps=1e-5, block_rows=block_rows,
                            interpret=True)
    mean, rstd = ln._ln_stats_xla(x, 1e-5)
    ref = (x - mean[:, None]) * rstd[:, None] * g + b
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def _run_paged(pick, D, page_size, n_pages):
    """`pick` = (heads in all: the pick is every head to one program,
    pages a grid step); two sequences."""
    block_h, pages = pick
    rng = np.random.default_rng(block_h + D + pages)
    H, P = block_h, 2 * n_pages + 1
    q = _randn(rng, (2, H, D))
    kp, vp = (_randn(rng, (P, page_size, H * D)) for _ in range(2))
    bt = jnp.asarray(rng.integers(1, P, (2, n_pages)).astype(np.int32))
    cl = jnp.asarray([n_pages * page_size - 3, page_size + 1], jnp.int32)
    sc = float(1.0 / np.sqrt(D))
    out = pa._paged_attn_pallas(q, kp, vp, bt, cl, sc, block_h, pages,
                                interpret=True)
    ref = pa.paged_attention_xla(q, kp, vp, bt, cl, scale=sc)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=0, atol=5e-6)


def _run_grouped(pages, G, Hkv, page_size, n_pages):
    """The grouped kernel at `pages` a grid step, `G` query heads on each
    of `Hkv` K/V heads of 128; two sequences."""
    rng = np.random.default_rng(pages + G)
    D, P = 128, 2 * n_pages + 1
    q = _randn(rng, (2, Hkv * G, D))
    kp, vp = (_randn(rng, (P, page_size, Hkv * D)) for _ in range(2))
    bt = jnp.asarray(rng.integers(1, P, (2, n_pages)).astype(np.int32))
    cl = jnp.asarray([n_pages * page_size - 3, page_size + 1], jnp.int32)
    sc = float(1.0 / np.sqrt(D))
    out = pa._paged_attn_grouped_pallas(q, kp, vp, bt, cl, sc, pages,
                                        interpret=True)
    ref = pa._paged_attention_grouped_xla(q, kp, vp, bt, cl, sc)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=0, atol=5e-6)


# the rows of `_CELL_PICKS` with L <= 512 whose blocks differ, batch and
# heads cut to 2
_RUN = [
    ("flash", _run_flash, ("small", 128, 64)),
    ("flash", _run_flash, ((256, 256), 256, 64)),
    ("flash", _run_flash, ((256, 512), 512, 64)),
    ("flash", _run_flash, ((64, 64), 64, 128)),
    ("flash", _run_flash, ((128, 128), 128, 128)),
    ("flash", _run_flash, ((256, 512), 512, 128)),
    # the ladder's step at 768 (PR 38): a k block with a tail of 256
    ("flash", _run_flash, (FLASH_768, 768, 64)),
    ("layer_norm", _run_layer_norm, (256, 512, 768)),
    ("layer_norm", _run_layer_norm, (256, 256, 2048)),
    ("layer_norm", _run_layer_norm, (256, 768, 768)),   # a ladder step
    # each paged-attention pick's pages a step, over a table they do not
    # divide
    ("paged_attn", _run_paged, ((2, 8), 64, 16, 11)),
    ("paged_attn", _run_paged, ((2, 4), 128, 16, 11)),
    ("paged_attn", _run_paged, ((2, 8), 128, 16, 11)),
    ("paged_attn", _run_paged, ((2, 2), 128, 16, 11)),
    # the grouped pick at both cells' head groupings (8 and 16 query
    # heads a K/V head), over a table it does not divide
    ("paged_attn_grouped", _run_grouped, (32, 8, 2, 16, 40)),
    ("paged_attn_grouped", _run_grouped, (32, 16, 1, 16, 40)),
]


@pytest.mark.parametrize(
    "family,run,args", _RUN,
    ids=[f"{f}-" + "x".join(str(a).replace(" ", "") for a in args)
         for f, _, args in _RUN])
def test_pick_runs_under_the_interpreter(family, run, args):
    run(*args)


# ----------------------- what used to change a pick --------------------------

def _write_parent_cache_entry(root, H, D, page_size, n_pages):
    """An on-disk entry of the deleted tuner, as the parent wrote them
    (CRC'd JSON under a hashed name), saying `impl=0`, the XLA gather, won
    `paged_attn` at this shape: read by the parent, it sent every such
    call to `paged_attention_xla`."""
    def sha(s):
        return hashlib.sha1(s.encode()).hexdigest()
    bucket = 1
    while bucket < n_pages:
        bucket <<= 1
    key = [H, D, page_size, bucket, "float32"]
    chip = (jax.devices()[0].device_kind.strip().replace(" ", "_")
            + "+interpret")
    space = sha("|".join(sorted(["impl0-heads0", f"impl1-heads{H}"])))[:12]
    payload = {"version": 1, "op": "paged_attn", "key": key, "chip": chip,
               "config": {"names": ["impl", "heads"], "dims": [0, 0]},
               "probe_ms": 0.1, "tuned_at": 0.0}
    name = sha(json.dumps(["paged_attn", key, chip, space],
                          sort_keys=True))[:16]
    blob = json.dumps(payload, sort_keys=True).encode()
    with open(os.path.join(root, f"paged_attn-{name}.json"), "w") as f:
        json.dump({"crc32": zlib.crc32(blob) & 0xFFFFFFFF,
                   "payload": payload}, f)


def _picks_and_paged_stats(monkeypatch):
    """Every row of `_CELL_PICKS` and what one real paged-attention
    dispatch (interpreter, H4 x D64, page 8) counts."""
    with monkeypatch.context() as mp:
        picks = [_PICK[f](mp, *shape) for f, shape, _ in _CELL_PICKS]
    tiling.reset_compile_checks()
    monkeypatch.setattr(pa, "_INTERPRET", True)
    before = dict(pa._stats)
    q = jnp.ones((1, 4, 64), jnp.float32)
    pool = jnp.ones((4, 8, 256), jnp.float32)
    pa.paged_attention(q, pool, pool, jnp.zeros((1, 2), jnp.int32),
                       jnp.asarray([5], jnp.int32))
    return picks, {k: pa._stats[k] - before[k] for k in before}


@pytest.mark.parametrize("knob,value", [
    ("PADDLE_TPU_AUTOTUNE", "force"),
    ("PADDLE_TPU_AUTOTUNE_CACHE_DIR", None),  # a directory with an entry
    ("PADDLE_TPU_AUTOTUNE_MAX_CONFIGS", "1"),
    ("PADDLE_TPU_AUTOTUNE_BUDGET_S", "0"),
    ("PADDLE_TPU_AUTOTUNE_REPEATS", "9"),
])
def test_old_knobs_are_not_read(knob, value, monkeypatch, tmp_path):
    for name in list(os.environ):
        if name.startswith("PADDLE_TPU_AUTOTUNE"):
            monkeypatch.delenv(name)
    plain = _picks_and_paged_stats(monkeypatch)
    assert plain[0] == [pick for _, _, pick in _CELL_PICKS]
    assert plain[1] == {"pallas": 1, "folded": 1, "grouped": 0, "xla": 0,
                        "append": 0, "cow": 0}

    _write_parent_cache_entry(str(tmp_path), 4, 64, 8, 2)
    on_disk = {n: open(tmp_path / n, "rb").read()
               for n in os.listdir(tmp_path)}
    # the tuner consulted the other four only while it was tuning
    monkeypatch.setenv("PADDLE_TPU_AUTOTUNE", "force")
    monkeypatch.setenv(knob, str(tmp_path) if value is None else value)
    assert _picks_and_paged_stats(monkeypatch) == plain
    assert {n: open(tmp_path / n, "rb").read()
            for n in os.listdir(tmp_path)} == on_disk


@pytest.mark.parametrize("name", ["FLAGS_autotune",
                                  "FLAGS_autotune_cache_dir"])
def test_unknown_flag(name):
    with pytest.raises(ValueError, match="unknown flag"):
        flags.set_flags({name: "1"})
    assert name not in flags.all_flags()
