"""Paged KV-cache decode attention: vLLM-style PagedAttention for TPU.

Autoregressive decode is the serving hot loop: one query token per
sequence attends over that sequence's whole generated context. A dense
per-sequence KV cache `[B, max_len, H, D]` wastes HBM on short sequences
and forces whole-cache reallocation as sequences grow; following vLLM
(Kwon et al., 2023), K/V live in a shared pool of fixed-size PAGES

    k_pages, v_pages: [num_pages, page_size, num_heads * head_dim]

(the heads FOLDED into the minor axis, see "Why the pool is folded" below)
and each sequence owns a BLOCK TABLE of page indices

    block_tables: [B, pages_per_seq] int32   (unused slots -> page 0)
    context_lens: [B] int32                  (tokens stored per sequence)

so memory is allocated page-at-a-time and fragmentation is bounded by
one page per sequence. Page 0 is the NULL page by convention: the
serving allocator never hands it out, idle batch slots point every
block-table entry at it, and the cache-append scatter parks dead slots'
writes there.

Decode attention (one query token per sequence) gathers the scattered
pages:

* the Pallas kernel: grid ``(head-blocks, items)`` under a
  :class:`PrefetchScalarGridSpec`. An ITEM is one live page group of one
  sequence: ``P`` consecutive slots of its block table that hold at least
  one of its tokens (`page_walk` lists them from the context lengths,
  inside the same program; the grid's bound is their number, traced, so
  the walk follows the contexts and not the table's shape: a slot without
  a live token is no grid step). The scalar-prefetched work list and block
  table drive the q, k/v and output BlockSpec index maps, so each grid
  step DMAs ``P`` folded pages ``[page_size, heads * head_dim]`` of K and
  of V from wherever they live in the pool into VMEM (the pipeline
  double-buffers page fetches against compute) and makes one
  online-softmax update over their ``P * page_size`` rows, the state
  carried across a sequence's items in VMEM scratch, per-head sums taken
  by masked lane reductions. Dispatch gives one program all the heads and
  picks ``P`` by the pool's shape (`pages_per_step`).
* the kernel for GROUPED K/V heads (``Hkv < H``): the same walk over the
  same list, all the query heads of a lane to a grid step; the pools stay
  in HBM and the kernel copies a step's pages itself, one step ahead, and
  its products run on the MXU as float32's bfloat16 parts, K and V
  crossing it once a part (`_paged_attn_grouped_kernel`,
  `grouped_pages_per_step`).
* the kernel for LATENT rows (`latent_attention`, DeepSeek-V2's absorbed
  decode): ONE pool, a row a token that is every head's key and, in its
  first lanes, every head's value; all the query heads of a lane read a
  row once for both (`_paged_attn_latent_kernel`).
* ``paged_attention_xla``: gather pages via ``k_pages[block_tables]``,
  mask past ``context_lens``, dense softmax. The path off the TPU, for
  fp16 and for head sizes the kernel's lane groups do not take, and the
  CI parity reference.

`cache_append` is the matching single-token K/V scatter; its eager form
is jitted with the page pools DONATED, so the steady-state decode loop
updates the (potentially multi-GB) pool in place instead of copying it
per token.

Layout convention (paddle): q is [batch, heads, head_dim] (ONE decode
token per sequence); a page carries [page_size, heads * head_dim], one
token a row, head h in lanes [h * head_dim, (h + 1) * head_dim).

Why the pool is folded (PERF.md section 5, PR 26). The arguments and
results of a jitted program are held to the device's DEFAULT layout for
their shape. For a 4-D f32 array whose last two dimensions do not fill an
(8, 128) tile, e.g. GPT-2's (12, 64), the TPU's default puts the largest
dimension, the pages, in the lanes (`{0,3,2,1}`), while the scatter and
the kernel need row-major: donation aliases the buffers and the compiler
still copies every pool once on the way in and once on the way out of
every decode and prefill program (83 % of the device time when serving
GPT-2 small). `[pages, page_size, H*D]` is laid out row-major and
unpadded whenever `H*D` is a multiple of 128, so the programs update the
pools in place. Shapes whose tiles are filled exactly (`H % 8 == 0` and
`D % 128 == 0` in f32) never had the copies; folded and 4-D are the same
bytes in the same tiles there. To check a new shape ahead of time, without
a chip: `analysis.pool_relayout_report` on the program compiled for a
described topology, as `tests/test_paged_attention.py::TestPoolLayout`
does (it wants 0 copies of pool shape and a `temp` smaller than one pool).
The entry points here also take 4-D pools and 3-D new rows (tests, eager
callers off the hot path): scatters work on any trailing shape, attention
folds by a reshape.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas import tpu as pltpu

from . import tiling as _tiling
from .tiling import on_tpu as _on_tpu

_NEG = -1e30
_LANE = _tiling.LANE

# dispatch decisions, counted at trace time (reset freely in tests)
# ("folded": those of "pallas" that went to the full-heads kernel, whose
# walk visits live page groups only, `pages_per_step` pages a grid step
# (PR 34); "grouped": those that went to the kernel for grouped K/V heads,
# which walks the same list, `grouped_pages_per_step` pages a step (PR 36);
# "latent": calls of `latent_attention` on either path, "latent_pallas"
# those that took its kernel)
_stats = {"pallas": 0, "folded": 0, "grouped": 0, "xla": 0, "append": 0,
          "cow": 0, "latent": 0, "latent_pallas": 0}

# tests set True: the kernel runs in the Pallas interpreter on CPU, so
# the real gather/online-softmax logic is exercised without a TPU
_INTERPRET = False


def _folded(pool):
    """A pool as the kernels and the engine hold it: [pages, page, H*D]."""
    return pool if pool.ndim == 3 else pool.reshape(*pool.shape[:2], -1)


def _rows_like(pool, new):
    """New K/V rows [N, H, D] or [N, H*D] in the pool's own trailing
    shape, so that one scatter serves folded and 4-D pools."""
    return new.reshape(new.shape[0], *pool.shape[2:]).astype(pool.dtype)


# ------------------------------ XLA reference --------------------------------


def paged_attention_xla(q, k_pages, v_pages, block_tables, context_lens,
                        scale=None):
    """Dense gather reference: correct for every shape, and the CPU
    path. A sequence with ``context_lens==0`` (idle serving slot) outputs
    exactly zero. It unfolds the pool itself
    (a copy on the TPU, which this path can afford). A pool that holds
    fewer K/V heads than q has heads (grouped K/V heads) takes
    `_paged_attention_grouped_xla`."""
    B, H, D = q.shape
    page_size = k_pages.shape[1]
    n_pages = block_tables.shape[1]
    if scale is None:
        scale = 1.0 / np.sqrt(D)
    if _kv_heads(k_pages, D) != H:
        return _paged_attention_grouped_xla(q, k_pages, v_pages,
                                            block_tables, context_lens, scale)
    k_pages = k_pages.reshape(*k_pages.shape[:2], H, D)
    v_pages = v_pages.reshape(*v_pages.shape[:2], H, D)
    # [B, n_pages, page_size, H, D] -> [B, L_max, H, D]
    k = k_pages[block_tables].reshape(B, n_pages * page_size, H, D)
    v = v_pages[block_tables].reshape(B, n_pages * page_size, H, D)
    s = jnp.einsum("bhd,blhd->bhl", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    pos = jnp.arange(n_pages * page_size, dtype=jnp.int32)[None, None, :]
    live = pos < context_lens[:, None, None]
    s = jnp.where(live, s, _NEG)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    p = jnp.where(live, p, 0.0)
    l = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    out = jnp.einsum("bhl,blhd->bhd", p / l, v.astype(jnp.float32))
    # fully-empty sequence: m == _NEG everywhere -> p all zero -> out 0
    return out.astype(q.dtype)


def _kv_heads(pool, D: int) -> int:
    """K/V heads a pool holds: folded [.., Hkv*D] or 4-D [.., Hkv, D]."""
    return pool.shape[2] // D if pool.ndim == 3 else pool.shape[2]


def _paged_attention_grouped_xla(q, k_pages, v_pages, block_tables,
                                 context_lens, scale):
    """Grouped K/V heads: q ``[B, H, D]`` over pools of ``Hkv`` heads, H a
    multiple of Hkv; query head h reads K/V head ``h // (H // Hkv)``. The
    pages are gathered FOLDED, as the pool stores them, and a K/V head is
    a slice of their lanes: unfolding the pool first made the compiler
    re-lay out every pool whole in every decode step (four copies of 100
    MB a layer in the compiled step of Nemotron-3-Nano's 2 K/V heads of
    128). The group is an axis of q and of the scores, K/V are never
    repeated, and the products run at `highest`: a float32 pool is served
    as float32."""
    B, H, D = q.shape
    k_pages, v_pages = _folded(k_pages), _folded(v_pages)
    Hkv = k_pages.shape[2] // D
    if H % Hkv:
        raise ValueError(f"{H} query heads do not divide over {Hkv} K/V "
                         f"heads")
    G = H // Hkv
    hi = jax.lax.Precision.HIGHEST
    L = block_tables.shape[1] * k_pages.shape[1]
    k = k_pages[block_tables].reshape(B, L, Hkv * D).astype(jnp.float32)
    v = v_pages[block_tables].reshape(B, L, Hkv * D).astype(jnp.float32)
    live = (jnp.arange(L, dtype=jnp.int32)[None, None, :]
            < context_lens[:, None, None])
    out = []
    for h in range(Hkv):
        lanes = slice(h * D, (h + 1) * D)
        qh = q[:, h * G:(h + 1) * G].astype(jnp.float32)        # [B, G, D]
        s = jnp.einsum("bgd,bld->bgl", qh, k[..., lanes],
                       precision=hi) * scale
        s = jnp.where(live, s, _NEG)
        p = jnp.where(live, jnp.exp(s - jnp.max(s, -1, keepdims=True)), 0.0)
        p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
        out.append(jnp.einsum("bgl,bld->bgd", p, v[..., lanes],
                              precision=hi))
    return jnp.concatenate(out, axis=1).astype(q.dtype)


# ------------------------------ Pallas kernel --------------------------------


def _lane_groups(width: int, D: int):
    """Lane ranges of a folded block that are worked on together: one
    128-lane tile holding 128/D whole heads (the last tile of a block may
    be narrower) or, where D > 128, one head."""
    step = max(D, _LANE)
    return [(lo, min(lo + step, width)) for lo in range(0, width, step)]


def _head_sums(x, D: int):
    """x [rows, w] (one lane group) -> the same shape, every lane holding
    the sum over the D lanes of its own head. Lane reductions under lane
    masks on the XLU. The same sums as three bfloat16 products with a 0/1
    matrix on the MXU (exact to float32) took 0-14 % longer a call at the
    four measured shapes' picks (PERF.md section 6, PR 34)."""
    w = x.shape[1]
    if D >= w:
        if w > _LANE:  # one head over several tiles: add the tiles first
            t = x[:, :_LANE]
            for lo in range(_LANE, w, _LANE):
                t = t + x[:, lo:lo + _LANE]
            x = t
        return jnp.sum(x, axis=-1, keepdims=True)       # broadcasts back
    head = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1) // D
    out = None
    for j in range(w // D):
        mine = head == j
        t = jnp.sum(jnp.where(mine, x, 0.0), axis=-1, keepdims=True)
        out = jnp.broadcast_to(t, x.shape) if out is None \
            else jnp.where(mine, t, out)
    return out


# pages a grid step of the full-heads kernel may hold: its blocks (2 pools x
# 2 buffers x P pages) within 2 MiB and P within 8. Least ms a call of P = 1 /
# 2 / 4 / 8 at float32, page 16 (PERF.md section 5, PR 34's chain): 0.450 /
# 0.332 / 0.317 / 0.335 at a folded width of 2048 (P = 4 is 2 MiB), 1.343 /
# 1.199 / 1.218 / 1.250 at 3840 (P = 2 is 1.9 MiB), 0.294 / 0.194 / 0.161 /
# 0.152 at 768 and 0.236 / 0.150 / 0.117 / 0.110 at 512; past 8 not measured
_STEP_BYTES = 2 << 20
_MAX_PAGES = 8


def pages_per_step(width: int, page_size: int, itemsize: int,
                    n_pages: int) -> int:
    """P, the pages of K and of V one grid step of the full-heads walk
    holds, from what a call can observe: the folded width `H*D`, the page
    size, the pool's bytes an element and the table's width. A step's own
    cost (its bookkeeping, the softmax state's update) is paid once for P
    pages, and a lane's last step fetches and computes up to P - 1 pages
    past its context: P doubles while the step's blocks stay within
    `_STEP_BYTES` and P within `_MAX_PAGES` and the table
    (`tests/test_kernel_blocks.py` pins the picks at the cells' shapes)."""
    return _pages_that_fit(2 * 2 * page_size * width * itemsize, n_pages,
                           _STEP_BYTES, _MAX_PAGES)


def _pages_that_fit(page: int, n_pages: int, step_bytes: int,
                    max_pages: int) -> int:
    """The largest power of two of pages (`page` bytes each, all of a
    step's buffers counted) within `step_bytes`, `max_pages` and the
    table."""
    P = 1
    while 2 * P <= min(max_pages, n_pages) and 2 * P * page <= step_bytes:
        P *= 2
    return P


def page_walk(context_lens, span: int, n_groups: int):
    """The work list of one call of the page walk: one item for every
    (lane, live page group), ``max(1, ceil(ctx / span))`` items a lane, so
    that an idle lane (``ctx == 0``) still has the one step that writes
    its zeros. Returns int32 ``(lane of item, group of item)``, each
    ``[B * n_groups]`` (the items first, lane by lane and group by group,
    the rest never visited), and the number of items ``[1]``. Computed
    inside the program that calls the kernel; every layer of a decode step
    computes it from the same lengths, and XLA keeps one copy."""
    B = context_lens.shape[0]
    per_lane = jnp.clip(-(-context_lens.astype(jnp.int32) // span), 1,
                        n_groups)
    ends = jnp.cumsum(per_lane)
    t = jnp.arange(B * n_groups, dtype=jnp.int32)
    lane = jnp.minimum(
        jnp.sum(t[:, None] >= ends[None, :], axis=1, dtype=jnp.int32), B - 1)
    group = jnp.minimum(t - (ends - per_lane)[lane], n_groups - 1)
    return lane, group, ends[-1:]


def page_group_counts(context_lens, span: int):
    """(live, walked) page groups of one call over these context lengths
    (NumPy, on the host: `ServingEngine.stats`): the groups of `span`
    tokens that hold a live token, and the grid steps `page_walk` makes,
    which differ by the idle lanes' one step each."""
    groups = -(-np.asarray(context_lens, np.int64) // span)
    return int(groups.sum()), int(np.maximum(groups, 1).sum())


def _paged_attn_kernel(bt_ref, cl_ref, lane_ref, group_ref, q_ref, *refs,
                       page_size, scale, D, P):
    """Grid (head-blocks, items); the item axis is the minormost,
    sequentially-executed dim and walks `page_walk`'s list: item t is page
    group `group_ref[t]` of lane `lane_ref[t]`, a lane's items are
    consecutive and carry its online-softmax state in VMEM scratch. The
    block table itself picked which pages this step's k/v blocks were
    DMA'd from (see the BlockSpec index maps in `_paged_attn_pallas`).

    A block is a FOLDED page [page, heads * D]: a row is one token, a
    head is D lanes of it; a step holds P of K and P of V (`refs`: P K
    blocks, P V blocks, the output, three scratches) and makes ONE
    softmax update over their P * page rows. One query token per head
    makes the scores a matrix-VECTOR product per head, so the products run
    on the VPU at the page's own layout. The softmax state is kept per
    lane (every lane of a head carries that head's max and sum):
    everything but the per-head sum of q * k is elementwise work and
    reductions over rows."""
    from jax.experimental import pallas as pl

    k_refs, v_refs = refs[:P], refs[P:2 * P]
    o_ref, acc_ref, m_ref, l_ref = refs[2 * P:]
    t = pl.program_id(1)
    g = group_ref[t]
    ctx = cl_ref[lane_ref[t]]
    span = P * page_size

    @pl.when(g == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # false only in an idle lane's one item: the list holds no other
    # group without a live token
    @pl.when(g * span < ctx)
    def _compute():
        for lo, hi in _lane_groups(k_refs[0].shape[-1], D):
            qb = q_ref[:, lo:hi].astype(jnp.float32)        # [1, w]
            kb = jnp.concatenate([r[:, lo:hi] for r in k_refs],
                                 axis=0).astype(jnp.float32)  # [span, w]
            vb = jnp.concatenate([r[:, lo:hi] for r in v_refs],
                                 axis=0).astype(jnp.float32)
            s = _head_sums(qb * kb, D) * scale
            pos = g * span + jax.lax.broadcasted_iota(
                jnp.int32, kb.shape, 0)
            live = pos < ctx
            s = jnp.where(live, s, _NEG)
            m_prev = m_ref[:, lo:hi]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
            # the last live group's tail rows are masked, not underflowed:
            # exp(_NEG - m) is 0 only where m is real
            p = jnp.where(live, jnp.exp(s - m_new), 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_ref[:, lo:hi] = l_ref[:, lo:hi] * corr + jnp.sum(
                p, axis=0, keepdims=True)
            acc_ref[:, lo:hi] = acc_ref[:, lo:hi] * corr + jnp.sum(
                p * vb, axis=0, keepdims=True)
            m_ref[:, lo:hi] = m_new

    @pl.when(g == jnp.maximum(pl.cdiv(ctx, span), 1) - 1)
    def _finalize():
        # ctx == 0 (idle slot): acc and l still zero -> exactly zero
        o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                      ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "block_h", "pages",
                                             "interpret"))
def _paged_attn_pallas(q, k_pages, v_pages, block_tables, context_lens,
                       scale, block_h, pages, interpret=False):
    """q [B, H, D] over pools [num_pages, page_size, H * D]; `block_h`
    heads (block_h * D lanes) and `pages` pages of K and of V to a grid
    step. The grid's item axis follows the contexts, not the table: its
    bound is the number of (lane, live page group) items of `page_walk`,
    traced, as megablox's tile count is, so a page group without a live
    token costs nothing. A step's pages are `pages` blocks of the same
    pool, each with its own entry of the block table (a slot past the
    table's end reads its last entry, and is masked by its position)."""
    from jax.experimental import pallas as pl

    B, H, D = q.shape
    k_pages, v_pages = _folded(k_pages), _folded(v_pages)
    page_size = k_pages.shape[1]
    n_pages = block_tables.shape[1]
    w = block_h * D
    P = pages
    lane, group, n_items = page_walk(context_lens, P * page_size,
                                     pl.cdiv(n_pages, P))

    # the scalar-prefetched work list and block table drive the page
    # fetch: item t DMAs pool pages block_tables[lane[t], group[t] * P + j]
    # — this is the paged gather, done by the Pallas pipeline's own
    # double-buffered DMA
    def page(j):
        return pl.BlockSpec(
            (None, page_size, w),
            lambda h, t, bt, cl, ln, gr: (
                bt[ln[t], jnp.minimum(gr[t] * P + j, n_pages - 1)], 0, h))

    # q and the output ride as [B, 1, H*D]: Mosaic refuses a (1, w) block
    # of a [B, H*D] array
    qspec = pl.BlockSpec((None, 1, w),
                         lambda h, t, bt, cl, ln, gr: (ln[t], 0, h))
    kv = [page(j) for j in range(P)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(pl.cdiv(H, block_h), n_items[0]),
        in_specs=[qspec] + kv + kv,
        out_specs=qspec,
        scratch_shapes=[pltpu.VMEM((1, w), jnp.float32)] * 3,
    )
    # the item axis carries the softmax state from one item of a lane to
    # its next -> ARBITRARY. v5e has one core a chip; a chip with two
    # would want the list cut in two halves of equal work at a lane's
    # edge, a leading PARALLEL axis of 2 over them (the head blocks are
    # PARALLEL already, but dispatch gives one program all the heads)
    params = None if interpret else pltpu.CompilerParams(
        dimension_semantics=(pltpu.PARALLEL, pltpu.ARBITRARY))
    out = pl.pallas_call(
        functools.partial(_paged_attn_kernel, page_size=page_size,
                          scale=scale, D=D, P=P),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, 1, H * D), q.dtype),
        compiler_params=params,
        interpret=interpret,
    )(block_tables, context_lens, lane, group, q.reshape(B, 1, H * D),
      *([k_pages] * P), *([v_pages] * P))
    return out.reshape(B, H, D)


# pages a grid step of the grouped kernel holds: its buffers (2 pools x 2
# slots x P pages) within 4 MiB and P within 32. Least ms a call of P = 4 /
# 8 / 16 / 32 at float32, page 16, 64 lanes of 32 query heads (PERF.md
# section 5, PR 36's chain): 2.563 / 1.468 / 1.143 / 1.092 at a folded width
# of 512 and a table of 320 (contexts 734-4,863), 1.117 / 0.632 / 0.488 /
# 0.440 at 512 and a ring of 64 pages, 0.557 / 0.337 / 0.283 / 0.294 at 256
# and a table of 128 (contexts 99-1,332: a step of 512 tokens is half dead
# there); past 32 not measured. Page copies written out a turn of the
# kernel's copy loop: 1 / 4 / 8 / 16 / all 32 took 1.235 / 1.115 / 1.092 /
# 1.080 / 1.045 ms at the first shape, and all 32 cost `mellum2`'s cell 48 s
# of warm set-up (its decode programs' trace and lowering)
_GROUPED_STEP_BYTES = 4 << 20
_GROUPED_MAX_PAGES = 32
_COPIES_UNROLLED = 8


def grouped_pages_per_step(width: int, page_size: int, itemsize: int,
                           n_pages: int) -> int:
    """P of the grouped kernel, as `pages_per_step` is the full-heads
    kernel's: from the folded width `Hkv*D`, the page size, the pool's
    bytes an element and the table's width. A step pays its own cost (the
    packed q tile, the softmax state's update, a transpose of the scores)
    once for P pages, and a lane's last step computes up to P - 1 pages
    past its context: P doubles while the two slots of both pools stay
    within `_GROUPED_STEP_BYTES` and P within `_GROUPED_MAX_PAGES` and the
    table (`tests/test_kernel_blocks.py` pins the picks at the cells'
    shapes)."""
    return _pages_that_fit(2 * 2 * page_size * width * itemsize, n_pages,
                           _GROUPED_STEP_BYTES, _GROUPED_MAX_PAGES)


def _bf16_parts(x):
    """x as float32 arrays that are each exactly a bfloat16 and sum to x
    exactly: one for a bfloat16 input, three for float32 (the top 8
    significant bits, the next 8, the last 8, by masks: no rounding, no
    conversion). A product of two parts is exact in float32, so a matrix
    product of parts in ONE pass of the MXU is exact up to its float32
    accumulation."""
    if x.dtype == jnp.bfloat16:
        return (x.astype(jnp.float32),)
    x = x.astype(jnp.float32)

    def top(v):
        bits = jax.lax.bitcast_convert_type(v, jnp.uint32)
        return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                            jnp.float32)
    hi = top(x)
    rest = x - hi
    mid = top(rest)
    return hi, mid, rest - mid


def _one_pass(a, b, contract):
    """One pass of the MXU over operands that hold bfloat16 values."""
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               precision=jax.lax.Precision.DEFAULT,
                               preferred_element_type=jnp.float32)


def _sum_row_blocks(x, n: int):
    """x [n * r, c] -> [r, c], the sum of its n blocks of r rows."""
    r = x.shape[0] // n
    out = x[:r]
    for i in range(1, n):
        out = out + x[i * r:(i + 1) * r]
    return out


def _paged_attn_grouped_kernel(bt_ref, cl_ref, lane_ref, group_ref, n_ref,
                               q_ref, k_hbm, v_hbm, o_ref, acc_ref, m_ref,
                               l_ref, k_buf, v_buf, sem, *, page_size,
                               n_pages, scale, D, G, P):
    """Grouped K/V heads. Grid (items): `page_walk`'s list, as the
    full-heads kernel walks it (item t is page group `group_ref[t]` of lane
    `lane_ref[t]`, a lane's items consecutive, its softmax state in VMEM
    scratch, a row a query head: `m_ref`, `l_ref` [H, 128], every lane the
    same). q_ref holds ALL the query heads of the lane, [H, D] with
    H = Hkv * G: query heads h*G .. h*G+G-1 read K/V head h, the lanes
    [h*D, (h+1)*D) of a page.

    The pools stay in HBM and the kernel fetches a step's P pages of K and
    of V itself, one async copy a page into one of two slots (`k_buf`,
    `v_buf` [2, P * page, Hkv * D]): item t + 1's copies start before item
    t is computed. As 2P blocks of the pipeline the same pages cost 56 ns
    of its bookkeeping each, serial with the compute (PERF.md section 6,
    PR 36).

    The products are float32's, made of bfloat16 parts (`_bf16_parts`):
    every part of one operand against every part of the other, nine exact
    partial products where `highest` keeps six, accumulated in float32.
    They are arranged so that K and V cross the MXU once a part. Scores:
    K's three parts stacked as ROWS stream against one latched tile that
    holds q's parts side by side, head by head in their K/V head's lanes
    (`q_rows`), the sum over K's parts is a sum of row blocks and the sum
    over q's parts a sum of row blocks of the transpose [3H, span].
    Weighted sum: p's parts stacked as rows stream against each latched
    part of V."""
    from jax.experimental import pallas as pl

    t = pl.program_id(0)
    g = group_ref[t]
    ctx = cl_ref[lane_ref[t]]
    span = P * page_size
    W = k_buf.shape[-1]
    H = (W // D) * G
    slot = jax.lax.rem(t, 2)

    def copies(item, slot):
        """The 2P page copies of `item` into `slot`; None waits for them
        (a wait needs the copy's shape and semaphore, not its source). A
        loop of `_COPIES_UNROLLED` pages a turn: written out whole the
        kernel's text, and with it the seconds a program that calls it
        takes to trace and lower, grows with P (PERF.md section 6,
        PR 36: a cell's warm `setup_s`); one page a turn leaves the
        table's lookups nothing to overlap with."""
        unrolled = min(P, _COPIES_UNROLLED)

        def turn(n, carry):
            for u in range(unrolled):
                j = n * unrolled + u
                page = 0 if item is None else bt_ref[
                    lane_ref[item],
                    jnp.minimum(group_ref[item] * P + j, n_pages - 1)]
                rows = pl.ds(pl.multiple_of(j * page_size, page_size),
                             page_size)
                for i, (hbm, buf) in enumerate(((k_hbm, k_buf),
                                                (v_hbm, v_buf))):
                    copy = pltpu.make_async_copy(
                        hbm.at[page], buf.at[slot, rows], sem.at[i, slot])
                    copy.wait() if item is None else copy.start()
            return carry
        jax.lax.fori_loop(0, P // unrolled, turn, 0)

    @pl.when(t == 0)
    def _first():
        copies(0, 0)

    @pl.when(t + 1 < n_ref[0])
    def _next():
        copies(t + 1, 1 - slot)

    @pl.when(g == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    copies(None, slot)

    # false only in an idle lane's one item
    @pl.when(g * span < ctx)
    def _compute():
        q_parts = _bf16_parts(q_ref[...])                       # [H, D] each
        row_head = jax.lax.broadcasted_iota(jnp.int32, (H, D), 0) // G
        # row (part, query head) holds its part of q in the lanes of its
        # K/V head and zeros elsewhere; whole lane tiles of rows
        q_rows = jnp.concatenate(
            [jnp.concatenate([jnp.where(row_head == h, x, 0.0)
                              for h in range(W // D)], axis=1)
             for x in q_parts], axis=0)
        pad = -len(q_parts) * H % _LANE
        if pad:
            q_rows = jnp.concatenate(
                [q_rows, jnp.zeros((pad, W), jnp.float32)], axis=0)
        k_parts = _bf16_parts(k_buf[slot])
        v_parts = _bf16_parts(v_buf[slot])
        s = _one_pass(jnp.concatenate(k_parts, axis=0), q_rows,
                      ((1,), (1,)))                     # [parts * span, R]
        s = _sum_row_blocks(s, len(k_parts)).T                  # [R, span]
        s = _sum_row_blocks(s[:len(q_parts) * H], len(q_parts)) * scale
        pos = g * span + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        live = pos < ctx
        s = jnp.where(live, s, _NEG)                            # [H, span]
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(live, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_ref[:, :1] * corr + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)
        for h in range(W // D):
            rows, lanes = slice(h * G, (h + 1) * G), slice(h * D, (h + 1) * D)
            p_parts = _bf16_parts(p[rows])
            p_rows = jnp.concatenate(p_parts, axis=0)           # [3G, span]
            pv = _one_pass(p_rows, v_parts[0][:, lanes], ((1,), (0,)))
            for v in v_parts[1:]:
                pv = pv + _one_pass(p_rows, v[:, lanes], ((1,), (0,)))
            acc_ref[rows, :] = (acc_ref[rows, :] * corr[rows]
                                + _sum_row_blocks(pv, len(p_parts)))

    @pl.when(g == jnp.maximum(pl.cdiv(ctx, span), 1) - 1)
    def _finalize():
        # ctx == 0 (idle slot): acc and l still zero -> exactly zero
        o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[:, :1], 1e-30)
                      ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "pages", "interpret"))
def _paged_attn_grouped_pallas(q, k_pages, v_pages, block_tables,
                               context_lens, scale, pages, interpret=False):
    """q [B, H, D] over pools [num_pages, page_size, Hkv * D], H a
    multiple of Hkv; `pages` pages of K and of V to a grid step. The grid
    is `page_walk`'s list of (lane, live page group) items, its bound
    their number, traced: a page group without a live token is no grid
    step, whatever the call's longest context. The pools are handed over
    where they lie (`pl.ANY`) and the kernel copies its pages itself, each
    by its own entry of the block table (a slot past the table's end reads
    its last entry, and is masked by its position)."""
    from jax.experimental import pallas as pl

    B, H, D = q.shape
    k_pages, v_pages = _folded(k_pages), _folded(v_pages)
    page_size, width = k_pages.shape[1:]
    n_pages = block_tables.shape[1]
    G = H // (width // D)
    P = pages
    lane, group, n_items = page_walk(context_lens, P * page_size,
                                     pl.cdiv(n_pages, P))
    qspec = pl.BlockSpec((None, H, D),
                         lambda t, bt, cl, ln, gr, n: (ln[t], 0, 0))
    pool = pl.BlockSpec(memory_space=pl.ANY)
    slots = pltpu.VMEM((2, P * page_size, width), k_pages.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(n_items[0],),
        in_specs=[qspec, pool, pool],
        out_specs=qspec,
        scratch_shapes=[pltpu.VMEM((H, D), jnp.float32),
                        pltpu.VMEM((H, _LANE), jnp.float32),
                        pltpu.VMEM((H, _LANE), jnp.float32),
                        slots, slots, pltpu.SemaphoreType.DMA((2, 2))],
    )
    # the items carry a lane's softmax state and the next item's copies
    params = None if interpret else pltpu.CompilerParams(
        dimension_semantics=(pltpu.ARBITRARY,))
    return pl.pallas_call(
        functools.partial(_paged_attn_grouped_kernel, page_size=page_size,
                          n_pages=n_pages, scale=scale, D=D, G=G, P=P),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        compiler_params=params,
        interpret=interpret,
    )(block_tables, context_lens, lane, group, n_items, q, k_pages, v_pages)


def _check_compiles_grouped(dtype, H: int, Hkv: int, D: int, page_size: int,
                            n_pages: int, pages: int):
    """Eager compile check of the grouped kernel (`tiling.compile_check`)."""
    def run():
        q = jnp.ones((2, H, D), dtype)
        kp = jnp.ones((max(n_pages, 2), page_size, Hkv * D), dtype)
        bt = jnp.zeros((2, n_pages), jnp.int32)
        cl = jnp.full((2,), page_size, jnp.int32)
        return _paged_attn_grouped_pallas(q, kp, kp, bt, cl,
                                          float(1.0 / np.sqrt(D)), pages,
                                          interpret=_INTERPRET)

    _tiling.compile_check(
        "paged_attn_grouped", run, dtype=jnp.dtype(dtype).name, heads=H,
        kv_heads=Hkv, head_dim=D, page_size=page_size, pages_per_seq=n_pages,
        pages_per_step=pages, interpret=_INTERPRET)


def _check_compiles(dtype, H: int, D: int, page_size: int, n_pages: int,
                    pages: int):
    """Eager compile check at the blocks dispatch uses, all H to a program
    and `pages` pages to a step (`tiling.compile_check`)."""
    def run():
        q = jnp.ones((2, H, D), dtype)
        kp = jnp.ones((max(n_pages, 2), page_size, H * D), dtype)
        bt = jnp.zeros((2, n_pages), jnp.int32)
        cl = jnp.full((2,), page_size, jnp.int32)
        return _paged_attn_pallas(q, kp, kp, bt, cl, float(1.0 / np.sqrt(D)),
                                 H, pages, interpret=_INTERPRET)

    _tiling.compile_check(
        "paged_attn", run, dtype=jnp.dtype(dtype).name, heads=H, head_dim=D,
        page_size=page_size, pages_per_seq=n_pages, block_heads=H,
        pages_per_step=pages, interpret=_INTERPRET)


def paged_attention(q, k_pages, v_pages, block_tables, context_lens,
                    scale=None):
    """Single-token decode attention over a paged KV pool.

    q [B, H, D]; k_pages/v_pages [num_pages, page_size, Hkv * D] (a 4-D
    [.., Hkv, D] pool is folded by a reshape), Hkv == H or, with grouped
    K/V heads, a whole fraction of it (query head h reads K/V head
    h // (H // Hkv): `_paged_attn_grouped_pallas`); block_tables
    [B, pages_per_seq] int32 (unused slots MUST index a valid page — the
    serving layer points them at the null page 0); context_lens [B]
    int32. Returns [B, H, D].

    Dispatch mirrors `flash_attention`: an eligible call takes the Pallas
    page walk, all heads to a program, after one eager compile check that
    raises (grouped K/V heads: the grouped kernel, all query heads of a
    sequence to a grid step); anything else (off the TPU without interpret
    mode, fp16, a head size off the lane groups) takes the XLA gather. Safe to call at
    trace time of an outer jit (the check runs eagerly at trace, like
    every kernel in this package)."""
    B, H, D = q.shape
    page_size = k_pages.shape[1]
    n_pages = block_tables.shape[1]
    if scale is None:
        scale = 1.0 / np.sqrt(D)
    Hkv = _kv_heads(k_pages, D)
    kernels = ((_on_tpu() or _INTERPRET)
               and q.dtype == k_pages.dtype == v_pages.dtype
               and q.dtype != jnp.dtype(jnp.float16)
               and isinstance(H, int))
    if kernels and Hkv != H and H % Hkv == 0 and (
            _INTERPRET or (D % _LANE == 0 and (H // Hkv) % 8 == 0)):
        # grouped K/V heads: a K/V head is a whole number of lane tiles
        # of the folded page and its query heads whole sublane tiles of q
        pages = grouped_pages_per_step(Hkv * D, page_size, q.dtype.itemsize,
                                       n_pages)
        _check_compiles_grouped(q.dtype, H, Hkv, D, page_size, n_pages, pages)
        _stats["pallas"] += 1
        _stats["grouped"] += 1
        return _paged_attn_grouped_pallas(q, k_pages, v_pages, block_tables,
                                          context_lens, float(scale), pages,
                                          interpret=_INTERPRET)
    # a head is a whole number of lane tiles or a whole fraction of one
    # (`_lane_groups`)
    eligible = kernels and Hkv == H and (D % _LANE == 0 or _LANE % D == 0)
    if eligible:
        pages = pages_per_step(H * D, page_size, q.dtype.itemsize, n_pages)
        _check_compiles(q.dtype, H, D, page_size, n_pages, pages)
        _stats["pallas"] += 1
        _stats["folded"] += 1
        return _paged_attn_pallas(q, k_pages, v_pages, block_tables,
                                 context_lens, float(scale), H, pages,
                                 interpret=_INTERPRET)
    _stats["xla"] += 1
    return paged_attention_xla(q, k_pages, v_pages, block_tables,
                               context_lens, scale=scale)


# ------------------------------ latent rows ----------------------------------
#
# Latent attention decoded in its ABSORBED form (`models/deepseek_v2.py`):
# each query head's query is projected into the latent space beside its
# rotary part, so that a head's score against token j is ``q_h . row_j``
# over the whole row and its output (before the head's own up-projection)
# is ``sum_j p_hj row_j[:Dv]``. One pool and one read of a row serve all
# the heads' scores and values.

# pages a grid step of the latent kernel holds: the two slots of its one
# pool within 4 MiB and P within 16 (at a row of 640 float32, 16 pages: 1.3
# MiB of slots and 2 MiB of the rows' bfloat16 parts); not tuned on the
# chip
_LATENT_STEP_BYTES = 4 << 20
_LATENT_MAX_PAGES = 16

# the partial products of two float32 operands' bfloat16 parts (hi, mid,
# lo) that the latent kernel makes: six, those `highest` keeps (the three
# left out are below float32's rounding of the sum)
_SIX_PASSES = ((0, 0), (0, 1), (1, 0), (0, 2), (2, 0), (1, 1))


def latent_pages_per_step(width: int, page_size: int, itemsize: int,
                          n_pages: int) -> int:
    """P of the latent kernel, as `grouped_pages_per_step`, for its ONE
    pool of rows `width` wide."""
    return _pages_that_fit(2 * page_size * width * itemsize, n_pages,
                           _LATENT_STEP_BYTES, _LATENT_MAX_PAGES)


def _passes(a_parts, b_parts, contract):
    """The product of two operands given as bfloat16 parts, in the passes
    of `_SIX_PASSES` (one where both are bfloat16)."""
    out = None
    for i, j in _SIX_PASSES:
        if i < len(a_parts) and j < len(b_parts):
            t = _one_pass(a_parts[i], b_parts[j], contract)
            out = t if out is None else out + t
    return out


def _paged_attn_latent_kernel(bt_ref, cl_ref, lane_ref, group_ref, n_ref,
                              q_ref, pool_hbm, o_ref, acc_ref, m_ref, l_ref,
                              buf, sem, *, page_size, n_pages, scale, P):
    """Grid (items): `page_walk`'s list, as the grouped kernel walks it;
    q_ref holds ALL the query heads of the lane ``[H, W]`` (a head's
    absorbed query, its rotary part, zeros), o_ref their outputs ``[H,
    Dv]``. The kernel copies a step's P pages of the one pool itself into
    one of two slots (`buf` [2, P * page, W]), item t + 1's before item t
    is computed, as `_paged_attn_grouped_kernel` does for two pools.

    Scores: every head against every row of the step, over all W lanes;
    values: the same rows' first Dv lanes. Both products are float32's,
    made of bfloat16 parts in the six passes of `_SIX_PASSES`."""
    from jax.experimental import pallas as pl

    t = pl.program_id(0)
    g = group_ref[t]
    ctx = cl_ref[lane_ref[t]]
    span = P * page_size
    Dv = o_ref.shape[-1]
    slot = jax.lax.rem(t, 2)

    def copies(item, slot):
        """The P page copies of `item` into `slot`; None waits for them."""
        unrolled = min(P, _COPIES_UNROLLED)

        def turn(n, carry):
            for u in range(unrolled):
                j = n * unrolled + u
                page = 0 if item is None else bt_ref[
                    lane_ref[item],
                    jnp.minimum(group_ref[item] * P + j, n_pages - 1)]
                copy = pltpu.make_async_copy(
                    pool_hbm.at[page],
                    buf.at[slot, pl.ds(pl.multiple_of(j * page_size,
                                                      page_size),
                                       page_size)],
                    sem.at[slot])
                copy.wait() if item is None else copy.start()
            return carry
        jax.lax.fori_loop(0, P // unrolled, turn, 0)

    @pl.when(t == 0)
    def _first():
        copies(0, 0)

    @pl.when(t + 1 < n_ref[0])
    def _next():
        copies(t + 1, 1 - slot)

    @pl.when(g == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    copies(None, slot)

    # false only in an idle lane's one item
    @pl.when(g * span < ctx)
    def _compute():
        row_parts = _bf16_parts(buf[slot])                    # [span, W]
        s = _passes(_bf16_parts(q_ref[...]), row_parts,
                    ((1,), (1,))) * scale                     # [H, span]
        pos = g * span + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        live = pos < ctx
        s = jnp.where(live, s, _NEG)
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(live, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_ref[:, :1] * corr + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)
        pv = _passes(_bf16_parts(p), tuple(x[:, :Dv] for x in row_parts),
                     ((1,), (0,)))                            # [H, Dv]
        acc_ref[...] = acc_ref[...] * corr + pv

    @pl.when(g == jnp.maximum(pl.cdiv(ctx, span), 1) - 1)
    def _finalize():
        # ctx == 0 (idle slot): acc and l still zero -> exactly zero
        o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[:, :1], 1e-30)
                      ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "value_width", "pages",
                                             "interpret"))
def _paged_attn_latent_pallas(q, pool, block_tables, context_lens, scale,
                              value_width, pages, interpret=False):
    """q [B, H, W] over a pool [num_pages, page_size, W]; `pages` pages to
    a grid step of `page_walk`'s list. Returns [B, H, value_width]."""
    from jax.experimental import pallas as pl

    B, H, W = q.shape
    page_size = pool.shape[1]
    n_pages = block_tables.shape[1]
    P = pages
    lane, group, n_items = page_walk(context_lens, P * page_size,
                                     pl.cdiv(n_pages, P))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(n_items[0],),
        in_specs=[pl.BlockSpec((None, H, W),
                               lambda t, bt, cl, ln, gr, n: (ln[t], 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((None, H, value_width),
                               lambda t, bt, cl, ln, gr, n: (ln[t], 0, 0)),
        scratch_shapes=[pltpu.VMEM((H, value_width), jnp.float32),
                        pltpu.VMEM((H, _LANE), jnp.float32),
                        pltpu.VMEM((H, _LANE), jnp.float32),
                        pltpu.VMEM((2, P * page_size, W), pool.dtype),
                        pltpu.SemaphoreType.DMA((2,))],
    )
    params = None if interpret else pltpu.CompilerParams(
        dimension_semantics=(pltpu.ARBITRARY,))
    return pl.pallas_call(
        functools.partial(_paged_attn_latent_kernel, page_size=page_size,
                          n_pages=n_pages, scale=scale, P=P),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, value_width), q.dtype),
        compiler_params=params,
        interpret=interpret,
    )(block_tables, context_lens, lane, group, n_items, q, pool)


def latent_attention_xla(q, pool, block_tables, context_lens, scale,
                         value_width: int):
    """The gather reference of `latent_attention`, at `highest`: the
    path off the TPU and the kernel's parity reference. A sequence with
    ``context_lens == 0`` outputs zeros."""
    B, H, W = q.shape
    L = block_tables.shape[1] * pool.shape[1]
    rows = pool[block_tables].reshape(B, L, W).astype(jnp.float32)
    hi = jax.lax.Precision.HIGHEST
    s = jnp.einsum("bhw,blw->bhl", q.astype(jnp.float32), rows,
                   precision=hi) * scale
    live = (jnp.arange(L, dtype=jnp.int32)[None, None, :]
            < context_lens[:, None, None])
    s = jnp.where(live, s, _NEG)
    p = jnp.where(live, jnp.exp(s - jnp.max(s, -1, keepdims=True)), 0.0)
    p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    return jnp.einsum("bhl,blv->bhv", p, rows[..., :value_width],
                      precision=hi).astype(q.dtype)


def _check_compiles_latent(dtype, H: int, W: int, value_width: int,
                           page_size: int, n_pages: int, pages: int):
    """Eager compile check of the latent kernel (`tiling.compile_check`)."""
    def run():
        q = jnp.ones((2, H, W), dtype)
        pool = jnp.ones((max(n_pages, 2), page_size, W), dtype)
        bt = jnp.zeros((2, n_pages), jnp.int32)
        cl = jnp.full((2,), page_size, jnp.int32)
        return _paged_attn_latent_pallas(q, pool, bt, cl, 1.0, value_width,
                                         pages, interpret=_INTERPRET)

    _tiling.compile_check(
        "paged_attn_latent", run, dtype=jnp.dtype(dtype).name, heads=H,
        width=W, value_width=value_width, page_size=page_size,
        pages_per_seq=n_pages, pages_per_step=pages, interpret=_INTERPRET)


def latent_attention(q, pool, block_tables, context_lens, scale: float,
                     value_width: int):
    """Single-token decode attention over a pool of LATENT rows.

    q [B, H, W]: each query head's query in the row's space (the absorbed
    latent query, its rotary part, zeros where the row is zero); pool
    [num_pages, page_size, W]; block_tables [B, pages_per_seq] int32;
    context_lens [B] int32. Returns [B, H, value_width]: each head's
    softmax(scale * q_h . row_j) over its context, applied to the rows'
    first `value_width` lanes. The kernel where it is eligible (on the TPU
    or in interpret mode; W and `value_width` whole lane tiles, H whole
    sublane tiles, after one eager compile check that raises), the XLA
    gather otherwise."""
    B, H, W = q.shape
    page_size = pool.shape[1]
    n_pages = block_tables.shape[1]
    _stats["latent"] += 1
    kernels = ((_on_tpu() or _INTERPRET) and q.dtype == pool.dtype
               and q.dtype != jnp.dtype(jnp.float16) and isinstance(H, int)
               and (_INTERPRET or (W % _LANE == 0 and value_width % _LANE == 0
                                   and H % 8 == 0)))
    if not kernels:
        return latent_attention_xla(q, pool, block_tables, context_lens,
                                    scale, value_width)
    pages = latent_pages_per_step(W, page_size, q.dtype.itemsize, n_pages)
    _check_compiles_latent(q.dtype, H, W, value_width, page_size, n_pages,
                           pages)
    _stats["latent_pallas"] += 1
    return _paged_attn_latent_pallas(q, pool, block_tables, context_lens,
                                     float(scale), int(value_width), pages,
                                     interpret=_INTERPRET)


def latent_append(pool, rows, block_tables, context_lens, active):
    """One latent row a sequence ``[B, W]`` at position ``context_lens[b]``
    of each active one (the null page otherwise); traceable."""
    _stats["append"] += 1
    slot, off = _write_slots(block_tables, context_lens, active,
                             pool.shape[1])
    return pool.at[slot, off].set(_rows_like(pool, rows))


def latent_prefill_append(pool, seq, page_ids, length, start=0):
    """`prefill_append` for a pool of latent rows: a prompt's rows
    ``[L, W]`` into one sequence's pages, from `start` to `length`."""
    pages, live = _prompt_pages(pool.shape[1], seq.shape[0], page_ids,
                                length, start)
    return _put_prompt(pool, seq, pages, live)


# ------------------- tensor-parallel (head-sharded) path ---------------------
#
# Decode attention is embarrassingly parallel over HEADS: each head's
# page gather, online softmax and weighted sum touch only that head's
# slice of the pools. Sharding the pools' folded axis over a mesh axis
# (heads are contiguous in it, so a shard holds whole heads)
# therefore needs NO cross-device math — every shard runs the normal
# single-chip dispatch on its local head slice (the Pallas page walk or
# the XLA gather, by the LOCAL shape), and concatenating shard outputs
# reproduces the single-chip result
# BIT-EXACTLY because no floating-point reduction ever crosses the
# shard boundary. The serving layer replicates the attention output
# before the proj matmul (see models/gpt.py) so the contraction that
# follows is also never split — that is the whole bit-exactness
# contract of TP decode.


def _rep_put(x, mesh):
    """Replicate `x` onto `mesh`: a sharding constraint under a trace
    (GSPMD inserts the all-gather — pure data movement), a device_put
    eagerly (with_sharding_constraint needs a surrounding jit)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec
    sh = NamedSharding(mesh, PartitionSpec())
    if isinstance(x, jax.core.Tracer):
        return jax.lax.with_sharding_constraint(x, sh)
    return jax.device_put(x, sh)


def decode_step_tp(q, k_new, v_new, k_pages, v_pages, block_tables,
                   context_lens, active, mesh, axis="tp", scale=None):
    """One TP decode-attention step on head-sharded pools: per-shard K/V
    append + paged attention over the LOCAL head slice (the page gather
    is unchanged inside each shard — block tables and context lens
    replicate), then the attention output is gathered back to replicated
    so the caller's proj matmul never splits a contraction.

    q is [B, H, D], k_new/v_new [B, H*D] (or [B, H, D]); pools
    [num_pages, page_size, H*D] sharded (or shardable) over `axis` on the
    folded dim. Returns (out [B, H, D] replicated, k_pages, v_pages
    head-sharded). H must divide by the mesh axis size. Traceable — the
    serving engine's fused step jits over it with the pools donated."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from jax import shard_map
    B, H, D = q.shape
    n_shards = mesh.shape[axis]
    if H % n_shards:
        raise ValueError(f"decode_step_tp: {H} heads do not divide over "
                         f"mesh axis {axis!r} of size {n_shards}")
    if scale is None:
        scale = 1.0 / np.sqrt(D)
    scale = float(scale)

    def body(q_s, kn_s, vn_s, kp_s, vp_s, bt, cl, act):
        kp_s, vp_s = _append_impl(kp_s, vp_s, kn_s, vn_s, bt, cl, act)
        out = paged_attention(q_s, kp_s, vp_s, bt,
                              jnp.where(act, cl + 1, 0), scale=scale)
        return out, kp_s, vp_s

    head = P(None, axis, None)
    rows = P(None, axis)
    pool = P(None, None, axis)
    rep = P()
    out, k_pages, v_pages = shard_map(
        body, mesh=mesh,
        in_specs=(head, rows, rows, pool, pool, rep, rep, rep),
        out_specs=(head, pool, pool), check_vma=False)(
            q, k_new.reshape(B, H * D), v_new.reshape(B, H * D), k_pages,
            v_pages, block_tables, context_lens, active)
    return _rep_put(out, mesh), k_pages, v_pages


def prefill_append_tp(k_pages, v_pages, k_seq, v_seq, page_ids, length,
                      mesh, axis="tp", start=0):
    """`prefill_append` on head-sharded pools: each shard scatters its
    own head slice of the prompt K/V [L, H*D] into its pool slice. The
    scatter indices (page ids, offsets) are head-independent, so this is
    the identical write per shard — no communication at all."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    def body(kp_s, vp_s, ks_s, vs_s, pid, ln, st):
        return prefill_append(kp_s, vp_s, ks_s, vs_s, pid, ln, start=st)

    pool = P(None, None, axis)
    seq = P(None, axis)
    rep = P()
    L = k_seq.shape[0]
    return shard_map(
        body, mesh=mesh,
        in_specs=(pool, pool, seq, seq, rep, rep, rep),
        out_specs=(pool, pool), check_vma=False)(
            k_pages, v_pages, k_seq.reshape(L, -1), v_seq.reshape(L, -1),
            page_ids,
            jnp.asarray(length, jnp.int32), jnp.asarray(start, jnp.int32))


# ----------------------------- cache append ----------------------------------


def _append_impl(k_pages, v_pages, k_new, v_new, block_tables,
                 context_lens, active):
    """Scatter one new K/V token per ACTIVE sequence into its current
    page slot. Inactive slots write to the null page 0 at offset 0
    (garbage the attention mask never reads — the serving allocator
    reserves page 0)."""
    slot, off = _write_slots(block_tables, context_lens, active,
                             k_pages.shape[1])
    k_pages = k_pages.at[slot, off].set(_rows_like(k_pages, k_new))
    v_pages = v_pages.at[slot, off].set(_rows_like(v_pages, v_new))
    return k_pages, v_pages


def _write_slots(block_tables, context_lens, active, page_size: int):
    """(page, offset) of each sequence's next token; (0, 0), the null
    page, where it is not active."""
    slot = jnp.take_along_axis(
        block_tables, (context_lens // page_size)[:, None], axis=1)[:, 0]
    off = context_lens % page_size
    return jnp.where(active, slot, 0), jnp.where(active, off, 0)


_append_jit = jax.jit(_append_impl, donate_argnums=(0, 1))


def cache_append(k_pages, v_pages, k_new, v_new, block_tables,
                 context_lens, active=None):
    """Append k_new/v_new [B, H*D] (or [B, H, D]) at position
    context_lens[b] of each active sequence. Returns the updated pools.

    Eagerly this routes through a jitted scatter whose page pools are
    DONATED, so XLA updates the buffers in place — the decode loop never
    copies the pool per token. Under an outer trace the raw scatter
    inlines (the outer jit owns donation there). Callers must drop their
    references to the passed-in pools (the returned arrays replace
    them)."""
    _stats["append"] += 1
    if active is None:
        active = jnp.ones(k_new.shape[:1], bool)
    if isinstance(jnp.asarray(context_lens), jax.core.Tracer) or \
            isinstance(k_pages, jax.core.Tracer):
        return _append_impl(k_pages, v_pages, k_new, v_new, block_tables,
                            context_lens, active)
    return _append_jit(k_pages, v_pages, k_new, v_new, block_tables,
                       context_lens, active)


def prefill_append(k_pages, v_pages, k_seq, v_seq, page_ids, length,
                   start=0):
    """Scatter a whole prompt's K/V [L, H*D] (or [L, H, D]) into the
    pages of ONE sequence: position i lands in page_ids[i // page_size]
    at offset i % page_size. Positions at/past `length` (bucket padding)
    are not written, and neither are positions below `start` — the
    copy-on-write shared-prefix path prefills a request whose first
    `start` tokens' K/V already live in pages FORKED from another
    request; writing them again would clobber the shared (refcount > 1)
    pages. `page_ids` is the sequence's block-table row [n_pages].
    Traceable (used inside the jitted prefill step).

    The write is a PAGE at a time: in the folded pool a page is one
    contiguous block of tiles while a token's row is a sublane strided
    over H*D/128 of them, and a scatter of L rows took four times as long
    as the same rows did in a [.., H, D] pool (PERF.md, PR 26). Each page
    the prompt spans is read, its live rows replaced, and written back;
    a page with no live row is parked on the null page 0."""
    pages, live = _prompt_pages(k_pages.shape[1], k_seq.shape[0], page_ids,
                                length, start)
    return (_put_prompt(k_pages, k_seq, pages, live),
            _put_prompt(v_pages, v_seq, pages, live))


def _prompt_pages(page_size: int, L: int, page_ids, length, start):
    """The pages a padded prompt of L rows spans (the null page where no
    row of one is live) and which rows are live ``[n, page_size]``."""
    n = -(-L // page_size)
    pos = jnp.arange(n * page_size, dtype=jnp.int32).reshape(n, page_size)
    live = (pos >= start) & (pos < length)
    return jnp.where(jnp.any(live, axis=1), page_ids[:n], 0), live


def _put_prompt(pool, seq, pages, live):
    """A prompt's rows into `pages`, page by page, its live rows only."""
    n, page_size = live.shape
    rows = _rows_like(pool, seq)
    rows = jnp.pad(rows, [(0, n * page_size - rows.shape[0])]
                   + [(0, 0)] * (rows.ndim - 1))
    rows = rows.reshape(n, page_size, *pool.shape[2:])
    keep = live.reshape(n, page_size, *[1] * (pool.ndim - 2))
    return pool.at[pages].set(jnp.where(keep, rows, pool[pages]))


# --------------------------- copy-on-write fork -------------------------------


def _cow_copy_impl(k_pages, v_pages, src, dst):
    """Duplicate pool page `src` into `dst` across every layer's K and V
    pools (k_pages/v_pages are the per-layer lists)."""
    k_pages = [kp.at[dst].set(kp[src]) for kp in k_pages]
    v_pages = [vp.at[dst].set(vp[src]) for vp in v_pages]
    return k_pages, v_pages


_cow_jit = jax.jit(_cow_copy_impl, donate_argnums=(0, 1))


def cow_copy_pages(k_pages, v_pages, src, dst):
    """Copy-on-write fork of ONE pool page: page `src` (shared,
    refcount > 1) is duplicated into the freshly-allocated page `dst`
    so the writer can diverge without clobbering the other sharers.

    `k_pages`/`v_pages` are the per-layer pool lists; one donated jitted
    dispatch copies the page across all layers in place (the pool is
    never materialized twice). Callers must drop their references to
    the passed-in pools — the returned lists replace them."""
    _stats["cow"] += 1
    return _cow_jit(list(k_pages), list(v_pages),
                    np.int32(src), np.int32(dst))
