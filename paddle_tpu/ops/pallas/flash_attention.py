"""Flash attention: Pallas fwd+bwd kernels under `jax.custom_vjp`.

TPU-native replacement for the reference's fused attention
(`/root/reference/paddle/fluid/operators/fused/fused_attention_op.cu` +
`fmha_ref.h` — which materializes the [B,H,L,L] score matrix in fwd AND
saves softmax-out for bwd, and handles arbitrary attention masks). Here:

* forward: online-softmax Pallas kernel tiled for the MXU; residuals are
  only (q, k, v, out, logsumexp) — O(L) extra memory, never [L,L];
* backward: two Pallas kernels (dq over q-blocks; dk/dv over k-blocks)
  that RECOMPUTE the probabilities from (q, k, lse) per tile, flash-style;
* K/V (and Q/dO in the dkv pass) are GRID-WALKED via BlockSpecs — the
  Pallas pipeline streams one (block, D) tile per grid step with
  double-buffered DMA, so sequence length is bounded by HBM, not VMEM
  (the round-2 kernel kept K/V VMEM-resident, capping Lk at 4096);
* tail blocks are masked IN-KERNEL (rows >= Lq / cols >= Lk), so any
  Lq/Lk >= 64 is eligible — including the BERT/ERNIE seq-128 shapes that
  round 2 sent down the score-materializing XLA path;
* boolean or additive masks broadcastable to [B,H,Lq,Lk] are streamed
  block-by-block like K/V (the reference's fmha path also applies the
  mask inside the fused kernel);
* dispatch is by shape/dtype eligibility alone: an eligible call takes
  the Pallas path, after one eager compile check at the exact production
  shapes (`tiling.compile_check`) whose failure RAISES, naming the
  kernel — a kernel Mosaic refuses is a bug, not a route to XLA.

* FORWARD ONLY (serving's prefill): `window=W` on a causal call keeps key j
  for query i iff ``i - W < j <= i`` and SKIPS the key blocks outside that
  band (their grid steps compute nothing and fetch nothing new); K/V may
  hold fewer heads than q (grouped K/V heads: query head h reads K/V head
  ``h // (H // Hkv)`` through the block index map, never repeated); and
  `precision` sets the kernel's matrix products as `ops/moe.py` sets
  megablox's (`jax.default_matmul_precision` while the kernel is traced:
  Mosaic takes "default", one bfloat16 pass, or "highest"), for a model
  whose attention sits in front of a router; V may have a head size of
  its own (DeepSeek-V2's prefill: q and k of 192, v of 128), which the
  output takes. Its walk (`_live_walk`) covers only the (q-block,
  key-block) steps that hold a live query and a key it may see: the
  steps above the diagonal, outside the band, and past `length` (a
  prompt's live rows in its bucket, a scalar the kernel takes by scalar
  prefetch) compute nothing and fetch nothing new, and the rows at and
  past `length` come out as zeros. The backward pass refuses all five by
  name.

`_stats` counts dispatch decisions at trace time so tests can assert the
kernel path is actually exercised (round-1 review found the old fwd-only
kernel silently dead in training).

Layout convention (paddle): q/k/v are [batch, seq, heads, head_dim].
"""
from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas import tpu as pltpu

from . import tiling as _tiling
from .tiling import ceil_to as _ceil_to
from .tiling import on_tpu as _on_tpu
from .tiling import zero_tail_rows as _zero_tail_rows

_NEG = -1e30

# dispatch decisions, counted at trace time (reset freely in tests)
# ("window": forward-only calls with a `window`, on either path;
# "live_length": forward-only calls given a `length` that took the kernel)
_stats = {"pallas": 0, "pallas_fwd": 0, "pallas_bwd": 0, "xla": 0,
          "window": 0, "live_length": 0}

# tests set True: kernels run in the Pallas interpreter on CPU, so the
# real kernel logic + custom_vjp wiring is exercised without a TPU
_INTERPRET = False

_STATS_LANES = 8    # lse/delta lane padding (see _fa_fwd_kernel comment)
_CARRY_LANES = 128  # m/l scratch lane width (f32 native lane tile)

_DEF_BLOCK_Q = 256
_DEF_BLOCK_K = 512


def flash_attention_xla(q, k, v, mask=None, causal=False, scale=None,
                        dropout_p=0.0, dropout_key=None):
    """XLA-composed attention (fallback for ragged/tiny seqs, CPU, fp16,
    and training-time attention dropout).

    `dropout_p` drops attention WEIGHTS (the post-softmax probabilities),
    matching the reference (`nn/layer/transformer.py:412-415` applies
    F.dropout to `weights` before the @V matmul) — NOT the output features.

    The [B,H,L,L] score matrix is kept in the INPUT dtype (bf16 in mixed-
    precision training) — on a bandwidth-bound chip the fp32 score array is
    the single largest HBM write of the transformer layer. Stability is
    preserved by the max-subtracted softmax whose row statistics (max, sum)
    are computed with fp32 accumulation; only the big [L,L] arrays stay
    narrow. fp32 inputs keep the all-fp32 path.
    """
    B, Lq, H, D = q.shape
    Lk = k.shape[1]
    if scale is None:
        scale = 1.0 / np.sqrt(D)
    acc_t = q.dtype if q.dtype in (jnp.dtype(jnp.bfloat16),
                                   jnp.dtype(jnp.float16)) else jnp.float32
    # "floor" = very-negative but FINITE in acc_t, used for the where()
    # branches and to clamp the ADDITIVE mask term (so a -1e9/-inf mask
    # cannot overflow acc_t). Genuine logits are never clamped: for the
    # sum logit+floor to overflow fp16 a real logit would have to be
    # below -5e4, far outside the plausible range.
    floor = jnp.asarray(-1e4 if acc_t == jnp.dtype(jnp.float16) else _NEG,
                        acc_t)
    qs = (q * jnp.asarray(scale, q.dtype))
    logits = jnp.einsum("blhd,bmhd->bhlm", qs, k,
                        preferred_element_type=acc_t).astype(acc_t)
    # `valid` tracks which positions may attend, so fully-masked rows are
    # detected from the masks themselves — thresholding the score max
    # misclassifies a fully-masked fp16 row whenever an additive mask rides
    # on real logits above ~100 (ADVICE r3)
    valid = None
    if causal:
        cmask = jnp.tril(jnp.ones((Lq, Lk), dtype=bool), k=Lk - Lq)
        logits = jnp.where(cmask, logits, floor)
        valid = jnp.broadcast_to(cmask, logits.shape)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            logits = jnp.where(mask, logits, floor)
            mvalid = jnp.broadcast_to(mask, logits.shape)
        else:
            # clamp ONLY the mask term (ADVICE r1): real scores stay exact
            logits = logits + jnp.maximum(mask.astype(acc_t), floor)
            mvalid = jnp.broadcast_to(
                mask.astype(jnp.float32) > floor.astype(jnp.float32),
                logits.shape)
        valid = mvalid if valid is None else (valid & mvalid)
    # max-subtracted softmax; row stats accumulate in fp32 (tiny arrays)
    m = jnp.max(logits.astype(jnp.float32), axis=-1, keepdims=True)
    p = jnp.exp(logits - m.astype(acc_t))
    denom = jnp.sum(p.astype(jnp.float32), axis=-1, keepdims=True)
    denom = jnp.maximum(denom, 1e-30)
    probs = (p / denom.astype(acc_t)).astype(v.dtype)
    if valid is not None:
        # a row with EVERY position masked outputs zero (matching the
        # Pallas kernels, which zero p when s sits at the floor) instead of
        # the uniform 1/Lk attention a naive softmax of all-floor rows
        # yields — keeps numerics identical across dispatch paths
        probs = jnp.where(jnp.any(valid, axis=-1, keepdims=True),
                          probs, 0.0).astype(v.dtype)
    if dropout_p > 0.0:
        assert dropout_key is not None, "dropout_p > 0 needs dropout_key"
        keep = jax.random.bernoulli(dropout_key, 1.0 - dropout_p, probs.shape)
        probs = jnp.where(keep, probs / jnp.asarray(1.0 - dropout_p,
                                                    probs.dtype), 0.0)
    out = jnp.einsum("bhlm,bmhd->blhd", probs, v)
    return out.astype(q.dtype)


# --------------------------- Pallas kernels ---------------------------------
#
# All kernels run over a 4-D grid (B, H, outer-blocks, inner-blocks) with the
# INNER sequence axis as the minormost, sequentially-executed ("arbitrary")
# dimension: fwd/dq walk (q-block, k-block), dkv walks (k-block, q-block).
# Running softmax / gradient state is carried across inner iterations in VMEM
# scratch accumulators; inputs stream one block per step through the Pallas
# pipeline (double-buffered DMA — this is what lets Lk grow past VMEM).
# MXU matmuls take narrow (bf16) inputs with fp32 accumulation via
# preferred_element_type; softmax math is fp32.


def _dotT(a, b):
    # a [m, d] @ b.T [d, n] -> f32 [m, n]
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _dot(a, b):
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _apply_mask(s, mask_ref, mask_is_bool, rows, cols, q_len, kv_len,
                causal, kv_offset, need_tail_q, need_tail_k, window=None):
    """Shared score-masking: user mask block, causal triangle, tail blocks.

    Returns (s, masked) where `masked` says any position may sit at the
    _NEG floor (so callers zero p there instead of trusting exp(_NEG)).
    """
    masked = False
    if mask_ref is not None:
        mb = mask_ref[...]
        mb = jnp.broadcast_to(mb, s.shape)
        if mask_is_bool:
            s = jnp.where(mb, s, _NEG)
        else:
            # clamp ONLY the mask term (ADVICE r1): -inf/-1e9 masks must not
            # poison the fp32 accumulator; real scores stay exact
            s = s + jnp.maximum(mb.astype(jnp.float32), _NEG)
        masked = True
    if causal:
        s = jnp.where(rows + kv_offset >= cols, s, _NEG)
        if window is not None:
            s = jnp.where(cols > rows + kv_offset - window, s, _NEG)
        masked = True
    if need_tail_q:
        s = jnp.where(rows < q_len, s, _NEG)
        masked = True
    if need_tail_k:
        s = jnp.where(cols < kv_len, s, _NEG)
        masked = True
    return s, masked


# (_zero_tail_rows now lives in tiling.zero_tail_rows — shared by every
# row-blocked kernel in the package)


def _band_k_blocks(i, block_q, block_k, kv_offset, window, n_k):
    """(first, last) key block that holds a key some query of q-block `i`
    may see under ``row - window < col <= row`` (rows shifted by
    `kv_offset`; `window` None: ``col <= row``); `i` a Python int or a
    traced one."""
    first_row = i * block_q + kv_offset
    lo = 0 if window is None else jnp.maximum(
        (first_row - window + 1) // block_k, 0)
    hi = jnp.minimum((first_row + block_q - 1) // block_k, n_k - 1)
    return lo, hi


def _live_walk(i, j, n, block_q, block_k, kv_offset, window, n_k):
    """Step (q-block `i`, key block `j`) of a causal forward-only call whose
    first `n` query rows are live (the rest a prompt's bucket padding, so
    the keys from row ``n + kv_offset`` on are padding too): (the q block
    and the key block the step names, whether it computes). A step
    computes iff q-block `i` holds a live row and key block `j` a key one
    of them may see (`_band_k_blocks`, cut at the last live key); every
    other step names the block the step before it named, so the pipeline
    fetches nothing for it. What the block index maps and the kernel's
    `pl.when` both read; Python ints or traced ones."""
    last_q = (jnp.maximum(n, 1) - 1) // block_q
    qi = jnp.minimum(i, last_q)
    lo, hi = _band_k_blocks(qi, block_q, block_k, kv_offset, window, n_k)
    hi = jnp.minimum(hi, (jnp.maximum(n, 1) - 1 + kv_offset) // block_k)
    kj = jnp.where(i > last_q, hi, jnp.clip(j, lo, hi))
    return qi, kj, (i * block_q < n) & (j >= lo) & (j <= hi)


def _fa_fwd_kernel(*refs, scale, causal, has_mask, mask_is_bool, block_q,
                   block_k, q_len, kv_len, kv_offset, n_k, window=None,
                   live=False):
    """Grid (B, H, q-blocks, k-blocks); online softmax carried in scratch.
    `live`: the first ref is the live rows' count (scalar prefetch), and
    the walk is `_live_walk`'s."""
    from jax.experimental import pallas as pl

    if live:
        n_ref, refs = refs[0], refs[1:]
    if has_mask:
        mask_ref, q_ref, k_ref, v_ref = refs[:4]
        o_ref, lse_ref, acc_ref, m_ref, l_ref = refs[4:]
    else:
        mask_ref = None
        q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref = refs

    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _compute(causal_band):
        # `causal_band` False = block proven fully below the diagonal, so
        # the iota/compare/select per-element mask work is skipped — at
        # D=64 this kernel is VPU-bound, and the interior blocks are the
        # majority, so the triangle math is only paid where it matters
        qb = q_ref[...]
        kb = k_ref[...]
        vb = v_ref[...]
        if q_len % block_q:
            qb = _zero_tail_rows(qb, i * block_q, q_len)
        if kv_len % block_k:
            kb = _zero_tail_rows(kb, j * block_k, kv_len)
            vb = _zero_tail_rows(vb, j * block_k, kv_len)
        s = _dotT(qb, kb) * scale  # f32 [bq, bk]
        masked = False
        if has_mask or causal_band or q_len % block_q or kv_len % block_k:
            rows = i * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = j * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s, masked = _apply_mask(
                s, mask_ref, mask_is_bool, rows, cols, q_len, kv_len,
                causal_band, kv_offset, need_tail_q=q_len % block_q != 0,
                need_tail_k=kv_len % block_k != 0, window=window)
        m_prev = m_ref[...][:, :1]            # [bq, 1]
        l_prev = l_ref[...][:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        # (a window's first block of a q-block holds rows whose own band
        # starts in a LATER block: all floor, nothing real before them)
        if masked and (has_mask or q_len % block_q or kv_len % block_k
                       or window is not None):
            # a fully-masked row has m_new == s == _NEG -> exp(0) == 1 for
            # every masked column; zero them explicitly. Pure-causal rows
            # never need this: every row's first valid column lives in an
            # EARLIER block (iteration order j=0,1,...), so by the time a
            # row is all-floor in this block, m_prev is real and
            # exp(_NEG - m_prev) underflows to exactly 0 in f32.
            p = jnp.where(s > 0.5 * _NEG, p, 0.0)
        corr = jnp.exp(m_prev - m_new)        # [bq, 1]
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + _dot(p.astype(vb.dtype), vb)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    if causal:
        # whole block above the diagonal contributes nothing — skip compute;
        # blocks fully below it need no triangle masking at all
        first_row = i * block_q + kv_offset
        last_row = first_row + block_q - 1
        active = last_row >= j * block_k
        interior = first_row >= (j + 1) * block_k - 1
        if window is not None:
            # key blocks wholly before the band are skipped like those
            # wholly above the diagonal; a block is interior when its
            # first key is inside the LAST row's band too
            lo, _ = _band_k_blocks(i, block_q, block_k, kv_offset, window,
                                   n_k)
            active = active & (j >= lo)
            interior = interior & (j * block_k > last_row - window)
        if live:
            active = active & _live_walk(i, j, n_ref[0], block_q, block_k,
                                         kv_offset, window, n_k)[2]
        pl.when(active & interior)(lambda: _compute(False))
        pl.when(active & jnp.logical_not(interior))(lambda: _compute(True))
    else:
        _compute(False)

    @pl.when(j == n_k - 1)
    def _finalize():
        m = m_ref[...][:, :1]
        l = jnp.maximum(l_ref[...][:, :1], 1e-30)
        o = acc_ref[...] / l
        if live:
            # padding rows come out as zeros (a q-block past the live rows
            # computed nothing: its accumulator is already zero)
            rows = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, o.shape, 0)
            o = jnp.where(rows < n_ref[0], o, 0.0)
        o_ref[...] = o.astype(o_ref.dtype)
        # row stats live in a [.., L, 8]-padded layout: Mosaic requires the
        # last two block dims be (8k, 128k) or equal to the array dims — a
        # 1-D (block_q,) stats block is rejected once B/H are squeezed
        lse_ref[...] = jnp.broadcast_to(m + jnp.log(l), lse_ref.shape)


def _fa_bwd_dq_kernel(*refs, scale, causal, has_mask, mask_is_bool, block_q,
                      block_k, q_len, kv_len, kv_offset, n_k):
    """Grid (B, H, q-blocks, k-blocks); dq accumulated in scratch."""
    from jax.experimental import pallas as pl

    if has_mask:
        mask_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = refs[:7]
        dq_ref, dqacc_ref = refs[7:]
    else:
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = refs[:6]
        dq_ref, dqacc_ref = refs[6:]
        mask_ref = None

    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        dqacc_ref[...] = jnp.zeros_like(dqacc_ref)

    def _compute(causal_band):
        qb = q_ref[...]
        kb = k_ref[...]
        vb = v_ref[...]
        dob = do_ref[...]
        if q_len % block_q:
            qb = _zero_tail_rows(qb, i * block_q, q_len)
            dob = _zero_tail_rows(dob, i * block_q, q_len)
        if kv_len % block_k:
            kb = _zero_tail_rows(kb, j * block_k, kv_len)
            vb = _zero_tail_rows(vb, j * block_k, kv_len)
        s = _dotT(qb, kb) * scale
        masked = False
        need_iota = (has_mask or causal_band or q_len % block_q
                     or kv_len % block_k)
        if need_iota:
            rows = i * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = j * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s, masked = _apply_mask(
                s, mask_ref, mask_is_bool, rows, cols, q_len, kv_len,
                causal_band, kv_offset, need_tail_q=q_len % block_q != 0,
                need_tail_k=kv_len % block_k != 0)
        lse = lse_ref[...][:, :1]
        delta = delta_ref[...][:, :1]
        p = jnp.exp(s - lse)                 # [bq, bk]
        if masked and (has_mask or q_len % block_q or kv_len % block_k):
            # pure-causal needs no select: lse is the row's REAL logsumexp,
            # so exp(_NEG - lse) underflows to exactly 0
            p = jnp.where(s > 0.5 * _NEG, p, 0.0)
        dp = _dotT(dob, vb)
        ds = p * (dp - delta)
        if q_len % block_q:
            # tail q rows carry garbage lse/delta; 0 * nan == nan
            ds = jnp.where(rows < q_len, ds, 0.0)
        dqacc_ref[...] = dqacc_ref[...] + _dot(ds.astype(kb.dtype), kb) * scale

    if causal:
        first_row = i * block_q + kv_offset
        last_row = first_row + block_q - 1
        active = last_row >= j * block_k
        interior = first_row >= (j + 1) * block_k - 1
        pl.when(active & interior)(lambda: _compute(False))
        pl.when(active & jnp.logical_not(interior))(lambda: _compute(True))
    else:
        _compute(False)

    @pl.when(j == n_k - 1)
    def _finalize():
        dq_ref[...] = dqacc_ref[...].astype(dq_ref.dtype)


def _fa_bwd_dkv_kernel(*refs, scale, causal, has_mask, mask_is_bool, block_q,
                       block_k, q_len, kv_len, kv_offset, n_q):
    """Grid (B, H, k-blocks, q-blocks); dk/dv accumulated in scratch."""
    from jax.experimental import pallas as pl

    if has_mask:
        mask_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = refs[:7]
        dk_ref, dv_ref, dkacc_ref, dvacc_ref = refs[7:]
    else:
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = refs[:6]
        dk_ref, dv_ref, dkacc_ref, dvacc_ref = refs[6:]
        mask_ref = None

    ki = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        dkacc_ref[...] = jnp.zeros_like(dkacc_ref)
        dvacc_ref[...] = jnp.zeros_like(dvacc_ref)

    def _compute(causal_band):
        qb = q_ref[...]
        kb = k_ref[...]
        vb = v_ref[...]
        dob = do_ref[...]
        if q_len % block_q:
            qb = _zero_tail_rows(qb, j * block_q, q_len)
            dob = _zero_tail_rows(dob, j * block_q, q_len)
        if kv_len % block_k:
            kb = _zero_tail_rows(kb, ki * block_k, kv_len)
            vb = _zero_tail_rows(vb, ki * block_k, kv_len)
        s = _dotT(qb, kb) * scale            # [bq, bk]
        masked = False
        need_iota = (has_mask or causal_band or q_len % block_q
                     or kv_len % block_k)
        if need_iota:
            rows = j * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s, masked = _apply_mask(
                s, mask_ref, mask_is_bool, rows, cols, q_len, kv_len,
                causal_band, kv_offset, need_tail_q=q_len % block_q != 0,
                need_tail_k=kv_len % block_k != 0)
        lse = lse_ref[...][:, :1]
        delta = delta_ref[...][:, :1]
        p = jnp.exp(s - lse)
        if (masked and (has_mask or kv_len % block_k)) or q_len % block_q:
            # tail q rows carry garbage lse/delta: 0 * nan == nan, so the
            # row guard must zero p/ds explicitly, not rely on s == _NEG.
            # Pure-causal needs no select (real lse -> exact underflow).
            rowmask = rows < q_len
            p = jnp.where((s > 0.5 * _NEG) & rowmask, p, 0.0)
        dvacc_ref[...] = dvacc_ref[...] + _dot(p.astype(dob.dtype).T, dob)
        dp = _dotT(dob, vb)
        ds = p * (dp - delta)
        if q_len % block_q:
            ds = jnp.where(rows < q_len, ds, 0.0)
        dkacc_ref[...] = dkacc_ref[...] + _dot(
            ds.astype(qb.dtype).T, qb) * scale

    if causal:
        # q-blocks strictly above this k-block's diagonal see nothing;
        # q-blocks fully below it need no triangle masking at all
        first_row = j * block_q + kv_offset
        last_row = first_row + block_q - 1
        active = last_row >= ki * block_k
        interior = first_row >= (ki + 1) * block_k - 1
        pl.when(active & interior)(lambda: _compute(False))
        pl.when(active & jnp.logical_not(interior))(lambda: _compute(True))
    else:
        _compute(False)

    @pl.when(j == n_q - 1)
    def _finalize():
        dk_ref[...] = dkacc_ref[...].astype(dk_ref.dtype)
        dv_ref[...] = dvacc_ref[...].astype(dv_ref.dtype)


# Below this (square) seq length the walk-grid launches B*H tiny programs
# whose fixed cost dwarfs the work; a single-shot kernel batching all heads
# of one batch element per program wins (measured: BERT s128 b32 h12 d64
# walk-grid 56ms/step vs XLA 48ms vs small-path — see bench_bert_base).
_SMALL_MAX_L = 512


def _fa_small_fwd_kernel(*refs, scale, causal, has_mask, mask_is_bool,
                         q_len, kv_len):
    """One program = all H heads of one batch element; single-shot softmax.

    Blocks are [H, L, D]; the scores tensor [H, Lq, Lk] lives in VMEM for
    the program's lifetime — eligibility caps L so this fits.
    """
    if has_mask:
        mask_ref, q_ref, k_ref, v_ref, o_ref, lse_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref = refs
        mask_ref = None

    qb = q_ref[...]
    kb = k_ref[...]
    vb = v_ref[...]
    # batched matmul over the head dim: [H,Lq,D] @ [H,Lk,D]^T -> [H,Lq,Lk]
    s = jax.lax.dot_general(qb, kb, (((2,), (2,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32) * scale
    rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
    s, masked = _apply_mask(
        s, mask_ref, mask_is_bool, rows, cols, q_len, kv_len, causal,
        kv_len - q_len, need_tail_q=False, need_tail_k=False)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    if masked:
        p = jnp.where(s > 0.5 * _NEG, p, 0.0)
    l = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    p = p / l
    o_ref[...] = jax.lax.dot_general(
        p.astype(vb.dtype), vb, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32).astype(o_ref.dtype)
    lse_ref[...] = jnp.broadcast_to(m + jnp.log(l), lse_ref.shape)


def _fa_small_bwd_kernel(*refs, scale, causal, has_mask, mask_is_bool,
                         q_len, kv_len):
    """Single-shot dq/dk/dv for one batch element (all heads)."""
    if has_mask:
        (mask_ref, q_ref, k_ref, v_ref, do_ref, out_ref, lse_ref,
         dq_ref, dk_ref, dv_ref) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, out_ref, lse_ref,
         dq_ref, dk_ref, dv_ref) = refs
        mask_ref = None

    qb = q_ref[...]
    kb = k_ref[...]
    vb = v_ref[...]
    dob = do_ref[...]
    s = jax.lax.dot_general(qb, kb, (((2,), (2,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32) * scale
    rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
    s, masked = _apply_mask(
        s, mask_ref, mask_is_bool, rows, cols, q_len, kv_len, causal,
        kv_len - q_len, need_tail_q=False, need_tail_k=False)
    lse = lse_ref[...][..., :1]              # [H, Lq, 1]
    p = jnp.exp(s - lse)
    if masked:
        p = jnp.where(s > 0.5 * _NEG, p, 0.0)
    # delta = rowsum(do * out)  [H, Lq, 1]
    delta = jnp.sum(dob.astype(jnp.float32) * out_ref[...].astype(jnp.float32),
                    axis=-1, keepdims=True)
    # dv = p^T do : [H,Lk,Lq] @ [H,Lq,D]
    dv_ref[...] = jax.lax.dot_general(
        p.astype(dob.dtype), dob, (((1,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32).astype(dv_ref.dtype)
    dp = jax.lax.dot_general(dob, vb, (((2,), (2,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta)
    dq_ref[...] = (jax.lax.dot_general(
        ds.astype(kb.dtype), kb, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32) * scale).astype(dq_ref.dtype)
    dk_ref[...] = (jax.lax.dot_general(
        ds.astype(qb.dtype), qb, (((1,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32) * scale).astype(dk_ref.dtype)


def _small_mask_spec(mask):
    """BlockSpec for the small path: grid is (B,), block covers all heads."""
    from jax.experimental import pallas as pl

    bdims = (None, mask.shape[1], mask.shape[2], mask.shape[3])
    b_b = mask.shape[0] != 1

    def index(b):
        return (b if b_b else 0, 0, 0, 0)

    return pl.BlockSpec(bdims, index)


@functools.partial(jax.jit, static_argnames=(
    "causal", "scale", "mask_is_bool", "interpret"))
def _fa_small_fwd_pallas(q, k, v, mask, causal, scale, mask_is_bool=False,
                         interpret=False):
    from jax.experimental import pallas as pl

    B, Lq, H, D = q.shape
    Lk = k.shape[1]
    qt, kt, vt = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
    kw = dict(scale=scale, causal=causal, has_mask=mask is not None,
              mask_is_bool=mask_is_bool, q_len=Lq, kv_len=Lk)
    qspec = pl.BlockSpec((None, H, Lq, D), lambda b: (b, 0, 0, 0))
    kspec = pl.BlockSpec((None, H, Lk, D), lambda b: (b, 0, 0, 0))
    in_specs = [qspec, kspec, kspec]
    args = [qt, kt, vt]
    if mask is not None:
        in_specs.insert(0, _small_mask_spec(mask))
        args.insert(0, mask)
    out, lse = pl.pallas_call(
        functools.partial(_fa_small_fwd_kernel, **kw),
        grid=(B,),
        in_specs=in_specs,
        out_specs=[qspec,
                   pl.BlockSpec((None, H, Lq, _STATS_LANES),
                                lambda b: (b, 0, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((B, H, Lq, D), q.dtype),
                   jax.ShapeDtypeStruct((B, H, Lq, _STATS_LANES),
                                        jnp.float32)],
        interpret=interpret,
    )(*args)
    return jnp.swapaxes(out, 1, 2), lse[..., 0]


@functools.partial(jax.jit, static_argnames=(
    "causal", "scale", "mask_is_bool", "interpret"))
def _fa_small_bwd_pallas(q, k, v, out, lse, do, mask, causal, scale,
                         mask_is_bool=False, interpret=False):
    from jax.experimental import pallas as pl

    B, Lq, H, D = q.shape
    Lk = k.shape[1]
    qt, kt, vt, dot_, ot = (jnp.swapaxes(x, 1, 2)
                            for x in (q, k, v, do, out))
    lse_p = jnp.broadcast_to(lse[..., None], (B, H, Lq, _STATS_LANES))
    kw = dict(scale=scale, causal=causal, has_mask=mask is not None,
              mask_is_bool=mask_is_bool, q_len=Lq, kv_len=Lk)
    qspec = pl.BlockSpec((None, H, Lq, D), lambda b: (b, 0, 0, 0))
    kspec = pl.BlockSpec((None, H, Lk, D), lambda b: (b, 0, 0, 0))
    lspec = pl.BlockSpec((None, H, Lq, _STATS_LANES), lambda b: (b, 0, 0, 0))
    in_specs = [qspec, kspec, kspec, qspec, qspec, lspec]
    args = [qt, kt, vt, dot_, ot, lse_p]
    if mask is not None:
        in_specs.insert(0, _small_mask_spec(mask))
        args.insert(0, mask)
    dq, dk, dv = pl.pallas_call(
        functools.partial(_fa_small_bwd_kernel, **kw),
        grid=(B,),
        in_specs=in_specs,
        out_specs=[qspec, kspec, kspec],
        out_shape=[jax.ShapeDtypeStruct((B, H, Lq, D), q.dtype),
                   jax.ShapeDtypeStruct((B, H, Lk, D), k.dtype),
                   jax.ShapeDtypeStruct((B, H, Lk, D), v.dtype)],
        interpret=interpret,
    )(*args)
    return (jnp.swapaxes(dq, 1, 2), jnp.swapaxes(dk, 1, 2),
            jnp.swapaxes(dv, 1, 2))


def _use_small_path(Lq: int, Lk: int, H: int, D: int, dtype,
                    mask=None) -> bool:
    if Lq != Lk or Lq > _SMALL_MAX_L:
        return False
    # Sized by the single-shot BACKWARD, the larger of the pair (dispatch
    # cannot know whether a gradient will be asked for): three
    # [H, Lq, Lk] f32 score-shaped arrays live at once (p, dp, ds) beside
    # the double-buffered q/k/v/do/out in and dq/dk/dv out blocks. A mask
    # block is resident too ([H, Lq, Lk] per program). Over the budget
    # the grid-walk kernels take it — on the chip f32 H=12 L=512 asked
    # the compiler for more scoped VMEM than a v5e core grants.
    vmem = (3 * H * Lq * Lk * 4
            + 2 * 8 * H * Lq * D * jnp.dtype(dtype).itemsize)
    if mask is not None:
        vmem += 2 * H * Lq * Lk * mask.dtype.itemsize
    return vmem <= _tiling.VMEM_BUDGET


def _static_blocks(Lq: int, Lk: int):
    # blocks are multiples of 64 (covers f32/bf16 sublane granularity); a
    # block larger than the array is one virtually-padded block whose tail
    # the kernels mask in-register
    return (min(_DEF_BLOCK_Q, _ceil_to(Lq, 64)),
            min(_DEF_BLOCK_K, _ceil_to(Lk, 64)))


def _blocks_or_static(blocks, Lq: int, Lk: int):
    """(block_q, block_k) as given, `_static_blocks` otherwise."""
    return blocks if blocks is not None else _static_blocks(Lq, Lk)


def _resolve_flash_blocks(q, k, mask):
    """(block_q, block_k) for the grid-walk kernels, forward and backward
    alike, or None on the small path (whole-sequence blocks). Resolved at
    dispatch and carried by the custom_vjp as a static argument, so the
    compile check, the forward and the backward all see one pair."""
    B, Lq, H, D = q.shape
    Lk = k.shape[1]
    if _use_small_path(Lq, Lk, H, D, q.dtype, mask):
        return None
    return _static_blocks(Lq, Lk)


def _mask_spec(mask, block_q, block_k, *, q_axis, k_axis):
    """BlockSpec streaming a [b?,h?,Lq?,Lk?]-broadcastable mask block.

    Size-1 mask dims map to block index 0 with block size 1 (the kernel
    broadcasts in-VMEM), so a [B,1,1,Lk] padding mask streams Lk bytes per
    row, never a materialized [B,H,Lq,Lk].
    `q_axis`/`k_axis` give the grid positions of the q/k block indices
    (fwd/dq: (2, 3); dkv: (3, 2)).
    """
    from jax.experimental import pallas as pl

    bdims = (None, None,
             block_q if mask.shape[2] != 1 else 1,
             block_k if mask.shape[3] != 1 else 1)
    b_b = mask.shape[0] != 1
    h_b = mask.shape[1] != 1
    q_b = mask.shape[2] != 1
    k_b = mask.shape[3] != 1

    def index(b, h, x, y):
        gi = (b, h, x, y)
        return (b if b_b else 0, h if h_b else 0,
                gi[q_axis] if q_b else 0, gi[k_axis] if k_b else 0)

    return pl.BlockSpec(bdims, index)


def _compiler_params(interpret, n_arbitrary=1):
    """Grid semantics: trailing `n_arbitrary` dims carry cross-iteration
    scratch state and must stay ARBITRARY. The fused backward needs
    n_arbitrary=2: dqacc accumulates across dim 2 (k-blocks) and dk/dv
    across dim 3 (q-blocks) — marking dim 2 PARALLEL would let megacore
    TPUs (v4/v5p) split it across TensorCores with per-core scratch,
    losing dq partials."""
    if interpret:
        return None
    sem = ((pltpu.PARALLEL,) * (4 - n_arbitrary)
           + (pltpu.ARBITRARY,) * n_arbitrary)
    return pltpu.CompilerParams(dimension_semantics=sem)


@functools.partial(jax.jit, static_argnames=(
    "causal", "scale", "mask_is_bool", "interpret", "blocks", "window",
    "precision"))
def _fa_fwd_pallas(q, k, v, mask, causal, scale, mask_is_bool=False,
                   interpret=False, blocks=None, window=None, precision=None,
                   live=None):
    """Returns (out [B,L,H,D], lse [B,H,Lq] f32). mask may be None.
    `blocks` is (block_q, block_k); None = `_static_blocks`. `live` (an
    int32 scalar: the query rows before it are live), `window`,
    `precision`, K/V of fewer heads than q and V of another head size than
    q and K (the output takes V's): the module's docstring, "forward
    only"; a `window` walks `_live_walk`'s steps, every row live without
    a `live`."""
    from jax.experimental import pallas as pl

    B, Lq, H, D = q.shape
    Lk, Dv = k.shape[1], v.shape[-1]
    G = H // k.shape[2]             # query heads a K/V head
    block_q, block_k = _blocks_or_static(blocks, Lq, Lk)
    qt, kt, vt = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
    n_q, n_k = pl.cdiv(Lq, block_q), pl.cdiv(Lk, block_k)
    grid = (B, H, n_q, n_k)
    if window is not None and live is None:
        live = Lq
    kernel = functools.partial(
        _fa_fwd_kernel, scale=scale, causal=causal, has_mask=mask is not None,
        mask_is_bool=mask_is_bool, block_q=block_q, block_k=block_k,
        q_len=Lq, kv_len=Lk, kv_offset=Lk - Lq, n_k=n_k, window=window,
        live=live is not None)
    scalars = ()
    if live is not None:
        assert causal and mask is None, "a live length walks causal calls"
        scalars = (jnp.reshape(live, (1,)).astype(jnp.int32),)

    def walk(i, j, n_ref):
        return _live_walk(i, j, n_ref[0], block_q, block_k, Lk - Lq, window,
                          n_k)

    def q_index(b, h, i, j, *n_ref):
        return (b, h, walk(i, j, *n_ref)[0] if n_ref else i, 0)

    def kv_index(b, h, i, j, *n_ref):
        return (b, h if G == 1 else h // G,
                walk(i, j, *n_ref)[1] if n_ref else j, 0)

    in_specs = [
        pl.BlockSpec((None, None, block_q, D), q_index),
        pl.BlockSpec((None, None, block_k, D), kv_index),
        pl.BlockSpec((None, None, block_k, Dv), kv_index),
    ]
    args = [qt, kt, vt]
    if mask is not None:
        in_specs.insert(0, _mask_spec(mask, block_q, block_k,
                                      q_axis=2, k_axis=3))
        args.insert(0, mask)
    # the kernel's own `dot_general`s name no precision: they take the
    # default in force while the kernel is traced
    with (jax.default_matmul_precision(precision) if precision
          else contextlib.nullcontext()):
        out, lse = _fa_fwd_call(kernel, grid, in_specs, args, B, H, Lq, Dv,
                                block_q, q.dtype, interpret, scalars)
    return jnp.swapaxes(out, 1, 2), lse[..., 0]


def _fa_fwd_call(kernel, grid, in_specs, args, B, H, Lq, D, block_q, dtype,
                 interpret, scalars=()):
    """The forward's `pallas_call`; `scalars` go first, by scalar
    prefetch (every index map then takes their refs last)."""
    from jax.experimental import pallas as pl
    spec = dict(
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((None, None, block_q, D),
                         lambda b, h, i, j, *_: (b, h, i, 0)),
            pl.BlockSpec((None, None, block_q, _STATS_LANES),
                         lambda b, h, i, j, *_: (b, h, i, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, _CARRY_LANES), jnp.float32),
            pltpu.VMEM((block_q, _CARRY_LANES), jnp.float32),
        ])
    if scalars:
        spec = dict(grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars), **spec))
    return pl.pallas_call(
        kernel,
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Lq, D), dtype),
            jax.ShapeDtypeStruct((B, H, Lq, _STATS_LANES), jnp.float32),
        ],
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
        **spec,
    )(*scalars, *args)


def _fa_bwd_fused_kernel(*refs, scale, causal, has_mask, mask_is_bool,
                         block_q, block_k, q_len, kv_len, kv_offset,
                         n_q, n_k):
    """Single-pass backward: grid (B, H, k-blocks, q-blocks).

    The two-kernel split (dq walks k, dk/dv walk q) recomputes the score
    block and its softmax TWICE; at D=64 the kernels are VPU-bound, so
    that duplication is the dominant backward cost. Here p/ds are computed
    once per (k-block, q-block): dk/dv accumulate in per-k-block scratch,
    dq accumulates into a whole-(b,h) [Lq, D] f32 VMEM scratch indexed by
    the inner q-block (fits VMEM for the grid path's sequence lengths; the
    caller falls back to the split kernels when it would not). The dq
    OUTPUT block is rewritten every step — partial sums flushed at
    ki < n_k-1 land in HBM and are overwritten by the complete sums of
    the final ki pass (harmless extra writes, never read)."""
    from jax.experimental import pallas as pl

    if has_mask:
        mask_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = refs[:7]
        rest = refs[7:]
    else:
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = refs[:6]
        rest = refs[6:]
        mask_ref = None
    dq_ref, dk_ref, dv_ref, dkacc_ref, dvacc_ref, dqacc_ref = rest

    ki = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when((ki == 0) & (j == 0))
    def _init_dq():
        dqacc_ref[...] = jnp.zeros_like(dqacc_ref)

    @pl.when(j == 0)
    def _init_kv():
        dkacc_ref[...] = jnp.zeros_like(dkacc_ref)
        dvacc_ref[...] = jnp.zeros_like(dvacc_ref)

    def _compute(causal_band):
        qb = q_ref[...]
        kb = k_ref[...]
        vb = v_ref[...]
        dob = do_ref[...]
        if q_len % block_q:
            qb = _zero_tail_rows(qb, j * block_q, q_len)
            dob = _zero_tail_rows(dob, j * block_q, q_len)
        if kv_len % block_k:
            kb = _zero_tail_rows(kb, ki * block_k, kv_len)
            vb = _zero_tail_rows(vb, ki * block_k, kv_len)
        s = _dotT(qb, kb) * scale            # [bq, bk]
        masked = False
        need_iota = (has_mask or causal_band or q_len % block_q
                     or kv_len % block_k)
        if need_iota:
            rows = j * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s, masked = _apply_mask(
                s, mask_ref, mask_is_bool, rows, cols, q_len, kv_len,
                causal_band, kv_offset, need_tail_q=q_len % block_q != 0,
                need_tail_k=kv_len % block_k != 0)
        lse = lse_ref[...][:, :1]
        delta = delta_ref[...][:, :1]
        p = jnp.exp(s - lse)
        if (masked and (has_mask or kv_len % block_k)) or q_len % block_q:
            rowmask = rows < q_len
            p = jnp.where((s > 0.5 * _NEG) & rowmask, p, 0.0)
        dvacc_ref[...] = dvacc_ref[...] + _dot(p.astype(dob.dtype).T, dob)
        dp = _dotT(dob, vb)
        ds = p * (dp - delta)
        if q_len % block_q:
            ds = jnp.where(rows < q_len, ds, 0.0)
        dkacc_ref[...] = dkacc_ref[...] + _dot(
            ds.astype(qb.dtype).T, qb) * scale
        sl = pl.ds(j * block_q, block_q)
        dqacc_ref[sl, :] = dqacc_ref[sl, :] + _dot(
            ds.astype(kb.dtype), kb) * scale

    if causal:
        first_row = j * block_q + kv_offset
        last_row = first_row + block_q - 1
        active = last_row >= ki * block_k
        interior = first_row >= (ki + 1) * block_k - 1
        pl.when(active & interior)(lambda: _compute(False))
        pl.when(active & jnp.logical_not(interior))(lambda: _compute(True))
    else:
        _compute(False)

    # every step: flush this q-block's running dq total (see docstring)
    dq_ref[...] = dqacc_ref[pl.ds(j * block_q, block_q), :].astype(
        dq_ref.dtype)

    @pl.when(j == n_q - 1)
    def _finalize():
        dk_ref[...] = dkacc_ref[...].astype(dk_ref.dtype)
        dv_ref[...] = dvacc_ref[...].astype(dv_ref.dtype)


# dq slice scratch cap for the fused backward: [ceil(Lq), D] f32 must
# coexist with the block buffers in ~16MB VMEM
_FUSED_BWD_DQ_BYTES = 6 * 1024 * 1024


@functools.partial(jax.jit, static_argnames=(
    "causal", "scale", "mask_is_bool", "interpret", "blocks"))
def _fa_bwd_fused_pallas(q, k, v, out, lse, do, mask, causal, scale,
                         mask_is_bool=False, interpret=False, blocks=None):
    from jax.experimental import pallas as pl

    B, Lq, H, D = q.shape
    Lk = k.shape[1]
    block_q, block_k = _blocks_or_static(blocks, Lq, Lk)
    qt, kt, vt, dot_, ot = (jnp.swapaxes(x, 1, 2)
                            for x in (q, k, v, do, out))
    delta = jnp.sum(dot_.astype(jnp.float32) * ot.astype(jnp.float32), -1)
    lse_p = jnp.broadcast_to(lse[..., None], (B, H, Lq, _STATS_LANES))
    delta_p = jnp.broadcast_to(delta[..., None], (B, H, Lq, _STATS_LANES))

    n_q, n_k = pl.cdiv(Lq, block_q), pl.cdiv(Lk, block_k)
    Lq_pad = _ceil_to(Lq, block_q)

    qwalk = pl.BlockSpec((None, None, block_q, D),
                         lambda b, h, i, j: (b, h, j, 0))
    kspec = pl.BlockSpec((None, None, block_k, D),
                         lambda b, h, i, j: (b, h, i, 0))
    rowqw = pl.BlockSpec((None, None, block_q, _STATS_LANES),
                         lambda b, h, i, j: (b, h, j, 0))
    in_specs = [qwalk, kspec, kspec, qwalk, rowqw, rowqw]
    args = [qt, kt, vt, dot_, lse_p, delta_p]
    if mask is not None:
        in_specs.insert(0, _mask_spec(mask, block_q, block_k,
                                      q_axis=3, k_axis=2))
        args.insert(0, mask)
    dq, dk, dv = pl.pallas_call(
        functools.partial(
            _fa_bwd_fused_kernel, scale=scale, causal=causal,
            has_mask=mask is not None, mask_is_bool=mask_is_bool,
            block_q=block_q, block_k=block_k, q_len=Lq, kv_len=Lk,
            kv_offset=Lk - Lq, n_q=n_q, n_k=n_k),
        grid=(B, H, n_k, n_q),
        in_specs=in_specs,
        out_specs=[qwalk, kspec, kspec],
        out_shape=[jax.ShapeDtypeStruct((B, H, Lq, D), q.dtype),
                   jax.ShapeDtypeStruct((B, H, Lk, D), k.dtype),
                   jax.ShapeDtypeStruct((B, H, Lk, D), v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32),
                        pltpu.VMEM((block_k, D), jnp.float32),
                        pltpu.VMEM((Lq_pad, D), jnp.float32)],
        compiler_params=_compiler_params(interpret, n_arbitrary=2),
        interpret=interpret,
    )(*args)
    return (jnp.swapaxes(dq, 1, 2), jnp.swapaxes(dk, 1, 2),
            jnp.swapaxes(dv, 1, 2))


@functools.partial(jax.jit, static_argnames=(
    "causal", "scale", "mask_is_bool", "interpret", "blocks"))
def _fa_bwd_pallas(q, k, v, out, lse, do, mask, causal, scale,
                   mask_is_bool=False, interpret=False, blocks=None):
    from jax.experimental import pallas as pl

    B, Lq, H, D = q.shape
    Lk = k.shape[1]
    block_q, block_k = _blocks_or_static(blocks, Lq, Lk)
    qt, kt, vt, dot_, ot = (jnp.swapaxes(x, 1, 2)
                            for x in (q, k, v, do, out))
    # delta = rowsum(dout * out), fp32 [B,H,Lq] — one fused XLA pass
    delta = jnp.sum(dot_.astype(jnp.float32) * ot.astype(jnp.float32), -1)
    # lane-padded stats layout (see _fa_fwd_kernel comment)
    lse_p = jnp.broadcast_to(lse[..., None], (B, H, Lq, _STATS_LANES))
    delta_p = jnp.broadcast_to(delta[..., None], (B, H, Lq, _STATS_LANES))

    n_q, n_k = pl.cdiv(Lq, block_q), pl.cdiv(Lk, block_k)
    common = dict(scale=scale, causal=causal, has_mask=mask is not None,
                  mask_is_bool=mask_is_bool, block_q=block_q, block_k=block_k,
                  q_len=Lq, kv_len=Lk, kv_offset=Lk - Lq)

    # ---- dq: walk k-blocks per q-block ----
    qspec = pl.BlockSpec((None, None, block_q, D),
                         lambda b, h, i, j: (b, h, i, 0))
    kwalk = pl.BlockSpec((None, None, block_k, D),
                         lambda b, h, i, j: (b, h, j, 0))
    rowq = pl.BlockSpec((None, None, block_q, _STATS_LANES),
                        lambda b, h, i, j: (b, h, i, 0))
    in_specs = [qspec, kwalk, kwalk, qspec, rowq, rowq]
    args = [qt, kt, vt, dot_, lse_p, delta_p]
    if mask is not None:
        in_specs.insert(0, _mask_spec(mask, block_q, block_k,
                                      q_axis=2, k_axis=3))
        args.insert(0, mask)
    dq = pl.pallas_call(
        functools.partial(_fa_bwd_dq_kernel, n_k=n_k, **common),
        grid=(B, H, n_q, n_k),
        in_specs=in_specs,
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((B, H, Lq, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
    )(*args)

    # ---- dk/dv: walk q-blocks per k-block ----
    qwalk = pl.BlockSpec((None, None, block_q, D),
                         lambda b, h, i, j: (b, h, j, 0))
    kspec = pl.BlockSpec((None, None, block_k, D),
                         lambda b, h, i, j: (b, h, i, 0))
    rowqw = pl.BlockSpec((None, None, block_q, _STATS_LANES),
                         lambda b, h, i, j: (b, h, j, 0))
    in_specs = [qwalk, kspec, kspec, qwalk, rowqw, rowqw]
    args = [qt, kt, vt, dot_, lse_p, delta_p]
    if mask is not None:
        in_specs.insert(0, _mask_spec(mask, block_q, block_k,
                                      q_axis=3, k_axis=2))
        args.insert(0, mask)
    dk, dv = pl.pallas_call(
        functools.partial(_fa_bwd_dkv_kernel, n_q=n_q, **common),
        grid=(B, H, n_k, n_q),
        in_specs=in_specs,
        out_specs=[kspec, kspec],
        out_shape=[jax.ShapeDtypeStruct((B, H, Lk, D), k.dtype),
                   jax.ShapeDtypeStruct((B, H, Lk, D), v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32),
                        pltpu.VMEM((block_k, D), jnp.float32)],
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
    )(*args)
    return (jnp.swapaxes(dq, 1, 2), jnp.swapaxes(dk, 1, 2),
            jnp.swapaxes(dv, 1, 2))


# --------------------------- custom-vjp op ----------------------------------


def _fwd_any(q, k, v, mask, causal, scale, mask_is_bool, interpret,
             blocks=None):
    B, Lq, H, D = q.shape
    if _use_small_path(Lq, k.shape[1], H, D, q.dtype, mask):
        return _fa_small_fwd_pallas(q, k, v, mask, causal, scale,
                                    mask_is_bool=mask_is_bool,
                                    interpret=interpret)
    return _fa_fwd_pallas(q, k, v, mask, causal, scale,
                          mask_is_bool=mask_is_bool, interpret=interpret,
                          blocks=blocks)


def _bwd_any(q, k, v, out, lse, do, mask, causal, scale, mask_is_bool,
             interpret, blocks=None):
    B, Lq, H, D = q.shape
    if _use_small_path(Lq, k.shape[1], H, D, q.dtype, mask):
        return _fa_small_bwd_pallas(q, k, v, out, lse, do, mask, causal,
                                    scale, mask_is_bool=mask_is_bool,
                                    interpret=interpret)
    if Lq * D * 4 <= _FUSED_BWD_DQ_BYTES:
        f = _fa_bwd_fused_pallas  # one-pass p/ds; dq slice fits VMEM
    else:
        f = _fa_bwd_pallas        # very long seq: split dq / dkv walks
    return f(q, k, v, out, lse, do, mask, causal, scale,
             mask_is_bool=mask_is_bool, interpret=interpret,
             blocks=blocks)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash_fused(q, k, v, mask, causal, scale, mask_is_bool, interpret,
                 blocks=None):
    out, _ = _fwd_any(q, k, v, mask, causal, scale, mask_is_bool, interpret,
                      blocks)
    return out


def _flash_fused_fwd(q, k, v, mask, causal, scale, mask_is_bool, interpret,
                     blocks):
    _stats["pallas_fwd"] += 1
    out, lse = _fwd_any(q, k, v, mask, causal, scale, mask_is_bool,
                        interpret, blocks)
    return out, (q, k, v, mask, out, lse)


def _flash_fused_bwd(causal, scale, mask_is_bool, interpret, blocks, res,
                     do):
    _stats["pallas_bwd"] += 1
    q, k, v, mask, out, lse = res
    dq, dk, dv = _bwd_any(q, k, v, out, lse, do, mask, causal, scale,
                          mask_is_bool, interpret, blocks)
    # Only bool masks ride the fused path (dispatch keeps float masks —
    # potentially LEARNED biases — on the XLA path where their gradient is
    # real); their tangent type is float0. The assert keeps that invariant
    # self-enforcing if eligibility is ever widened.
    if mask is None:
        dmask = None
    else:
        assert not jnp.issubdtype(mask.dtype, jnp.floating), (
            "float attn_mask must not reach the fused vjp: its cotangent "
            "would be silently zero (learned-bias freeze); route float "
            "masks to flash_attention_xla")
        dmask = np.zeros(mask.shape, jax.dtypes.float0)
    return dq, dk, dv, dmask


_flash_fused.defvjp(_flash_fused_fwd, _flash_fused_bwd)


# --------------------------- dispatch ---------------------------------------

def _mask_key(mask):
    if mask is None:
        return None
    return (jnp.dtype(mask.dtype).name,) + tuple(
        int(d != 1) for d in mask.shape)


def _check_compiles(dtype, Lq, Lk, H, D, causal, mask=None, blocks=None):
    """Eager fwd+bwd compile check (`tiling.compile_check`) at the exact
    production (L, H, D) shapes AND the exact resolved block config —
    including the BACKWARD kernels, so the custom_vjp path is known-good
    under value_and_grad before it is staged into the user's jit. H is
    part of the key: kernel SELECTION (`_use_small_path`) and the small
    path's per-program VMEM footprint both depend on it."""
    def run():
        sc = float(1.0 / np.sqrt(D))
        q = jnp.ones((2, Lq, H, D), dtype)
        k = jnp.ones((2, Lk, H, D), dtype)
        pm = None
        is_bool = False
        if mask is not None:
            shp = tuple(1 if d == 1 else {0: 2, 1: H, 2: Lq, 3: Lk}[ax]
                        for ax, d in enumerate(mask.shape))
            is_bool = mask.dtype == jnp.bool_
            pm = (jnp.ones(shp, jnp.bool_) if is_bool
                  else jnp.zeros(shp, mask.dtype))

        def f(q, k, v):
            return _flash_fused(q, k, v, pm, bool(causal), sc, is_bool,
                                _INTERPRET, blocks).astype(jnp.float32).sum()

        return jax.grad(f, argnums=(0, 1, 2))(q, k, k)

    _tiling.compile_check(
        "flash_attention", run, dtype=jnp.dtype(dtype).name,
        q=(2, Lq, H, D), k=(2, Lk, H, D), causal=bool(causal),
        mask=_mask_key(mask), blocks=blocks or "whole-sequence",
        interpret=_INTERPRET)


def _pallas_eligible(q, k, v, mask, causal) -> bool:
    """Shape/dtype eligibility for the fused path."""
    if not (_on_tpu() or _INTERPRET):
        return False
    B, Lq, H, D = q.shape
    Lk = k.shape[1]
    if not (isinstance(Lq, int) and isinstance(Lk, int)):
        return False
    # tail blocks are masked in-kernel, so any length >= 64 works; below
    # that the [L,L] score tile is trivially small and XLA wins anyway
    if Lq < 64 or Lk < 64:
        return False
    if not (q.dtype == k.dtype == v.dtype):
        return False
    if q.dtype == jnp.dtype(jnp.float16):
        return False  # fp16 softmax floor handling lives on the XLA path
    if causal and Lq > Lk:
        # kv_offset < 0: top query rows have ZERO valid key columns, and the
        # kernels' pure-causal fast path skips the fully-masked-row p-zeroing
        # (fwd would emit an average of V; bwd lse for such rows is garbage).
        # flash_attention_xla handles the empty-row case correctly.
        return False
    if mask is not None:
        if mask.ndim != 4:
            return False
        # FLOAT masks stay on the XLA path: the fused custom_vjp returns a
        # zero mask cotangent, which would silently freeze a LEARNED
        # additive bias (ALiBi / relative-position) — bool masks cannot be
        # differentiated, so only they ride the kernel
        if mask.dtype != jnp.bool_:
            return False
        for ax, full in enumerate((B, H, Lq, Lk)):
            if mask.shape[ax] not in (1, full):
                return False
    return True


def _banded_xla(q, k, v, causal, scale, window, precision, length=None):
    """The forward-only call as a masked matrix product: q [B, L, H, D],
    k and v [B, Lk, Hkv, D], the group an axis of q and of the scores
    (K/V are not repeated); rows at and past `length` zeros. Off the TPU,
    and the kernel's parity reference."""
    B, Lq, H, D = q.shape
    Lk, Hkv = k.shape[1], k.shape[2]
    Dv = v.shape[-1]
    qg = q.reshape(B, Lq, Hkv, H // Hkv, D)
    rows = jnp.arange(Lq, dtype=jnp.int32)[:, None] + (Lk - Lq)
    cols = jnp.arange(Lk, dtype=jnp.int32)[None, :]
    keep = jnp.ones((Lq, Lk), bool)
    if causal:
        keep = cols <= rows
        if window is not None:
            keep = keep & (cols > rows - window)
    with (jax.default_matmul_precision(precision) if precision
          else contextlib.nullcontext()):
        s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(keep, s, -jnp.inf)
        out = jnp.einsum("bkgqs,bskd->bqkgd",
                         jax.nn.softmax(s, axis=-1).astype(v.dtype), v)
    out = out.reshape(B, Lq, H, Dv).astype(q.dtype)
    if length is not None:
        out = jnp.where((jnp.arange(Lq) < length)[:, None, None], out, 0)
    return out


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash_forward_only(q, k, v, live, causal, scale, window, precision,
                        interpret, blocks):
    return _fa_fwd_pallas(q, k, v, None, causal, scale, interpret=interpret,
                          blocks=blocks, window=window, precision=precision,
                          live=live)[0]


def _forward_only_fwd(q, k, v, live, causal, scale, window, precision,
                      interpret, blocks):
    return _flash_forward_only(q, k, v, live, causal, scale, window,
                               precision, interpret, blocks), None


def _forward_only_bwd(causal, scale, window, precision, interpret, blocks,
                      res, do):
    raise NotImplementedError(
        "flash_attention: the backward pass takes no `window`, no "
        "`precision`, no `length`, no grouped K/V heads and no value head "
        "size of its own (forward only: serving's prefill); train through "
        "the masked product, or add them to _fa_bwd_fused_kernel")


_flash_forward_only.defvjp(_forward_only_fwd, _forward_only_bwd)


def _forward_only(q, k, v, causal, scale, window, precision, length=None):
    """`flash_attention` with a `window`, a `precision`, a `length`,
    grouped K/V heads or a value head size of its own: the forward kernel
    alone where it is eligible (every row live where no `length` is
    given), the masked product otherwise."""
    B, Lq, H, D = q.shape
    Lk, Hkv, Dv = k.shape[1], k.shape[2], v.shape[-1]
    if window is not None:
        if not causal or int(window) < 1:
            raise ValueError("flash_attention: `window` needs causal=True "
                             f"and window >= 1, got causal={causal}, "
                             f"window={window}")
        window = int(window)
        _stats["window"] += 1
    if length is not None and not causal:
        raise ValueError("flash_attention: `length` needs causal=True")
    if H % Hkv or v.shape[:3] != k.shape[:3]:
        raise ValueError(f"flash_attention: {H} query heads do not divide "
                         f"over K/V of shape {k.shape} / {v.shape}")
    eligible = ((_on_tpu() or _INTERPRET) and Lq >= 64 and Lk >= 64
                and q.dtype == k.dtype == v.dtype
                and q.dtype != jnp.dtype(jnp.float16)
                and not (causal and Lq > Lk))
    if not eligible:
        _stats["xla"] += 1
        return _banded_xla(q, k, v, causal, scale, window, precision, length)
    blocks = _static_blocks(Lq, Lk)

    def live(n):
        # a causal call walks `_live_walk`'s steps (every row live without
        # a `length`); a call without the triangle computes every step
        return jnp.asarray(n, jnp.int32) if causal else None

    def run():
        return _fa_fwd_pallas(
            jnp.ones((1, Lq, H, D), q.dtype), jnp.ones((1, Lk, Hkv, D), q.dtype),
            jnp.ones((1, Lk, Hkv, Dv), q.dtype), None, bool(causal),
            float(scale), interpret=_INTERPRET, blocks=blocks, window=window,
            precision=precision, live=live(Lq))

    _tiling.compile_check(
        "flash_attention_forward_only", run, dtype=jnp.dtype(q.dtype).name,
        q=(Lq, H, D), k=(Lk, Hkv, D), v=(Lk, Hkv, Dv), causal=bool(causal),
        window=window, precision=precision, blocks=blocks,
        interpret=_INTERPRET)
    _stats["pallas"] += 1
    if length is not None:
        _stats["live_length"] += 1
    return _flash_forward_only(q, k, v, live(Lq if length is None else length),
                               bool(causal), float(scale), window, precision,
                               _INTERPRET, blocks)


def _flash_per_shard(km, q, k, v, mask, causal, scale):
    """The dispatch below, per shard of a declared multi-device program
    (`tiling.kernel_mesh`): batch over the data axes, heads over the
    tensor-parallel axis — attention never mixes either, so no collective
    is needed. A dim the axis does not divide stays whole."""
    from jax.sharding import PartitionSpec as P
    B, H = q.shape[0], q.shape[2]
    b_ax = km.batch if B % km.size(km.batch) == 0 else None
    h_ax = km.heads if H % km.size(km.heads) == 0 else None
    spec = P(b_ax, None, h_ax, None)
    args, specs = (q, k, v), (spec, spec, spec)
    if mask is not None:
        args += (mask,)
        specs += (P(b_ax if mask.shape[0] != 1 else None,
                    h_ax if mask.shape[1] != 1 else None, None, None),)

    def local(q, k, v, *m):
        return flash_attention(q, k, v, mask=m[0] if m else None,
                               causal=causal, scale=scale)

    return _tiling.per_shard(km, local, specs, spec)(*args)


def flash_attention(q, k, v, mask=None, causal=False, scale=None,
                    dropout_p=0.0, dropout_key=None, window=None,
                    precision=None, length=None):
    """Dispatch: fused Pallas fwd+bwd on TPU (masks + causal + any seq len
    >= 64, streamed K/V so Lk is HBM-bounded); XLA composition otherwise.

    `window` (causal only: key j for query i iff ``i - window < j <= i``),
    `precision` ("default" or "highest", of the kernel's products),
    `length` (causal only, an int32 scalar, traced or not: the query rows
    from it on are a prompt's bucket padding, computed by nobody and
    returned as zeros; the rows before it are what the call without it
    gives, bit for bit), K/V of fewer heads than q and V of another head
    size than q take the FORWARD-ONLY path (`_forward_only`), which takes
    no `mask` and no dropout.

    `dropout_p > 0` (training-time attention dropout) ALWAYS takes the XLA
    path: the fused kernels do not thread a dropout seed, and weight-level
    dropout semantics (reference `nn/layer/transformer.py:412-415`) require
    dropping post-softmax probabilities, which the online-softmax kernels
    never materialize normalized. This is a documented, loud fallback —
    benches and inference run dropout_p == 0 and stay fused."""
    if scale is None:
        scale = 1.0 / np.sqrt(q.shape[-1])
    if window is not None or precision is not None or length is not None \
            or k.shape[2] != q.shape[2] or v.shape[-1] != q.shape[-1]:
        if mask is not None or dropout_p > 0.0:
            raise ValueError("flash_attention: `window`, `precision`, "
                             "`length` and grouped K/V heads take no mask "
                             "and no dropout")
        return _forward_only(q, k, v, causal, scale, window, precision,
                             length)
    if dropout_p > 0.0:
        _stats["xla"] += 1
        return flash_attention_xla(q, k, v, mask=mask, causal=causal,
                                   scale=scale, dropout_p=dropout_p,
                                   dropout_key=dropout_key)
    km = _tiling.current_kernel_mesh()
    if km is not None and (_on_tpu() or _INTERPRET):
        return _flash_per_shard(km, q, k, v, mask, causal, scale)
    if _pallas_eligible(q, k, v, mask, causal):
        B, Lq, H, D = q.shape
        blocks = _resolve_flash_blocks(q, k, mask)
        _check_compiles(q.dtype, Lq, k.shape[1], H, D, causal, mask, blocks)
        _stats["pallas"] += 1
        is_bool = mask is not None and mask.dtype == jnp.bool_
        return _flash_fused(q, k, v, mask, bool(causal), float(scale),
                            is_bool, _INTERPRET, blocks)
    _stats["xla"] += 1
    return flash_attention_xla(q, k, v, mask=mask, causal=causal, scale=scale)
