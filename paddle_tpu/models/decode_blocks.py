"""What the serving methods of the hybrid models share, so that a model
file writes out only its own layers: matrix products at three bfloat16
passes, the head under a scope, and the parts of `forward_prefill` /
`forward_decode` that are the same whatever the layers are (the slot's
arguments, the lanes' view of the tables, a full-attention layer's append
and paged attention, a sliding-window layer's ring, a prompt's grouped
attention through the flash kernel, the length bump, the last real
position), the attention layer with grouped K/V heads and q/k norms that
`models/mellum.py` and `models/exaone_moe.py` both have
(`GroupedAttention`), and the same appends and attentions for R new rows
a lane (`*_rows_attention`: a model that verifies a draft gives every
lane the positions `context .. context + R - 1` in one call).
`models/olmo_hybrid.py`, `models/nemotron_h.py`, `models/mellum.py` and
`models/exaone_moe.py` call these; the rest of ROADMAP D1 (one model
runner instead of a copy a model) is a `simplicity` issue's.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import nn
from ..framework.tensor import Tensor
from ..ops import _dispatch as _d
from ..ops import reshape
from ..ops import rope as _rope
from .gpt import GPT

# Matrix products of float32 weights run in three bfloat16 passes
# (`Precision.HIGH`), not the TPU's default one: every branch of these
# models ends in (or starts from) a norm, so the residual stream carries
# each layer's rounding on at full size, and with single-pass products 8
# layers put the logits 0.12 from the float32 reference (of a mean size of
# 0.22; PERF.md, PR 27): the argmax turned in one run of three. A choice of
# these models', in their own products; the Pallas kernels they share keep
# their own precision (Mosaic takes DEFAULT or HIGHEST only).
PRODUCTS = jax.lax.Precision.HIGH


@_d.kernel("linear_high")
def _matmul(x, weight):
    return jnp.matmul(x, weight, precision=PRODUCTS)


# under an inner jit of its own: the op dispatcher stages a shape it has
# seen twice, and a program meets the head once, so it would be traced
# bare and carry no scope in the device trace
head = jax.jit(_matmul)


class HighLinear(nn.Linear):
    """`nn.Linear` without bias, its product at `PRODUCTS`."""

    def __init__(self, n_in: int, n_out: int):
        super().__init__(n_in, n_out, bias_attr=False)

    def forward(self, x):
        return _d.call(_matmul, (x, self.weight))


# A model whose expert blocks ROUTE on the residual stream keeps the
# products in front of its routers at `highest`: at three passes the
# margin between the last expert chosen and the first left out sat up to
# 4e-5 from the float32 reference's, and 13 prompts of 48 met another
# expert somewhere in their 256 tokens, after which the two runs are no
# longer the same computation (PERF.md, PR 31).
EXACT = jax.lax.Precision.HIGHEST


@_d.kernel("linear_highest")
def _matmul_exact(x, weight):
    return jnp.matmul(x, weight, precision=EXACT)


class ExactLinear(nn.Linear):
    """`nn.Linear` without bias, its product at `EXACT`."""

    def __init__(self, n_in: int, n_out: int):
        super().__init__(n_in, n_out, bias_attr=False)

    def forward(self, x):
        return _d.call(_matmul_exact, (x, self.weight))


class GroupedAttention(nn.Layer):
    """Attention with grouped K/V heads (query head h reads K/V head
    ``h // (heads / kv_heads)``), no bias, q and k RMS-normed per head over
    their D values before any rotation, every product at `EXACT`.
    `window` tokens (None: every past token) is the mask of a prompt's
    attention; `rope` is one group of a config's `rope_parameters`, or
    None for a layer that rotates nothing."""

    def __init__(self, hidden: int, heads: int, kv_heads: int, head_dim: int,
                 eps: float, window=None, rope=None):
        super().__init__()
        self.heads, self.kv_heads, self.head_dim = heads, kv_heads, head_dim
        self.window = window
        self.q_proj = ExactLinear(hidden, heads * head_dim)
        self.k_proj = ExactLinear(hidden, kv_heads * head_dim)
        self.v_proj = ExactLinear(hidden, kv_heads * head_dim)
        self.o_proj = ExactLinear(heads * head_dim, hidden)
        self.q_norm = nn.RMSNorm(head_dim, eps)
        self.k_norm = nn.RMSNorm(head_dim, eps)
        self.rope_kind = None
        if rope is not None:
            self.rope_kind = rope.get("rope_type", "default")
            # constants of the configuration, not weights
            self.inv_freq, self.rope_factor = _rope.inverse_frequencies(
                head_dim, rope)

    def qkv(self, u, positions):
        """u Tensor ``[B, L, h]`` at `positions` ``[B, L]`` (or ``[L]``):
        q ``[B, L, H, D]`` and k ``[B, L, Hkv, D]``, normed per head and
        rotated where the layer rotates, and v ``[B, L, Hkv, D]``
        (arrays)."""
        B, L, _ = u.shape
        D = self.head_dim
        q = self.q_norm(reshape(self.q_proj(u), [B, L, self.heads, D]))
        k = self.k_norm(reshape(self.k_proj(u), [B, L, self.kv_heads, D]))
        v = self.v_proj(u).data.reshape(B, L, self.kv_heads, D)
        if self.rope_kind is None:
            return q.data, k.data, v
        q, k = _rope.rotate(q.data, k.data,
                            jnp.broadcast_to(positions, (B, L)),
                            self.inv_freq, self.rope_factor, self.rope_kind)
        return q, k, v

    def attend(self, q, k, v, length=None):
        """A prompt's attention under this layer's mask: the flash
        kernel's forward, which skips what lies outside the band and past
        the prompt's live rows `length` (None: every row), its products
        at `highest` (they sit in front of a router)."""
        from ..ops.pallas import flash_attention as _fa
        return _fa.flash_attention(q, k, v, causal=True, window=self.window,
                                   precision="highest", length=length)

    def output(self, out):
        """The heads' outputs ``[B, L, H, D]`` -> the layer's Tensor."""
        B, L = out.shape[:2]
        return self.o_proj(Tensor(out.reshape(B, L, self.heads
                                              * self.head_dim)))


# the head's reason, for a product at `EXACT` whose shape a program meets
# once (a dense MLP among expert layers, a projection of two hidden widths):
# traced bare it reaches the device trace as `dot_general:` with no scope
exact = jax.jit(_matmul_exact)


class ExactLinearOnce(ExactLinear):
    """`ExactLinear` under an inner jit of its own (`exact`). Serving only:
    the product is made on the arrays, outside the tape."""

    def forward(self, x):
        return Tensor(exact(x.data, self.weight.data))


class TokensToLogits:
    """What a hybrid model's class does around its blocks, for a class
    that holds `wte` (the embedding), `norm_f` (the final RMSNorm) and
    `lm_head` (the untied head, a `HighLinear`): mixed in before
    `nn.Layer`."""

    def num_params(self):
        return sum(p.size for p in self.parameters())

    def _embed(self, ids):
        with jax.named_scope("embed"):
            return self.wte(ids)

    def _logits(self, x):
        with jax.named_scope("ln"):
            x = self.norm_f(x)
        with jax.named_scope("logits"):
            return Tensor(head(x.data, self.lm_head.weight.data))

    def tp_mesh(self):
        return None

    generate_dense = GPT.generate_dense


def pages_for(max_batch: int, max_len: int, page_size: int, num_pages: int):
    """(pages a sequence, pages of a pool): the default backs every slot
    in full, +1 for the null page."""
    pages_per_seq = -(-max_len // page_size)
    return pages_per_seq, num_pages or 1 + max_batch * pages_per_seq


def prefill_args(input_ids, cache, slot, length, write_start):
    """`forward_prefill`'s scalars as int32 arrays, and the slot's row of
    the block table."""
    if input_ids.shape[0] != 1:
        raise ValueError(f"forward_prefill fills ONE slot; got batch "
                         f"{input_ids.shape[0]} (serving prefills per "
                         f"request)")
    slot = jnp.asarray(slot, jnp.int32)
    return (slot, jnp.asarray(length, jnp.int32),
            jnp.asarray(write_start, jnp.int32),
            jnp.take(cache.block_tables, slot, axis=0))


def last_real_position(x, length):
    """x Tensor [1, L, h] -> Tensor [1, h] at position `length - 1`."""
    return Tensor(jax.lax.dynamic_index_in_dim(
        x.data, length - 1, axis=1, keepdims=False))


def decode_view(cache, active, slot_map):
    """The block tables, lengths and activity one decode step works on:
    in lane mode the rows `slot_map` names (gathered clamped: a padding
    lane carries the sentinel `max_batch`), else every slot's. Returns
    ``(slot_map or None, block_tables, context_lens, active)``."""
    if slot_map is not None:
        slot_map = jnp.asarray(slot_map, jnp.int32)
        bt = jnp.take(cache.block_tables, slot_map, axis=0, mode="clip")
        ctx = jnp.take(cache.context_lens, slot_map, mode="clip")
        if active is None:
            active = slot_map < cache.max_batch
    else:
        bt, ctx = cache.block_tables, cache.context_lens
        if active is None:
            active = jnp.ones((cache.max_batch,), bool)
    return slot_map, bt, ctx, jnp.asarray(getattr(active, "data", active),
                                          bool)


def bump_lengths(cache, slot_map, ctx, active):
    """One more token in every active sequence; a padding lane's sentinel
    is dropped."""
    if slot_map is not None:
        cache.context_lens = cache.context_lens.at[slot_map].add(
            jnp.where(active, 1, 0).astype(jnp.int32), mode="drop")
    else:
        cache.context_lens = jnp.where(active, ctx + 1, ctx)


def advance_lengths(cache, slot_map, ctx, n_new):
    """`n_new` ``[B]`` more tokens in each sequence (0 for a lane that is
    not active), decided on the device: a verified draft makes it 2."""
    n_new = n_new.astype(jnp.int32)
    if slot_map is not None:
        cache.context_lens = cache.context_lens.at[slot_map].add(
            n_new, mode="drop")
    else:
        cache.context_lens = ctx + n_new


def _rows_as_lanes(bt_or_slots, ctx, active, R: int):
    """Each lane's R new rows as R lanes of their own: the lane's table
    row (or slot), the positions ``ctx .. ctx + R - 1`` and its activity,
    ``[B * R]`` each, a lane's rows consecutive."""
    positions = (ctx[:, None] + jnp.arange(R, dtype=ctx.dtype)).reshape(-1)
    return (jnp.repeat(bt_or_slots, R, axis=0), positions,
            jnp.repeat(active, R))


def paged_rows_attention(cache, i, q, k, v, bt, ctx, active):
    """R rows a lane of a full-attention layer: append K/V ``[B, R,
    Hkv*D]`` at positions ``ctx .. ctx + R - 1`` of pools `i`, then attend
    with q ``[B, R, H, D]``, row r over the keys ``<= ctx + r``: B x R
    lanes of the one-query kernel over the lanes' table rows repeated
    (it reads a lane's pages R times; a kernel that reads them once for
    all R queries is a later `perf_opt`'s). Returns ``[B, R, H, D]``."""
    from ..ops.pallas import paged_attention as _pa
    B, R = q.shape[:2]
    table, positions, live = _rows_as_lanes(bt, ctx, active, R)
    cache.k_pages[i], cache.v_pages[i] = _pa.cache_append(
        cache.k_pages[i], cache.v_pages[i], k.reshape(B * R, -1),
        v.reshape(B * R, -1), table, positions, live)
    out = _pa.paged_attention(
        q.reshape((B * R,) + q.shape[2:]), cache.k_pages[i],
        cache.v_pages[i], table, jnp.where(live, positions + 1, 0))
    return out.reshape(q.shape)


def paged_decode_attention(cache, i, q, k, v, bt, ctx, active):
    """One token of a full-attention layer: append its K/V ``[B, Hkv*D]``
    to pools `i`, then attend with q ``[B, H, D]`` over the pages (the new
    token is part of its own context). Returns ``[B, H, D]``."""
    from ..ops.pallas import paged_attention as _pa
    cache.k_pages[i], cache.v_pages[i] = _pa.cache_append(
        cache.k_pages[i], cache.v_pages[i], k, v, bt, ctx, active)
    return _pa.paged_attention(q, cache.k_pages[i], cache.v_pages[i], bt,
                               jnp.where(active, ctx + 1, 0))


def paged_prefill_append(cache, i, k, v, page_row, length, write_start):
    """A prompt's K/V ``[L, Hkv*D]`` into the pages of its slot's row."""
    from ..ops.pallas import paged_attention as _pa
    cache.k_pages[i], cache.v_pages[i] = _pa.prefill_append(
        cache.k_pages[i], cache.v_pages[i], k, v, page_row, length,
        start=write_start)


# ---- a sliding-window layer's ring (`decode_cache.KV_WINDOW`): the same
# scatter and the same paged-attention kernel at a table that is a
# function of the slot, so none is stored or sent


def ring_tables(cache, slots):
    """The ring pages of `slots` ``[B]`` (clamped: a padding lane carries
    the sentinel `max_batch`, and its writes are dropped by `active`):
    ``[B, window / page_size]``, slot b owning ``1 + b * n .. + n - 1``."""
    n = cache.window_pages
    slots = jnp.minimum(jnp.asarray(slots, jnp.int32), cache.max_batch - 1)
    return 1 + slots[:, None] * n + jnp.arange(n, dtype=jnp.int32)[None]


def ring_decode_attention(cache, i, q, k, v, slots, ctx, active):
    """One token of a sliding-window layer: write its K/V ``[B, Hkv*D]``
    at row ``context mod window`` of each lane's ring, then attend with q
    ``[B, H, D]`` over the ``min(context + 1, window)`` rows the ring
    holds. Returns ``[B, H, D]``."""
    from ..ops.pallas import paged_attention as _pa
    W = cache.window
    table = ring_tables(cache, slots)
    cache.window_k[i], cache.window_v[i] = _pa.cache_append(
        cache.window_k[i], cache.window_v[i], k, v, table, ctx % W, active)
    return _pa.paged_attention(
        q, cache.window_k[i], cache.window_v[i], table,
        jnp.where(active, jnp.minimum(ctx + 1, W), 0))


def ring_rows_attention(cache, i, q, k, v, slots, ctx, active):
    """R rows a lane of a sliding-window layer, q ``[B, R, H, D]`` and
    K/V ``[B, R, Hkv*D]`` at positions ``ctx .. ctx + R - 1``: one row
    at a time, write it and attend, `ring_decode_attention` R times. Not
    all writes first: the row that position ``ctx + 1`` takes is the one
    position ``ctx + 1 - window`` holds, which the query at ``ctx`` still
    sees. A row whose token turns out rejected is overwritten by the next
    call's write at the same position before anything reads it, and the
    position it displaced is by then outside every later query's window.
    Returns ``[B, R, H, D]``."""
    return jnp.stack(
        [ring_decode_attention(cache, i, q[:, r], k[:, r], v[:, r], slots,
                               ctx + r, active)
         for r in range(q.shape[1])], axis=1)


def ring_prefill_write(cache, i, k, v, slot, length):
    """A prompt's K/V ``[L, Hkv*D]`` into its slot's ring, which is
    REWRITTEN whole: row r takes the LAST position t < `length` with
    ``t mod window == r`` (the last `window` tokens of the prompt, each at
    its own row), and zeros where the prompt is shorter than the ring.
    A position at or past `length` (bucket padding) is never the last
    below `length`, so it cannot reach a row, though its row ``t mod
    window`` holds a live token once the bucket is longer than the
    window. The slot's pages are contiguous: one in-place update."""
    W, n = cache.window, cache.window_pages
    r = jnp.arange(W, dtype=jnp.int32)
    t = r + W * ((length - 1 - r) // W)           # < length wherever r < length
    live = (r < length)[:, None]
    start = 1 + jnp.asarray(slot, jnp.int32) * n

    def put(ring, seq):
        rows = jnp.take(seq, jnp.clip(t, 0, seq.shape[0] - 1), axis=0)
        rows = jnp.where(live, rows, 0).astype(ring.dtype)
        return jax.lax.dynamic_update_slice(
            ring, rows.reshape(n, cache.page_size, -1), (start, 0, 0))

    cache.window_k[i], cache.window_v[i] = (put(cache.window_k[i], k),
                                            put(cache.window_v[i], v))
