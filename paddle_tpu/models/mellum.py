"""Mellum (`model_type` `mellum`; Mellum2-12B-A2.5B-Instruct is 28 layers
by ``layer_types`` = (sliding, sliding, sliding, full) x 7): every layer
attention and a sparse expert MLP behind an RMSNorm each::

    h  = x + Attn_i(RMSNorm(x))
    x' = h + MoE_i(RMSNorm(h))
    logits = W_head RMSNorm(x_final)       # head untied from the embedding

    Attn: q [H, D], k and v [Hkv, D] (grouped K/V heads: query head h reads
          K/V head h // (H/Hkv)), no bias; q and k RMS-normed per head over
          their D values (a learned weight of D each), then ROTATED
          (`ops/rope.py`: the layer kind's own `rope_parameters` group,
          plain on the sliding layers and YaRN on the full ones);
          softmax(q k^T / sqrt(D) + mask) v; W_o.
          mask of `full_attention`: key j for query t iff j <= t;
          of `sliding_attention`: iff t - sliding_window < j <= t.
    MoE:  z = u W_r over ALL `num_experts`; p = softmax(z); the top k;
          w_e = p_e / (sum of p over the chosen);
          out = sum over chosen experts HELD HERE of w_e expert_e(u),
          expert(u) = (silu(u Wg) * (u Wu)) Wd (`ops/moe.py`: dropless,
          gate and up one grouped product over weights stored stacked).
          No shared expert.

`experts_held` = (first, count) says which routed experts this chip holds,
as `models/nemotron_h.py` says: the router keeps its full width, and what
an absent expert would add is left out.

Precision: float32 weights served as float32, every product in front of a
router at `highest` (`decode_blocks.ExactLinear`, the grouped products,
the flash kernel's and the paged kernel's), for the reason
`models/nemotron_h.py` gives. The head runs in three passes.

The decode protocol of `inference/serving.ServingEngine` over a cache of
two kinds (`models/decode_cache.py`): K/V pages ``[pages, page, Hkv*D]``
for a full layer, a RING of `sliding_window` tokens a slot for a sliding
layer (`kv_window`). Keys are stored rotated, so a ring's rows need no
order: position t is written at row ``t mod window`` and the layer
attends over ``min(context, window)`` rows with the same paged kernel, at
a table computed from the slot (`decode_blocks.ring_*`). Prefill rotates
at positions ``0 .. L-1``, decode at each lane's context.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Tuple

import jax
import jax.numpy as jnp

from .. import nn
from ..framework.tensor import Tensor
from ..nn.initializer import Uniform
from ..ops import moe as _moe
from ..ops import reshape
from . import decode_blocks as _blocks
from .decode_cache import (KV, KV_WINDOW, PagedKVCache,
                           WindowLayersUnsupported)

SLIDING, FULL = "sliding_attention", "full_attention"
# the scope of a layer's append and kernel, under `attention`
_SCOPE = {SLIDING: "window", FULL: "full"}
PUBLISHED_LAYER_TYPES = (SLIDING, SLIDING, SLIDING, FULL) * 7
PUBLISHED_ROPE = {
    FULL: {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
           "original_max_position_embeddings": 8192, "beta_fast": 32,
           "beta_slow": 1, "attention_factor": 1.2772588722239782},
    SLIDING: {"rope_type": "default", "rope_theta": 500000}}


@dataclasses.dataclass
class MellumConfig:
    """The source's keys under the source's names (Hugging Face
    `config.json` of `mellum`), and `experts_held`."""
    vocab_size: int = 98304
    hidden_size: int = 2304
    num_hidden_layers: int = 28
    layer_types: Tuple[str, ...] = PUBLISHED_LAYER_TYPES
    sliding_window: int = 1024
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 131072
    # attention
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    attention_bias: bool = False
    rope_parameters: dict = dataclasses.field(
        default_factory=lambda: {k: dict(v)
                                 for k, v in PUBLISHED_ROPE.items()})
    # experts
    num_experts: int = 64
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 896
    norm_topk_prob: bool = True
    hidden_act: str = "silu"
    tie_word_embeddings: bool = False
    # not in the source: (first, count) of the routed experts held here;
    # () => all of them
    experts_held: Tuple[int, ...] = ()

    def __post_init__(self):
        n = self.num_hidden_layers
        # a depth cut keeps the leading layers of the published list
        self.layer_types = tuple(self.layer_types)[:n]
        if len(self.layer_types) != n \
                or set(self.layer_types) - {SLIDING, FULL}:
            raise ValueError(f"layer_types must name {n} layers as "
                             f"{SLIDING} or {FULL}, got {self.layer_types}")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_attention_heads must be a multiple of "
                             "num_key_value_heads")
        if self.attention_bias or self.tie_word_embeddings \
                or not self.norm_topk_prob or self.hidden_act != "silu":
            raise ValueError(
                "only attention_bias=False, tie_word_embeddings=False, "
                "norm_topk_prob=True and hidden_act='silu' are implemented")
        first, count = self.experts_held or (0, self.num_experts)
        if not 0 <= first < first + count <= self.num_experts:
            raise ValueError(f"experts_held {self.experts_held} is no range "
                             f"of the {self.num_experts} routed experts")
        if self.num_experts_per_tok >= self.num_experts:
            raise ValueError("num_experts_per_tok must be below num_experts")
        self.experts_held = (int(first), int(count))

    @staticmethod
    def tiny(layer_types=(SLIDING, SLIDING, SLIDING, FULL), **changes):
        """Both kinds of layer in the published 3:1; a window of 8, 8
        experts top-2, 2 K/V heads for 4 query heads, YaRN of factor 4
        over 16 positions on the full layer."""
        return MellumConfig(**{**dict(
            vocab_size=256, hidden_size=64,
            num_hidden_layers=len(layer_types), layer_types=layer_types,
            sliding_window=8, max_position_embeddings=512,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            rope_parameters={
                FULL: {"rope_type": "yarn", "rope_theta": 10000, "factor": 4,
                       "original_max_position_embeddings": 16,
                       "beta_fast": 32, "beta_slow": 1},
                SLIDING: {"rope_type": "default", "rope_theta": 10000}},
            num_experts=8, num_experts_per_tok=2, moe_intermediate_size=32),
            **changes})


class MellumAttention(_blocks.GroupedAttention):
    """`decode_blocks.GroupedAttention` at the configuration's sizes, the
    layer kind's own window and `rope_parameters` group."""

    def __init__(self, cfg: MellumConfig, kind: str):
        super().__init__(
            cfg.hidden_size, cfg.num_attention_heads,
            cfg.num_key_value_heads, cfg.head_dim, cfg.rms_norm_eps,
            window=int(cfg.sliding_window) if kind == SLIDING else None,
            rope=cfg.rope_parameters[kind])
        self.kind = kind


class MellumExperts(nn.Layer):
    """A softmax-routed dropless SwiGLU expert layer holding
    `experts_held` of the layer's routed experts."""

    def __init__(self, cfg: MellumConfig):
        super().__init__()
        h, f = cfg.hidden_size, cfg.moe_intermediate_size
        self.first, held = cfg.experts_held
        self.top_k = cfg.num_experts_per_tok
        bound = math.sqrt(6.0 / (h + cfg.num_experts))
        self.router = self.create_parameter(
            (h, cfg.num_experts), default_initializer=Uniform(-bound, bound))
        # stacked once: [Wg^T; Wu^T] [E_held, 2f, h] and Wd [E_held, f, h]
        # (ops/moe.py: the hidden width minor)
        bound = math.sqrt(6.0 / (h + f))
        self.w_gate_up = self.create_parameter(
            (held, 2 * f, h), default_initializer=Uniform(-bound, bound))
        self.w_down = self.create_parameter(
            (held, f, h), default_initializer=Uniform(-bound, bound))

    def forward(self, u, active=None):
        """u Tensor [.., h] -> (the layer's output Tensor, counters [3]
        int32 as `ops/moe.COUNTERS`, margin [..] float32). A token whose
        `active` [..] is False (padding) meets no expert."""
        lead, h = u.shape[:-1], u.shape[-1]
        flat = u.data.reshape(-1, h)
        if active is not None:
            active = jnp.broadcast_to(active, lead).reshape(-1)
        with jax.named_scope("mlp"), jax.named_scope("moe"):
            experts, weights, margin = _moe.softmax_route(
                flat, self.router.data, top_k=self.top_k)
            out, counters = _moe.held_experts(
                flat, experts, weights, self.w_gate_up.data,
                self.w_down.data, first=self.first, active=active,
                form="swiglu")
        return Tensor(out.reshape(*lead, h)), counters, margin.reshape(lead)


class MellumBlock(nn.Layer):
    def __init__(self, cfg: MellumConfig, kind: str):
        super().__init__()
        self.kind = kind
        self.attn_norm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        self.attn = MellumAttention(cfg, kind)
        self.moe_norm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        self.moe = MellumExperts(cfg)

    def normed(self, norm, x):
        with jax.named_scope("ln"):
            return norm(x)

    def experts(self, h, active):
        """h + MoE(RMSNorm(h)) and what the layer counted."""
        mixed, counters, _ = self.moe(self.normed(self.moe_norm, h), active)
        return h + mixed, counters


class Mellum(_blocks.TokensToLogits, nn.Layer):
    def __init__(self, cfg: MellumConfig):
        super().__init__()
        self.cfg = cfg
        # `wte`, as the decode protocol's other models name it
        self.wte = nn.Embedding(cfg.vocab_size, cfg.hidden_size)
        self.blocks = nn.LayerList(
            [MellumBlock(cfg, kind) for kind in cfg.layer_types])
        self.norm_f = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        # the head routes nothing: three passes, as the other hybrids'
        self.lm_head = _blocks.HighLinear(cfg.hidden_size, cfg.vocab_size)

    def forward(self, input_ids):
        """Whole-sequence logits [B, L, V], no cache."""
        positions = jnp.arange(input_ids.shape[1], dtype=jnp.int32)
        x = self._embed(input_ids)
        for blk in self.blocks:
            u = blk.normed(blk.attn_norm, x)
            with jax.named_scope("attention"):
                q, k, v = blk.attn.qkv(u, positions)
                with jax.named_scope(_SCOPE[blk.kind]):
                    out = blk.attn.attend(q, k, v)
                h = x + blk.attn.output(out)
            x, _ = blk.experts(h, None)
        return self._logits(x)

    # ------------------- decode protocol (inference/serving.py) -------------

    def _layer_counts(self):
        kinds = self.cfg.layer_types
        return kinds.count(FULL), kinds.count(SLIDING)

    def set_tp_mesh(self, mesh, axis: str = "tp"):
        if mesh is not None:
            n_kv, n_window = self._layer_counts()
            raise WindowLayersUnsupported(
                "tensor-parallel decode (ServingEngine(mesh=...))",
                "sharding a window layer's ring over the TP axis and "
                "running its kernel per shard (set_tp_mesh covers the "
                "paged pools of models/gpt.py only)",
                kv_layers=n_kv, window_layers=n_window)

    def init_cache(self, max_batch: int, max_len: int, page_size: int = 16,
                   num_pages: int = 0, dtype=None) -> PagedKVCache:
        """An empty cache for `max_batch` concurrent sequences of up to
        `max_len` tokens: K/V page pools ``[pages, page, Hkv*D]`` for the
        full layers only (`num_pages` as in `GPT.init_cache`), for each
        sliding layer a ring of `sliding_window` tokens a slot, ``[1 +
        max_batch * window / page, page, Hkv*D]``, and the counters: the
        expert layers' of every DECODE step (`moe`, `ops/moe.COUNTERS`),
        the (token, expert) pairs the PREFILLS computed here
        (`moe_prefill`), and the ring rows ONE sliding layer attended over
        in every decode step (`window_rows`: the sum over active lanes of
        ``min(context, window)``)."""
        cfg = self.cfg
        if max_len > cfg.max_position_embeddings:
            raise ValueError(
                f"init_cache: max_len {max_len} exceeds "
                f"max_position_embeddings {cfg.max_position_embeddings}")
        W = int(cfg.sliding_window)
        n_kv, n_window = self._layer_counts()
        if n_window and W % page_size:
            raise ValueError(f"init_cache: sliding_window {W} is no whole "
                             f"number of pages of {page_size}")
        pages_per_seq, num_pages = _blocks.pages_for(
            max_batch, max_len, page_size, num_pages)
        if dtype is None:
            dtype = self.wte.weight.dtype
        width = cfg.num_key_value_heads * cfg.head_dim
        pool = (num_pages, page_size, width)
        ring = (1 + max_batch * (W // page_size), page_size, width)
        zero = lambda: jnp.zeros((1,), jnp.int32)   # noqa: E731
        return PagedKVCache(
            [jnp.zeros(pool, dtype) for _ in range(n_kv)],
            [jnp.zeros(pool, dtype) for _ in range(n_kv)],
            jnp.zeros((max_batch, pages_per_seq), jnp.int32),
            jnp.zeros((max_batch,), jnp.int32),
            page_size, cfg.num_attention_heads, cfg.head_dim,
            layer_kinds=[KV if t == FULL else KV_WINDOW
                         for t in cfg.layer_types],
            num_kv_heads=cfg.num_key_value_heads,
            window_k=[jnp.zeros(ring, dtype) for _ in range(n_window)],
            window_v=[jnp.zeros(ring, dtype) for _ in range(n_window)],
            window=W if n_window else 0,
            counters={"moe": jnp.zeros((len(_moe.COUNTERS),), jnp.int32),
                      "moe_prefill": zero(), "window_rows": zero()})

    def forward_prefill(self, input_ids, cache: PagedKVCache, slot,
                        length, write_start=0):
        """Prefill ONE sequence into batch slot `slot` (the contract of
        `GPT.forward_prefill`): `input_ids` [1, L_bucket], `length` the
        real prompt length, `write_start` masks the FULL layers' scatter
        below a shared prefix. The slot's rings are REWRITTEN with the
        last `sliding_window` tokens of the prompt; bucket padding reaches
        no ring and meets no expert. Returns (last-position logits
        [1, V], updated cache)."""
        slot, length, write_start, page_row = _blocks.prefill_args(
            input_ids, cache, slot, length, write_start)
        L = input_ids.shape[1]
        positions = jnp.arange(L, dtype=jnp.int32)
        real = positions[None] < length
        x = self._embed(input_ids)
        computed = cache.counters["moe_prefill"]
        for li, blk in enumerate(self.blocks):
            i = cache.index_of(li)
            u = blk.normed(blk.attn_norm, x)
            with jax.named_scope("attention"):
                q, k, v = blk.attn.qkv(u, positions)
                rows = k[0].reshape(L, -1), v[0].reshape(L, -1)
                with jax.named_scope(_SCOPE[blk.kind]):
                    if blk.kind == SLIDING:
                        _blocks.ring_prefill_write(cache, i, *rows, slot,
                                                   length)
                    else:
                        _blocks.paged_prefill_append(
                            cache, i, *rows, page_row, length, write_start)
                    out = blk.attn.attend(q, k, v)
                h = x + blk.attn.output(out)
            x, counters = blk.experts(h, real)
            computed = computed + counters[:1]
        cache.counters["moe_prefill"] = computed
        cache.context_lens = cache.context_lens.at[slot].set(length)
        # logits of the LAST REAL position only
        return self._logits(_blocks.last_real_position(x, length)), cache

    def forward_decode(self, tokens, cache: PagedKVCache, active=None,
                       slot_map=None):
        """ONE incremental decode step (the contract of
        `GPT.forward_decode`, lane mode included): a full layer appends to
        and attends over its pages, a sliding layer writes row ``context
        mod window`` of its lane's ring and attends over the ring, an
        expert layer adds what it counted to `cache.counters["moe"]` (a
        padding or inactive lane writes nothing, meets no expert and
        counts nothing)."""
        cfg = self.cfg
        slot_map, bt, ctx, active = _blocks.decode_view(cache, active,
                                                        slot_map)
        slots = slot_map if slot_map is not None \
            else jnp.arange(cache.max_batch, dtype=jnp.int32)
        x = self._embed(tokens)
        B = x.shape[0]
        x = reshape(x, [B, 1, cfg.hidden_size])
        counted = cache.counters["moe"]
        for li, blk in enumerate(self.blocks):
            i = cache.index_of(li)
            u = blk.normed(blk.attn_norm, x)
            with jax.named_scope("attention"):
                # the new token sits at position `context`
                q, k, v = blk.attn.qkv(u, ctx[:, None])
                new = q[:, 0], k[:, 0].reshape(B, -1), v[:, 0].reshape(B, -1)
                with jax.named_scope(_SCOPE[blk.kind]):
                    if blk.kind == SLIDING:
                        out = _blocks.ring_decode_attention(
                            cache, i, *new, slots, ctx, active)
                    else:
                        out = _blocks.paged_decode_attention(
                            cache, i, *new, bt, ctx, active)
                h = x + blk.attn.output(out[:, None])
            x, counters = blk.experts(h, active[:, None])
            counted = counted + counters
        cache.counters["moe"] = counted
        if cache.has_window:
            cache.counters["window_rows"] = cache.counters["window_rows"] \
                + jnp.sum(jnp.where(active, jnp.minimum(ctx + 1,
                                                        cache.window), 0))
        _blocks.bump_lengths(cache, slot_map, ctx, active)
        return self._logits(reshape(x, [B, cfg.hidden_size])), cache
