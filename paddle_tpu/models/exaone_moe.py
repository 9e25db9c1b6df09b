"""EXAONE-MoE (`model_type` `exaone_moe`; K-EXAONE-236B-A23B is 48 layers
by ``layer_types`` = (sliding, sliding, sliding, full) x 12 and
``mlp_layer_types`` = dense, then sparse x 47) with its
multi-token-prediction (MTP) module, which DRAFTS the token after the
next one so that a decode iteration verifies two positions a lane::

    h  = x + Attn_i(RMSNorm(x))
    x' = h + MLP_i(RMSNorm(h))
    hid = x' of the last layer            # kept: the MTP module reads it
    logits = W_head RMSNorm_f(hid)        # head untied from the embedding

    Attn: `decode_blocks.GroupedAttention`: q [H, D], k and v [Hkv, D], no
          bias; q and k RMS-normed per head; ROTATED on the
          `sliding_attention` layers only (`rope_parameters`, `default`),
          a `full_attention` layer carries no position at all;
          mask of `full_attention`: key j for query t iff j <= t;
          of `sliding_attention`: iff t - sliding_window < j <= t.
    MLP `dense`:  (silu(u W_g) * (u W_u)) W_d at `intermediate_size`.
    MLP `sparse`: s = sigmoid(u W_r) over ALL `num_experts`;
          chosen = top_k(s + b);  w_e = scale * s_e / (sum of s over the
          chosen);  out = Shared(u) + sum over chosen experts HELD HERE of
          w_e expert_e(u); Shared and every expert SwiGLU of
          `moe_intermediate_size` (`ops/moe.py`: `sigmoid_route`,
          `held_experts(form="swiglu")`), the shared one unweighted.

    MTP (`num_nextn_predict_layers` 1; DeepSeek-V3's form), for position i
    with the main model's `hid_i` and the token t_{i+1} that follows:
          x_i = [RMSNorm_e(Emb(t_{i+1})) ; RMSNorm_h(hid_i)] W_p
          hid'_i = Block(x_i)   a block of the model's own kind:
                   `mtp_layer_types[0]` attention over ITS OWN K/V of the
                   positions <= i, a sparse MLP of its own
          logits'_i = W_head RMSNorm_f'(hid'_i)      # a guess at t_{i+2}
    Embedding and head are the main model's.

`experts_held` = (first, count) says which routed experts this chip holds,
as `models/nemotron_h.py` says. Precision as `models/mellum.py`: float32,
every product in front of a router at `highest`, the head in three passes.

The decode protocol of `inference/serving.ServingEngine`, and beside it
the DRAFTING protocol (`draft_tokens` = `num_nextn_predict_layers` > 0):

* `forward_verify(tokens [W, R], cache, active, slot_map=)`: R = 1 +
  `draft_tokens` rows a lane, the lane's last token and its standing
  draft at the positions ``context`` and ``context + 1``: every layer
  appends both rows' K/V and row r attends over the keys ``<= context +
  r``. Returns (logits [W, R, V], hid [W, R, h], cache) and leaves the
  lengths alone;
* `draft_decode(hid, next_tokens [W, R], cache, active, slot_map=)`: the
  MTP module over the rows (hid_r, the token the main model gives for the
  position after r), appending to its own pool at the same positions.
  Returns (draft logits [W, R, V], cache);
* `accept_drafts(cache, accepted [W], active, slot_map=)`: the lengths
  advance by ``1 + accepted``, decided on the device, and the counters
  take the iteration (`mtp` = drafted, accepted);
* `forward_prefill(..., with_hidden=True)` also returns `hid` [1, L, h]
  and `draft_prefill(hid, ids, first_token, cache, slot, length,
  write_start=)` runs the module over the prompt: row i from (hid_i,
  p_{i+1}), the last from (hid_{L-1}, the token just sampled).

The cache (`models/decode_cache.py`): pages for a full layer, a ring of
`sliding_window` tokens a slot for a sliding one, and ONE MORE paged
layer, the last, for the MTP block (`draft_layers`). Row ``context + 1``
of every pool and ring is garbage when the draft was rejected, and the
next iteration rewrites it before any query's mask reaches it.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
from typing import Tuple

import jax
import jax.numpy as jnp

from .. import nn
from ..framework.tensor import Tensor
from ..nn.initializer import Uniform
from ..ops import moe as _moe
from . import decode_blocks as _blocks
from .decode_cache import (KV, KV_WINDOW, PagedKVCache,
                           WindowLayersUnsupported)

SLIDING, FULL = "sliding_attention", "full_attention"
DENSE, SPARSE = "dense", "sparse"
# the scope of a layer's append and kernel, under `attention`
_SCOPE = {SLIDING: "window", FULL: "full"}
PUBLISHED_LAYER_TYPES = (SLIDING, SLIDING, SLIDING, FULL) * 12
PUBLISHED_MLP_LAYER_TYPES = (DENSE,) + (SPARSE,) * 47


@dataclasses.dataclass
class ExaoneMoeConfig:
    """The source's keys under the source's names (Hugging Face
    `config.json` of `exaone_moe`), and `experts_held`."""
    vocab_size: int = 153600
    hidden_size: int = 6144
    num_hidden_layers: int = 48
    layer_types: Tuple[str, ...] = PUBLISHED_LAYER_TYPES
    mlp_layer_types: Tuple[str, ...] = PUBLISHED_MLP_LAYER_TYPES
    sliding_window: int = 128
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 262144
    # attention
    num_attention_heads: int = 64
    num_key_value_heads: int = 8
    head_dim: int = 128
    rope_parameters: dict = dataclasses.field(
        default_factory=lambda: {"rope_theta": 1000000,
                                 "rope_type": "default"})
    # MLPs
    intermediate_size: int = 18432
    first_k_dense_replace: int = 1
    num_experts: int = 128
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 2048
    num_shared_experts: int = 1
    scoring_func: str = "sigmoid"
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    n_group: int = 1
    topk_group: int = 1
    hidden_act: str = "silu"
    tie_word_embeddings: bool = False
    # the MTP module: 0 => none, and the plain decode protocol alone
    num_nextn_predict_layers: int = 1
    mtp_layer_types: Tuple[str, ...] = (FULL,)
    # not in the source: (first, count) of the routed experts held here;
    # () => all of them
    experts_held: Tuple[int, ...] = ()

    def __post_init__(self):
        n = self.num_hidden_layers
        # a depth cut keeps the leading layers of the published lists
        self.layer_types = tuple(self.layer_types)[:n]
        self.mlp_layer_types = tuple(self.mlp_layer_types)[:n]
        self.mtp_layer_types = tuple(self.mtp_layer_types)
        if len(self.layer_types) != n \
                or set(self.layer_types) - {SLIDING, FULL}:
            raise ValueError(f"layer_types must name {n} layers as "
                             f"{SLIDING} or {FULL}, got {self.layer_types}")
        k = self.first_k_dense_replace
        if self.mlp_layer_types != (DENSE,) * min(k, n) \
                + (SPARSE,) * max(n - k, 0):
            raise ValueError(
                f"mlp_layer_types must name {n} layers, the first "
                f"first_k_dense_replace = {k} {DENSE} and the rest "
                f"{SPARSE}, got {self.mlp_layer_types}")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_attention_heads must be a multiple of "
                             "num_key_value_heads")
        if self.tie_word_embeddings or not self.norm_topk_prob \
                or self.hidden_act != "silu" \
                or self.scoring_func != "sigmoid" \
                or (self.n_group, self.topk_group) != (1, 1):
            raise ValueError(
                "only tie_word_embeddings=False, norm_topk_prob=True, "
                "hidden_act='silu', scoring_func='sigmoid' and one group "
                "of experts (n_group = topk_group = 1) are implemented")
        if self.rope_parameters.get("rope_type", "default") != "default":
            raise ValueError("rope_parameters: only rope_type 'default' "
                             "is implemented")
        if self.num_nextn_predict_layers not in (0, 1) \
                or self.mtp_layer_types[:self.num_nextn_predict_layers] \
                != (FULL,) * self.num_nextn_predict_layers:
            raise ValueError(
                "the MTP module is implemented for num_nextn_predict_layers "
                f"0 or 1 with mtp_layer_types ['{FULL}'], got "
                f"{self.num_nextn_predict_layers} and {self.mtp_layer_types}")
        first, count = self.experts_held or (0, self.num_experts)
        if not 0 <= first < first + count <= self.num_experts:
            raise ValueError(f"experts_held {self.experts_held} is no range "
                             f"of the {self.num_experts} routed experts")
        if self.num_experts_per_tok >= self.num_experts:
            raise ValueError("num_experts_per_tok must be below num_experts")
        self.experts_held = (int(first), int(count))

    @staticmethod
    def tiny(layer_types=(SLIDING, SLIDING, SLIDING, FULL, SLIDING),
             **changes):
        """The published cut's five layers (a dense MLP, then sparse), a
        window of 8, 8 experts top-2 beside a shared one, 2 K/V heads for
        4 query heads, and the MTP module."""
        n = len(layer_types)
        return ExaoneMoeConfig(**{**dict(
            vocab_size=64, hidden_size=64, num_hidden_layers=n,
            layer_types=layer_types,
            mlp_layer_types=(DENSE,) + (SPARSE,) * (n - 1),
            sliding_window=8, max_position_embeddings=512,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            rope_parameters={"rope_type": "default", "rope_theta": 10000},
            intermediate_size=96, num_experts=8, num_experts_per_tok=2,
            moe_intermediate_size=32), **changes})


class ExaoneSwiGLU(nn.Layer):
    """``(silu(u W_g) * (u W_u)) W_d``, gate and up one product: the dense
    MLP, and the shared expert beside the routed ones."""

    def __init__(self, hidden: int, width: int):
        super().__init__()
        self.width = width
        # the dense MLP's two shapes come once a program
        self.gate_up = _blocks.ExactLinearOnce(hidden, 2 * width)
        self.down = _blocks.ExactLinearOnce(width, hidden)

    def forward(self, u):
        both = self.gate_up(u).data
        return self.down(Tensor(jax.nn.silu(both[..., :self.width])
                                * both[..., self.width:]))


class ExaoneExperts(nn.Layer):
    """A sigmoid-routed dropless SwiGLU expert layer holding
    `experts_held` of the layer's routed experts, and the shared expert
    whole. `scoped` False leaves the `mlp` / `moe` scopes out (the MTP
    module's block sits under `mtp`, and the readers of `mlp/moe` must not
    count it with the decoder's layers)."""

    def __init__(self, cfg: ExaoneMoeConfig, scoped: bool = True):
        super().__init__()
        h, f = cfg.hidden_size, cfg.moe_intermediate_size
        self.first, held = cfg.experts_held
        self.top_k = cfg.num_experts_per_tok
        self.scale = float(cfg.routed_scaling_factor)
        self.scoped = scoped
        bound = math.sqrt(6.0 / (h + cfg.num_experts))
        self.router = self.create_parameter(
            (h, cfg.num_experts), default_initializer=Uniform(-bound, bound))
        # the published one is learned; it only selects. Zeros would leave
        # the path untested (as `models/nemotron_h.py`)
        self.e_score_correction_bias = self.create_parameter(
            (cfg.num_experts,), default_initializer=Uniform(-0.05, 0.05))
        # stacked once: [Wg^T; Wu^T] [E_held, 2f, h] and Wd [E_held, f, h]
        bound = math.sqrt(6.0 / (h + f))
        self.w_gate_up = self.create_parameter(
            (held, 2 * f, h), default_initializer=Uniform(-bound, bound))
        self.w_down = self.create_parameter(
            (held, f, h), default_initializer=Uniform(-bound, bound))
        self.shared = ExaoneSwiGLU(h, f * cfg.num_shared_experts)

    def forward(self, u, active=None):
        """u Tensor [.., h] -> (the layer's output Tensor, counters [3]
        int32 as `ops/moe.COUNTERS`). A token whose `active` [..] is False
        (padding) meets no routed expert."""
        lead, h = u.shape[:-1], u.shape[-1]
        flat = u.data.reshape(-1, h)
        if active is not None:
            active = jnp.broadcast_to(active, lead).reshape(-1)
        with contextlib.ExitStack() as scopes:
            if self.scoped:
                scopes.enter_context(jax.named_scope("mlp"))
                scopes.enter_context(jax.named_scope("moe"))
            experts, weights, _ = _moe.sigmoid_route(
                flat, self.router.data, self.e_score_correction_bias.data,
                top_k=self.top_k, scale=self.scale)
            routed, counters = _moe.held_experts(
                flat, experts, weights, self.w_gate_up.data,
                self.w_down.data, first=self.first, active=active,
                form="swiglu")
            with jax.named_scope("shared"):
                shared = self.shared(u)
            out = shared.data + routed.reshape(*lead, h)
        return Tensor(out), counters


class ExaoneBlock(nn.Layer):
    """One layer: attention of `kind`, then a dense MLP or the experts."""

    def __init__(self, cfg: ExaoneMoeConfig, kind: str, mlp: str,
                 scoped: bool = True):
        super().__init__()
        self.kind = kind
        self.attn_norm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        self.attn = _blocks.GroupedAttention(
            cfg.hidden_size, cfg.num_attention_heads,
            cfg.num_key_value_heads, cfg.head_dim, cfg.rms_norm_eps,
            window=int(cfg.sliding_window) if kind == SLIDING else None,
            rope=cfg.rope_parameters if kind == SLIDING else None)
        self.mlp_norm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        if mlp == DENSE:
            self.mlp = ExaoneSwiGLU(cfg.hidden_size, cfg.intermediate_size)
        else:
            self.moe = ExaoneExperts(cfg, scoped)
        self.sparse = mlp == SPARSE

    def forward(self, x, positions, attend, active=None):
        """x Tensor [B, L, h] at `positions`; `attend(block, q, k, v)` is
        the caller's way through this layer's K/V (a prompt whole, a
        prompt into the cache, new rows over the cache) and returns the
        heads' outputs [B, L, H, D]. Returns (the layer's output, what its
        experts counted or None)."""
        with jax.named_scope("ln"):
            u = self.attn_norm(x)
        with jax.named_scope("attention"):
            q, k, v = self.attn.qkv(u, positions)
            with jax.named_scope(_SCOPE[self.kind]):
                out = attend(self, q, k, v)
            h = x + self.attn.output(out)
        with jax.named_scope("ln"):
            u = self.mlp_norm(h)
        if self.sparse:
            mixed, counters = self.moe(u, active)
            return h + mixed, counters
        with jax.named_scope("mlp"), jax.named_scope("dense_mlp"):
            return h + self.mlp(u), None


class ExaoneMTP(nn.Layer):
    """The multi-token-prediction module: two norms, the projection of
    [embedding ; hidden state] back to the hidden width, one block of the
    model's own kind with a sparse MLP, and a final norm of its own. The
    embedding and the head are the model's."""

    def __init__(self, cfg: ExaoneMoeConfig):
        super().__init__()
        h = cfg.hidden_size
        self.embed_norm = nn.RMSNorm(h, cfg.rms_norm_eps)
        self.hidden_norm = nn.RMSNorm(h, cfg.rms_norm_eps)
        self.proj = _blocks.ExactLinearOnce(2 * h, h)
        self.block = ExaoneBlock(cfg, cfg.mtp_layer_types[0], SPARSE,
                                 scoped=False)
        self.norm_f = nn.RMSNorm(h, cfg.rms_norm_eps)


def _following(ids):
    """ids [B, L] -> the token after each position (0 after the last)."""
    return jnp.concatenate([ids[:, 1:], jnp.zeros_like(ids[:, :1])], axis=1)


class ExaoneMoe(_blocks.TokensToLogits, nn.Layer):
    def __init__(self, cfg: ExaoneMoeConfig):
        super().__init__()
        self.cfg = cfg
        # `wte`, as the decode protocol's other models name it
        self.wte = nn.Embedding(cfg.vocab_size, cfg.hidden_size)
        self.blocks = nn.LayerList(
            [ExaoneBlock(cfg, kind, mlp) for kind, mlp
             in zip(cfg.layer_types, cfg.mlp_layer_types)])
        self.norm_f = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        # the head routes nothing: three passes, as the other hybrids'
        self.lm_head = _blocks.HighLinear(cfg.hidden_size, cfg.vocab_size)
        #: tokens the model drafts a lane and iteration (the engine steps
        #: a model that has any through `forward_verify` / `draft_decode`)
        self.draft_tokens = int(cfg.num_nextn_predict_layers)
        if self.draft_tokens:
            self.mtp = ExaoneMTP(cfg)

    # ---- the one walk through the layers, whatever the K/V's way

    def _layers(self, x, positions, attend, active=None):
        """x through every block; `attend(layer, block, q, k, v)`. Returns
        (hid, the sparse layers' counters summed [3])."""
        counted = jnp.zeros((len(_moe.COUNTERS),), jnp.int32)
        for li, blk in enumerate(self.blocks):
            x, counters = blk(
                x, positions,
                lambda b, q, k, v, li=li: attend(li, b, q, k, v), active)
            if counters is not None:
                counted = counted + counters
        return x, counted

    def _draft(self, hid, next_tokens, positions, attend, active=None):
        """The MTP module over rows (hid [B, L, h], the token that follows
        each [B, L]) at `positions`; `attend(block, q, k, v)`. Returns
        (draft logits' input: the module's hidden state [B, L, h], its
        experts' counters [3])."""
        mtp = self.mtp
        hid = hid if isinstance(hid, Tensor) else Tensor(hid)
        with jax.named_scope("project"):
            both = jnp.concatenate(
                [mtp.embed_norm(self.wte(next_tokens)).data,
                 mtp.hidden_norm(hid).data], axis=-1)
            x = mtp.proj(Tensor(both))
        # the block sits one position on (it only matters to a rotation,
        # and a `full_attention` block has none)
        return mtp.block(x, positions + 1, attend, active)

    def _draft_logits(self, x):
        with jax.named_scope("logits"):
            return Tensor(_blocks.head(self.mtp.norm_f(x).data,
                                       self.lm_head.weight.data))

    def forward(self, input_ids, with_drafts: bool = False):
        """Whole-sequence logits [B, L, V], no cache; `with_drafts` also
        the MTP module's [B, L, V] given each position's own successor in
        `input_ids` (the last row's successor is not there: it reads token
        0 and is not meaningful)."""
        positions = jnp.arange(input_ids.shape[1], dtype=jnp.int32)
        whole = lambda blk, q, k, v: blk.attn.attend(q, k, v)  # noqa: E731
        hid, _ = self._layers(self._embed(input_ids), positions,
                              lambda li, *a: whole(*a))
        logits = self._logits(hid)
        if not with_drafts:
            return logits
        following = _following(getattr(input_ids, "data", input_ids))
        with jax.named_scope("mtp"):
            x, _ = self._draft(hid, Tensor(following), positions, whole)
            return logits, self._draft_logits(x)

    # ------------------- decode protocol (inference/serving.py) -------------

    def _layer_counts(self):
        kinds = self.cfg.layer_types
        return kinds.count(FULL) + self.draft_tokens, kinds.count(SLIDING)

    def set_tp_mesh(self, mesh, axis: str = "tp"):
        if mesh is None:
            return
        n_kv, n_window = self._layer_counts()
        raise WindowLayersUnsupported(
            "tensor-parallel decode (ServingEngine(mesh=...))",
            "sharding a window layer's ring over the TP axis and "
            "running its kernel per shard (set_tp_mesh covers the "
            "paged pools of models/gpt.py only)",
            kv_layers=n_kv, window_layers=n_window)

    def init_cache(self, max_batch: int, max_len: int, page_size: int = 16,
                   num_pages: int = 0, dtype=None) -> PagedKVCache:
        """As `Mellum.init_cache`, with one more paged layer, the last,
        for the MTP block (`draft_layers`), and the counters: `moe` (the
        decoder's sparse layers in every DECODE step), `mtp_moe` (the MTP
        block's), `moe_prefill` (the (token, expert) pairs the PREFILLS
        computed here, the MTP block's among them), `window_rows` (the
        ring rows ONE sliding layer attended over in every decode step,
        every query row counted) and `mtp` (drafted, accepted: the drafts
        the decode steps verified and those that were the main model's
        own token)."""
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
        zeros = lambda n: jnp.zeros((n,), jnp.int32)   # noqa: E731
        counters = {"moe": zeros(len(_moe.COUNTERS)),
                    "moe_prefill": zeros(1), "window_rows": zeros(1)}
        if self.draft_tokens:
            counters.update(mtp=zeros(2), mtp_moe=zeros(len(_moe.COUNTERS)))
        return PagedKVCache(
            [jnp.zeros(pool, dtype) for _ in range(n_kv)],
            [jnp.zeros(pool, dtype) for _ in range(n_kv)],
            jnp.zeros((max_batch, pages_per_seq), jnp.int32),
            jnp.zeros((max_batch,), jnp.int32),
            page_size, cfg.num_attention_heads, cfg.head_dim,
            layer_kinds=[KV if t == FULL else KV_WINDOW
                         for t in cfg.layer_types]
            + [KV] * self.draft_tokens,
            num_kv_heads=cfg.num_key_value_heads,
            window_k=[jnp.zeros(ring, dtype) for _ in range(n_window)],
            window_v=[jnp.zeros(ring, dtype) for _ in range(n_window)],
            window=W if n_window else 0, counters=counters,
            draft_layers=self.draft_tokens)

    def forward_prefill(self, input_ids, cache: PagedKVCache, slot,
                        length, write_start=0, with_hidden: bool = False):
        """Prefill ONE sequence into batch slot `slot` (the contract of
        `Mellum.forward_prefill`). Returns (last-position logits [1, V],
        updated cache) and, `with_hidden`, the hidden states [1, L, h]
        that `draft_prefill` reads."""
        slot, length, write_start, page_row = _blocks.prefill_args(
            input_ids, cache, slot, length, write_start)
        L = input_ids.shape[1]
        positions = jnp.arange(L, dtype=jnp.int32)

        def attend(li, blk, q, k, v):
            rows = k[0].reshape(L, -1), v[0].reshape(L, -1)
            if blk.kind == SLIDING:
                _blocks.ring_prefill_write(cache, cache.index_of(li), *rows,
                                           slot, length)
            else:
                _blocks.paged_prefill_append(cache, cache.index_of(li),
                                             *rows, page_row, length,
                                             write_start)
            return blk.attn.attend(q, k, v, length)

        hid, counted = self._layers(self._embed(input_ids), positions,
                                    attend, positions[None] < length)
        cache.counters["moe_prefill"] = cache.counters["moe_prefill"] \
            + counted[:1]
        cache.context_lens = cache.context_lens.at[slot].set(length)
        # logits of the LAST REAL position only
        logits = self._logits(_blocks.last_real_position(hid, length))
        return (logits, cache, hid) if with_hidden else (logits, cache)

    def draft_prefill(self, hid, input_ids, first_token, cache, slot,
                      length, write_start=0):
        """The MTP module over a prompt just prefilled: row i < length - 1
        from (hid_i, the prompt's token i + 1), row length - 1 from
        (hid, `first_token` [1], the token sampled from the prompt's last
        logits); its K/V into the module's own pool, the slot's pages from
        `write_start` on. Returns (the draft logits of the last real row
        [1, V]: a guess at the token after `first_token`, cache)."""
        slot, length, write_start, page_row = _blocks.prefill_args(
            input_ids, cache, slot, length, write_start)
        ids = getattr(input_ids, "data", input_ids)
        L = ids.shape[1]
        positions = jnp.arange(L, dtype=jnp.int32)
        following = jnp.where(positions[None] == length - 1,
                              jnp.asarray(first_token, ids.dtype)[:, None],
                              _following(ids))
        i = cache.index_of(len(self.blocks))

        def attend(blk, q, k, v):
            _blocks.paged_prefill_append(
                cache, i, k[0].reshape(L, -1), v[0].reshape(L, -1),
                page_row, length, write_start)
            return blk.attn.attend(q, k, v, length)

        with jax.named_scope("mtp"):
            x, counters = self._draft(hid, Tensor(following), positions,
                                      attend, positions[None] < length)
            cache.counters["moe_prefill"] = cache.counters["moe_prefill"] \
                + counters[:1]
            return self._draft_logits(
                _blocks.last_real_position(x, length)), cache

    def _rows_attend(self, cache, bt, slots, ctx, active):
        """`attend(pool or ring index, block, q, k, v)` for R new rows a
        lane over the cache."""
        def attend(i, blk, q, k, v):
            B, R = q.shape[:2]
            new = q, k.reshape(B, R, -1), v.reshape(B, R, -1)
            if blk.kind == SLIDING:
                return _blocks.ring_rows_attention(cache, i, *new, slots,
                                                   ctx, active)
            return _blocks.paged_rows_attention(cache, i, *new, bt, ctx,
                                                active)
        return attend

    def _view(self, cache, active, slot_map):
        slot_map, bt, ctx, active = _blocks.decode_view(cache, active,
                                                        slot_map)
        slots = slot_map if slot_map is not None \
            else jnp.arange(cache.max_batch, dtype=jnp.int32)
        return slot_map, bt, slots, ctx, active

    def forward_verify(self, tokens, cache: PagedKVCache, active=None,
                       slot_map=None):
        """`tokens` [W, R]: each lane's rows at the positions ``context ..
        context + R - 1`` through every layer (a full layer appends to and
        attends over its pages, a sliding layer writes and attends a row
        at a time, an expert layer counts into `moe`; a padding or
        inactive lane writes nothing, meets no expert and counts nothing).
        Returns (logits [W, R, V], hid [W, R, h], cache); the lengths stay
        as they were (`accept_drafts` or `forward_decode` moves them)."""
        slot_map, bt, slots, ctx, active = self._view(cache, active,
                                                      slot_map)
        R = tokens.shape[1]
        rows = self._rows_attend(cache, bt, slots, ctx, active)
        hid, counted = self._layers(
            self._embed(tokens),
            ctx[:, None] + jnp.arange(R, dtype=jnp.int32),
            lambda li, *a: rows(cache.index_of(li), *a), active[:, None])
        cache.counters["moe"] = cache.counters["moe"] + counted
        if cache.has_window:
            seen = jnp.minimum(ctx[:, None] + 1 + jnp.arange(R),
                               cache.window)
            cache.counters["window_rows"] = cache.counters["window_rows"] \
                + jnp.sum(jnp.where(active[:, None], seen, 0))
        return self._logits(hid), hid, cache

    def forward_decode(self, tokens, cache: PagedKVCache, active=None,
                       slot_map=None):
        """ONE incremental decode step of one token a lane (the contract
        of `GPT.forward_decode`): `forward_verify` of one row, and the
        length bump. What a model without the MTP module is stepped by."""
        slot_map, _, _, ctx, active = self._view(cache, active, slot_map)
        tokens = getattr(tokens, "data", tokens)
        logits, _, cache = self.forward_verify(
            Tensor(tokens[:, None]), cache, active, slot_map=slot_map)
        _blocks.bump_lengths(cache, slot_map, ctx, active)
        return Tensor(logits.data[:, 0]), cache

    def draft_decode(self, hid, next_tokens, cache: PagedKVCache,
                     active=None, slot_map=None):
        """The MTP module over each lane's R rows (hid [W, R, h] from
        `forward_verify`, `next_tokens` [W, R] the token the main model
        gives for the position after each row), its K/V appended to its
        own pool at the positions ``context .. context + R - 1``. Returns
        (draft logits [W, R, V], cache)."""
        slot_map, bt, slots, ctx, active = self._view(cache, active,
                                                      slot_map)
        R = hid.shape[1]
        rows = self._rows_attend(cache, bt, slots, ctx, active)
        i = cache.index_of(len(self.blocks))
        with jax.named_scope("mtp"):
            x, counters = self._draft(
                hid, next_tokens,
                ctx[:, None] + jnp.arange(R, dtype=jnp.int32),
                lambda *a: rows(i, *a), active[:, None])
            cache.counters["mtp_moe"] = cache.counters["mtp_moe"] + counters
            return self._draft_logits(x), cache

    def accept_drafts(self, cache: PagedKVCache, accepted, active=None,
                      slot_map=None):
        """The end of a drafting iteration: every active lane's length
        advances by ``1 + accepted`` and `mtp` takes (the drafts verified,
        those accepted)."""
        slot_map, _, _, ctx, active = self._view(cache, active, slot_map)
        accepted = accepted & active
        _blocks.advance_lengths(cache, slot_map, ctx,
                                active.astype(jnp.int32) + accepted)
        cache.counters["mtp"] = cache.counters["mtp"] + jnp.stack(
            [jnp.sum(active), jnp.sum(accepted)]).astype(jnp.int32)
        return cache
