"""DeepSeek-V2 (`model_type` `deepseek_v2`; 60 layers, the first dense and
the rest experts), with its multi-head LATENT attention (MLA)::

    h  = x + MLA_i(RMSNorm(x))
    x' = h + MLP_i(RMSNorm(h))
    logits = W_head RMSNorm_f(x' of the last layer)     # untied head

    MLA, H heads, a query/key head of nope + rope, a value head of v:
        c_q = RMSNorm(u W_qa);  q = c_q W_qb  -> [H, nope + rope]: q_nope, q_pe
        [c_kv ; k_pe] = u W_kva;  c_kv <- RMSNorm(c_kv)      [R], [rope]
        q_pe, k_pe rotated by YaRN (`ops/rope.py`; k_pe ONE key for all
        heads); k_nope_h = c_kv W_UK_h, v_h = c_kv W_UV_h    (W_kvb)
        score_hj = (q_nope_h . k_nope_hj + q_pe_h . k_pe_j) * s,
        s = (nope + rope)^-1/2 * m^2 (`rope.softmax_scale_factor`), causal
        out = concat_h(softmax(score_h) v_h) W_o
    MLP `dense` (the first `first_k_dense_replace` layers): SwiGLU of
        `intermediate_size`.
    MLP `moe`: p = softmax(u W_r) over ALL `n_routed_experts`;
        `group_limited_greedy`: the `topk_group` of `n_group` groups whose
        largest p is largest, then the `num_experts_per_tok` largest p
        among their experts; w_e = `routed_scaling_factor` * p_e (not
        renormalised); out = Shared(u) + sum over the chosen experts HELD
        HERE of w_e expert_e(u), Shared one SwiGLU of
        `n_shared_experts` x `moe_intermediate_size` (`ops/moe.py`:
        `group_limited_route`, `held_experts(form="swiglu")`).

The cache (`models/decode_cache.py`, kind ``"latent"``) holds for each
layer ONE pool of rows ``[c_kv ; rotated k_pe ; zeros]``, 640 wide for
512 + 64: what every head's key and value are made from. The two serving
programs compute the same attention two ways:

* PREFILL, EXPANDED: c_kv goes through W_UK and W_UV into every head's
  K and V, and the flash forward runs causal over the prompt's live rows
  of the bucket (q and k of nope + rope, v of its own width; the padding's
  steps compute and fetch nothing), `_PREFILL_HEADS` heads at a time (and
  W_o by those heads' rows) so that the expanded q, K and V of a long
  prompt never exist for all heads at once; the latent rows are appended
  to the layer's pages.
* DECODE, ABSORBED: q_lat_h = q_nope_h W_UK_h^T (R wide) and q_pe_h go to
  `paged_attention.latent_attention` against the pages' rows, which reads
  a row once for all heads' scores and values; its output o_lat_h is
  taken through W_UV_h, then W_o. The same algebra: q_nope_h . (c_kv
  W_UK_h) = (q_nope_h W_UK_h^T) . c_kv, and sum_j p_j (c_kv_j W_UV_h) =
  (sum_j p_j c_kv_j) W_UV_h.

W_kvb is stored as its two halves, head-major: ``w_uk`` ``[H, nope, R]``
and ``w_uv`` ``[H, R, v]`` (the published ``kv_b_proj`` is [H (nope + v),
R] with a head's k_nope rows then its v rows: the same numbers), so that
each head's absorption is a batched product over the leading axis.

`experts_held` = (first, count) says which routed experts this chip holds,
as `models/nemotron_h.py` says. Precision as `models/mellum.py`: float32,
every product in front of a router at `highest` (the attention's
projections, both absorptions, the flash forward and the latent kernel's
six passes), the head in three passes.

Scopes, for the benchmark's readers: ``attention/latent`` over the whole
attention block but the rotation, below it ``proj`` (W_qa, W_qb, W_kva,
the two norms, W_o in decode), ``absorb`` (W_UK and W_UV in decode),
``decode`` (the row's append and the kernel) and ``prefill`` (the
expansion, the flash forward, W_o and the rows' append);
``attention/rope``; ``mlp/moe`` with ``route``, ``experts`` and
``shared``; ``mlp/dense_mlp``. The decode step
adds to ``cache.counters["latent_rows"]`` the rows each active lane's
layers attended over (its context, new token included, a layer), and to
``"moe"`` what the expert layers counted.
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
from ..ops import rope as _rope
from ..ops.pallas import paged_attention as _pa
from ..ops.pallas.tiling import LANE as _LANE
from . import decode_blocks as _blocks
from .decode_cache import LATENT, LatentCacheUnsupported, PagedKVCache
from .exaone_moe import ExaoneSwiGLU as SwiGLU

_HI = jax.lax.Precision.HIGHEST

# query heads a pass of the expanded prefill computes: their K, V, q and
# output are 1/4 of a layer's at DeepSeek-V2's 128 heads (805 + 537 MB for
# K and V of 8,192 tokens whole, 201 + 134 MB a pass), so that the largest
# prefill bucket fits beside the weights and the pool
_PREFILL_HEADS = 32

PUBLISHED_ROPE_SCALING = {"type": "yarn", "factor": 40,
                          "original_max_position_embeddings": 4096,
                          "beta_fast": 32, "beta_slow": 1, "mscale": 0.707,
                          "mscale_all_dim": 0.707}


@dataclasses.dataclass
class DeepseekV2Config:
    """The source's keys under the source's names (Hugging Face
    `config.json` of `deepseek_v2`), and `experts_held`."""
    vocab_size: int = 102400
    hidden_size: int = 5120
    num_hidden_layers: int = 60
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 163840
    # attention (MLA)
    num_attention_heads: int = 128
    num_key_value_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    attention_bias: bool = False
    rope_theta: float = 10000
    rope_scaling: dict = dataclasses.field(
        default_factory=lambda: dict(PUBLISHED_ROPE_SCALING))
    # MLPs
    intermediate_size: int = 12288
    first_k_dense_replace: int = 1
    moe_layer_freq: int = 1
    moe_intermediate_size: int = 1536
    n_routed_experts: int = 160
    n_shared_experts: int = 2
    num_experts_per_tok: int = 6
    n_group: int = 8
    topk_group: int = 3
    topk_method: str = "group_limited_greedy"
    scoring_func: str = "softmax"
    norm_topk_prob: bool = False
    routed_scaling_factor: float = 16.0
    hidden_act: str = "silu"
    tie_word_embeddings: bool = False
    # not in the source: (first, count) of the routed experts held here;
    # () => all of them
    experts_held: Tuple[int, ...] = ()

    def __post_init__(self):
        if self.tie_word_embeddings or self.norm_topk_prob \
                or self.hidden_act != "silu" or self.attention_bias \
                or self.scoring_func != "softmax" \
                or self.topk_method != "group_limited_greedy" \
                or self.moe_layer_freq != 1 or not self.q_lora_rank:
            raise ValueError(
                "only tie_word_embeddings=False, norm_topk_prob=False, "
                "hidden_act='silu', attention_bias=False, scoring_func="
                "'softmax', topk_method='group_limited_greedy', "
                "moe_layer_freq=1 and a q_lora_rank are implemented")
        if self.num_key_value_heads != self.num_attention_heads:
            raise ValueError("num_key_value_heads must equal "
                             "num_attention_heads (every head has its own "
                             "key and value, made from the latent)")
        if self.rope_scaling.get("type", "yarn") != "yarn":
            raise ValueError("rope_scaling: only type 'yarn' is implemented")
        if self.qk_rope_head_dim % 2:
            raise ValueError("qk_rope_head_dim must be even")
        first, count = self.experts_held or (0, self.n_routed_experts)
        if not 0 <= first < first + count <= self.n_routed_experts:
            raise ValueError(f"experts_held {self.experts_held} is no range "
                             f"of the {self.n_routed_experts} routed "
                             f"experts")
        self.experts_held = (int(first), int(count))

    @property
    def rope_parameters(self) -> dict:
        """The `ops/rope.py` group of `rope_theta` and `rope_scaling`."""
        group = {k: v for k, v in self.rope_scaling.items() if k != "type"}
        return {"rope_type": "yarn", "rope_theta": self.rope_theta, **group}

    @property
    def latent_width(self) -> int:
        """A pool row: c_kv and k_pe, in whole lane tiles."""
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim)
                 // _LANE) * _LANE

    @property
    def softmax_scale(self) -> float:
        return ((self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5
                * _rope.softmax_scale_factor(self.rope_parameters))

    @staticmethod
    def tiny(**changes):
        """A dense layer then three expert layers; 4 heads of 16 + 8 / 16,
        ranks 24 and 32; 16 experts in 4 groups, 2 kept, top-3, 2 shared,
        DeepSeek-V2's YaRN keys."""
        return DeepseekV2Config(**{**dict(
            vocab_size=64, hidden_size=64, num_hidden_layers=4,
            max_position_embeddings=512, num_attention_heads=4,
            num_key_value_heads=4, q_lora_rank=24, kv_lora_rank=32,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            intermediate_size=96, moe_intermediate_size=16,
            n_routed_experts=16, n_shared_experts=2, num_experts_per_tok=3,
            n_group=4, topk_group=2), **changes})


class LatentAttention(nn.Layer):
    """MLA's weights and its two ways through a layer's latent pool."""

    def __init__(self, cfg: DeepseekV2Config):
        super().__init__()
        h, H = cfg.hidden_size, cfg.num_attention_heads
        self.heads, self.nope = H, cfg.qk_nope_head_dim
        self.rope_dim, self.v_dim = cfg.qk_rope_head_dim, cfg.v_head_dim
        self.rank, self.width = cfg.kv_lora_rank, cfg.latent_width
        self.scale = cfg.softmax_scale
        self.q_a_proj = _blocks.ExactLinear(h, cfg.q_lora_rank)
        self.q_a_norm = nn.RMSNorm(cfg.q_lora_rank, cfg.rms_norm_eps)
        self.q_b_proj = _blocks.ExactLinear(
            cfg.q_lora_rank, H * (self.nope + self.rope_dim))
        self.kv_a_proj = _blocks.ExactLinear(h, self.rank + self.rope_dim)
        self.kv_a_norm = nn.RMSNorm(self.rank, cfg.rms_norm_eps)
        # W_kvb's two halves, head-major (the module's docstring), at the
        # fan of the published [R, H (nope + v)]
        bound = math.sqrt(6.0 / (self.rank + H * (self.nope + self.v_dim)))
        self.w_uk = self.create_parameter(
            (H, self.nope, self.rank),
            default_initializer=Uniform(-bound, bound))
        self.w_uv = self.create_parameter(
            (H, self.rank, self.v_dim),
            default_initializer=Uniform(-bound, bound))
        self.o_proj = _blocks.ExactLinear(H * self.v_dim, h)
        self.inv_freq, self.rope_factor = _rope.inverse_frequencies(
            self.rope_dim, cfg.rope_parameters)

    def project(self, u, positions, whole_q: bool):
        """u Tensor [B, L, h] at `positions` ([L] or [B, L]) -> (c_q [B, L,
        q_rank], q_nope [B, L, H, nope] or, not `whole_q`, None, q_pe [B,
        L, H, rope] rotated, c_kv [B, L, R] normed, k_pe [B, L, rope]
        rotated); arrays. A prompt takes its q_nope a few heads at a time
        (`expanded`), a decode step whole."""
        B, L, _ = u.shape
        with jax.named_scope("latent"), jax.named_scope("proj"):
            c_q = self.q_a_norm(self.q_a_proj(u)).data
            both = self.kv_a_proj(u).data
            c_kv = self.kv_a_norm(Tensor(both[..., :self.rank])).data
            q_nope = None
            if whole_q:
                q = jnp.matmul(c_q, self.q_b_proj.weight.data,
                               precision=_HI).reshape(
                    B, L, self.heads, self.nope + self.rope_dim)
                q_nope, q_pe = q[..., :self.nope], q[..., self.nope:]
            else:
                q_pe = jnp.einsum("blq,qhd->blhd", c_q,
                                  self._q_b()[..., self.nope:],
                                  precision=_HI)
        q_pe, k_pe = _rope.rotate(
            q_pe, both[..., None, self.rank:],
            jnp.broadcast_to(positions, (B, L)), self.inv_freq,
            self.rope_factor, "yarn")
        return c_q, q_nope, q_pe, c_kv, k_pe[..., 0, :]

    def _q_b(self):
        """W_qb as [q_rank, H, nope + rope]."""
        return self.q_b_proj.weight.data.reshape(
            -1, self.heads, self.nope + self.rope_dim)

    def rows(self, c_kv, k_pe):
        """The pool's rows ``[c_kv ; k_pe ; zeros]`` [..., width]."""
        pad = self.width - self.rank - self.rope_dim
        return jnp.concatenate(
            [c_kv, k_pe, jnp.zeros(c_kv.shape[:-1] + (pad,), c_kv.dtype)],
            axis=-1)

    def expanded(self, c_q, q_pe, c_kv, k_pe, length=None):
        """Prompts' attention, causal, EXPANDED: `_PREFILL_HEADS` heads a
        pass (their q_nope, K and V made for them alone, the flash forward
        over them, their share of W_o added in). c_q, q_pe, c_kv, k_pe as
        `project` gives them; `length` the prompt's live rows (the flash
        forward computes nothing past them; None: every row). Returns
        [B, L, h]."""
        from ..ops.pallas import flash_attention as _fa
        B, L = c_kv.shape[:2]
        n = min(self.heads, _PREFILL_HEADS)
        w_o = self.o_proj.weight.data.reshape(self.heads, self.v_dim, -1)

        def heads(i, c_q, q_pe, c_kv, k_pe, out):
            def at(x, axis=0):
                return jax.lax.slice_in_dim(x, i * n, (i + 1) * n, axis=axis)
            q_nope = jnp.einsum("blq,qhd->blhd", c_q,
                                at(self._q_b(), 1)[..., :self.nope],
                                precision=_HI)
            k_nope = jnp.einsum("blr,hdr->blhd", c_kv, at(self.w_uk.data),
                                precision=_HI)
            v = jnp.einsum("blr,hrv->blhv", c_kv, at(self.w_uv.data),
                           precision=_HI)
            q = jnp.concatenate([q_nope, at(q_pe, 2)], axis=-1)
            k = jnp.concatenate(
                [k_nope, jnp.broadcast_to(k_pe[:, :, None],
                                          (B, L, n, self.rope_dim))], -1)
            o = _fa.flash_attention(q, k, v, causal=True, scale=self.scale,
                                    precision="highest", length=length)
            return out + jnp.einsum("blhv,hvo->blo", o, at(w_o),
                                    precision=_HI)

        out = jnp.zeros((B, L, w_o.shape[-1]), c_kv.dtype)
        inputs = (c_q, q_pe, c_kv, k_pe)
        for i in range(self.heads // n):
            if i:
                # a pass's inputs wait for the pass before it: without the
                # barrier XLA runs the passes' expansions side by side (a
                # layer's scratch at 8,192: 2.49 GB against 1.84). Not a
                # `fori_loop`: the TPU trace shows a loop's operations
                # without their scopes, and the benchmark reads these
                *inputs, out = jax.lax.optimization_barrier((*inputs, out))
            out = heads(i, *inputs, out)
        return out

    def absorbed_query(self, q_nope, q_pe):
        """One new token a lane: q_nope [B, H, nope], q_pe [B, H, rope] ->
        the kernel's query [B, H, width]: q_nope_h W_UK_h^T, q_pe_h,
        zeros."""
        q_lat = jnp.einsum("bhd,hdr->bhr", q_nope, self.w_uk.data,
                           precision=_HI)
        pad = self.width - self.rank - self.rope_dim
        return jnp.concatenate(
            [q_lat, q_pe, jnp.zeros(q_pe.shape[:-1] + (pad,), q_pe.dtype)],
            axis=-1)

    def absorbed_output(self, o_lat):
        """The kernel's output [B, H, R] -> [B, H * v]: each head's
        W_UV_h."""
        o = jnp.einsum("bhr,hrv->bhv", o_lat, self.w_uv.data, precision=_HI)
        return o.reshape(o.shape[0], -1)


class DeepseekV2MoE(nn.Layer):
    """A group-limited softmax-routed dropless SwiGLU expert layer holding
    `experts_held` of the layer's routed experts, and the shared experts
    whole, as one SwiGLU."""

    def __init__(self, cfg: DeepseekV2Config):
        super().__init__()
        h, f = cfg.hidden_size, cfg.moe_intermediate_size
        self.first, held = cfg.experts_held
        self.route_args = dict(top_k=cfg.num_experts_per_tok,
                               n_group=cfg.n_group,
                               topk_group=cfg.topk_group,
                               scale=float(cfg.routed_scaling_factor))
        bound = math.sqrt(6.0 / (h + cfg.n_routed_experts))
        self.router = self.create_parameter(
            (h, cfg.n_routed_experts),
            default_initializer=Uniform(-bound, bound))
        # stacked once: [Wg^T; Wu^T] [E_held, 2f, h] and Wd [E_held, f, h]
        bound = math.sqrt(6.0 / (h + f))
        self.w_gate_up = self.create_parameter(
            (held, 2 * f, h), default_initializer=Uniform(-bound, bound))
        self.w_down = self.create_parameter(
            (held, f, h), default_initializer=Uniform(-bound, bound))
        self.shared = SwiGLU(h, f * cfg.n_shared_experts)

    def forward(self, u, active=None):
        """u Tensor [.., h] -> (the layer's output Tensor, counters [3]
        int32 as `ops/moe.COUNTERS`, (margin, group margin) [..])."""
        lead, h = u.shape[:-1], u.shape[-1]
        flat = u.data.reshape(-1, h)
        if active is not None:
            active = jnp.broadcast_to(active, lead).reshape(-1)
        with jax.named_scope("mlp"), jax.named_scope("moe"):
            experts, weights, margin, group_margin = \
                _moe.group_limited_route(flat, self.router.data,
                                         **self.route_args)
            routed, counters = _moe.held_experts(
                flat, experts, weights, self.w_gate_up.data,
                self.w_down.data, first=self.first, active=active,
                form="swiglu")
            with jax.named_scope("shared"):
                shared = self.shared(u)
            out = shared.data + routed.reshape(*lead, h)
        return Tensor(out), counters, (margin.reshape(lead),
                                       group_margin.reshape(lead))


class DeepseekV2Block(nn.Layer):
    """One layer: MLA, then a dense MLP or the experts."""

    def __init__(self, cfg: DeepseekV2Config, dense: bool):
        super().__init__()
        self.attn_norm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        self.attn = LatentAttention(cfg)
        self.mlp_norm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        self.sparse = not dense
        if dense:
            self.mlp = SwiGLU(cfg.hidden_size, cfg.intermediate_size)
        else:
            self.moe = DeepseekV2MoE(cfg)

    def forward(self, x, positions, attend, active=None):
        """x Tensor [B, L, h] at `positions`; `attend(attn, u)` is the
        caller's way through this layer's latent pool and returns the
        attention's output [B, L, h] (an array). Returns (the layer's
        output, what its experts counted or None)."""
        with jax.named_scope("ln"):
            u = self.attn_norm(x)
        with jax.named_scope("attention"):
            h = x + Tensor(attend(self.attn, u, positions))
        with jax.named_scope("ln"):
            u = self.mlp_norm(h)
        if self.sparse:
            mixed, counters, _ = self.moe(u, active)
            return h + mixed, counters
        with jax.named_scope("mlp"), jax.named_scope("dense_mlp"):
            return h + self.mlp(u), None


class DeepseekV2(_blocks.TokensToLogits, nn.Layer):
    def __init__(self, cfg: DeepseekV2Config):
        super().__init__()
        self.cfg = cfg
        # `wte`, as the decode protocol's other models name it
        self.wte = nn.Embedding(cfg.vocab_size, cfg.hidden_size)
        self.blocks = nn.LayerList(
            [DeepseekV2Block(cfg, i < cfg.first_k_dense_replace)
             for i in range(cfg.num_hidden_layers)])
        self.norm_f = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        # the head routes nothing: three passes, as the other hybrids'
        self.lm_head = _blocks.HighLinear(cfg.hidden_size, cfg.vocab_size)

    # ---- the one walk through the layers, whatever the pool's way

    def _layers(self, x, positions, attend, active=None):
        """x through every block; `attend(layer, attn, u, positions)`.
        Returns (x, the sparse layers' counters summed [3])."""
        counted = jnp.zeros((len(_moe.COUNTERS),), jnp.int32)
        for li, blk in enumerate(self.blocks):
            x, counters = blk(
                x, positions,
                lambda a, u, p, li=li: attend(li, a, u, p), active)
            if counters is not None:
                counted = counted + counters
        return x, counted

    @staticmethod
    def _expanded(attn, u, positions, append=None, length=None):
        """Whole prompts [B, L] through MLA, expanded; a prompt's rows
        handed to `append(rows [L, width])` first (B = 1; None: no
        cache), its live rows `length` (None: every row)."""
        c_q, _, q_pe, c_kv, k_pe = attn.project(u, positions, False)
        with jax.named_scope("latent"), jax.named_scope("prefill"):
            if append is not None:
                append(attn.rows(c_kv, k_pe)[0])
            return attn.expanded(c_q, q_pe, c_kv, k_pe, length)

    def forward(self, input_ids):
        """Whole-sequence logits [B, L, V] (B = 1), no cache: the expanded
        attention."""
        positions = jnp.arange(input_ids.shape[1], dtype=jnp.int32)
        x, _ = self._layers(self._embed(input_ids), positions,
                            lambda li, a, u, p: self._expanded(a, u, p))
        return self._logits(x)

    # ------------------- decode protocol (inference/serving.py) -------------

    def set_tp_mesh(self, mesh, axis: str = "tp"):
        if mesh is not None:
            raise LatentCacheUnsupported(
                "tensor-parallel decode (ServingEngine(mesh=...))",
                "sharding a latent pool, whose row serves every head, over "
                "the TP axis (set_tp_mesh covers the head-folded K/V pools "
                "of models/gpt.py only)",
                latent_layers=self.cfg.num_hidden_layers)

    def init_cache(self, max_batch: int, max_len: int, page_size: int = 16,
                   num_pages: int = 0, dtype=None) -> PagedKVCache:
        """An empty cache for `max_batch` concurrent sequences of up to
        `max_len` tokens: ONE pool of latent rows ``[pages, page,
        latent_width]`` a layer (kind ``"latent"``; `num_pages` as in
        `GPT.init_cache`) and the counters: the expert layers' of every
        DECODE step (`moe`, `ops/moe.COUNTERS`), the (token, expert) pairs
        the PREFILLS computed here (`moe_prefill`) and the rows the layers
        attended over in every decode step (`latent_rows`: the sum over
        layers and active lanes of the context, new token included)."""
        cfg = self.cfg
        if max_len > cfg.max_position_embeddings:
            raise ValueError(
                f"init_cache: max_len {max_len} exceeds "
                f"max_position_embeddings {cfg.max_position_embeddings}")
        pages_per_seq, num_pages = _blocks.pages_for(
            max_batch, max_len, page_size, num_pages)
        if dtype is None:
            dtype = self.wte.weight.dtype
        n = cfg.num_hidden_layers
        zero = lambda: jnp.zeros((1,), jnp.int32)   # noqa: E731
        return PagedKVCache(
            [jnp.zeros((num_pages, page_size, cfg.latent_width), dtype)
             for _ in range(n)], [],
            jnp.zeros((max_batch, pages_per_seq), jnp.int32),
            jnp.zeros((max_batch,), jnp.int32),
            page_size, cfg.num_attention_heads, cfg.kv_lora_rank,
            layer_kinds=[LATENT] * n, num_kv_heads=1,
            counters={"moe": jnp.zeros((len(_moe.COUNTERS),), jnp.int32),
                      "moe_prefill": zero(), "latent_rows": zero()})

    def forward_prefill(self, input_ids, cache: PagedKVCache, slot,
                        length, write_start=0):
        """Prefill ONE sequence into batch slot `slot` (the contract of
        `GPT.forward_prefill`): `input_ids` [1, L_bucket], `length` the
        real prompt length, `write_start` masks the rows' scatter below a
        shared prefix; the attention EXPANDED. Returns (last-position
        logits [1, V], updated cache)."""
        slot, length, write_start, page_row = _blocks.prefill_args(
            input_ids, cache, slot, length, write_start)
        positions = jnp.arange(input_ids.shape[1], dtype=jnp.int32)

        def attend(li, attn, u, p):
            i = cache.index_of(li)

            def append(rows):
                cache.k_pages[i] = _pa.latent_prefill_append(
                    cache.k_pages[i], rows, page_row, length,
                    start=write_start)
            return self._expanded(attn, u, p, append, length)

        x, counted = self._layers(self._embed(input_ids), positions, attend,
                                  positions[None] < length)
        cache.counters["moe_prefill"] = cache.counters["moe_prefill"] \
            + counted[:1]
        cache.context_lens = cache.context_lens.at[slot].set(length)
        # logits of the LAST REAL position only
        return self._logits(_blocks.last_real_position(x, length)), cache

    def forward_decode(self, tokens, cache: PagedKVCache, active=None,
                       slot_map=None):
        """ONE incremental decode step (the contract of
        `GPT.forward_decode`, lane mode included), the attention ABSORBED:
        each layer appends the new token's row to its pages and attends
        over them with `paged_attention.latent_attention` (a padding or
        inactive lane writes nothing, meets no expert and counts
        nothing)."""
        cfg = self.cfg
        slot_map, bt, ctx, active = _blocks.decode_view(cache, active,
                                                        slot_map)
        lens = jnp.where(active, ctx + 1, 0)

        def attend(li, attn, u, p):
            i = cache.index_of(li)
            _, q_nope, q_pe, c_kv, k_pe = attn.project(u, p, True)
            with jax.named_scope("latent"):
                with jax.named_scope("absorb"):
                    q = attn.absorbed_query(q_nope[:, 0], q_pe[:, 0])
                with jax.named_scope("decode"):
                    cache.k_pages[i] = _pa.latent_append(
                        cache.k_pages[i], attn.rows(c_kv[:, 0], k_pe[:, 0]),
                        bt, ctx, active)
                    o_lat = _pa.latent_attention(q, cache.k_pages[i], bt,
                                                 lens, attn.scale, attn.rank)
                with jax.named_scope("absorb"):
                    o = attn.absorbed_output(o_lat)
                with jax.named_scope("proj"):
                    return attn.o_proj(Tensor(o[:, None])).data

        x = self._embed(tokens)
        B = x.shape[0]
        x, counted = self._layers(
            Tensor(x.data.reshape(B, 1, cfg.hidden_size)), ctx[:, None],
            attend, active[:, None])
        cache.counters["moe"] = cache.counters["moe"] + counted
        cache.counters["latent_rows"] = cache.counters["latent_rows"] \
            + cfg.num_hidden_layers * jnp.sum(lens)
        _blocks.bump_lengths(cache, slot_map, ctx, active)
        return self._logits(Tensor(x.data.reshape(B, cfg.hidden_size))), \
            cache
