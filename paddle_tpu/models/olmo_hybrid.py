"""Olmo-Hybrid: gated-delta-rule linear-attention layers beside full
attention (`model_type` `olmo_hybrid`; Olmo-Hybrid-7B is three linear
layers then one full layer, eight times).

Per layer on ``x [T, h]``, no biases anywhere::

    x = x + RMSNorm(mixer(x))            # the norm sits on the branch's OUTPUT
    x = x + RMSNorm(W_down(silu(W_gate x) * (W_up x)))
    logits = W_head RMSNorm(x_final)     # head untied from the embedding

    full_attention:   q = RMSNorm_h(W_q x), k = RMSNorm_h(W_k x), v = W_v x;
                      causal softmax(q k^T / sqrt(D)) v over heads of D = h / H;
                      W_o. No rotary embedding and no position table: order
                      comes from the recurrent layers.
    linear_attention: [q | k | v] = silu(conv_K([W_q | W_k | W_v] x)), a causal
                      depthwise convolution over time, kernel K, per channel;
                      per head the gated delta rule of `ops/linear_attention.py`
                      with beta = 2 sigmoid(W_b x), g = -exp(A_log) softplus(W_a x
                      + dt_bias); y = RMSNorm_dv(o) * silu(W_g x) per head; W_o.

The decode protocol of `inference/serving.ServingEngine` (`init_cache`,
`forward_prefill`, `forward_decode`) is implemented over a cache of two
kinds (`models/decode_cache.py`): K/V pages for the full-attention
layers, a per-slot state ``[H, dk, dv]`` and the convolution's last K-1
inputs for the linear ones. Three things a recurrence needs that causal
attention forgave:

* a prompt padded to its bucket: positions at or past `length` must not
  touch the state (the scan is told `length`), and the convolution state
  is the inputs at ``length-K+1 .. length-1``;
* a padding lane of the lane-bucketed decode step reads a real slot
  through the clamped gather: its state write is dropped, and an inactive
  lane writes back what it read;
* a reused slot holds the previous request's state until prefill
  overwrites it, which prefill always does (a shared prefix masks only
  the K/V scatter: the prompt is computed whole, so the state needs no
  snapshot).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Tuple

import jax
import jax.numpy as jnp

from .. import nn
from ..nn import functional as F
from ..framework.tensor import Tensor
from ..nn.initializer import Uniform
from ..ops import reshape
from ..ops import linear_attention as _la
from . import decode_blocks as _blocks
from .decode_blocks import HighLinear as _Linear
from .decode_cache import KV, STATE, PagedKVCache, StateLayersUnsupported

LINEAR = "linear_attention"
FULL = "full_attention"


@dataclasses.dataclass
class OlmoHybridConfig:
    """The source's keys under the source's names (Hugging Face
    `config.json` of `olmo_hybrid`)."""
    vocab_size: int = 100352
    hidden_size: int = 3840
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 30
    num_key_value_heads: int = 30
    max_position_embeddings: int = 65536
    rms_norm_eps: float = 1e-6
    layer_types: Tuple[str, ...] = ()     # () => 3 linear : 1 full, repeated
    linear_num_key_heads: int = 30
    linear_num_value_heads: int = 30
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True
    # not in the source: tokens to a chunk of the prefill scan
    linear_chunk_size: int = _la.DEFAULT_CHUNK

    def __post_init__(self):
        n = self.num_hidden_layers
        kinds = tuple(self.layer_types) or tuple(
            FULL if i % 4 == 3 else LINEAR for i in range(n))
        # a depth cut keeps the leading layers of the published pattern
        self.layer_types = kinds[:n]
        if len(self.layer_types) != n or \
                set(self.layer_types) - {LINEAR, FULL}:
            raise ValueError(
                f"layer_types must name {n} layers as {LINEAR!r} or "
                f"{FULL!r}, got {kinds}")
        if self.num_key_value_heads != self.num_attention_heads:
            raise ValueError("grouped K/V heads are not implemented: "
                             "num_key_value_heads must equal "
                             "num_attention_heads")
        if self.linear_num_key_heads != self.linear_num_value_heads:
            raise ValueError("linear_num_key_heads must equal "
                             "linear_num_value_heads")
        if self.hidden_size % self.num_attention_heads:
            raise ValueError("hidden_size must divide over the heads")
        if not self.linear_allow_neg_eigval:
            raise ValueError("only linear_allow_neg_eigval=True (beta in "
                             "(0, 2)) is implemented")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @staticmethod
    def tiny(periods: int = 1):
        """One period (4 layers) is the least that has both kinds."""
        return OlmoHybridConfig(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=4 * periods, num_attention_heads=2,
            num_key_value_heads=2, max_position_embeddings=512,
            linear_num_key_heads=2, linear_num_value_heads=2,
            linear_key_head_dim=8, linear_value_head_dim=16,
            linear_chunk_size=16)


class OlmoFullAttention(nn.Layer):
    def __init__(self, cfg: OlmoHybridConfig):
        super().__init__()
        h = cfg.hidden_size
        self.q_proj, self.k_proj = _Linear(h, h), _Linear(h, h)
        self.v_proj, self.o_proj = _Linear(h, h), _Linear(h, h)
        # over the whole projected width, not per head (OLMo 2/3)
        self.q_norm = nn.RMSNorm(h, cfg.rms_norm_eps)
        self.k_norm = nn.RMSNorm(h, cfg.rms_norm_eps)

    def qkv(self, x):
        """(q, k, v) Tensors [B, L, H*D], heads folded as the pools store
        them."""
        return (self.q_norm(self.q_proj(x)), self.k_norm(self.k_proj(x)),
                self.v_proj(x))


class OlmoLinearAttention(nn.Layer):
    def __init__(self, cfg: OlmoHybridConfig):
        super().__init__()
        h, H = cfg.hidden_size, cfg.linear_num_value_heads
        self.num_heads = H
        self.dk, self.dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
        self.eps = float(cfg.rms_norm_eps)
        self.chunk = int(cfg.linear_chunk_size)
        K = cfg.linear_conv_kernel_dim
        self.conv_channels = H * (2 * self.dk + self.dv)
        self.qkv = _Linear(h, self.conv_channels)       # columns q | k | v
        # [K, channels]: row K-1 meets the current token
        bound = 1.0 / math.sqrt(K)
        self.conv_weight = self.create_parameter(
            (K, self.conv_channels),
            default_initializer=Uniform(-bound, bound))
        self.ab = _Linear(h, 2 * H)                      # columns a | b
        # A_log = log U(0, 16); dt_bias = softplus^-1 of a step that is
        # log-uniform in [1e-3, 1e-1] (the gated-delta-net initialisers)
        self.A_log = self.create_parameter(
            (H,), default_initializer=lambda shape, dtype: jnp.log(
                Uniform(1e-3, 16.0)(shape, dtype)))

        def inverse_softplus_of_step(shape, dtype):
            dt = jnp.exp(Uniform(math.log(1e-3), math.log(1e-1))(shape,
                                                                  dtype))
            return dt + jnp.log(-jnp.expm1(-dt))

        self.dt_bias = self.create_parameter(
            (H,), default_initializer=inverse_softplus_of_step)
        self.gate = _Linear(h, H * self.dv)
        self.o_norm = nn.RMSNorm(self.dv, cfg.rms_norm_eps)
        self.o_proj = _Linear(H * self.dv, h)

    def split(self, y):
        """A convolved [.., channels] array -> q, k [.., H, dk], v
        [.., H, dv]."""
        H, dk, dv = self.num_heads, self.dk, self.dv
        lead = y.shape[:-1]
        return (y[..., :H * dk].reshape(*lead, H, dk),
                y[..., H * dk:2 * H * dk].reshape(*lead, H, dk),
                y[..., 2 * H * dk:].reshape(*lead, H, dv))

    def gates(self, x):
        return _la.delta_gates(self.ab(x).data, self.A_log.data,
                               self.dt_bias.data)

    def output(self, o, x):
        """o [.., H, dv] raw, x the layer's input Tensor -> the branch's
        output Tensor [.., h]."""
        y = _la.gated_rms_norm(o, self.gate(x).data, self.o_norm.weight.data,
                               epsilon=self.eps)
        return self.o_proj(Tensor(y))

    def forward(self, x, length=None):
        """Whole sequences, x [B, L, h]. Returns (out [B, L, h], state
        [B, H, dk, dv], conv_state [B, K-1, channels]); `length` as in
        `gated_delta_rule_chunked`."""
        with jax.named_scope("attention"), jax.named_scope("linear"):
            y, conv_state = _la.causal_conv_prefill(
                self.qkv(x).data, self.conv_weight.data, length)
            q, k, v = self.split(y)
            g, beta = self.gates(x)
            o, state = _la.gated_delta_rule_chunked(
                q, k, v, g, beta, length=length, chunk=self.chunk)
            return self.output(o, x), state, conv_state

    def prefill(self, x, length, states, conv_states, slot):
        """`forward` over ONE prompt, x [1, L, h], whose final states
        OVERWRITE row `slot` of the per-slot arrays."""
        out, state, conv_state = self.forward(x, length)
        with jax.named_scope("attention"), jax.named_scope("linear"):
            with jax.named_scope("delta_rule"):
                states = _la.state_scatter(states, slot[None], state)
            with jax.named_scope("conv"):
                conv_states = _la.state_scatter(conv_states, slot[None],
                                                conv_state)
        return out, states, conv_states

    def step(self, x, states, conv_states, active, slot_map=None):
        """One token, x [B, 1, h], against the per-slot arrays, updated in
        place: row b's own state or, in lane mode, the row `slot_map[b]`
        names. There the lanes' small inputs are scattered to their
        slots' rows (a padding lane's sentinel dropped), every slot's
        state is stepped with the slots no lane named inactive, and the
        outputs are gathered back. A row that is not `active` keeps its
        state."""
        to_slots = (lambda t: t) if slot_map is None else (
            lambda t: _la.lanes_to_slots(t, slot_map, slots=states.shape[0]))
        with jax.named_scope("attention"), jax.named_scope("linear"):
            qkv = self.qkv(x).data[:, 0]
            g, beta = self.gates(x)
            # the moves between lanes and slots sit under the scope of the
            # state they serve: the compiler fuses them with the passes
            # over that state, and the benchmark sums the recurrence's
            # device time by the scope `delta_rule`
            with jax.named_scope("conv"):
                qkv, active = to_slots(qkv), to_slots(active)
            y, conv_states = _la.causal_conv_update(
                conv_states, qkv, self.conv_weight.data, active)
            q, k, v = self.split(y)
            with jax.named_scope("delta_rule"):
                g, beta = to_slots(g[:, 0]), to_slots(beta[:, 0])
            o, states = _la.gated_delta_rule_step(states, q, k, v, g, beta,
                                                  active)
            if slot_map is not None:
                with jax.named_scope("delta_rule"):
                    o = _la.slots_to_lanes(o, slot_map)
            return self.output(o[:, None], x), states, conv_states


class OlmoMLP(nn.Layer):
    def __init__(self, cfg: OlmoHybridConfig):
        super().__init__()
        h, f = cfg.hidden_size, cfg.intermediate_size
        self.gate_proj, self.up_proj = _Linear(h, f), _Linear(h, f)
        self.down_proj = _Linear(f, h)

    def forward(self, x):
        with jax.named_scope("mlp"):
            return self.down_proj(F.silu(self.gate_proj(x)) * self.up_proj(x))


class OlmoHybridBlock(nn.Layer):
    def __init__(self, cfg: OlmoHybridConfig, kind: str):
        super().__init__()
        self.kind = kind
        self.attn = (OlmoLinearAttention(cfg) if kind == LINEAR
                     else OlmoFullAttention(cfg))
        self.attn_norm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        self.mlp = OlmoMLP(cfg)
        self.mlp_norm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)

    def add_branch(self, x, branch, norm):
        with jax.named_scope("ln"):
            return x + norm(branch)

    def finish(self, x, mixed):
        """The residual around the mixer's output, then the MLP's."""
        x = self.add_branch(x, mixed, self.attn_norm)
        return self.add_branch(x, self.mlp(x), self.mlp_norm)


class OlmoHybrid(_blocks.TokensToLogits, nn.Layer):
    def __init__(self, cfg: OlmoHybridConfig):
        super().__init__()
        self.cfg = cfg
        # `wte`, as the decode protocol's other model names it
        self.wte = nn.Embedding(cfg.vocab_size, cfg.hidden_size)
        self.blocks = nn.LayerList(
            [OlmoHybridBlock(cfg, kind) for kind in cfg.layer_types])
        self.norm_f = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        self.lm_head = _Linear(cfg.hidden_size, cfg.vocab_size)

    def _full_attention(self, attn, q, k, v):
        """Causal attention over whole sequences; q, k, v Tensors
        [B, L, H*D]."""
        B, L, h = q.shape
        per_head = [B, L, self.cfg.num_attention_heads, self.cfg.head_dim]
        out = F.scaled_dot_product_attention(
            reshape(q, per_head), reshape(k, per_head), reshape(v, per_head),
            is_causal=True, training=False)
        return attn.o_proj(reshape(out, [B, L, h]))

    def forward(self, input_ids):
        """Whole-sequence logits [B, L, V], no cache."""
        x = self._embed(input_ids)
        for blk in self.blocks:
            if blk.kind == LINEAR:
                mixed, _, _ = blk.attn(x)
            else:
                with jax.named_scope("attention"):
                    mixed = self._full_attention(blk.attn, *blk.attn.qkv(x))
            x = blk.finish(x, mixed)
        return self._logits(x)

    # ------------------- decode protocol (inference/serving.py) -------------

    def set_tp_mesh(self, mesh, axis: str = "tp"):
        if mesh is not None:
            kinds = self.cfg.layer_types
            raise StateLayersUnsupported(
                "tensor-parallel decode (ServingEngine(mesh=...))",
                "sharding a per-slot recurrent state and its update over "
                "the TP axis (set_tp_mesh covers K/V pools only)",
                kv_layers=kinds.count(FULL), state_layers=kinds.count(LINEAR))

    def init_cache(self, max_batch: int, max_len: int, page_size: int = 16,
                   num_pages: int = 0, dtype=None) -> PagedKVCache:
        """An empty cache for `max_batch` concurrent sequences of up to
        `max_len` tokens: K/V page pools for the full-attention layers
        only (`num_pages` as in `GPT.init_cache`), and for each linear
        layer a state [max_batch, H, dk, dv] and a convolution tail
        [max_batch, K-1, channels], zero like a fresh sequence's."""
        cfg = self.cfg
        if max_len > cfg.max_position_embeddings:
            raise ValueError(
                f"init_cache: max_len {max_len} exceeds "
                f"max_position_embeddings {cfg.max_position_embeddings}")
        pages_per_seq, num_pages = _blocks.pages_for(
            max_batch, max_len, page_size, num_pages)
        if dtype is None:
            dtype = self.wte.weight.dtype
        n_full = cfg.layer_types.count(FULL)
        n_lin = cfg.layer_types.count(LINEAR)
        pool = (num_pages, page_size, cfg.hidden_size)
        H, dk, dv = (cfg.linear_num_value_heads, cfg.linear_key_head_dim,
                     cfg.linear_value_head_dim)
        channels = H * (2 * dk + dv)
        return PagedKVCache(
            [jnp.zeros(pool, dtype) for _ in range(n_full)],
            [jnp.zeros(pool, dtype) for _ in range(n_full)],
            jnp.zeros((max_batch, pages_per_seq), jnp.int32),
            jnp.zeros((max_batch,), jnp.int32),
            page_size, cfg.num_attention_heads, cfg.head_dim,
            states=[jnp.zeros((max_batch, H, dk, dv), dtype)
                    for _ in range(n_lin)],
            conv_states=[jnp.zeros(
                (max_batch, cfg.linear_conv_kernel_dim - 1, channels), dtype)
                for _ in range(n_lin)],
            layer_kinds=[KV if t == FULL else STATE
                         for t in cfg.layer_types])

    def forward_prefill(self, input_ids, cache: PagedKVCache, slot,
                        length, write_start=0):
        """Prefill ONE sequence into batch slot `slot` (the contract of
        `GPT.forward_prefill`): `input_ids` [1, L_bucket], `length` the
        real prompt length, `write_start` masks the K/V scatter below a
        shared prefix. The slot's recurrent and convolution states are
        OVERWRITTEN with the prompt's, computed from a zero state over
        the first `length` positions. Returns (last-position logits
        [1, V], updated cache)."""
        slot, length, write_start, page_row = _blocks.prefill_args(
            input_ids, cache, slot, length, write_start)
        x = self._embed(input_ids)
        for li, blk in enumerate(self.blocks):
            i = cache.index_of(li)
            if blk.kind == LINEAR:
                mixed, cache.states[i], cache.conv_states[i] = \
                    blk.attn.prefill(x, length, cache.states[i],
                                     cache.conv_states[i], slot)
            else:
                with jax.named_scope("attention"):
                    q, k, v = blk.attn.qkv(x)
                    _blocks.paged_prefill_append(
                        cache, i, k.data[0], v.data[0], page_row, length,
                        write_start)
                    mixed = self._full_attention(blk.attn, q, k, v)
            x = blk.finish(x, mixed)
        cache.context_lens = cache.context_lens.at[slot].set(length)
        # logits of the LAST REAL position only
        return self._logits(_blocks.last_real_position(x, length)), cache

    def forward_decode(self, tokens, cache: PagedKVCache, active=None,
                       slot_map=None):
        """ONE incremental decode step (the contract of
        `GPT.forward_decode`, lane mode included): full-attention layers
        append to and attend over their pages; linear layers update the
        rows of their states. In lane mode the rows are gathered with the
        clamped `slot_map` and scattered back with the sentinel of a
        padding lane DROPPED; an inactive lane writes back what it read."""
        cfg = self.cfg
        slot_map, bt, ctx, active = _blocks.decode_view(cache, active,
                                                        slot_map)
        x = self._embed(tokens)
        B = x.shape[0]
        x = reshape(x, [B, 1, cfg.hidden_size])
        for li, blk in enumerate(self.blocks):
            i = cache.index_of(li)
            if blk.kind == LINEAR:
                mixed, cache.states[i], cache.conv_states[i] = \
                    blk.attn.step(x, cache.states[i], cache.conv_states[i],
                                  active, slot_map)
            else:
                with jax.named_scope("attention"):
                    q, k, v = blk.attn.qkv(x)          # [B, 1, H*D]
                    out = _blocks.paged_decode_attention(
                        cache, i, q.data.reshape(
                            B, cfg.num_attention_heads, cfg.head_dim),
                        k.data[:, 0], v.data[:, 0], bt, ctx, active)
                    mixed = blk.attn.o_proj(
                        reshape(Tensor(out), [B, 1, cfg.hidden_size]))
            x = blk.finish(x, mixed)
        _blocks.bump_lengths(cache, slot_map, ctx, active)
        return self._logits(reshape(x, [B, cfg.hidden_size])), cache
