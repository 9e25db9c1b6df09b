"""Nemotron-H (`model_type` `nemotron_h`; NVIDIA-Nemotron-3-Nano-30B-A3B is
52 blocks by the pattern ``MEMEM*EMEMEM*...``): a block is ONE mixer
behind one RMSNorm, and the pattern string names each block's mixer::

    x = x + mixer_i(RMSNorm(x))            # no MLP beside a mixer
    logits = W_head RMSNorm(x_final)       # head untied from the embedding

    M  Mamba-2:  [z | xBC | dt] = u W_in; xBC = silu(conv_K(xBC) + b), a
                 causal depthwise convolution over time; x [H, P], B and
                 C [G, N] (head h uses group h // (H/G)); the state-space
                 recurrence of `ops/ssm.py` with delta = softplus(dt +
                 dt_bias), A = -exp(A_log), skip D;
                 y = GroupRMSNorm(y * silu(z)) * w; W_out.
    E  experts:  s = sigmoid(u W_r) over ALL `n_routed_experts`; the top
                 k of s + b; weights scale * s / sum(s chosen);
                 out = shared(u) + sum over chosen experts HELD HERE of
                 w_e expert_e(u), expert(u) = relu(u W1)^2 W2
                 (`ops/moe.py`: dropless, one grouped product a
                 projection over weights stored stacked).
    *  attention: q [H, D], k and v [Hkv, D] (grouped K/V heads: query
                 head h reads K/V head h // (H/Hkv)); causal
                 softmax(q k^T / sqrt(D)) v; W_o. No positional encoding
                 (the Nemotron-H report, arXiv:2504.03624): order comes
                 from the Mamba-2 layers.

Granite 4.0-H (`model_type` `granitemoehybrid`; Granite-4.0-H-Small is
40 layers, 36 Mamba-2 and 4 attention by `layer_types`) is this model
with options (`GraniteMoeHybridConfig`): each of its layers is TWO
blocks here, its mixer (`M` or `*`) then an expert block `E`, and four
multipliers scale what Nemotron-H leaves alone::

    x = embedding_multiplier * wte[ids]
    x = x + residual_multiplier * block_i(RMSNorm(x))     # every block
    *: softmax(q k^T * attention_multiplier) v            # not 1/sqrt(D)
    E: softmax over the top k LOGITS (`router` "softmax"), no bias, no
       scale; experts and the shared expert SwiGLU (`expert_form`
       "swiglu": (silu(u Wg) * u Wu) Wd, gate and up stacked)
    logits = RMSNorm(x_final) wte^T / logits_scaling     # tied head

`experts_held` = (first, count) says which routed experts this chip
holds: one share of a deployment that spreads each expert layer over
several chips. The router keeps its full width, the shared expert is held
whole, and what an absent expert would add is left out (the deployment
sums the chips' partial outputs; on one chip the layer runs without that
exchange).

Precision: float32 weights served as float32. Every product in front of a
router runs at `highest` (`decode_blocks.ExactLinear`, the recurrence, the
grouped products, both attention paths): a rounding of the residual stream
changes WHICH experts a token meets, after which the run is another
computation than the reference's. The head runs in three passes.

The decode protocol of `inference/serving.ServingEngine` over a cache of
three kinds (`models/decode_cache.py`): K/V pages ``[pages, page, Hkv*D]``
for `*`, a state ``[H, P, N]`` and the convolution's last K-1 inputs a
slot for `M`, nothing for `E`. What a recurrence needs of prefill and of
the lane-bucketed step is what `models/olmo_hybrid.py` says; the decode
step moves the lanes' inputs to their slots' rows and steps every slot's
state in place.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .. import nn
from ..framework.tensor import Tensor
from ..nn.initializer import Constant, Uniform
from ..ops import reshape
from ..ops import linear_attention as _la
from ..ops import moe as _moe
from ..ops import ssm as _ssm
from . import decode_blocks as _blocks
from .decode_blocks import ExactLinear as _Linear
from .decode_cache import KV, NONE, STATE, PagedKVCache

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"
PUBLISHED_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
ROUTERS = ("sigmoid", "softmax")


@dataclasses.dataclass
class NemotronHConfig:
    """The source's keys under the source's names (Hugging Face
    `config.json` of `nemotron_h`), and `experts_held`."""
    vocab_size: int = 131072
    hidden_size: int = 2688
    num_hidden_layers: int = 52
    hybrid_override_pattern: str = PUBLISHED_PATTERN
    layer_norm_epsilon: float = 1e-5
    max_position_embeddings: int = 262144
    # attention
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    # Mamba-2
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    n_groups: int = 8
    conv_kernel: int = 4
    chunk_size: int = 128
    use_conv_bias: bool = True
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # experts
    n_routed_experts: int = 128
    num_experts_per_tok: int = 6
    n_shared_experts: int = 1
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    # not in the source: (first, count) of the routed experts held here;
    # () => all of them
    experts_held: Tuple[int, ...] = ()
    # Granite 4.0-H's options (`GraniteMoeHybridConfig`); each default is
    # Nemotron-H's behaviour. attention_multiplier None: 1/sqrt(head_dim);
    # logits_scaling divides the logits
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: Optional[float] = None
    logits_scaling: float = 1.0
    tie_word_embeddings: bool = False
    router: str = "sigmoid"
    expert_form: str = "relu2"

    def __post_init__(self):
        n = self.num_hidden_layers
        # a depth cut keeps the leading blocks of the published pattern
        self.hybrid_override_pattern = self.hybrid_override_pattern[:n]
        kinds = self.hybrid_override_pattern
        if len(kinds) != n or set(kinds) - {MAMBA, EXPERTS, ATTENTION}:
            raise ValueError(f"hybrid_override_pattern must name {n} blocks "
                             f"as M, E or *, got {kinds!r}")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_attention_heads must be a multiple of "
                             "num_key_value_heads")
        if self.mamba_num_heads % self.n_groups:
            raise ValueError("mamba_num_heads must be a multiple of n_groups")
        if not self.norm_topk_prob or self.n_shared_experts != 1:
            raise ValueError("only norm_topk_prob=True with one shared "
                             "expert is implemented")
        first, count = self.experts_held or (0, self.n_routed_experts)
        if not 0 <= first < first + count <= self.n_routed_experts:
            raise ValueError(f"experts_held {self.experts_held} is no range "
                             f"of the {self.n_routed_experts} routed experts")
        if self.num_experts_per_tok >= self.n_routed_experts:
            raise ValueError("num_experts_per_tok must be below "
                             "n_routed_experts")
        self.experts_held = (int(first), int(count))
        if self.router not in ROUTERS or self.expert_form not in _moe.FORMS:
            raise ValueError(f"router {self.router!r} must be one of "
                             f"{ROUTERS}, expert_form {self.expert_form!r} "
                             f"one of {_moe.FORMS}")
        if self.router == "softmax" and self.routed_scaling_factor != 1.0:
            raise ValueError("the softmax router scales nothing: "
                             "routed_scaling_factor must be 1.0")

    @property
    def d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_channels(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    @staticmethod
    def tiny(pattern: str = "MEM*E", **changes):
        """Every kind of block, twice the recurrence; 8 experts top-2, 2
        K/V heads for 4 query heads."""
        return NemotronHConfig(**{**dict(
            vocab_size=256, hidden_size=64, num_hidden_layers=len(pattern),
            hybrid_override_pattern=pattern, max_position_embeddings=512,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            mamba_num_heads=8, mamba_head_dim=8, ssm_state_size=16,
            n_groups=2, chunk_size=16, n_routed_experts=8,
            num_experts_per_tok=2, moe_intermediate_size=32,
            moe_shared_expert_intermediate_size=64), **changes})


# Granite-4.0-H-Small's 40 layers: attention at 5, 15, 25 and 35
GRANITE_LAYER_TYPES = tuple(
    "attention" if i % 10 == 5 else "mamba" for i in range(40))
_GRANITE_MIXERS = {"mamba": MAMBA, "attention": ATTENTION}


@dataclasses.dataclass
class GraniteMoeHybridConfig:
    """Granite 4.0-H under the source's own keys (Hugging Face
    `config.json` of `granitemoehybrid`), and `experts_held`. The model is
    `NemotronH` with Granite's options (`as_nemotron_h`): a layer of
    `layer_types` is its mixer block then an expert block, `mamba` -> ME
    and `attention` -> *E. A depth cut keeps the leading
    `num_hidden_layers` entries of `layer_types`."""
    vocab_size: int = 100352
    hidden_size: int = 4096
    num_hidden_layers: int = 40
    layer_types: Tuple[str, ...] = GRANITE_LAYER_TYPES
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 131072
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    attention_multiplier: float = 0.0078125
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    logits_scaling: float = 16.0
    tie_word_embeddings: bool = True
    mamba_n_heads: int = 128
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256
    mamba_conv_bias: bool = True
    num_local_experts: int = 72
    num_experts_per_tok: int = 10
    intermediate_size: int = 768
    shared_intermediate_size: int = 1536
    # not in the source: the Mamba-2 initialiser's step range, and (first,
    # count) of the routed experts held here; () => all of them
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    experts_held: Tuple[int, ...] = ()

    def as_nemotron_h(self) -> NemotronHConfig:
        n = self.num_hidden_layers
        types = tuple(self.layer_types)[:n]
        if len(types) != n or set(types) - set(_GRANITE_MIXERS):
            raise ValueError(f"layer_types must name {n} layers as mamba "
                             f"or attention, got {types!r}")
        pattern = "".join(_GRANITE_MIXERS[t] + EXPERTS for t in types)
        return NemotronHConfig(
            vocab_size=self.vocab_size, hidden_size=self.hidden_size,
            num_hidden_layers=len(pattern), hybrid_override_pattern=pattern,
            layer_norm_epsilon=self.rms_norm_eps,
            max_position_embeddings=self.max_position_embeddings,
            num_attention_heads=self.num_attention_heads,
            num_key_value_heads=self.num_key_value_heads,
            head_dim=self.hidden_size // self.num_attention_heads,
            mamba_num_heads=self.mamba_n_heads,
            mamba_head_dim=self.mamba_d_head,
            ssm_state_size=self.mamba_d_state, n_groups=self.mamba_n_groups,
            conv_kernel=self.mamba_d_conv, chunk_size=self.mamba_chunk_size,
            use_conv_bias=self.mamba_conv_bias,
            time_step_min=self.time_step_min,
            time_step_max=self.time_step_max,
            time_step_floor=self.time_step_floor,
            n_routed_experts=self.num_local_experts,
            num_experts_per_tok=self.num_experts_per_tok,
            moe_intermediate_size=self.intermediate_size,
            moe_shared_expert_intermediate_size=self.shared_intermediate_size,
            routed_scaling_factor=1.0, experts_held=self.experts_held,
            embedding_multiplier=self.embedding_multiplier,
            residual_multiplier=self.residual_multiplier,
            attention_multiplier=self.attention_multiplier,
            logits_scaling=self.logits_scaling,
            tie_word_embeddings=self.tie_word_embeddings,
            router="softmax", expert_form="swiglu")

    @staticmethod
    def tiny(layer_types=("mamba", "attention", "mamba"), **changes):
        """Both mixers, one group of 8 state-space heads; 8 experts
        top-3, 2 K/V heads for 4 query heads of 16."""
        return GraniteMoeHybridConfig(**{**dict(
            vocab_size=256, hidden_size=64,
            num_hidden_layers=len(layer_types),
            layer_types=tuple(layer_types), max_position_embeddings=512,
            num_attention_heads=4, num_key_value_heads=2, mamba_n_heads=8,
            mamba_d_head=8, mamba_d_state=16, mamba_chunk_size=16,
            num_local_experts=8, num_experts_per_tok=3,
            intermediate_size=32, shared_intermediate_size=64), **changes})


class NemotronHMamba(nn.Layer):
    def __init__(self, cfg: NemotronHConfig):
        super().__init__()
        h, H = cfg.hidden_size, cfg.mamba_num_heads
        self.num_heads, self.head_dim = H, cfg.mamba_head_dim
        self.groups, self.state_size = cfg.n_groups, cfg.ssm_state_size
        self.d_inner, self.conv_channels = cfg.d_inner, cfg.conv_channels
        self.eps = float(cfg.layer_norm_epsilon)
        self.chunk = int(cfg.chunk_size)
        K = cfg.conv_kernel
        # columns z | xBC | dt
        self.in_proj = _Linear(h, self.d_inner + self.conv_channels + H)
        # [K, channels]: row K-1 meets the current token
        bound = 1.0 / math.sqrt(K)
        self.conv_weight = self.create_parameter(
            (K, self.conv_channels),
            default_initializer=Uniform(-bound, bound))
        self.conv_bias = self.create_parameter(
            (self.conv_channels,), default_initializer=Constant(0.0)) \
            if cfg.use_conv_bias else None
        # A_log = log U(1, 16); dt_bias = softplus^-1 of a step that is
        # log-uniform in [time_step_min, time_step_max], floored (the
        # Mamba-2 initialisers)
        self.A_log = self.create_parameter(
            (H,), default_initializer=lambda shape, dtype: jnp.log(
                Uniform(1.0, 16.0)(shape, dtype)))

        def inverse_softplus_of_step(shape, dtype):
            dt = jnp.exp(Uniform(math.log(cfg.time_step_min),
                                 math.log(cfg.time_step_max))(shape, dtype))
            dt = jnp.maximum(dt, cfg.time_step_floor)
            return dt + jnp.log(-jnp.expm1(-dt))

        self.dt_bias = self.create_parameter(
            (H,), default_initializer=inverse_softplus_of_step)
        self.D = self.create_parameter((H,),
                                       default_initializer=Constant(1.0))
        self.norm = nn.RMSNorm(self.d_inner, cfg.layer_norm_epsilon)
        self.out_proj = _Linear(self.d_inner, h)

    def project(self, u):
        """u Tensor [.., h] -> raw z [.., d_inner], xBC [.., channels],
        delta [.., H] (float32, positive)."""
        zxd = self.in_proj(u).data
        z = zxd[..., :self.d_inner]
        xbc = zxd[..., self.d_inner:self.d_inner + self.conv_channels]
        delta = _ssm.step_sizes(zxd[..., self.d_inner + self.conv_channels:],
                                self.dt_bias.data)
        return z, xbc, delta

    def split(self, y):
        """A convolved [.., channels] array -> x [.., H, P], B and C
        [.., G, N]."""
        G, N = self.groups, self.state_size
        lead = y.shape[:-1]
        return (y[..., :self.d_inner].reshape(*lead, self.num_heads,
                                              self.head_dim),
                y[..., self.d_inner:self.d_inner + G * N].reshape(*lead, G, N),
                y[..., self.d_inner + G * N:].reshape(*lead, G, N))

    def rates(self):
        return -jnp.exp(self.A_log.data.astype(jnp.float32))

    def output(self, y, z):
        """y [.., H, P] raw, z the gate [.., d_inner] -> the mixer's output
        Tensor [.., h]."""
        y = _ssm.gated_group_rms_norm(
            y.reshape(z.shape), z, self.norm.weight.data,
            groups=self.groups, epsilon=self.eps)
        return self.out_proj(Tensor(y.astype(z.dtype)))

    def _bias(self):
        return None if self.conv_bias is None else self.conv_bias.data

    def forward(self, u, length=None):
        """Whole sequences, u [B, L, h]. Returns (out [B, L, h], state
        [B, H, P, N], conv_state [B, K-1, channels]); `length` as in
        `ops/ssm.ssd_chunked`."""
        with jax.named_scope("attention"), jax.named_scope("ssm"):
            z, xbc, delta = self.project(u)
            y, conv_state = _la.causal_conv_prefill(
                xbc, self.conv_weight.data, length, self._bias())
            x, Bm, Cm = self.split(y)
            y, state = _ssm.ssd_chunked(
                x, delta, self.rates(), Bm, Cm, self.D.data, length=length,
                chunk=self.chunk)
            return self.output(y, z), state, conv_state

    def prefill(self, u, length, states, conv_states, slot):
        """`forward` over ONE prompt, u [1, L, h], whose final states
        OVERWRITE row `slot` of the per-slot arrays."""
        out, state, conv_state = self.forward(u, length)
        with jax.named_scope("attention"), jax.named_scope("ssm"):
            with jax.named_scope("scan"):
                states = _la.state_scatter(states, slot[None], state)
            with jax.named_scope("conv"):
                conv_states = _la.state_scatter(conv_states, slot[None],
                                                conv_state)
        return out, states, conv_states

    def step(self, u, states, conv_states, active, slot_map=None):
        """One token, u [B, 1, h], against the per-slot arrays, updated in
        place: row b's own state or, in lane mode, the row `slot_map[b]`
        names: the lanes' small inputs are scattered to their slots' rows
        (a padding lane's sentinel dropped), every slot's state is stepped
        with the slots no lane named inactive, and the outputs are
        gathered back (`ops/linear_attention.py`, "rows of a per-slot
        state")."""
        to_slots = (lambda t: t) if slot_map is None else (
            lambda t: _la.lanes_to_slots(t, slot_map, slots=states.shape[0]))
        with jax.named_scope("attention"), jax.named_scope("ssm"):
            z, xbc, delta = self.project(u)
            # the moves between lanes and slots sit under the scope of the
            # state they serve, as the delta rule's do
            with jax.named_scope("conv"):
                xbc, active = to_slots(xbc[:, 0]), to_slots(active)
            y, conv_states = _la.causal_conv_update(
                conv_states, xbc, self.conv_weight.data, active, self._bias())
            x, Bm, Cm = self.split(y)
            with jax.named_scope("scan"):
                delta = to_slots(delta[:, 0])
            y, states = _ssm.ssd_step(states, x, delta, self.rates(), Bm, Cm,
                                      self.D.data, active)
            if slot_map is not None:
                with jax.named_scope("scan"):
                    y = _la.slots_to_lanes(y, slot_map)
            return self.output(y[:, None], z), states, conv_states


class NemotronHExperts(nn.Layer):
    """A dropless expert block holding `experts_held` of the layer's
    routed experts and the shared expert whole: sigmoid-routed relu^2
    experts, or by the config's `router` and `expert_form` softmax-routed
    SwiGLU experts (Granite 4.0-H)."""

    def __init__(self, cfg: NemotronHConfig):
        super().__init__()
        h, f = cfg.hidden_size, cfg.moe_intermediate_size
        self.first, held = cfg.experts_held
        self.top_k = cfg.num_experts_per_tok
        self.scale = float(cfg.routed_scaling_factor)
        self.router_kind, self.form = cfg.router, cfg.expert_form
        # gate and up stacked as one matrix, the gate's rows first
        # (ops/moe.py)
        stacked = 2 if cfg.expert_form == "swiglu" else 1
        bound = math.sqrt(6.0 / (h + cfg.n_routed_experts))
        self.router = self.create_parameter(
            (h, cfg.n_routed_experts),
            default_initializer=Uniform(-bound, bound))
        if cfg.router == "sigmoid":
            # the published one is learned; it only selects. Zeros would
            # leave the path untested
            self.e_score_correction_bias = self.create_parameter(
                (cfg.n_routed_experts,),
                default_initializer=Uniform(-0.05, 0.05))
        # stacked once, [E_held, f, h] both (W1 transposed: ops/moe.py)
        bound = math.sqrt(6.0 / (h + f))
        self.w1 = self.create_parameter(
            (held, stacked * f, h), default_initializer=Uniform(-bound, bound))
        self.w2 = self.create_parameter(
            (held, f, h), default_initializer=Uniform(-bound, bound))
        fs = cfg.moe_shared_expert_intermediate_size
        self.shared_up = _Linear(h, stacked * fs)
        self.shared_down = _Linear(fs, h)

    def _route(self, flat):
        if self.router_kind == "softmax":
            return _moe.softmax_route(flat, self.router.data,
                                      top_k=self.top_k)
        return _moe.sigmoid_route(
            flat, self.router.data, self.e_score_correction_bias.data,
            top_k=self.top_k, scale=self.scale)

    def _shared(self, u):
        mid = self.shared_up(u).data
        if self.form == "swiglu":
            fs = mid.shape[-1] // 2
            mid = jax.nn.silu(mid[..., :fs]) * mid[..., fs:]
        else:
            mid = jnp.square(jax.nn.relu(mid))
        return self.shared_down(Tensor(mid))

    def forward(self, u, active=None):
        """u Tensor [.., h] -> (the block's output Tensor, counters [3]
        int32 as `ops/moe.COUNTERS`, margin [..] float32: how close each
        token's routing came to another choice). A token whose `active`
        [..] is False (padding) meets no routed expert."""
        lead, h = u.shape[:-1], u.shape[-1]
        flat = u.data.reshape(-1, h)
        if active is not None:
            active = jnp.broadcast_to(active, lead).reshape(-1)
        with jax.named_scope("mlp"), jax.named_scope("moe"):
            experts, weights, margin = self._route(flat)
            routed, counters = _moe.held_experts(
                flat, experts, weights, self.w1.data, self.w2.data,
                first=self.first, active=active, form=self.form)
            with jax.named_scope("shared"):
                shared = self._shared(u)
            out = shared.data + routed.reshape(*lead, h)
        return Tensor(out), counters, margin.reshape(lead)


class NemotronHAttention(nn.Layer):
    def __init__(self, cfg: NemotronHConfig):
        super().__init__()
        h, D = cfg.hidden_size, cfg.head_dim
        self.q_proj = _Linear(h, cfg.num_attention_heads * D)
        self.k_proj = _Linear(h, cfg.num_key_value_heads * D)
        self.v_proj = _Linear(h, cfg.num_key_value_heads * D)
        self.o_proj = _Linear(cfg.num_attention_heads * D, h)

    def qkv(self, u):
        """(q [B, L, H*D], k, v [B, L, Hkv*D]) Tensors, heads folded as
        the pools store them."""
        return self.q_proj(u), self.k_proj(u), self.v_proj(u)


@functools.partial(jax.jit, static_argnames=("scale",))
def _grouped_causal_attention(q, k, v, scale=None):
    """A prompt's attention as a masked matrix product at `highest`: q
    ``[B, L, Hkv, G, D]``, k and v ``[B, L, Hkv, D]`` (the group is an axis
    of q and of the scores; K/V are not repeated). Not the flash kernel:
    Mosaic runs that kernel's products in single bfloat16 passes, which put
    the scores of the expert blocks behind an attention block up to 9e-4
    from the float32 reference's, so that 83 prompts of 96 met other
    experts somewhere in their 256 tokens (PERF.md, PR 31); here attention
    is 2 blocks of 13 at prompts of at most 2048, and the whole square is
    a millisecond. `scale` None: 1/sqrt(D)."""
    hi = jax.lax.Precision.HIGHEST
    L, D = q.shape[1], q.shape[-1]
    s = jnp.einsum("bqkgd,bskd->bkgqs", q, k, precision=hi)
    s = s / math.sqrt(D) if scale is None else s * scale
    s = jnp.where(jnp.tril(jnp.ones((L, L), bool)), s, -jnp.inf)
    return jnp.einsum("bkgqs,bskd->bqkgd", jax.nn.softmax(s, axis=-1), v,
                      precision=hi)


_MIXERS = {MAMBA: NemotronHMamba, EXPERTS: NemotronHExperts,
           ATTENTION: NemotronHAttention}


class NemotronHBlock(nn.Layer):
    def __init__(self, cfg: NemotronHConfig, kind: str):
        super().__init__()
        self.kind = kind
        self.norm = nn.RMSNorm(cfg.hidden_size, cfg.layer_norm_epsilon)
        self.mixer = _MIXERS[kind](cfg)

    def normed(self, x):
        with jax.named_scope("ln"):
            return self.norm(x)


class NemotronH(_blocks.TokensToLogits, nn.Layer):
    def __init__(self, cfg):
        """`cfg` a `NemotronHConfig`, or a `GraniteMoeHybridConfig`, which
        is this model with Granite's options."""
        super().__init__()
        if isinstance(cfg, GraniteMoeHybridConfig):
            cfg = cfg.as_nemotron_h()
        self.cfg = cfg
        # `wte`, as the decode protocol's other models name it
        self.wte = nn.Embedding(cfg.vocab_size, cfg.hidden_size)
        self.blocks = nn.LayerList(
            [NemotronHBlock(cfg, kind)
             for kind in cfg.hybrid_override_pattern])
        self.norm_f = nn.RMSNorm(cfg.hidden_size, cfg.layer_norm_epsilon)
        if not cfg.tie_word_embeddings:
            # the head routes nothing: three passes, as the other hybrid's
            self.lm_head = _blocks.HighLinear(cfg.hidden_size,
                                              cfg.vocab_size)

    def _embed(self, ids):
        x = super()._embed(ids)
        m = self.cfg.embedding_multiplier
        if m == 1.0:
            return x
        with jax.named_scope("embed"):
            return Tensor(x.data * m)

    def _add(self, x, mixed):
        """The residual stream after a block: x + residual_multiplier *
        mixed."""
        r = self.cfg.residual_multiplier
        return x + (mixed if r == 1.0 else Tensor(mixed.data * r))

    def _logits(self, x):
        cfg = self.cfg
        if not cfg.tie_word_embeddings and cfg.logits_scaling == 1.0:
            return super()._logits(x)
        with jax.named_scope("ln"):
            x = self.norm_f(x)
        with jax.named_scope("logits"):
            # tied: the embedding's rows are the head's columns, three
            # passes as the untied head's
            w = (self.wte.weight.data.T if cfg.tie_word_embeddings
                 else self.lm_head.weight.data)
            return Tensor(_blocks.head(x.data, w) / cfg.logits_scaling)

    def _full_attention(self, attn, q, k, v):
        """Causal attention over whole sequences; q [B, L, H*D], k and v
        [B, L, Hkv*D] Tensors."""
        cfg = self.cfg
        B, L, _ = q.shape
        H, Hkv, D = (cfg.num_attention_heads, cfg.num_key_value_heads,
                     cfg.head_dim)
        out = _grouped_causal_attention(
            q.data.reshape(B, L, Hkv, H // Hkv, D),
            k.data.reshape(B, L, Hkv, D), v.data.reshape(B, L, Hkv, D),
            scale=cfg.attention_multiplier)
        return attn.o_proj(Tensor(out.reshape(B, L, H * D)))

    def forward(self, input_ids):
        """Whole-sequence logits [B, L, V], no cache."""
        x = self._embed(input_ids)
        for blk in self.blocks:
            u = blk.normed(x)
            if blk.kind == ATTENTION:
                with jax.named_scope("attention"):
                    mixed = self._full_attention(blk.mixer,
                                                 *blk.mixer.qkv(u))
            else:   # both return (output, and two things a cache wants)
                mixed, _, _ = blk.mixer(u)
            x = self._add(x, mixed)
        return self._logits(x)

    # ------------------- decode protocol (inference/serving.py) -------------

    def _layer_counts(self):
        kinds = self.cfg.hybrid_override_pattern
        return kinds.count(ATTENTION), kinds.count(MAMBA)

    def init_cache(self, max_batch: int, max_len: int, page_size: int = 16,
                   num_pages: int = 0, dtype=None) -> PagedKVCache:
        """An empty cache for `max_batch` concurrent sequences of up to
        `max_len` tokens: K/V page pools ``[pages, page, Hkv*D]`` for the
        attention blocks only (`num_pages` as in `GPT.init_cache`), for
        each Mamba-2 block a state [max_batch, H, P, N] and a convolution
        tail [max_batch, K-1, channels], zero like a fresh sequence's,
        nothing for an expert block; and the expert blocks' counters."""
        cfg = self.cfg
        if max_len > cfg.max_position_embeddings:
            raise ValueError(
                f"init_cache: max_len {max_len} exceeds "
                f"max_position_embeddings {cfg.max_position_embeddings}")
        pages_per_seq, num_pages = _blocks.pages_for(
            max_batch, max_len, page_size, num_pages)
        if dtype is None:
            dtype = self.wte.weight.dtype
        n_kv, n_state = self._layer_counts()
        pool = (num_pages, page_size, cfg.num_key_value_heads * cfg.head_dim)
        state = (max_batch, cfg.mamba_num_heads, cfg.mamba_head_dim,
                 cfg.ssm_state_size)
        tail = (max_batch, cfg.conv_kernel - 1, cfg.conv_channels)
        return PagedKVCache(
            [jnp.zeros(pool, dtype) for _ in range(n_kv)],
            [jnp.zeros(pool, dtype) for _ in range(n_kv)],
            jnp.zeros((max_batch, pages_per_seq), jnp.int32),
            jnp.zeros((max_batch,), jnp.int32),
            page_size, cfg.num_attention_heads, cfg.head_dim,
            states=[jnp.zeros(state, dtype) for _ in range(n_state)],
            conv_states=[jnp.zeros(tail, dtype) for _ in range(n_state)],
            layer_kinds=[{MAMBA: STATE, EXPERTS: NONE, ATTENTION: KV}[k]
                         for k in cfg.hybrid_override_pattern],
            num_kv_heads=cfg.num_key_value_heads,
            # summed over the expert blocks of every DECODE step since the
            # cache was made: `ops/moe.COUNTERS`
            counters={"moe": jnp.zeros((len(_moe.COUNTERS),), jnp.int32)})

    def forward_prefill(self, input_ids, cache: PagedKVCache, slot,
                        length, write_start=0):
        """Prefill ONE sequence into batch slot `slot` (the contract of
        `GPT.forward_prefill`): `input_ids` [1, L_bucket], `length` the
        real prompt length, `write_start` masks the K/V scatter below a
        shared prefix. The slot's recurrent and convolution states are
        OVERWRITTEN with the prompt's; bucket padding meets no routed
        expert. Returns (last-position logits [1, V], updated cache)."""
        slot, length, write_start, page_row = _blocks.prefill_args(
            input_ids, cache, slot, length, write_start)
        real = jnp.arange(input_ids.shape[1], dtype=jnp.int32)[None] < length
        x = self._embed(input_ids)
        for li, blk in enumerate(self.blocks):
            i = cache.index_of(li)
            u = blk.normed(x)
            if blk.kind == MAMBA:
                mixed, cache.states[i], cache.conv_states[i] = \
                    blk.mixer.prefill(u, length, cache.states[i],
                                      cache.conv_states[i], slot)
            elif blk.kind == EXPERTS:
                mixed, _, _ = blk.mixer(u, real)
            else:
                with jax.named_scope("attention"):
                    q, k, v = blk.mixer.qkv(u)
                    _blocks.paged_prefill_append(
                        cache, i, k.data[0], v.data[0], page_row, length,
                        write_start)
                    mixed = self._full_attention(blk.mixer, q, k, v)
            x = self._add(x, mixed)
        cache.context_lens = cache.context_lens.at[slot].set(length)
        # logits of the LAST REAL position only
        return self._logits(_blocks.last_real_position(x, length)), cache

    def forward_decode(self, tokens, cache: PagedKVCache, active=None,
                       slot_map=None):
        """ONE incremental decode step (the contract of
        `GPT.forward_decode`, lane mode included): attention blocks append
        to and attend over their pages, Mamba-2 blocks update the rows of
        their states in place, expert blocks add what they counted to
        `cache.counters["moe"]` (a padding or inactive lane meets no
        routed expert and counts nothing)."""
        cfg = self.cfg
        slot_map, bt, ctx, active = _blocks.decode_view(cache, active,
                                                        slot_map)
        x = self._embed(tokens)
        B = x.shape[0]
        x = reshape(x, [B, 1, cfg.hidden_size])
        counted = cache.counters["moe"]
        for li, blk in enumerate(self.blocks):
            i = cache.index_of(li)
            u = blk.normed(x)
            if blk.kind == MAMBA:
                mixed, cache.states[i], cache.conv_states[i] = \
                    blk.mixer.step(u, cache.states[i], cache.conv_states[i],
                                   active, slot_map)
            elif blk.kind == EXPERTS:
                mixed, counters, _ = blk.mixer(u, active[:, None])
                counted = counted + counters
            else:
                with jax.named_scope("attention"):
                    q, k, v = blk.mixer.qkv(u)             # [B, 1, ...]
                    out = _blocks.paged_decode_attention(
                        cache, i, q.data.reshape(
                            B, cfg.num_attention_heads, cfg.head_dim),
                        k.data[:, 0], v.data[:, 0], bt, ctx, active,
                        scale=cfg.attention_multiplier)
                    mixed = blk.mixer.o_proj(reshape(
                        Tensor(out),
                        [B, 1, cfg.num_attention_heads * cfg.head_dim]))
            x = self._add(x, mixed)
        cache.counters["moe"] = counted
        _blocks.bump_lengths(cache, slot_map, ctx, active)
        return self._logits(reshape(x, [B, cfg.hidden_size])), cache
