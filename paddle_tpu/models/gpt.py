"""GPT — decoder-only transformer LM (flagship model).

Capability target: the reference's fleet GPT examples (GPT-3 1.3B/6.7B hybrid
TP+PP configs in `BASELINE.json`). Architecture is GPT-2/3 style: learned
positions, pre-LN blocks, causal flash attention. The hybrid-parallel variant
lives in `paddle_tpu.distributed.hybrid` (stacked-layer pipeline + TP
shardings); this module is the single-device/DP definition.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from .. import nn
from ..nn import functional as F
from ..framework.tensor import Tensor
from ..ops import arange, reshape, transpose
from .decode_cache import PagedKVCache  # noqa: F401  (its home until PR 27)


@dataclasses.dataclass
class GPTConfig:
    vocab_size: int = 50304
    max_position_embeddings: int = 1024
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 0  # 0 => 4*hidden
    dropout: float = 0.1
    attn_dropout: float = 0.1
    tie_word_embeddings: bool = True
    # activation-checkpoint policy per block: "" (save-everything),
    # "dots" (selective: keep matmul outputs, recompute elementwise chains
    # in backward — HBM-for-VPU trade), "full" (recompute whole block)
    remat: str = ""

    def __post_init__(self):
        if not self.intermediate_size:
            self.intermediate_size = 4 * self.hidden_size
        if self.remat not in ("", "dots", "full"):
            raise ValueError(
                f"GPTConfig.remat must be '', 'dots' or 'full', "
                f"got {self.remat!r}")

    @staticmethod
    def gpt2_small():
        return GPTConfig(hidden_size=768, num_layers=12, num_heads=12)

    @staticmethod
    def gpt3_1p3b():
        return GPTConfig(hidden_size=2048, num_layers=24, num_heads=16,
                         max_position_embeddings=2048)

    @staticmethod
    def gpt3_6p7b():
        return GPTConfig(hidden_size=4096, num_layers=32, num_heads=32,
                         max_position_embeddings=2048)

    @staticmethod
    def tiny():
        return GPTConfig(vocab_size=1024, max_position_embeddings=128,
                         hidden_size=64, num_layers=2, num_heads=4, dropout=0.0,
                         attn_dropout=0.0)


class GPTAttention(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        h = cfg.hidden_size
        self.num_heads = cfg.num_heads
        self.head_dim = h // cfg.num_heads
        self.qkv = nn.Linear(h, 3 * h)
        self.proj = nn.Linear(h, h)
        self.attn_dropout = cfg.attn_dropout
        self.resid_drop = nn.Dropout(cfg.dropout)

    def forward(self, x):
        import jax
        # named scopes -> XLA op metadata: the trace-measured per-segment
        # breakdown (profiler/xplane.segment_breakdown) attributes work
        # events to attention/mlp/ln/... by these scope tags
        with jax.named_scope("attention"):
            B, L, H = x.shape
            qkv = self.qkv(x)
            qkv = reshape(qkv, [B, L, 3, self.num_heads, self.head_dim])
            q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
            out = F.scaled_dot_product_attention(
                q, k, v, is_causal=True, dropout_p=self.attn_dropout,
                training=self.training)
            out = reshape(out, [B, L, H])
            return self.resid_drop(self.proj(out))


class GPTMLP(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.fc1 = nn.Linear(cfg.hidden_size, cfg.intermediate_size)
        self.fc2 = nn.Linear(cfg.intermediate_size, cfg.hidden_size)
        self.drop = nn.Dropout(cfg.dropout)

    def forward(self, x):
        import jax
        with jax.named_scope("mlp"):
            return self.drop(self.fc2(F.gelu(self.fc1(x), approximate=True)))


class GPTBlock(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.ln1 = nn.LayerNorm(cfg.hidden_size)
        self.attn = GPTAttention(cfg)
        self.ln2 = nn.LayerNorm(cfg.hidden_size)
        self.mlp = GPTMLP(cfg)

    def forward(self, x):
        import jax
        with jax.named_scope("ln"):
            h = self.ln1(x)
        x = x + self.attn(h)
        with jax.named_scope("ln"):
            h = self.ln2(x)
        x = x + self.mlp(h)
        return x


class GPT(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        self.wte = nn.Embedding(cfg.vocab_size, cfg.hidden_size)
        self.wpe = nn.Embedding(cfg.max_position_embeddings, cfg.hidden_size)
        self.drop = nn.Dropout(cfg.dropout)
        self.blocks = nn.LayerList([GPTBlock(cfg) for _ in range(cfg.num_layers)])
        self.ln_f = nn.LayerNorm(cfg.hidden_size)
        if not cfg.tie_word_embeddings:
            self.lm_head = nn.Linear(cfg.hidden_size, cfg.vocab_size,
                                     bias_attr=False)

    # pipeline protocol (distributed.meta_parallel.pipeline_parallel):
    # pre -> scanned homogeneous blocks -> post
    def pipeline_pre(self, input_ids):
        import jax
        with jax.named_scope("embed"):
            B, L = input_ids.shape
            pos = arange(0, L, dtype="int32")
            x = self.wte(input_ids) + self.wpe(pos)
            return self.drop(x)

    def pipeline_post(self, x):
        import jax
        with jax.named_scope("ln"):
            x = self.ln_f(x)
        with jax.named_scope("logits"):
            if self.cfg.tie_word_embeddings:
                from ..ops import matmul
                return matmul(x, self.wte.weight, transpose_y=True)
            return self.lm_head(x)

    def forward(self, input_ids):
        x = self.pipeline_pre(input_ids)
        if self.cfg.remat and self.training:
            import jax

            from ..distributed.fleet.utils import recompute
            pol = (None if self.cfg.remat == "full" else
                   jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
            for blk in self.blocks:
                x = recompute(blk, x, policy=pol)
        else:
            for blk in self.blocks:
                x = blk(x)
        return self.pipeline_post(x)

    def loss(self, input_ids, labels):
        logits = self(input_ids)
        return F.cross_entropy(logits, labels)

    def num_params(self):
        return sum(p.size for p in self.parameters())

    # ---------------- autoregressive decode (paged KV cache) ----------------
    #
    # The training forward above re-runs full-sequence attention for every
    # generated token — O(n^2) FLOPs and HBM traffic per sequence. The
    # decode path below is the serving shape: K/V of every past token live
    # in fixed-size pages (ops/pallas/paged_attention.py), prefill runs the
    # prompt once through the normal flash-attention path while scattering
    # its K/V into the pages, and each generated token is ONE incremental
    # step (append one K/V row, attend over the pages). All methods are
    # traceable — inference/serving.py jits the whole batched step with the
    # cache donated.

    def set_tp_mesh(self, mesh, axis: str = "tp"):
        """Arm the tensor-parallel decode path: `init_cache` shards the
        K/V page pools over `axis` on the HEAD dim, and the decode/
        prefill page paths run per-shard via shard_map (the attention
        output is gathered back to replicated before the proj matmul, so
        no floating-point contraction ever splits across devices —
        greedy decode stays bit-exact vs single-chip). Pass None to
        disarm. Weights stay replicated (decode is KV-bandwidth bound;
        the pool is the memory that scales N×)."""
        if mesh is not None:
            if axis not in mesh.shape:
                raise ValueError(f"set_tp_mesh: mesh has no axis "
                                 f"{axis!r} (axes: {dict(mesh.shape)})")
            if self.cfg.num_heads % mesh.shape[axis]:
                raise ValueError(
                    f"set_tp_mesh: num_heads {self.cfg.num_heads} does "
                    f"not divide over mesh axis {axis!r} of size "
                    f"{mesh.shape[axis]}")
        self._tp_mesh = mesh
        self._tp_axis = axis

    def tp_mesh(self):
        return getattr(self, "_tp_mesh", None)

    def init_cache(self, max_batch: int, max_len: int, page_size: int = 16,
                   num_pages: int = 0, dtype=None,
                   sharded: bool = True) -> PagedKVCache:
        """Build an empty paged KV cache for `max_batch` concurrent
        sequences of up to `max_len` tokens. `num_pages` defaults to full
        backing (every slot can reach max_len) + the null page; a serving
        deployment may pass less and rely on allocator preemption.

        With a TP mesh armed (`set_tp_mesh`) the pools allocate SHARDED
        over the folded axis (heads are contiguous in it, so a shard holds
        whole heads) — each device holds 1/N of every layer's pool,
        which is the N×-larger-model capacity claim — while block tables
        and context lens replicate (they are host-updated control state).
        `sharded=False` builds a plain single-device cache regardless
        (the disaggregated prefill workers' private caches)."""
        import jax
        import jax.numpy as jnp
        if max_len > self.cfg.max_position_embeddings:
            raise ValueError(
                f"init_cache: max_len {max_len} exceeds "
                f"max_position_embeddings {self.cfg.max_position_embeddings}")
        pages_per_seq = -(-max_len // page_size)
        if not num_pages:
            num_pages = 1 + max_batch * pages_per_seq  # +1: the null page
        if dtype is None:
            dtype = self.wte.weight.dtype
        H, D = self.cfg.num_heads, self.cfg.hidden_size // self.cfg.num_heads
        shape = (num_pages, page_size, H * D)
        mesh = self.tp_mesh() if sharded else None
        if mesh is None:
            k_pages = [jnp.zeros(shape, dtype) for _ in self.blocks]
            v_pages = [jnp.zeros(shape, dtype) for _ in self.blocks]
            bt = jnp.zeros((max_batch, pages_per_seq), jnp.int32)
            cl = jnp.zeros((max_batch,), jnp.int32)
        else:
            from jax.sharding import NamedSharding, PartitionSpec as P
            pool_sh = NamedSharding(mesh, P(None, None, self._tp_axis))
            rep_sh = NamedSharding(mesh, P())
            # allocate THROUGH the sharding: each device materializes
            # only its pool shard — the whole point of TP decode is that
            # the full pool never exists on one chip
            zeros = jax.jit(lambda: jnp.zeros(shape, dtype),
                            out_shardings=pool_sh)
            k_pages = [zeros() for _ in self.blocks]
            v_pages = [zeros() for _ in self.blocks]
            bt = jax.device_put(
                jnp.zeros((max_batch, pages_per_seq), jnp.int32), rep_sh)
            cl = jax.device_put(jnp.zeros((max_batch,), jnp.int32), rep_sh)
        return PagedKVCache(k_pages, v_pages, bt, cl, page_size, H, D)

    def _block_qkv(self, blk, x):
        """(q, k, v) raw arrays [B, L, H*D] from one block's qkv proj:
        three slices of its lanes, heads left folded as the pools store
        them (what wants [.., H, D] — flash attention, the decode query —
        reshapes its own operand)."""
        h = self.cfg.hidden_size
        qkv = blk.attn.qkv(x).data
        return qkv[..., :h], qkv[..., h:2 * h], qkv[..., 2 * h:]

    def forward_prefill(self, input_ids, cache: PagedKVCache, slot,
                        length, write_start=0, use_tp: bool = True):
        """Prefill ONE sequence: run the prompt through the normal (flash)
        causal attention while scattering every position's K/V into the
        pages of batch slot `slot`. `input_ids` is [1, L_bucket] (L may be
        padded up to a shape bucket — the retrace watchdog stays quiet
        because serving always pads to a bucket); `length` is the real
        prompt length (traced ok). `write_start` masks the K/V scatter
        below that position: a request admitted with a SHARED prefix
        (serving's copy-on-write page fork) already has positions
        [0, write_start) in pages forked from another request, and must
        not re-write them — attention still runs over the full prompt
        (the logits need the whole context; only the scatter is masked).
        Returns (last-position logits [1, V], updated cache)."""
        import jax
        import jax.numpy as jnp
        from ..ops.pallas import paged_attention as _pa
        from ..ops.pallas import tiling as _tiling
        B, L = input_ids.shape
        if B != 1:
            raise ValueError(f"forward_prefill fills ONE slot's pages; got "
                             f"batch {B} (serving prefills per request)")
        with jax.named_scope("embed"):
            pos = arange(0, L, dtype="int32")
            x = self.wte(input_ids) + self.wpe(pos)
        slot = jnp.asarray(slot, jnp.int32)
        length = jnp.asarray(length, jnp.int32)
        write_start = jnp.asarray(write_start, jnp.int32)
        page_row = jnp.take(cache.block_tables, slot, axis=0)
        per_head = (B, L, cache.num_heads, cache.head_dim)
        mesh = self.tp_mesh() if use_tp else None
        # under a TP mesh the prefill program is multi-device: the Pallas
        # dispatch sites run per shard, heads over the TP axis
        with _tiling.kernel_mesh(mesh, heads=getattr(self, "_tp_axis", None)):
            for li, blk in enumerate(self.blocks):
                with jax.named_scope("ln"):
                    h = blk.ln1(x)
                with jax.named_scope("attention"):
                    q, k, v = self._block_qkv(blk, h)   # [1, L, H*D]
                    if mesh is not None:
                        cache.k_pages[li], cache.v_pages[li] = \
                            _pa.prefill_append_tp(
                                cache.k_pages[li], cache.v_pages[li], k[0],
                                v[0], page_row, length, mesh,
                                axis=self._tp_axis, start=write_start)
                    else:
                        cache.k_pages[li], cache.v_pages[li] = \
                            _pa.prefill_append(
                                cache.k_pages[li], cache.v_pages[li], k[0],
                                v[0], page_row, length, start=write_start)
                    q, k, v = (Tensor(t.reshape(per_head))
                               for t in (q, k, v))
                    out = F.scaled_dot_product_attention(
                        q, k, v, is_causal=True, training=False)
                    out = reshape(out, [B, L, self.cfg.hidden_size])
                    x = x + blk.attn.proj(out)
                with jax.named_scope("ln"):
                    h = blk.ln2(x)
                x = x + blk.mlp(h)
        cache.context_lens = cache.context_lens.at[slot].set(length)
        with jax.named_scope("logits"):
            # logits of the LAST REAL position only (bucket padding past
            # `length` attends causally to junk and is never read)
            last = Tensor(jax.lax.dynamic_index_in_dim(
                x.data, length - 1, axis=1, keepdims=False))
            logits = self.pipeline_post(last)
        return logits, cache

    def forward_decode(self, tokens, cache: PagedKVCache, active=None,
                       slot_map=None, use_tp: bool = True):
        """ONE incremental decode step: append each sequence's new token
        K/V to its pages, attend over the paged context. `tokens` is [B]
        int (the token sitting at position context_lens[b]); `active`
        [B] bool masks idle serving slots (their writes land on the null
        page, their logits are garbage nobody reads). Returns
        (logits [B, V], updated cache).

        `slot_map` [W] int32 switches to LANE mode (the serving engine's
        width-bucketed fused step): lane i computes the decode step for
        cache slot slot_map[i], so a batch with few active sequences
        runs a W << max_batch executable instead of the full-width one.
        Padding lanes carry slot_map[i] >= max_batch (the gather clamps,
        active[i] is False, and the context-length scatter-back drops
        them); `tokens`/`active` are then [W]-shaped per lane."""
        import jax
        import jax.numpy as jnp
        from ..ops.pallas import paged_attention as _pa
        lanes = slot_map is not None
        if lanes:
            slot_map = jnp.asarray(slot_map, jnp.int32)
            # clamp-gather: padding lanes read SOME real slot's row, but
            # their active mask parks writes on the null page and zeroes
            # their attention context
            bt = jnp.take(cache.block_tables, slot_map, axis=0,
                          mode="clip")
            ctx = jnp.take(cache.context_lens, slot_map, mode="clip")
            if active is None:
                active = slot_map < cache.max_batch
        else:
            bt = cache.block_tables
            ctx = cache.context_lens
            if active is None:
                active = jnp.ones((cache.max_batch,), bool)
        with jax.named_scope("embed"):
            # position of the incoming token = current context length
            pos = Tensor(jnp.minimum(
                ctx, self.cfg.max_position_embeddings - 1))
            x = self.wte(tokens) + self.wpe(pos)       # [B, hidden]
        B = x.shape[0]
        x = reshape(x, [B, 1, self.cfg.hidden_size])
        mesh = self.tp_mesh() if use_tp else None
        for li, blk in enumerate(self.blocks):
            with jax.named_scope("ln"):
                h = blk.ln1(x)
            with jax.named_scope("attention"):
                q, k, v = self._block_qkv(blk, h)      # [B, 1, H*D]
                q = q.reshape(B, cache.num_heads, cache.head_dim)
                if mesh is not None:
                    # TP: per-shard append + attention on the local head
                    # slice; `out` comes back REPLICATED so the proj
                    # contraction below never splits (bit-exactness)
                    out, cache.k_pages[li], cache.v_pages[li] = \
                        _pa.decode_step_tp(
                            q, k[:, 0], v[:, 0], cache.k_pages[li],
                            cache.v_pages[li], bt, ctx, active, mesh,
                            axis=self._tp_axis)
                else:
                    cache.k_pages[li], cache.v_pages[li] = \
                        _pa.cache_append(
                            cache.k_pages[li], cache.v_pages[li],
                            k[:, 0], v[:, 0], bt, ctx, active)
                    out = _pa.paged_attention(
                        q, cache.k_pages[li], cache.v_pages[li], bt,
                        # the new token is part of its own context
                        jnp.where(active, ctx + 1, 0))
                out = reshape(Tensor(out), [B, 1, self.cfg.hidden_size])
                x = x + blk.attn.proj(out)
            with jax.named_scope("ln"):
                h = blk.ln2(x)
            x = x + blk.mlp(h)
        if lanes:
            # scatter-back: +1 for each active lane's slot; padding-lane
            # sentinels (>= max_batch) drop instead of clamping onto a
            # real slot's counter
            cache.context_lens = cache.context_lens.at[slot_map].add(
                jnp.where(active, 1, 0).astype(jnp.int32), mode="drop")
        else:
            cache.context_lens = jnp.where(active, ctx + 1, ctx)
        with jax.named_scope("logits"):
            logits = self.pipeline_post(reshape(x, [B, self.cfg.hidden_size]))
        return logits, cache

    # -- reference decode loops (bench A/B + parity tests) -------------------

    def generate_dense(self, input_ids, max_new_tokens: int,
                       eos_id: int = -1):
        """Cacheless greedy decode: the O(n^2) baseline — every token
        re-runs the FULL forward over the whole growing sequence. Returns
        [B, L + max_new_tokens] (generation stops early only when every
        row hit eos_id)."""
        import numpy as np
        from ..ops import argmax, concat
        ids = input_ids
        for _ in range(max_new_tokens):
            logits = self(ids)                          # [B, L', V]
            nxt = argmax(logits[:, -1], axis=-1, dtype="int32")
            ids = concat([ids, reshape(nxt, [ids.shape[0], 1])], axis=1)
            if eos_id >= 0 and bool(np.all(np.asarray(nxt.data) == eos_id)):
                break
        return ids

    def generate_paged(self, input_ids, max_new_tokens: int,
                       eos_id: int = -1, page_size: int = 8):
        """Greedy decode through the paged path: prefill once, then one
        incremental `forward_decode` per token. The parity counterpart of
        `generate_dense` (inference/serving.py is the production loop —
        this helper allocates pages contiguously per row)."""
        import numpy as np
        import jax.numpy as jnp
        from ..ops import argmax, concat
        if max_new_tokens <= 0:
            return input_ids  # match generate_dense's [B, L] contract
        B, L = input_ids.shape
        max_len = L + max_new_tokens
        cache = self.init_cache(B, max_len, page_size=page_size)
        pps = cache.pages_per_seq
        # contiguous page plan: row b owns pages [1 + b*pps, 1 + (b+1)*pps)
        bt = 1 + np.arange(B * pps, dtype=np.int32).reshape(B, pps)
        cache.block_tables = jnp.asarray(bt)
        for b in range(B):
            logits, cache = self.forward_prefill(
                input_ids[b:b + 1], cache, b, L)
            last = logits if b == 0 else concat([last, logits], axis=0)
        ids = input_ids
        nxt = argmax(last, axis=-1, dtype="int32")
        ids = concat([ids, reshape(nxt, [B, 1])], axis=1)
        for _ in range(max_new_tokens - 1):
            if eos_id >= 0 and bool(np.all(np.asarray(nxt.data) == eos_id)):
                break
            logits, cache = self.forward_decode(nxt, cache)
            nxt = argmax(logits, axis=-1, dtype="int32")
            ids = concat([ids, reshape(nxt, [B, 1])], axis=1)
        return ids
