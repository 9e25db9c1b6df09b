"""The decode cache a model hands the serving engine: paged K/V for the
layers that attend over every past token, a paged LATENT row a token for
the layers of latent attention, a fixed ring of K/V per batch
slot for the layers that attend over a sliding window, a fixed-size state
per batch slot for the layers that carry a recurrence, all in one pytree.

A model's `init_cache` builds it, its `forward_prefill` /
`forward_decode` take and return it, and `inference/serving.py` reads
its own description (`layer_kinds`, `describe()`) instead of assuming
one K and one V pool per model layer.
"""
from __future__ import annotations

import math
from typing import Optional, Sequence

import numpy as np

KV = "kv"          # a layer with a paged K and V pool
KV_WINDOW = "kv_window"   # a layer whose K/V is a ring of `window` tokens a slot
STATE = "state"    # a layer with a recurrent state and a convolution tail
NONE = "none"      # a layer that carries nothing from token to token
LATENT = "latent"  # a layer with ONE paged pool of latent rows, no V pool


class StateLayersUnsupported(NotImplementedError):
    """A serving path that moves K/V pages and knows no protocol for a
    per-slot recurrent state was asked to serve a model that has state
    layers."""

    def __init__(self, path: str, missing: str, *, kv_layers: int,
                 state_layers: int):
        super().__init__(
            f"{path} cannot serve a model with recurrent-state layers "
            f"({state_layers} state, {kv_layers} paged K/V): missing "
            f"protocol: {missing}")
        self.path = path
        self.missing = missing


class WindowLayersUnsupported(NotImplementedError):
    """A serving path that moves or shards K/V pages by the engine's
    block table was asked to serve a model with sliding-window layers,
    whose K/V is a ring a slot that no table names."""

    def __init__(self, path: str, missing: str, *, kv_layers: int,
                 window_layers: int):
        super().__init__(
            f"{path} cannot serve a model with sliding-window layers "
            f"({window_layers} window rings, {kv_layers} paged K/V): "
            f"missing protocol: {missing}")
        self.path = path
        self.missing = missing


class LatentCacheUnsupported(NotImplementedError):
    """A serving path that moves or shards K and V pages by heads was
    asked to serve a model of latent attention, whose pool holds one
    latent row a token for all heads and no V pool."""

    def __init__(self, path: str, missing: str, *, latent_layers: int):
        super().__init__(
            f"{path} cannot serve a model with latent-attention layers "
            f"({latent_layers} latent pools, no K/V heads to shard or hand "
            f"off): missing protocol: {missing}")
        self.path = path
        self.missing = missing


class DraftingUnsupported(NotImplementedError):
    """A serving path that knows one new token a lane and iteration was
    asked to serve a model that drafts with a module of its own and
    verifies the draft in the next iteration (`draft_tokens` > 0)."""

    def __init__(self, path: str, missing: str, *, draft_tokens: int):
        super().__init__(
            f"{path} cannot serve a model that drafts {draft_tokens} "
            f"token(s) an iteration with a module of its own: missing "
            f"protocol: {missing}")
        self.path = path
        self.missing = missing


def _nbytes(x) -> int:
    """Of an array or of its shape alone (`jax.eval_shape`)."""
    return math.prod(x.shape) * np.dtype(x.dtype).itemsize


class PagedKVCache:
    """Paged decode KV cache + per-slot recurrent state.

    ``k_pages[i]`` / ``v_pages[i]`` are ``[num_pages, page_size, Hkv*D]``,
    one pair for each layer whose kind is ``"kv"``, in layer order, the
    K/V heads FOLDED into the minor axis (head h in lanes [h*D, (h+1)*D);
    ``num_kv_heads`` / ``head_dim`` say how). ``num_heads`` counts the
    QUERY heads: where it is a multiple of ``num_kv_heads`` (grouped K/V
    heads), query head h reads K/V head ``h // (num_heads //
    num_kv_heads)`` and a K/V head is stored once, never repeated. Folded,
    because a jitted
    program holds its arguments and results to the device's default
    layout for their shape: the TPU lays ``[.., H*D]`` out row-major
    whenever H*D is a multiple of 128, but puts the PAGES of a 4-D
    ``[.., 12, 64]`` pool in the lanes, and then every decode and prefill
    program re-lays out every pool on the way in and on the way out
    (ops/pallas/paged_attention.py says how to check a new shape ahead of
    time). ``block_tables`` is ``[max_batch, pages_per_seq]`` int32 and
    ``context_lens`` ``[max_batch]`` int32. Page 0 is the NULL page: idle
    batch slots point at it and their decode-step writes land there (see
    the serving allocator). WHO OWNS THEM: whoever schedules the slots.
    Under `inference/serving.ServingEngine` that is the host: the engine
    keeps both as NumPy arrays, writes only those, and assigns fresh
    device copies into these two fields right before a dispatch when a
    row changed; the programs pass the tables through and update the
    lengths (prefill sets a slot's, decode bumps each active lane's).
    A model's forward never edits a table, and in lane mode it reads
    only the rows its `slot_map` names, so a row of an idle slot may be
    stale on the device.

    ``states[j]`` ``[max_batch, ...]`` and ``conv_states[j]``
    ``[max_batch, K-1, C]`` belong to the j-th layer whose kind is
    ``"state"``: row b is batch slot b's recurrent state and the last
    K-1 inputs of its short convolution. They are not paged: their size
    does not grow with the context. Prefill OVERWRITES a slot's row
    (whatever a previous request left there), decode updates it in
    place.

    ``window_k[j]`` / ``window_v[j]`` belong to the j-th layer whose kind
    is ``"kv_window"`` (sliding-window attention over the last ``window``
    tokens): ``[1 + max_batch * window / page_size, page_size, Hkv*D]``,
    folded like the pools and read by the same kernels, but a RING a
    slot and never more, whatever the context: slot b owns pages
    ``1 + b * window / page_size`` onward (page 0 the null page), the
    token at position t is written at row ``t mod window`` of its slot's
    ring, and the layer attends over ``min(context, window)`` rows. Keys
    are stored with their position already in them (rotated), so the
    order of a ring's rows is immaterial. WHO OWNS ITS TABLE: nobody
    stores one. It is a function of the slot alone, computed inside the
    programs from the slot (prefill) or the `slot_map` (decode): the
    allocator hands out no ring page, admission and growth count the
    paged layers' pages only, copy-on-write and a prefix hit touch the
    paged layers only (prefill recomputes the prompt whole and REWRITES
    the slot's ring, as it overwrites a recurrent state).

    ``draft_layers`` of the ``"kv"`` layers, the LAST ones of
    ``layer_kinds``, belong to a model's drafting module (a
    multi-token-prediction block: `models/exaone_moe.py`) and not to a
    decoder layer: pools like any other, under the same block table and
    the same lengths (the module's row i is made from the main model's
    hidden state at i and the token at i + 1, so after an iteration it
    holds as many rows as the main layers do), so that allocation,
    growth, copy-on-write and `pool_bytes()` count them with the rest.

    A layer of kind ``"latent"`` (latent attention, `models/deepseek_v2.py`)
    keeps ONE pool and no V pool: its entry of ``k_pages`` is
    ``[num_pages, page_size, latent_width]``, a row a token holding what
    every head's key and value are made from (the compressed ``c_kv``, then
    the rotated key part shared by all heads, then zeros to
    `latent_width`), under the same block table and lengths as any paged
    layer, so that allocation, growth, copy-on-write, a prefix hit,
    `audit()` and `pool_bytes()` take it with the rest. A cache holds
    latent layers or ``"kv"`` layers, not both (``v_pages`` then lines up
    with ``k_pages``). ``latent_width`` is a multiple of 128 for the
    folded pool's reason above: DeepSeek-V2's row of 512 + 64 is stored
    640 wide, since a row of 576 fills four lane tiles and half a fifth
    and the chip would pad or re-lay out the pool in every program. The
    kernel reads a row once for the scores (all of it) and the values
    (its first ``head_dim`` lanes: the latent's width). ``num_heads``
    counts the query heads; ``num_kv_heads`` is 1.

    ``layer_kinds`` names each model layer's kind (default: every layer
    paged K/V, the GPT case); a layer of kind ``"none"`` (a feed-forward
    or expert block that is a layer of its own) holds nothing here.

    ``counters`` is a dict of small device arrays that the model's decode
    step adds to (what its layers count about the work they did: an
    expert layer's assignments); they ride in the donated step like the
    pools, and whoever wants them reads them when it chooses, never the
    engine's loop. Registered as a pytree so a whole serving
    decode step jits over it with pools and states donated."""

    def __init__(self, k_pages, v_pages, block_tables, context_lens,
                 page_size: int, num_heads: int, head_dim: int,
                 states: Sequence = (), conv_states: Sequence = (),
                 layer_kinds: Optional[Sequence[str]] = None,
                 num_kv_heads: Optional[int] = None,
                 counters: Optional[dict] = None,
                 window_k: Sequence = (), window_v: Sequence = (),
                 window: int = 0, draft_layers: int = 0):
        self.draft_layers = int(draft_layers)
        self.window_k = list(window_k)
        self.window_v = list(window_v)
        self.window = int(window)
        self.k_pages = list(k_pages)
        self.v_pages = list(v_pages)
        self.block_tables = block_tables
        self.context_lens = context_lens
        self.page_size = int(page_size)
        self.num_heads = int(num_heads)
        self.head_dim = int(head_dim)
        self.num_kv_heads = int(num_kv_heads or num_heads)
        self.counters = dict(counters or {})
        self.states = list(states)
        self.conv_states = list(conv_states)
        if layer_kinds is None:
            layer_kinds = (KV,) * len(self.k_pages)
        self.layer_kinds = tuple(layer_kinds)
        # layer index -> index into k_pages/v_pages or states/conv_states
        counts = {KV: 0, KV_WINDOW: 0, STATE: 0, NONE: 0, LATENT: 0}
        self._index = []
        for kind in self.layer_kinds:
            self._index.append(counts[kind])
            counts[kind] += 1
        if counts[LATENT] and (counts[KV] or self.v_pages):
            raise ValueError(
                f"layer_kinds {self.layer_kinds}: latent layers keep no V "
                f"pool and cannot share a cache with paged K/V layers")
        if counts[KV] + counts[LATENT] != len(self.k_pages) \
                or counts[STATE] != len(self.states) \
                or counts[KV_WINDOW] != len(self.window_k):
            raise ValueError(
                f"layer_kinds {self.layer_kinds} names "
                f"{counts[KV] + counts[LATENT]} paged, "
                f"{counts[KV_WINDOW]} window and {counts[STATE]} state "
                f"layers; the cache holds {len(self.k_pages)} pools, "
                f"{len(self.window_k)} rings and {len(self.states)} states")
        if self.window_k and (self.window < 1
                              or self.window % self.page_size):
            raise ValueError(
                f"a window of {self.window} tokens is no whole number of "
                f"pages of {self.page_size}")

    def index_of(self, layer: int) -> int:
        """Where model layer `layer` sits in the lists of its own kind."""
        return self._index[layer]

    @property
    def num_pages(self) -> int:
        return self.k_pages[0].shape[0]

    @property
    def pages_per_seq(self) -> int:
        return self.block_tables.shape[1]

    @property
    def max_batch(self) -> int:
        return self.block_tables.shape[0]

    @property
    def has_state(self) -> bool:
        return bool(self.states)

    @property
    def has_window(self) -> bool:
        return bool(self.window_k)

    @property
    def latent_layers(self) -> int:
        return self.layer_kinds.count(LATENT)

    @property
    def window_pages(self) -> int:
        """Pages of one slot's ring."""
        return self.window // self.page_size

    def pool_bytes(self) -> int:
        """Device bytes of K and V: every paged layer's pools (a latent
        layer's one), and every window layer's rings."""
        return self.window_bytes() + sum(
            _nbytes(p) for p in self.k_pages + self.v_pages)

    def window_bytes(self) -> int:
        """Device bytes of the window layers' rings, all slots: a fixed
        cost of `max_batch`, as the states are; no page of it is the
        allocator's to give."""
        return sum(_nbytes(p) for p in self.window_k + self.window_v)

    def state_bytes(self) -> int:
        """Device bytes of the recurrent and convolution states, all
        slots."""
        return sum(_nbytes(s) for s in self.states + self.conv_states)

    def describe(self) -> dict:
        """What the cache holds, by kind: for status pages and
        memory accounting."""
        slots = max(1, self.max_batch)
        return {
            "layer_kinds": list(self.layer_kinds),
            "kv_layers": self.layer_kinds.count(KV),
            "latent_layers": self.latent_layers,
            "latent_width": (self.k_pages[0].shape[-1]
                             if self.latent_layers else None),
            "draft_layers": self.draft_layers,
            "window_layers": len(self.window_k),
            "window": self.window,
            "window_bytes": self.window_bytes(),
            "state_layers": len(self.states),
            "cacheless_layers": self.layer_kinds.count(NONE),
            "num_heads": self.num_heads,
            "num_kv_heads": self.num_kv_heads,
            "num_pages": self.num_pages,
            "page_size": self.page_size,
            # of the paged layers: what one page of the allocator costs
            "page_bytes": ((self.pool_bytes() - self.window_bytes())
                           // max(1, self.num_pages)),
            "pool_bytes": self.pool_bytes(),
            "slots": self.max_batch,
            "state_shape": (list(self.states[0].shape[1:])
                            if self.states else None),
            "conv_state_shape": (list(self.conv_states[0].shape[1:])
                                 if self.conv_states else None),
            "state_dtype": str(self.states[0].dtype) if self.states else None,
            "state_bytes_per_slot": self.state_bytes() // slots,
            "state_bytes": self.state_bytes(),
        }

    def tree_flatten(self):
        return ((self.k_pages, self.v_pages, self.block_tables,
                 self.context_lens, self.states, self.conv_states,
                 self.counters, self.window_k, self.window_v),
                (self.page_size, self.num_heads, self.head_dim,
                 self.layer_kinds, self.num_kv_heads, self.window,
                 self.draft_layers))

    @classmethod
    def tree_unflatten(cls, aux, children):
        k, v, bt, cl, states, conv, counters, window_k, window_v = children
        (page_size, num_heads, head_dim, kinds, num_kv_heads, window,
         draft_layers) = aux
        return cls(k, v, bt, cl, page_size, num_heads, head_dim,
                   states=states, conv_states=conv, layer_kinds=kinds,
                   num_kv_heads=num_kv_heads, counters=counters,
                   window_k=window_k, window_v=window_v, window=window,
                   draft_layers=draft_layers)


def _register_cache_pytree():
    import jax
    jax.tree_util.register_pytree_node(
        PagedKVCache, PagedKVCache.tree_flatten,
        PagedKVCache.tree_unflatten)


_register_cache_pytree()
