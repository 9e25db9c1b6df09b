"""Device-side hot-row embedding cache for heterogeneous-PS training.

Reference analogue: the heter-PS GPU row cache
(`/root/reference/paddle/fluid/framework/fleet/heter_ps/hashtable.h` — hot
feasigns live in accelerator memory, the CPU PS is the backing store). On
TPU the cache is a fixed-capacity ``[capacity, dim]`` device buffer per
table plus a host-side LRU index keyed by feasign:

* **hit** — the row is gathered ON-CHIP out of the cache buffer; no pull
  RPC, no host→device transfer for that row.
* **miss** — only the missing rows ride the pull RPC; a free (or LRU-evicted)
  slot is assigned and the row becomes device-resident for later steps.
* **gradients** — cached rows are updated locally on-chip
  (``w -= lr * g``, the table's SGD rule) and the RAW gradient accumulates
  into a per-slot ``gsum`` buffer. The PS only sees the row again on
  **eviction or flush**, when the accumulated gradient is pushed in one
  write-back RPC and the server applies ``w -= lr * Σg`` — bitwise-close to
  having pushed every step, because SGD is linear in the gradient. This is
  why the cache REQUIRES ``optimizer="sgd"`` (or the additive ``"sum"``)
  tables: adagrad/adam server state is a function of the push schedule, so
  deferral would change numerics. Non-SGD tables are skipped with a warning.

Concurrency contract (enforced by `HeterPSTrainStep`): ``plan()`` runs on
the prefetch thread but is PURE with respect to the index — it computes the
hit/miss split and slot assignments against the last committed state and
returns them in a `CachePlan`. The owning trainer calls ``commit(plan)`` on
the main thread right before dispatching the step that consumes the plan;
an abandoned prefetch (mode flip, flush with a queued bundle) is simply
never committed, so the index can't drift from the device buffers. All
device-array mutation (``combine_rows`` / ``apply_step`` / write-back
gathers) happens on the main thread, ordered by jax's functional semantics.

Cache events land in the PR-2 metrics registry:
``embed_cache_events_total{event=hit|miss|eviction|writeback,table=}``.
"""
from __future__ import annotations

import functools
import warnings
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from ...profiler import metrics as _metrics_mod

_REG = _metrics_mod.default_registry()
_M_EVENTS = _REG.counter(
    "embed_cache_events_total",
    "hot-row embedding cache events by event kind and table "
    "(hit/miss/eviction/writeback are per ROW, overflow counts rows that "
    "found no slot)")

# optimizers whose server-side update is linear in the pushed gradient, so
# deferring the push to eviction/flush is numerically equivalent. The local
# on-chip rule must MATCH the server rule: plain SGD applies w -= lr*g;
# "sum"/"geo" tables (server OPT_SUM, ps.cc: w += g, lr ignored) are the
# lr = -1 special case of the same rule, wired up in build_caches.
CACHEABLE_OPTIMIZERS = ("sgd", "sum", "geo")


@dataclass
class CachePlan:
    """One batch's hit/miss decisions, computed against committed state.

    All index arrays are sized to the padded unique bucket ``U``; positions
    past ``n_unique``, and overflow positions that found no slot, carry the
    ``capacity`` sentinel in ``slot_idx`` so device scatters drop them.
    """
    uniq: np.ndarray                 # [n] uint64 unique feasigns
    slot_idx: np.ndarray             # [U] int32, sentinel=capacity
    hit_mask: np.ndarray             # [U] bool
    miss_idx: np.ndarray             # [U] int32 into the miss-row bucket
    miss_keys: np.ndarray            # [m] uint64 keys to pull from the PS
    hits: List[int] = field(default_factory=list)        # keys to LRU-touch
    inserts: List[tuple] = field(default_factory=list)   # (key, slot)
    evicts: List[tuple] = field(default_factory=list)    # (key, slot)
    overflow: List[int] = field(default_factory=list)    # positions w/o slot

    @property
    def n_unique(self) -> int:
        return int(self.uniq.size)


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _apply_step(values, gsum, slot_idx, hit_mask, rows, grows, lr):
    """Post-step cache update: local SGD on the served rows + gradient
    accumulation. Sentinel slots (padded tail / overflow) drop out of the
    scatters; a miss slot's stale gsum (from the evicted previous tenant,
    already written back) is reset rather than inherited."""
    upd = rows - lr * grows
    new_values = values.at[slot_idx].set(upd, mode="drop")
    prev = jnp.where(hit_mask[:, None],
                     gsum.at[slot_idx].get(mode="fill", fill_value=0.0),
                     0.0)
    new_gsum = gsum.at[slot_idx].set(prev + grows, mode="drop")
    return new_values, new_gsum


@jax.jit
def _combine_rows(values, slot_idx, hit_mask, miss_rows, miss_idx):
    """Serve the padded unique bucket: cache rows for hits (on-chip gather),
    freshly-pulled rows for misses. Padded-tail positions read junk that the
    inverse never addresses."""
    cached = values.at[slot_idx].get(mode="fill", fill_value=0.0)
    pulled = jnp.take(miss_rows, miss_idx, axis=0)
    return jnp.where(hit_mask[:, None], cached, pulled)


# multi-table variants: ONE dispatch per step for every cached table's
# gather (and one for every apply) instead of one per table — dispatch
# overhead is per call
@jax.jit
def _combine_many(values_t, slot_t, hit_t, miss_t, midx_t):
    return tuple(
        jnp.where(h[:, None], v.at[s].get(mode="fill", fill_value=0.0),
                  jnp.take(m, mi, axis=0))
        for v, s, h, m, mi in zip(values_t, slot_t, hit_t, miss_t, midx_t))


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _apply_many(values_t, gsum_t, slot_t, hit_t, rows_t, grows_t, lr_t):
    new_v, new_g = [], []
    for v, g, s, h, r, gr, lr in zip(values_t, gsum_t, slot_t, hit_t,
                                     rows_t, grows_t, lr_t):
        upd = r - lr * gr
        new_v.append(v.at[s].set(upd, mode="drop"))
        prev = jnp.where(h[:, None],
                         g.at[s].get(mode="fill", fill_value=0.0), 0.0)
        new_g.append(g.at[s].set(prev + gr, mode="drop"))
    return tuple(new_v), tuple(new_g)


def combine_batch(caches, plans_dev, miss_rows_t):
    """Serve every cached table's padded bucket in ONE jit dispatch.
    `plans_dev[i]` is (slot_idx, hit_mask, miss_idx) on device."""
    values_t = tuple(c.values for c in caches)
    slot_t = tuple(p[0] for p in plans_dev)
    hit_t = tuple(p[1] for p in plans_dev)
    midx_t = tuple(p[2] for p in plans_dev)
    return _combine_many(values_t, slot_t, hit_t, tuple(miss_rows_t), midx_t)


def apply_batch(caches, plans_dev, rows_t, grows_t):
    """Consume every cached table's row gradients in ONE jit dispatch,
    updating each cache's device buffers in place (donated)."""
    values_t = tuple(c.values for c in caches)
    gsum_t = tuple(c.gsum for c in caches)
    slot_t = tuple(p[0] for p in plans_dev)
    hit_t = tuple(p[1] for p in plans_dev)
    lr_t = tuple(c.lr for c in caches)
    new_v, new_g = _apply_many(values_t, gsum_t, slot_t, hit_t,
                               tuple(rows_t), tuple(grows_t), lr_t)
    for c, v, g in zip(caches, new_v, new_g):
        c.values, c.gsum = v, g


class HotRowCache:
    """Per-table device-resident LRU row cache (see module docstring)."""

    def __init__(self, table_id: int, dim: int, capacity: int,
                 learning_rate: float, client, device=None):
        if capacity < 1:
            raise ValueError("cache capacity must be >= 1")
        self.table_id = int(table_id)
        self.dim = int(dim)
        self.capacity = int(capacity)
        self.lr = jnp.asarray(learning_rate, jnp.float32)
        self.client = client
        put = (lambda x: jax.device_put(x, device)) if device is not None \
            else jax.device_put
        self.values = put(jnp.zeros((self.capacity, self.dim), jnp.float32))
        self.gsum = put(jnp.zeros((self.capacity, self.dim), jnp.float32))
        # feasign -> slot, in LRU order (front = coldest)
        self._slots: "OrderedDict[int, int]" = OrderedDict()
        self._free: List[int] = list(range(self.capacity - 1, -1, -1))
        self.stats = {"hit": 0, "miss": 0, "eviction": 0, "writeback": 0,
                      "overflow": 0, "invalidation": 0}
        # server-side lifecycle hook: PSClient.shrink() must flush +
        # invalidate this cache or evicted rows would be served stale
        reg = getattr(client, "register_row_cache", None)
        if callable(reg):
            reg(self)

    # ------------------------------ planning -------------------------------
    def plan(self, uniq: np.ndarray, bucket: int) -> CachePlan:
        """Pure hit/miss split + slot assignment for one batch's unique keys
        (no index mutation — see the concurrency contract above)."""
        n = uniq.size
        slot_idx = np.full(bucket, self.capacity, np.int32)
        hit_mask = np.zeros(bucket, bool)
        miss_idx = np.zeros(bucket, np.int32)
        miss_keys: List[int] = []
        plan = CachePlan(uniq=uniq, slot_idx=slot_idx, hit_mask=hit_mask,
                         miss_idx=miss_idx, miss_keys=uniq[:0])
        batch_keys = set(int(k) for k in uniq)
        free_cursor = len(self._free)
        # lazily walk LRU victims, skipping rows this batch itself uses and
        # rows already claimed by an earlier miss in this same plan. A
        # GENERATOR, not a list: an all-hit steady-state batch must not pay
        # an O(cache size) scan per step (it never draws a victim)
        victims = ((k, s) for k, s in self._slots.items()
                   if k not in batch_keys)
        for i in range(n):
            k = int(uniq[i])
            slot = self._slots.get(k)
            if slot is not None:
                hit_mask[i] = True
                slot_idx[i] = slot
                plan.hits.append(k)
                continue
            miss_idx[i] = len(miss_keys)
            miss_keys.append(k)
            if free_cursor > 0:
                free_cursor -= 1
                slot = self._free[free_cursor]
            else:
                nxt = next(victims, None)
                if nxt is None:
                    plan.overflow.append(i)
                    continue
                vk, slot = nxt
                plan.evicts.append((vk, slot))
            slot_idx[i] = slot
            plan.inserts.append((k, slot))
        plan.miss_keys = np.asarray(miss_keys, np.uint64)
        return plan

    def commit(self, plan: CachePlan):
        """Apply a plan's index mutations (main thread, at dispatch time)."""
        for k in plan.hits:
            self._slots.move_to_end(k)
        for vk, _slot in plan.evicts:
            del self._slots[vk]
        n_ins = len(plan.inserts)
        if n_ins:
            del self._free[len(self._free) - (n_ins - len(plan.evicts)):]
        for k, slot in plan.inserts:
            self._slots[k] = slot
        self.stats["hit"] += len(plan.hits)
        self.stats["miss"] += len(plan.inserts) + len(plan.overflow)
        self.stats["eviction"] += len(plan.evicts)
        self.stats["overflow"] += len(plan.overflow)
        if _metrics_mod.enabled():
            t = str(self.table_id)
            if plan.hits:
                _M_EVENTS.inc(len(plan.hits), event="hit", table=t)
            misses = len(plan.inserts) + len(plan.overflow)
            if misses:
                _M_EVENTS.inc(misses, event="miss", table=t)
            if plan.evicts:
                _M_EVENTS.inc(len(plan.evicts), event="eviction", table=t)
            if plan.overflow:
                _M_EVENTS.inc(len(plan.overflow), event="overflow", table=t)

    # --------------------------- device ops --------------------------------
    def combine(self, plan_dev, miss_rows):
        """Device gather serving the padded bucket (main thread)."""
        slot_idx, hit_mask, miss_idx = plan_dev
        return _combine_rows(self.values, slot_idx, hit_mask, miss_rows,
                             miss_idx)

    def apply(self, plan_dev, rows, grows):
        """Consume the step's row gradients into the cache buffers."""
        slot_idx, hit_mask, _ = plan_dev
        self.values, self.gsum = _apply_step(
            self.values, self.gsum, slot_idx, hit_mask, rows, grows, self.lr)

    def writeback_rows(self, slots_dev):
        """Gather pending gradients for evicted slots. MUST be dispatched
        before this step's `apply` so it reads the pre-overwrite gsum."""
        return jnp.take(self.gsum, slots_dev, axis=0)

    # ------------------------------ flush ----------------------------------
    def flush(self, push_fn=None) -> int:
        """Push every slot's accumulated gradient to the PS and zero the
        accumulator; cached VALUES stay resident (server now agrees with
        them). Returns rows written back."""
        if not self._slots:
            return 0
        keys = np.fromiter(self._slots.keys(), np.uint64, len(self._slots))
        slots = np.fromiter(self._slots.values(), np.int64, len(self._slots))
        g = np.asarray(jax.device_get(jnp.take(self.gsum, slots, axis=0)),
                       np.float32)
        nz = np.any(g != 0.0, axis=1)
        n = int(nz.sum())
        if n:
            push = push_fn or (lambda k, v: self.client.push_sparse(
                self.table_id, k, v))
            push(keys[nz], g[nz])
            self.gsum = jnp.zeros_like(self.gsum)
            self.stats["writeback"] += n
            if _metrics_mod.enabled():
                _M_EVENTS.inc(n, event="writeback",
                              table=str(self.table_id))
        return n

    def invalidate(self) -> int:
        """Drop EVERY cached row (index + gradient accumulators). For
        server-side shrink/eviction: the server just changed or removed
        rows out from under the cache, so any device-resident copy may be
        stale — the next batch misses and pulls fresh. Call `flush()`
        FIRST when gradients may be pending (PSClient.shrink does): the
        accumulators are zeroed here, and an un-flushed gradient would be
        silently dropped. Returns the number of rows invalidated."""
        n = len(self._slots)
        self._slots.clear()
        self._free = list(range(self.capacity - 1, -1, -1))
        self.gsum = jnp.zeros_like(self.gsum)
        self.stats["invalidation"] += n
        if _metrics_mod.enabled() and n:
            _M_EVENTS.inc(n, event="invalidation", table=str(self.table_id))
        return n

    def note_writeback(self, n: int):
        """Record an eviction write-back issued by the owning trainer."""
        self.stats["writeback"] += n
        if _metrics_mod.enabled() and n:
            _M_EVENTS.inc(n, event="writeback", table=str(self.table_id))

    def __len__(self) -> int:
        return len(self._slots)

    def hit_rate(self) -> float:
        tot = self.stats["hit"] + self.stats["miss"]
        return self.stats["hit"] / tot if tot else 0.0


def flush_all(caches) -> int:
    """Write back every cache's pending gradients with ONE batched
    device→host transfer (a per-table device_get costs a full host↔device
    round trip each). Returns total rows written back."""
    caches = [c for c in caches if len(c)]
    if not caches:
        return 0
    keys_l, slots_l = [], []
    for c in caches:
        keys_l.append(np.fromiter(c._slots.keys(), np.uint64, len(c._slots)))
        slots_l.append(np.fromiter(c._slots.values(), np.int64,
                                   len(c._slots)))
    gathered = jax.device_get(tuple(
        jnp.take(c.gsum, s, axis=0) for c, s in zip(caches, slots_l)))
    total = 0
    for c, keys, g in zip(caches, keys_l, gathered):
        g = np.asarray(g, np.float32)
        nz = np.any(g != 0.0, axis=1)
        n = int(nz.sum())
        if n:
            c.client.push_sparse(c.table_id, keys[nz], g[nz])
            c.gsum = jnp.zeros_like(c.gsum)
            c.stats["writeback"] += n
            if _metrics_mod.enabled():
                _M_EVENTS.inc(n, event="writeback", table=str(c.table_id))
        total += n
    return total


def build_caches(embeddings, capacity: int, device=None
                 ) -> Dict[int, HotRowCache]:
    """One cache per DISTINCT cacheable table among `embeddings`; non-SGD
    tables are skipped with a warning (see CACHEABLE_OPTIMIZERS)."""
    caches: Dict[int, HotRowCache] = {}
    for e in embeddings:
        cfg = e._table_cfg
        if cfg.table_id in caches:
            continue
        if cfg.optimizer not in CACHEABLE_OPTIMIZERS:
            warnings.warn(
                f"hot-row cache skipped for table {cfg.table_id}: server "
                f"optimizer {cfg.optimizer!r} is not linear in the gradient "
                f"(cacheable: {CACHEABLE_OPTIMIZERS}); rows of this table "
                "keep the per-step pull/push path")
            continue
        # sum/geo tables: the server applies w += g (lr ignored), which is
        # the lr = -1 case of the SGD rule the cache computes on-chip —
        # using cfg.learning_rate here would silently change numerics
        lr = -1.0 if cfg.optimizer in ("sum", "geo") else cfg.learning_rate
        caches[cfg.table_id] = HotRowCache(
            cfg.table_id, cfg.dim, capacity, lr, e.client, device=device)
    return caches


__all__ = ["HotRowCache", "CachePlan", "build_caches", "combine_batch",
           "apply_batch", "flush_all", "CACHEABLE_OPTIMIZERS"]
