"""Continuous-batching autoregressive serving on the inference path.

The L11 inference stack (Predictor -> StableHLO, int8 PTQ, hardened C
API) stops at single-request ``run()``. This module is the daemon shape
that makes "millions of users" literal for GPT-class decode: an
Orca-style (Yu et al., 2022) continuous-batching loop over the paged
KV cache (ops/pallas/paged_attention.py) —

* a **request queue** feeds a FIXED decode batch of ``max_batch`` slots;
  admission happens per iteration (a finished sequence's slot is refilled
  on the very next step, never at epoch/batch boundaries);
* **prefill is shape-bucketed**: a prompt pads up to the smallest
  bucket that holds it, so the whole serving life of the engine compiles
  one prefill executable per bucket — the retrace watchdog stays quiet
  and the PR-8 persistent compile cache
  (``PADDLE_TPU_COMPILE_CACHE_DIR``) makes cold-start cheap. The default
  buckets are a LADDER of `max_len` alone (`_prefill_ladder`): the powers
  of two from 128 (from 256 once `max_len` reaches 2048) and, from 512
  up, the step halfway to the next, then `max_len` itself: 256, 512,
  768, 1024, 1536, 2048 for a `max_len` of 2048. Every layer computes
  the whole bucket, so the padding is paid in device time: a step
  between the powers of two bounds it by a half where doubling bounds it
  by the whole prompt. Why no finer: every bucket is a program to trace,
  compile, load and warm, 2-5 s of a WARM start apiece once it holds the
  kernels and their compile checks (the programs under 64 rows that the
  ladder replaced held none and cost a quarter of that), so the ladder
  keeps the count of such programs at what powers of two from 16 had;
  under 128 rows a prefill costs its weights' bytes whatever its rows,
  and a half step under 512 saves at most 128 rows a prompt (PERF.md
  section 6, PR 38). Every step is a multiple of 64 (the
  linear-attention scan's chunk); `prefill_buckets=` replaces the
  ladder;
* the **decode iteration is ONE donated, jitted executable per lane
  bucket**: all transformer layers, the paged-attention kernel, the
  K/V page append, the in-graph sampling draw
  (inference/sampling.py — temperature / top-k / top-p with per-request
  seeds; ``temperature == 0`` lanes are bit-exact argmax) and the
  context-length bump fuse into a single dispatch with the page pools
  DONATED (the multi-GB pool updates in place per token). Active slots
  gather into ``W`` lanes (``W`` = smallest power-of-two bucket
  covering the active count), so a mostly-idle batch runs a narrow
  executable;
  ``decode_mode="eager"`` keeps the per-op dispatch path alive as the
  measured A/B baseline (``path`` label on the latency histograms);
* **pages, not slabs**: each sequence owns block-table pages from a
  refcounted :class:`PageAllocator`. Requests sharing a prompt prefix
  map their block tables at the SAME physical pages (registered and
  looked up at admission in the engine's prefix cache) — a shared page
  is copied only on first divergent write (copy-on-write fork, the
  vLLM trick that multiplies effective pool capacity under a common
  system prompt). Pages free on EOS/length, and when the pool runs dry
  the youngest request is PREEMPTED (pages freed, request requeued with
  its generated prefix — recompute-style) instead of the engine
  deadlocking;
* **serving metric families** land on the PR-6 metrics plane:
  ``serving_queue_depth``, ``serving_batch_occupancy``,
  ``serving_ttft_seconds``, ``serving_tpot_seconds``,
  ``serving_goodput_tokens_total`` (latency histograms split by the
  decode ``path`` — fused vs eager) — plus one ``serving_admission`` /
  ``serving_eviction`` structured event per request lifecycle edge
  (rendered by ``tools/obs_tail.py --serving``).

The engine is also the actuation surface of the self-healing serving
plane (inference/hotswap.py, the controller's serving policies):

* **zero-downtime weight hot-swap** — `request_swap` stages a validated
  replacement weight set; it rebinds atomically BETWEEN decode
  iterations (`serving_swap_pause_seconds` times the pause), in-flight
  requests keep their pages and continue on the new weights, and the
  outgoing weights are retained for `rollback_weights`;
* **watchdog restart** — `restart()` joins the decode loop, requeues
  every in-flight request through the existing preemption path (trace
  ids preserved), rebuilds the KV plane, and relaunches the loop;
* **graceful degradation** — `shrink_pool` parks free KV pages out of
  circulation and `suspend` refuses admission with
  :class:`EngineSuspended` (the /generate 503 + Retry-After surface)
  while in-flight work drains, so memory pressure never OOMs the chip.
"""
from __future__ import annotations

import itertools
import math
import threading
import time
import weakref
from collections import deque
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..fault import site as _fault_site
from ..framework import tape as tape_mod
from ..framework.tensor import Tensor
from ..profiler import events as _events
from ..profiler import metrics as _metrics
from ..profiler import reqtrace as _reqtrace
from ..profiler import slo as _slo
from ..profiler.utils import SPAN_PREFIX, RecordEvent
from ..utils.envparse import env_float, env_int
from .sampling import SamplingParams, sample_logits

__all__ = ["Request", "PageAllocator", "SamplingParams", "ServingEngine",
           "EngineSuspended", "current_engine", "live_engines"]


def _span(name: str, **args) -> RecordEvent:
    """A span of the engine, `pt.engine.<name>`: in any profiler trace
    being taken (and the host recorder while that is on). The names and
    arguments are an interface: `tests/test_program_spans.py` pins them,
    the benchmark's per-layer metrics read them (PERF.md section 3)."""
    return RecordEvent(SPAN_PREFIX + "engine." + name, **args)


#: live engines, newest last — how the ObservabilityServer's /requests,
#: /slo and /generate endpoints find the engine without plumbing a
#: handle through the server constructor
_engine_refs: List["weakref.ref[ServingEngine]"] = []
_engine_lock = threading.Lock()


def current_engine(name: Optional[str] = None) -> Optional["ServingEngine"]:
    """Most recently constructed live engine (or by model name)."""
    with _engine_lock:
        for ref in reversed(_engine_refs):
            eng = ref()
            if eng is None or eng._closed:
                continue
            if name is None or eng.name == name:
                return eng
    return None


def live_engines() -> List["ServingEngine"]:
    """Every live (non-closed) engine, oldest first — the controller's
    serving-policy scan and the /healthz serving-liveness walk."""
    out: List["ServingEngine"] = []
    with _engine_lock:
        for ref in _engine_refs:
            eng = ref()
            if eng is not None and not eng._closed:
                out.append(eng)
    return out


class EngineSuspended(RuntimeError):
    """Admission refused: the engine is suspended (memory-pressure
    degradation). Carries ``retry_after_s`` so the /generate endpoint
    can answer 503 with a Retry-After header instead of a bare error."""

    def __init__(self, model: str, reason: str, retry_after_s: float):
        super().__init__(
            f"engine {model!r} suspended ({reason}); "
            f"retry after {retry_after_s:g}s")
        self.model = model
        self.reason = reason
        self.retry_after_s = float(retry_after_s)


_REG = _metrics.default_registry()
_M_QUEUE = _REG.gauge(
    "serving_queue_depth",
    "requests queued waiting for a decode slot, by model")
_M_OCC = _REG.gauge(
    "serving_batch_occupancy",
    "active sequences in the fixed continuous-batching decode batch, "
    "by model")
_M_TTFT = _REG.histogram(
    "serving_ttft_seconds",
    "time to first token: request submit -> first generated token, "
    "by model and decode path (fused|eager)")
_M_TPOT = _REG.histogram(
    "serving_tpot_seconds",
    "time per output token after the first, observed once per finished "
    "request, by model and decode path (fused|eager)")
_M_GOODPUT = _REG.counter(
    "serving_goodput_tokens_total",
    "generated tokens delivered to finished or running requests, by model")
_M_SWAP_TOTAL = _REG.counter(
    "serving_swap_total",
    "weight hot-swap attempts by model and outcome "
    "(applied|rejected|rolled_back|failed)")
_M_SWAP_PAUSE = _REG.histogram(
    "serving_swap_pause_seconds",
    "decode-loop pause while a staged weight swap rebinds between "
    "iterations, by model")
_M_SWAP_STEP = _REG.gauge(
    "serving_swap_step",
    "checkpoint step of the live serving weights, by model "
    "(-1 until a hot-swap lands)")
_M_RESTARTS = _REG.counter(
    "serving_restart_total",
    "watchdog engine restarts by model and reason; in-flight requests "
    "requeue through the preemption path")
_M_SUSPENDED = _REG.gauge(
    "serving_suspended",
    "1 while admission is suspended under memory pressure, by model")
# disaggregated prefill/decode pipeline (inference/disagg.py): the
# prefill->decode KV handoff plane and per-stage occupancy
_M_HANDOFF_DEPTH = _REG.gauge(
    "serving_handoff_depth",
    "prefilled KV payloads queued for decode-side admission "
    "(disaggregated prefill/decode pipeline), by model")
_M_HANDOFF_WAIT = _REG.histogram(
    "serving_handoff_wait_seconds",
    "prefill->decode handoff latency: KV payload produced by a prefill "
    "worker -> admitted into the decode batch, by model")
_M_HANDOFF_BYTES = _REG.counter(
    "serving_handoff_bytes_total",
    "KV page payload bytes moved across the prefill->decode handoff, "
    "by model")
_M_STAGE_OCC = _REG.gauge(
    "serving_stage_occupancy",
    "busy units per pipeline stage (prefill: busy prefill workers; "
    "decode: active decode slots), by model and stage")


class PageAllocator:
    """Refcounted free-list allocator over the KV page pool. Page 0 is
    the NULL page (idle slots' block tables point at it; masked decode
    writes land there) and is never handed out.

    ``alloc`` hands out pages at refcount 1; ``fork`` increments the
    refcount of pages a second request maps at the same physical
    location (shared-prefix admission); ``free`` decrements, and a page
    returns to the free list only when its LAST holder releases it —
    preempting one sharer can never free a page another request still
    references. ``on_release(page)`` fires exactly once per page, at
    that last release (the engine evicts its prefix-cache entries
    there)."""

    def __init__(self, num_pages: int, on_release=None):
        self.num_pages = int(num_pages)
        self._free: List[int] = list(range(self.num_pages - 1, 0, -1))
        self._refs: Dict[int, int] = {}
        self._reserved: List[int] = []
        self._on_release = on_release

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def shared_page_count(self) -> int:
        """Pages currently held by more than one request (CoW-shared)."""
        return sum(1 for c in self._refs.values() if c > 1)

    def alloc(self, n: int) -> Optional[List[int]]:
        """n page ids at refcount 1, or None when the pool can't cover
        the request (caller preempts or queues — a partial grab is never
        left dangling)."""
        if n > len(self._free):
            return None
        out = [self._free.pop() for _ in range(n)]
        for p in out:
            self._refs[p] = 1
        return out

    def fork(self, pages: Sequence[int]):
        """Share already-allocated pages with one more holder (copy-on-
        write mapping: the new holder's block table points at the same
        physical pages; the first divergent write copies)."""
        for p in pages:
            if p:
                self._refs[p] = self._refs.get(p, 0) + 1

    def refcount(self, page: int) -> int:
        return self._refs.get(int(page), 0)

    def is_shared(self, page: int) -> bool:
        return self.refcount(page) > 1

    def outstanding(self) -> Dict[int, int]:
        """{page: refcount} for every live page — the no-leak audit
        surface (empty once every request has finished)."""
        return dict(self._refs)

    def free(self, pages: Sequence[int]):
        """Release one holder's reference on each page; a page recycles
        to the free list only at refcount zero."""
        for p in pages:
            if not p:  # the null page is not pool-managed
                continue
            p = int(p)
            refs = self._refs.get(p, 1) - 1
            if refs > 0:
                self._refs[p] = refs
                continue
            self._refs.pop(p, None)
            self._free.append(p)
            if self._on_release is not None:
                self._on_release(p)

    @property
    def reserved_pages(self) -> int:
        return len(self._reserved)

    def reserve(self, n: int) -> int:
        """Park up to `n` FREE pages out of circulation (memory-pressure
        degradation: a reserved page cannot be allocated until released).
        Live pages are never touched. Returns the count reserved."""
        take = min(max(0, int(n)), len(self._free))
        for _ in range(take):
            self._reserved.append(self._free.pop())
        return take

    def release_reserved(self, n: Optional[int] = None) -> int:
        """Return reserved pages to the free list (all by default)."""
        take = len(self._reserved) if n is None \
            else min(max(0, int(n)), len(self._reserved))
        for _ in range(take):
            self._free.append(self._reserved.pop())
        return take


class _PrefixCache:
    """Token-chain -> physical-page registry for shared-prefix admission.

    Registered at admission: every page-aligned prefix of an admitted
    request's tokens maps to the page holding its last ``page_size``
    tokens, and the exact full token list additionally maps to the
    partial tail page (if any). Lookup walks the longest chain of full
    pages matching a new prompt's prefix; the partial tail joins ONLY on
    an exact whole-prompt match (the parallel-sampling case — same
    prompt, different seeds — where the first divergent decode write
    triggers the copy-on-write fork).

    Entries never hold refcounts themselves: a page is only shareable
    while some live request holds it, and the allocator's release hook
    (`drop_page`) evicts its entries the moment the last holder frees
    it — the registry can never hand out a recycled page."""

    def __init__(self, page_size: int, lookahead: int = 0):
        self.page_size = int(page_size)
        # tokens PAST a page that its K/V depends on: a drafting model's
        # MTP row i is made from the token at i + 1, so its page is the
        # same only where that token is too (and a partial tail, whose
        # last row depends on a token not sampled yet, is never shared)
        self.lookahead = int(lookahead)
        self._full: Dict[Tuple[int, ...], int] = {}
        self._partial: Dict[Tuple[int, ...], int] = {}
        self._by_page: Dict[int, List[Tuple[str, Tuple[int, ...]]]] = {}

    def __len__(self):
        return len(self._full) + len(self._partial)

    def _put(self, kind: str, key: Tuple[int, ...], page: int):
        d = self._full if kind == "full" else self._partial
        if key in d:
            return
        d[key] = page
        self._by_page.setdefault(page, []).append((kind, key))

    def register(self, tokens: Sequence[int], pages: Sequence[int]):
        ps, ahead = self.page_size, self.lookahead
        tokens = tuple(int(t) for t in tokens)
        for i in range((len(tokens) - ahead) // ps):
            self._put("full", tokens[:(i + 1) * ps + ahead], pages[i])
        if len(tokens) % ps and not ahead:
            self._put("partial", tokens, pages[len(tokens) // ps])

    def lookup(self, tokens: Sequence[int]) -> Tuple[List[int], int]:
        """(shared_pages, shared_len): the longest registered chain
        covering a prefix of `tokens`. shared_len is page-aligned unless
        the exact-match partial tail joined (then == len(tokens))."""
        ps, ahead = self.page_size, self.lookahead
        tokens = tuple(int(t) for t in tokens)
        pages: List[int] = []
        n = 0
        for i in range((len(tokens) - ahead) // ps):
            page = self._full.get(tokens[:(i + 1) * ps + ahead])
            if page is None:
                break
            pages.append(page)
            n = (i + 1) * ps
        tail = len(tokens) % ps
        if tail and n == len(tokens) - tail:
            page = self._partial.get(tokens)
            if page is not None:
                pages.append(page)
                n = len(tokens)
        return pages, n

    def drop_page(self, page: int):
        for kind, key in self._by_page.pop(int(page), []):
            d = self._full if kind == "full" else self._partial
            if d.get(key) == page:
                del d[key]


class Request:
    """One generation request. Thread-safe result hand-off: `result()`
    blocks until the engine completes (or fails) the request."""

    _ids = itertools.count(1)

    def __init__(self, prompt: Sequence[int], max_new_tokens: int,
                 eos_id: int = -1,
                 sampling: Optional[SamplingParams] = None):
        self.rid = next(Request._ids)
        self.prompt = [int(t) for t in prompt]
        self.max_new_tokens = int(max_new_tokens)
        self.eos_id = int(eos_id)
        self.sampling = sampling or SamplingParams()
        # per-request RNG stream; the n-th token's key is
        # fold_in(PRNGKey(seed), n) — pure in (seed, n), so preemption +
        # recompute resumes the identical stream
        self.seed = (self.sampling.seed if self.sampling.seed is not None
                     else self.rid) & 0x7FFFFFFF
        self.generated: List[int] = []
        self.state = "queued"          # queued|running|done|failed
        self.finish_reason: Optional[str] = None
        self.error: Optional[str] = None
        self.submitted_ts = time.monotonic()
        self.admitted_ts: Optional[float] = None   # first admission only
        self.first_token_ts: Optional[float] = None
        self.done_ts: Optional[float] = None
        self.trace_id: Optional[int] = None        # reqtrace id (if on)
        self.preemptions = 0
        self.slot: Optional[int] = None
        self.pages: List[int] = []
        self.shared_tokens = 0         # prefix tokens served from shared pages
        # the MOST tokens dispatched for this request and not yet read
        # back: its prefill's one, and the engine keeps at most one decode
        # iteration in flight, which yields one token, or up to two of a
        # drafting model
        self.unread = 0
        # of a drafting model: (k, token) for each draft the engine read,
        # the model's guess at `generated[k]` made before that token was
        # sampled (the prefill's, then the standing draft after each
        # iteration); what a draft turned out to be changes no token
        self.drafts: List[Tuple[int, int]] = []
        self._done = threading.Event()

    # -- latency accounting ---------------------------------------------------
    @property
    def ttft_s(self) -> Optional[float]:
        if self.first_token_ts is None:
            return None
        return self.first_token_ts - self.submitted_ts

    @property
    def tpot_s(self) -> Optional[float]:
        """Per-output-token latency AFTER the first token (the streaming
        cadence a client sees); None until done or with <2 tokens."""
        if self.done_ts is None or self.first_token_ts is None \
                or len(self.generated) < 2:
            return None
        return (self.done_ts - self.first_token_ts) \
            / (len(self.generated) - 1)

    def result(self, timeout: Optional[float] = None) -> List[int]:
        """Generated token ids (eos included when hit). Raises on engine
        failure or timeout."""
        if not self._done.wait(timeout):
            raise TimeoutError(f"request {self.rid} not done")
        if self.state == "failed":
            raise RuntimeError(f"request {self.rid} failed: {self.error}")
        return list(self.generated)


def _pow2_buckets(lo: int, hi: int) -> List[int]:
    out, b = [], max(int(lo), 1)
    while b < hi:
        out.append(b)
        b <<= 1
    out.append(hi)
    return out


def _prefill_ladder(max_len: int) -> List[int]:
    """The default prefill buckets, a function of `max_len` alone: the
    powers of two from 128 (from 256 once `max_len` reaches 2048) and,
    from 512 up, 3/2 of each, then `max_len` itself. From 512 up no
    bucket is more than 1.5 times the one before it, and each is a
    multiple of 64 but `max_len`. 1024: 128, 256, 512, 768, 1024.
    2048: 256, 512, 768, 1024, 1536, 2048."""
    out, b = [], min(256 if max_len >= 2048 else 128, int(max_len))
    while b < max_len:
        out.append(b)
        if b >= 512 and 3 * b // 2 < max_len:
            out.append(3 * b // 2)
        b <<= 1
    out.append(int(max_len))
    return out


def _inject_pages_impl(k_pages, v_pages, k_payload, v_payload, page_ids):
    """Scatter a prefill worker's per-layer KV page payload into the
    decode pools (disaggregated handoff). The pools are DONATED — the
    multi-GB buffers update in place like the fused decode step.
    `page_ids` is padded to a power-of-two bucket with the null page 0;
    padding rows overwrite page 0, which by convention holds garbage —
    so the whole serving life compiles one executable per bucket."""
    k_out, v_out = [], []
    for kp, vp, kq, vq in zip(k_pages, v_pages, k_payload, v_payload):
        k_out.append(kp.at[page_ids].set(kq.astype(kp.dtype)))
        v_out.append(vp.at[page_ids].set(vq.astype(vp.dtype)))
    return k_out, v_out


class ServingEngine:
    """Continuous-batching decode engine over one model's decode cache.

    The decode protocol `model` implements (models/gpt.py,
    models/olmo_hybrid.py and models/nemotron_h.py do):

    * ``init_cache(max_batch, max_len, page_size=, num_pages=)`` returns a
      `models/decode_cache.PagedKVCache`: one pytree that DESCRIBES ITSELF
      per layer (`layer_kinds`, `describe()`): K/V page pools for the
      layers that attend over every past token (at the width of the K/V
      heads, which grouped heads make narrower than the query's), for
      each layer that carries a recurrence a fixed-size state per batch
      slot, nothing for a layer that carries nothing from token to token
      (an expert block that is a layer of its own), for each layer of
      latent attention ONE pool of latent rows under the same tables
      (kind `latent`: a row serves every head's key and value, and its
      pages are allocated, shared and copied as K/V pages are), for each
      layer that attends over a sliding window a RING of `window` tokens a batch
      slot (kind `kv_window`: K/V folded like the pools, never more
      whatever the context, at a table that is a function of the slot
      and is computed inside the programs: the engine stores none, sends
      none, allocates none of its pages), and `counters`, small
      device arrays the decode step adds to, which ride in the donated
      cache and are read by `device_counters()` alone. The engine
      allocates, shares, copies and injects pages of the pools that
      exist, counts every kind in `pool_bytes()` / `status()`, and never
      assumes one K/V pair per model layer, nor that every K/V layer
      shares the block table: admission, growth, copy-on-write and a
      prefix hit count and touch the PAGED layers' pages only; a ring
      is a fixed cost of `max_batch`, like a state. Its `block_tables` and
      `context_lens` are the ENGINE's: the source of truth is a pair of
      NumPy arrays on the host (`_block_tables`, `_context_lens`), every
      admission, page growth, copy-on-write repoint, release and
      hand-off writes those, and the cache's fields are copies the
      engine re-sends by one plain transfer right before a dispatch,
      and only if a row changed since the last (`stats["table_refreshes"]`).
      The programs keep the lengths current themselves (prefill sets
      its slot's, decode adds one for each active lane, the host does
      the same to its copy), so a length travels only after a hand-off.
      A released slot's row is zeroed on the host alone: no lane names
      an idle slot, and the next prefill overwrites its length;
    * ``forward_prefill(ids [1, bucket], cache, slot, length,
      write_start=)`` (`bucket`: the smallest of `prefill_buckets` that
      holds the prompt; by default `_prefill_ladder(max_len)`, so it
      may be 3/2 of a power of two as well as one, and is never under
      `min(128, max_len)`) computes the prompt whole, writes its K/V into the
      slot's pages from `write_start` on (a shared prefix's pages are
      already there) and OVERWRITES the slot's recurrent state and its
      window rings (whatever `write_start` is: the prompt is computed
      whole); positions at or past `length` are bucket padding and must
      not reach a state or a ring;
      returns (last real position's logits [1, V], cache);
    * ``forward_decode(tokens [W], cache, active [W], slot_map=[W])``
      is one token for each lane: lane i works on slot `slot_map[i]`; a
      padding lane carries the sentinel `max_batch`, gathers clamped and
      must have every write DROPPED; it reads the tables and lengths of
      the slots `slot_map` names and of no other; returns
      (logits [W, V], cache);
    * ``wte.weight``, the token embedding, whose dtype is the cache's
      default;
    * optionally ``set_tp_mesh(mesh, axis)`` / ``tp_mesh()``: a model
      without them is refused `mesh=` (tensor-parallel decode) with a
      ValueError naming the protocol, and a model with recurrent-state
      layers refuses it, as `inference/disagg.py` does, with
      `StateLayersUnsupported` naming the protocol that is missing (a
      model with sliding-window layers: `WindowLayersUnsupported`, one
      with latent-attention layers: `LatentCacheUnsupported`).

    Drive it either synchronously (`submit` then `run_until_idle`,
    tests/bench) or with the background thread (`start()`; `close()`
    joins it).

    `num_pages` below full backing turns the allocator into a real
    constraint: admission waits for pages and decode preempts when the
    pool runs dry. The default fully backs `max_batch` x `max_len`.

    `decode_mode`: "fused" (default) runs each decode iteration as ONE
    donated jitted executable per active-lane bucket — model layers,
    paged attention, K/V append, in-graph sampling and the length bump
    in a single dispatch. "eager" runs the identical math per-op
    (unjitted) — the measured baseline the `path` metric label and the
    bench's fused_vs_eager A/B compare against. Both modes produce
    bit-identical tokens.

    What `step()` launches and sends in steady state: one decode program
    an iteration and one prefill program an admission (`cow_copy_pages`
    when a shared page is written, the injection of a hand-off), and
    nothing else. The decode program's per-lane arguments travel as two
    packed arrays, the prefill's as ids and two small vectors, all NumPy
    handed to the call; with the table refresh that is at most 3
    transfers an iteration and 4 an admission (`stats["h2d_transfers"]`,
    `transfers` on the `pt.engine.upload` and `.prefill.dispatch` spans).

    One decode iteration may be IN FLIGHT behind the host. A step builds
    the lanes, dispatches iteration N, then reads and books iteration
    N-1 if that is still unread, and then reads N too only if one of N's
    tokens is a request's last by length (`_may_run_ahead`); else it
    returns with N unread, and the next step dispatches N+1 first. The
    device then never waits for the host's read and bookkeeping, and
    nothing is lost where a slot frees by length: that request is done
    when the step that dispatched its last token returns, so whoever
    takes the slot finds the device idle, as with every iteration read at
    once. What makes N+1 independent of the host's read: the last token
    sampled for each slot stays on the device (`_last_tokens`, int32
    [max_batch + 1], donated through the decode program and the prefill
    program like the cache), and a lane's token travels as -1, "the
    row's", while the program that samples it is unread (a hand-off's and
    a read iteration's token travel as they did). A PREFILL's token is
    unread too: an admission dispatches its prefill and reads nothing, a
    later admission of the step dispatches its own, and the step's decode
    iteration, dispatched next, feeds the new lanes from the row; only
    then are the prefills read, in the order of their launches, then
    iteration N-1. So the device runs prefills and decode back to back,
    and the host reads a first token while the iteration behind it runs.
    A prefill whose token is its request's last by length is read at once
    (`_may_run_ahead` again, the one predicate), so that its slot is free
    for the same admission loop. Positions, sampling step counters and
    page growth count tokens DISPATCHED (`len(generated) +
    Request.unread`, where `unread` counts an unread prefill's one token
    and the most an unread iteration may yield). The unread records hold
    the Request of each lane and prefill, not its slot. Everything that
    takes, frees or moves a slot outside that bookkeeping calls
    `_drain()` first, which reads the prefills, then the iteration: an
    admission with a free slot, a hand-off, preemption (a dry pool reads
    before it picks a victim), a weight swap, `restart`, `close`,
    `shrink_pool`, `audit`, `device_counters`; `pending()` stays true
    while anything is unread. Every device program `step()` launches,
    decode iterations and prefills in one series, carries a launch
    number: `seq` =
    `stats["launches"]` read before the call and bumped after it, on the
    `pt.engine.dispatch` / `.prefill.dispatch` span that made the call and
    on the `.fetch` / `.prefill.fetch` span that reads its tokens (it rides
    in the unread records), so a reader of a trace joins each
    call to its execution on the device, first in first out. Each number
    is read exactly once, after its dispatch span closed (an unread
    program that `close` or a failure drops is never read); page copies,
    injections and `eager` decode's per-op calls carry none.
    `.prefill.fetch` opens under `pt.engine.step`, after the `.dispatch`
    of the iteration behind it, with `behind` 1 where a later program was
    dispatched before the read (`stats["prefill_reads_behind"]`: over
    `prefills`, the share the mechanism engages); the reads made at once
    for a last token open under `pt.engine.admit`.
    `stats["read_wait_s"]` over `stats["step_wall_s"]` is the share of the
    loop spent inside the two reads: near 1 the device sets the pace.
    `stats["iterations"]` counts dispatches, `decode_tokens`
    tokens recorded; `page_groups_live` / `page_groups_walked` the page
    groups with a live token and the grid steps made by one paged layer's
    paged-attention walk, summed over dispatches (0 where no layer is
    paged); `ahead_iterations`
    dispatches made with the one before unread, `drained_for_length`
    iterations read in their own step for a last token, `discarded_tokens` the cost of the one completion
    the host cannot know ahead:

    * an END OF SEQUENCE sampled in N is seen when N is booked, with N+1
      already dispatched for the same lane. The request completes then
      (one step late), N+1's token for it is dropped at bookkeeping by the
      request's identity, never booked to the slot's next tenant, and
      N+1's writes are harmless: its K/V went to a page the request owned
      exclusively at that dispatch (`_ensure_capacity` forks and grows
      before every dispatch, by tokens dispatched) and its state to the
      request's own slot, both ahead, in the device's order, of any
      prefill, page copy or injection that reuses them.

    A DRAFTING model (`model.draft_tokens` = 1: a multi-token-prediction
    module of its own, `models/exaone_moe.py`; the model's configuration
    is the only switch) is stepped by the same loop, and an iteration
    yields ONE OR TWO tokens a lane. Each lane carries its last token
    t_n (sampled, not yet through the model) and a standing draft of
    t_{n+1}; the ONE decode program runs the model over both rows
    (`forward_verify`), samples t*_{n+1} and t*_{n+2} (keys by the token's
    index, as ever), accepts the draft where it EQUALS t*_{n+1}, so that
    the tokens are those of plain decoding whatever the sampler, runs the
    module over both rows for the next draft (`draft_decode`) and
    advances the lane's length by 1 or 2 on the device
    (`accept_drafts`). What changes in the loop: the program returns
    int32 [W, 4] (t*_{n+1}, t*_{n+2}, how many are new, the standing
    draft) and `_last_tokens` is [max_batch + 1, 3] (token, draft, how
    many tokens the request has generated: the next sample's index);
    `Request.unread` counts the MOST tokens unread (2 an iteration), so
    `_may_run_ahead` holds a request that could end by length at two
    tokens an iteration; the host's `_context_lens`, the
    page-walk counters, `stats["draft_tokens"]` / `["accepted_tokens"]`
    and `Request.drafts` are booked when the iteration is READ (between
    dispatch and read the host knows a context to within one); `_read`
    books up to two tokens in order and drops what follows an end of
    sequence or the budget (`discarded_tokens`); `_ensure_capacity` owns
    exclusively every page through the furthest row the undrained run
    may write; the prefill program also runs the module over the prompt
    (`draft_prefill`) and returns the first draft beside the first token,
    both written into the slot's row with the next sample's index;
    the prefix cache shares a page only where the token AFTER it matches
    too (the module's row i is made from token i + 1) and never a partial
    tail. The `pt.engine.bookkeep` span then carries `seq` and `tokens`
    (an annotation's arguments are fixed when it opens, and the fetch is
    what learns the count). `disagg` and TP decode refuse such a model by
    name (`DraftingUnsupported`).

    `share_prefix` (default True) admits requests whose prompt prefix
    is already resident (page-aligned prefix chains; exact-duplicate
    prompts additionally share the partial tail page) by FORKING the
    pages copy-on-write instead of recomputing + re-storing the KV."""

    def __init__(self, model, *, max_batch: int = 4, max_len: int = 256,
                 page_size: int = 16, num_pages: int = 0,
                 prefill_buckets: Optional[Sequence[int]] = None,
                 eos_id: int = -1, name: str = "gpt",
                 decode_mode: str = "fused", share_prefix: bool = True,
                 priority: int = 0, mem_budget_bytes: int = 0,
                 mesh=None, tp_axis: str = "tp"):
        import jax

        if decode_mode not in ("fused", "eager"):
            raise ValueError(f"decode_mode must be 'fused' or 'eager', "
                             f"got {decode_mode!r}")
        model.eval()
        self.model = model
        # tokens the model drafts a lane and iteration with a module of
        # its own (0: today's one-token step, untouched)
        self._drafts = int(getattr(model, "draft_tokens", 0) or 0)
        if self._drafts > 1:
            raise NotImplementedError(
                f"a model that drafts {self._drafts} tokens an iteration: "
                f"the verify step is written for one draft a lane")
        if self._drafts and mesh is not None:
            from ..models.decode_cache import DraftingUnsupported
            raise DraftingUnsupported(
                "tensor-parallel decode (ServingEngine(mesh=...))",
                "sharding the drafting module's pool and the verify "
                "step's two rows a lane over the TP axis",
                draft_tokens=self._drafts)
        self.name = name
        self.max_batch = int(max_batch)
        self.max_len = int(max_len)
        self.page_size = int(page_size)
        self.eos_id = int(eos_id)
        self.decode_mode = decode_mode
        self.share_prefix = bool(share_prefix)
        # tensor-parallel decode: shard the K/V page pools (and the
        # attention heads) over `tp_axis` of `mesh` — each device holds
        # 1/N of every pool, so the SAME engine serves an N×-larger
        # model at unchanged TPOT. Weights replicate; greedy decode is
        # bit-exact vs single-chip (models/gpt.py set_tp_mesh).
        self.mesh = mesh
        self.tp_axis = tp_axis
        if mesh is not None:
            if not hasattr(model, "set_tp_mesh"):
                raise ValueError(
                    f"model {type(model).__name__} does not implement the "
                    f"TP decode protocol (set_tp_mesh)")
            model.set_tp_mesh(mesh, tp_axis)
        elif hasattr(model, "set_tp_mesh") \
                and getattr(model, "tp_mesh", lambda: None)() is not None:
            # a previous TP engine armed this model: a meshless engine
            # must disarm, or init_cache builds sharded pools this
            # engine has no mesh to place payloads/buffers against
            model.set_tp_mesh(None)
        # multi-model co-residency: priority picks the degradation victim
        # (LOWEST degrades first) and mem_budget_bytes caps this engine's
        # page-pool footprint at construction (budget enforcement against
        # the device_memory_* watermarks happens in MemoryGovernor)
        self.priority = int(priority)
        self.mem_budget_bytes = int(mem_budget_bytes)
        self.cache = model.init_cache(max_batch, max_len,
                                      page_size=page_size,
                                      num_pages=num_pages)
        self._budget_capped: Optional[Tuple[int, int]] = None
        if self.mem_budget_bytes > 0:
            # the budget buys pages after the states' and the window
            # rings' fixed cost
            per_page = max(1, self.cache.describe()["page_bytes"])
            fit = int(max(0, self.mem_budget_bytes
                          - self.cache.state_bytes()
                          - self.cache.window_bytes()) // per_page)
            if fit < self.cache.num_pages:
                capped = max(2, fit)
                self._budget_capped = (self.cache.num_pages, capped)
                self.cache = model.init_cache(max_batch, max_len,
                                              page_size=page_size,
                                              num_pages=capped)
        self._prefix = _PrefixCache(page_size, lookahead=self._drafts)
        self.allocator = PageAllocator(self.cache.num_pages,
                                       on_release=self._prefix.drop_page)
        self._reset_tables()
        if prefill_buckets is None:
            prefill_buckets = _prefill_ladder(max_len)
        self.prefill_buckets = sorted(set(int(b) for b in prefill_buckets))
        if self.prefill_buckets[-1] < max_len:
            self.prefill_buckets.append(max_len)
        # one fused-step executable per power-of-two lane bucket
        self.decode_buckets = _pow2_buckets(1, self.max_batch)

        self._params = {k: p.data for k, p in model.named_parameters()}
        self._buffers = {k: b.data for k, b in model.named_buffers()}
        if mesh is not None:
            # weights replicate onto the mesh ONCE at construction (and
            # per hot-swap in request_swap) so every fused dispatch sees
            # committed, consistently-placed inputs
            self._params = {k: jax.device_put(v, self._rep_sharding())
                            for k, v in self._params.items()}
            self._buffers = {k: jax.device_put(v, self._rep_sharding())
                             for k, v in self._buffers.items()}
        self._queue: "deque[Request]" = deque()
        self._lock = threading.Lock()
        self._slots: List[Optional[Request]] = [None] * self.max_batch
        # the token each slot's next lane feeds: one the host knows (a
        # prefill's, a hand-off's, an iteration's that has been read), or
        # -1 while the iteration that samples it is unread: the decode
        # program then takes it from `_last_tokens`, its own row
        self._cur_tokens = np.zeros((self.max_batch,), np.int32)
        # of a drafting model: the standing draft that goes with it
        self._cur_drafts = np.zeros((self.max_batch,), np.int32)
        # the one decode iteration dispatched and not read back yet:
        # (tokens on the device, the Request of each lane, lane bucket,
        # iteration number), or None. `_step_lock` keeps a drain asked
        # for from another thread (a governor's `shrink_pool`, a status
        # page's `device_counters`) out of a step
        self._inflight: Optional[tuple] = None
        # the prefills dispatched and not read back yet, in launch order:
        # (first token on the device, Request, launch number, bucket,
        # prompt tokens)
        self._unread_prefills: List[tuple] = []
        self._step_lock = threading.RLock()
        self._closed = False
        self._audited = False
        self._thread: Optional[threading.Thread] = None
        self._loop_poll_s = 0.005
        # self-healing plane state: staged weight swap (applied between
        # decode iterations), previous weights kept for rollback, the
        # watchdog-restart flag, and the shed/suspend admission gates
        self._swap_lock = threading.Lock()
        self._dispatch_lock = threading.Lock()
        self._pending_swap: Optional[dict] = None
        self._prev_weights: Optional[tuple] = None
        self.weights_step: Optional[int] = None
        self.last_swap: Optional[dict] = None
        self.hotswap = None            # HotSwapManager attaches here
        self._restarting = False
        self.queue_limit: Optional[int] = None
        self._suspended: Optional[dict] = None
        # disaggregated-pipeline hooks: when set, a preempted request is
        # handed to `on_preempt_requeue` (back to the prefill stage)
        # instead of requeueing on this engine's own admission queue,
        # and `handoff_source` (peek/pop protocol — DisaggPipeline) is
        # drained at the top of every step(). Draining INSIDE step keeps
        # every cache mutation on the decode thread: a payload injection
        # racing the donated decode dispatch from another thread would
        # use buffers the dispatch just consumed.
        self.on_preempt_requeue = None
        self.handoff_source = None
        # rolling stats for bench/status
        self.stats = {"iterations": 0, "prefills": 0, "decode_tokens": 0,
                      "prefill_tokens": 0, "prefill_padded_tokens": 0,
                      "completed": 0, "preemptions": 0, "decode_wall_s": 0.0,
                      "cow_copies": 0, "prefix_hit_tokens": 0,
                      "shared_admissions": 0, "swaps": 0, "restarts": 0,
                      "handoffs": 0, "worker_prefills": 0,
                      "table_refreshes": 0, "h2d_transfers": 0,
                      "ahead_iterations": 0, "drained_for_length": 0,
                      "discarded_tokens": 0, "prefill_reads_behind": 0,
                      "draft_tokens": 0, "accepted_tokens": 0,
                      "page_groups_live": 0, "page_groups_walked": 0,
                      "launches": 0, "read_wait_s": 0.0, "step_wall_s": 0.0,
                      "min_free_pages": self.allocator.free_pages}
        # what the cache holds, by kind (constants of the engine's life)
        self._walk_span = self._page_walk_span()
        desc = self.cache.describe()
        self.stats.update({k: desc[k] for k in (
            "kv_layers", "draft_layers", "window_layers", "state_layers",
            "state_bytes_per_slot")})
        # request-scoped observability plane: lifecycle tracer, sliding-
        # window SLO tracker, and a bounded ring of per-iteration
        # introspection snapshots (the /requests endpoint payload tail)
        self.tracer = _reqtrace.RequestTracer(name)
        self.slo = _slo.SLOTracker(name)
        self._introspect: "deque[dict]" = deque(
            maxlen=max(1, env_int("PADDLE_TPU_SERVING_INTROSPECT_RING",
                                  256)))
        self._last_progress = time.monotonic()
        with _engine_lock:
            _engine_refs.append(weakref.ref(self))
            del _engine_refs[:-8]  # bound the registry

        # ONE jit object each: XLA specializes per input shape, so the
        # fused step compiles exactly one executable per decode-lane
        # bucket and prefill one per prompt bucket — both donate the
        # cache (the page pools update in place)
        self._fused_jit = jax.jit(self._fused_step_fn,
                                  donate_argnums=(2, 3))
        self._prefill_jit = jax.jit(self._prefill_fn, donate_argnums=(2, 3))
        # disagg handoff injection: ONE donated executable per pow2
        # page-count bucket scatters a prefill worker's page payload
        # into the (possibly head-sharded) pools in place
        self._inject_jit = jax.jit(_inject_pages_impl,
                                   donate_argnums=(0, 1))

    def _rep_sharding(self):
        from jax.sharding import NamedSharding, PartitionSpec
        return NamedSharding(self.mesh, PartitionSpec())

    # -- the scheduling state the device reads --------------------------------
    # The HOST owns it: `_block_tables` [max_batch, pages_per_seq] and
    # `_context_lens` [max_batch] are NumPy arrays, written on the
    # engine's thread only (the discipline `_slots` has), and the
    # cache's `block_tables` / `context_lens` are copies made by plain
    # transfers right before a dispatch. Nothing edits the device's copy
    # from Python: an eager `.at[].set` is a program launch of its own,
    # and the device waits through every one of them.

    def _reset_tables(self):
        """Host tables for a cache fresh from `init_cache`, whose own
        tables are zeros too: nothing to send but the device's row of
        each slot's last token, zeros as well."""
        self._block_tables = np.zeros(
            (self.cache.max_batch, self.cache.pages_per_seq), np.int32)
        self._context_lens = np.zeros((self.cache.max_batch,), np.int32)
        self._tables_dirty = False
        self._lens_dirty = False
        # a drafting model's row: (token, standing draft, tokens generated)
        self._last_tokens = self._put(np.zeros(
            (self.cache.max_batch + 1,) + (3,) * bool(self._drafts),
            np.int32))

    def _put(self, host):
        """One host-to-device transfer of a NumPy array nobody writes to
        afterwards (the CPU backend aliases the host's memory), replicated
        over the mesh under TP decode."""
        import jax
        if self.mesh is not None:
            return jax.device_put(host, self._rep_sharding())
        return jax.device_put(host)

    def _point_slot(self, slot: int, pages: Sequence[int] = ()):
        """The slot's row: `pages`, then the null page."""
        row = self._block_tables[slot]
        row[:] = 0
        row[:len(pages)] = pages
        self._tables_dirty = True

    def _count_transfers(self, with_call: int) -> int:
        """Arrays the dispatch about to be made hands to the device:
        `with_call` NumPy arguments of the call itself plus what
        `_refresh_tables` will send; counted here, once."""
        stale = int(self._tables_dirty) + int(self._lens_dirty)
        self.stats["h2d_transfers"] += with_call + stale
        self.stats["table_refreshes"] += bool(stale)
        return with_call + stale

    def _refresh_tables(self):
        """Before a dispatch: re-send the block tables if a row changed
        since the last one. The lengths follow the device's own updates
        (prefill sets a slot's, decode bumps each active lane's, and
        `_prefill` / `_decode_iteration` do the same to the host's), so
        they are sent only after a write no program makes: a hand-off."""
        if self._tables_dirty:
            self.cache.block_tables = self._put(self._block_tables.copy())
            self._tables_dirty = False
        if self._lens_dirty:
            self.cache.context_lens = self._put(self._context_lens.copy())
            self._lens_dirty = False

    def _page_walk_span(self) -> int:
        """Tokens one grid step of the paged-attention walk covers at this
        cache's shape (the full-heads kernel's pick or, with grouped K/V
        heads, the grouped kernel's, or for latent rows the latent
        kernel's), or 0 where no layer is paged."""
        from ..ops.pallas import paged_attention as _pa
        c = self.cache
        if not c.k_pages:
            return 0
        if c.latent_layers:
            return self.page_size * _pa.latent_pages_per_step(
                c.k_pages[0].shape[-1], self.page_size,
                c.k_pages[0].dtype.itemsize, c.pages_per_seq)
        pick = (_pa.pages_per_step if c.num_kv_heads == c.num_heads
                else _pa.grouped_pages_per_step)
        return self.page_size * pick(
            c.num_kv_heads * c.head_dim // self.tp_degree(), self.page_size,
            c.k_pages[0].dtype.itemsize, c.pages_per_seq)

    def tp_degree(self) -> int:
        """Shards the KV pools split over (1 = single-chip)."""
        return int(self.mesh.shape[self.tp_axis]) if self.mesh is not None \
            else 1

    # -- jitted model steps ---------------------------------------------------
    # The fused decode step is the tentpole: every layer, the paged-
    # attention kernel, the K/V page append, the in-graph sampling draw
    # and the context-length bump — one traced function, donated cache,
    # one dispatch per iteration per lane bucket. Each bucket's site
    # observes the retrace watchdog (an unexpected extra signature
    # surfaces like any other jit site) and compile time is attributed
    # on the compile-watch plane.

    def _fused_step_fn(self, params, buffers, cache, last_tokens, lanes_i,
                       lanes_f):
        """`lanes_i` int32 [6, W] and `lanes_f` float32 [2, W] are what
        `_lane_arrays` packed: one transfer each instead of eight.
        `last_tokens` int32 [max_batch + 1] is the last token sampled for
        each slot, kept on the device (donated, like the cache): a lane
        whose token the host sent as -1 feeds its slot's entry, so the
        iteration after one the host has not read yet needs nothing from
        the host. Entry `max_batch` takes the padding lanes' writes."""
        import jax.numpy as jnp
        from ..jit import _swapped_state
        if self._drafts:
            return self._verify_and_draft(params, buffers, cache,
                                          last_tokens, lanes_i, lanes_f)
        tokens, slot_map, lane_active, top_k, seeds, steps = lanes_i
        lane_active = lane_active.astype(bool)
        temp, top_p = lanes_f
        tokens = jnp.where(tokens >= 0, tokens, last_tokens[slot_map])
        with tape_mod.no_grad(), _swapped_state(self.model, params, buffers):
            logits, cache = self.model.forward_decode(
                Tensor(tokens), cache, lane_active, slot_map=slot_map)
        nxt = sample_logits(logits.data, temp, top_k, top_p, seeds, steps)
        nxt = jnp.where(lane_active, nxt, 0)
        return nxt, cache, last_tokens.at[slot_map].set(nxt)

    def _verify_and_draft(self, params, buffers, cache, last, lanes_i,
                          lanes_f):
        """A drafting model's decode iteration, traced as
        `_fused_step_fn` (the program keeps that name): `lanes_i` int32
        [7, W] carries the standing drafts as a seventh row, `last` int32
        [max_batch + 1, 3] is each slot's (last token, standing draft,
        tokens generated so far), fed wherever the host sent the token as -1.
        Returns (int32 [W, 4]: the two tokens the main model samples, how
        many of them are new (1, or 2 where the draft was the first), the
        next standing draft; cache; `last` updated)."""
        import jax
        import jax.numpy as jnp
        from ..jit import _swapped_state
        tokens, slot_map, lane_active, top_k, seeds, steps, drafts = lanes_i
        lane_active = lane_active.astype(bool)
        temp, top_p = lanes_f
        known = tokens >= 0
        row = last[slot_map]
        tokens = jnp.where(known, tokens, row[:, 0])
        drafts = jnp.where(known, drafts, row[:, 1])
        steps = jnp.where(known, steps, row[:, 2])
        W = tokens.shape[0]
        twice = lambda x: jnp.repeat(x, 2)   # noqa: E731  (a lane's 2 rows)
        with tape_mod.no_grad(), _swapped_state(self.model, params, buffers):
            logits, hid, cache = self.model.forward_verify(
                Tensor(jnp.stack([tokens, drafts], axis=1)), cache,
                lane_active, slot_map=slot_map)
            with jax.named_scope("spec_verify"):
                # token `steps` from the row of t_n, token `steps + 1`
                # from the draft's row: what plain decoding samples there
                # if the draft is what it sampled here
                sampled = sample_logits(
                    logits.data.reshape(2 * W, -1), twice(temp),
                    twice(top_k), twice(top_p), twice(seeds),
                    jnp.stack([steps, steps + 1], axis=1).reshape(-1)
                ).reshape(W, 2)
                accepted = lane_active & (drafts == sampled[:, 0])
            guesses, cache = self.model.draft_decode(
                hid, Tensor(sampled), cache, lane_active, slot_map=slot_map)
            cache = self.model.accept_drafts(cache, accepted, lane_active,
                                             slot_map=slot_map)
        guesses = jnp.argmax(guesses.data, axis=-1).astype(jnp.int32)
        pick = lambda x: jnp.where(accepted, x[:, 1], x[:, 0])  # noqa: E731
        n_new = lane_active.astype(jnp.int32) + accepted
        out = jnp.stack([sampled[:, 0], sampled[:, 1], n_new,
                         pick(guesses)], axis=1)
        out = jnp.where(lane_active[:, None], out, 0)
        return out, cache, last.at[slot_map].set(
            jnp.stack([pick(sampled), pick(guesses), steps + n_new], axis=1))

    def _prefill_fn(self, params, buffers, cache, last_tokens, ids, scalars,
                    floats):
        """`scalars` int32 [6] is slot, length, write start, top-k, seed,
        step; `floats` float32 [2] temperature, top-p (`_prefill` packs
        them on the host). The first token also goes into the slot's row
        of `last_tokens` (donated, as in `_fused_step_fn`), so that the
        decode iteration after the admission needs nothing from the host;
        a drafting model's row takes (token, first draft, step + 1)."""
        import jax.numpy as jnp
        from ..jit import _swapped_state
        slot, length, write_start = scalars[0], scalars[1], scalars[2]
        top_k, seed, step = scalars[3:4], scalars[4:5], scalars[5:6]
        temp, top_p = floats[0:1], floats[1:2]
        if self._drafts:
            # the first token, then the module over the prompt with it:
            # int32 [2], the token and the first standing draft
            with tape_mod.no_grad(), _swapped_state(self.model, params,
                                                    buffers):
                logits, cache, hid = self.model.forward_prefill(
                    Tensor(ids), cache, slot, length,
                    write_start=write_start, with_hidden=True)
                nxt = sample_logits(logits.data, temp, top_k, top_p, seed,
                                    step)
                guess, cache = self.model.draft_prefill(
                    hid, Tensor(ids), nxt, cache, slot, length,
                    write_start=write_start)
            nxt = jnp.concatenate(
                [nxt, jnp.argmax(guess.data, -1).astype(jnp.int32)])
            return nxt, cache, last_tokens.at[slot].set(
                jnp.concatenate([nxt, step + 1]))
        with tape_mod.no_grad(), _swapped_state(self.model, params, buffers):
            logits, cache = self.model.forward_prefill(
                Tensor(ids), cache, slot, length, write_start=write_start)
        # the FIRST generated token samples in-graph too (step counter 0,
        # or len(generated) on a post-preemption re-prefill)
        nxt = sample_logits(logits.data, temp, top_k, top_p, seed, step)
        return nxt, cache, last_tokens.at[slot].set(nxt[0])

    def audit(self, emit: bool = True):
        """Statically audit the fused decode step (smallest lane bucket)
        and the (smallest-bucket) prefill executable for perf hazards —
        donation/aliasing of the page pools, dtype hygiene, baked
        constants — and COMPILE both to see what "donation accepted"
        cannot: each report's `pool_relayout_copies` counts the `copy`
        instructions of a pool's shape (or a per-slot state's, where the
        model has state layers) in the optimized HLO (the device's
        default layout for the pool's shape differing from the one the
        program works in, PERF.md section 5) beside the program's
        `temp_size_in_bytes`. Nothing executes and the live cache is
        untouched. Returns [decode_report, prefill_report] (+ a per-link
        collective-bytes report when TP decode is on)."""
        from .. import analysis
        # one buffer of each shape the programs should update in place:
        # a K and a V pool, a K ring of a window layer (V's has the
        # same shape), and a recurrent and a convolution state
        pools = (self.cache.k_pages[:1] + self.cache.v_pages[:1]
                 + self.cache.window_k[:1]
                 + self.cache.states[:1] + self.cache.conv_states[:1])
        self._drain()
        # the token row, and lane arrays in which every lane is padding
        lane_args = (self._last_tokens,) + self._lane_arrays([])[1:]
        decode = analysis.audit_program(
            self._fused_step_fn,
            (self._params, self._buffers, self.cache) + lane_args,
            donate_argnums=(2, 3), relayout_of=pools,
            name=f"serving_decode:{self.name}", entry="serving_decode",
            emit=emit)
        bucket = self.prefill_buckets[0]
        prefill = analysis.audit_program(
            self._prefill_fn,
            (self._params, self._buffers, self.cache, self._last_tokens,
             np.zeros((1, bucket), np.int32),
             np.array([0, 1, 0, 0, 0, 0], np.int32),  # slot 0, length 1
             np.array([0.0, 1.0], np.float32)),        # greedy
            donate_argnums=(2, 3), relayout_of=pools,
            name=f"serving_prefill:{self.name}", entry="serving_prefill",
            emit=emit)
        reports = [decode, prefill]
        if self.mesh is not None:
            # TP decode: price the compiled program's collectives per
            # link class (ici vs dcn) against the per-link budgets — the
            # jaxpr-level audit above cannot see GSPMD-inserted
            # collectives, so this one compiles (cache untouched: XLA
            # donation is a compile-time aliasing hint, nothing runs)
            reports.append(analysis.audit_collectives_by_link(
                self._fused_step_fn,
                (self._params, self._buffers, self.cache) + lane_args,
                donate_argnums=(2, 3),
                name=f"serving_decode:{self.name}", emit=emit))
        return reports

    def _maybe_audit_once(self):
        """PADDLE_TPU_AUDIT runtime hook: vet both executables once per
        engine, before the first decode iteration."""
        if self._audited:
            return
        self._audited = True
        from ..jit import _analysis_enabled
        if not _analysis_enabled("serving"):
            return
        try:
            self.audit()
        except Exception as e:  # noqa: BLE001 — audit never kills serving
            import warnings
            warnings.warn(f"serving program audit failed "
                          f"({type(e).__name__}: {e}); skipping")

    def _observe_site(self, site: str, leaves):
        try:
            from ..profiler.watchdog import get_watchdog
            get_watchdog().observe("to_static", f"serving_{site}",
                                   list(leaves))
        except Exception:
            pass

    # -- public API -----------------------------------------------------------
    def make_request(self, prompt: Sequence[int], max_new_tokens: int = 16,
                     eos_id: Optional[int] = None,
                     sampling: Optional[SamplingParams] = None) -> Request:
        """Validate and build a Request WITHOUT enqueueing it — the
        disaggregated pipeline routes requests through its prefill stage
        first and hands the KV back via `admit_handoff`. All submit-time
        validation (pool coverage, length bounds, suspension) applies."""
        if self._closed:
            raise RuntimeError("engine is closed")
        # chaos: an armed `serving.admit` fails admission BEFORE the
        # request exists (error kinds propagate to the caller; delay
        # kinds slow the admission edge) — the shed drill
        _fault_site("serving.admit")
        susp = self._suspended
        if susp is not None:
            raise EngineSuspended(self.name, susp["reason"],
                                  susp["retry_after_s"])
        req = Request(prompt, max_new_tokens,
                      self.eos_id if eos_id is None else eos_id,
                      sampling=sampling)
        if not req.prompt:
            raise ValueError("empty prompt")
        if len(req.prompt) + req.max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt {len(req.prompt)} + max_new_tokens "
                f"{req.max_new_tokens} exceeds max_len {self.max_len}")
        total_pages = -(-(len(req.prompt) + req.max_new_tokens)
                        // self.page_size)
        if total_pages > self.cache.num_pages - 1:
            # a request the pool can NEVER satisfy would wedge the queue
            # head forever (admission waits for frees that cannot come)
            raise ValueError(
                f"request needs {total_pages} KV pages but the pool holds "
                f"{self.cache.num_pages - 1} (num_pages minus the null "
                f"page); raise num_pages or lower max_new_tokens")
        return req

    def submit(self, prompt: Sequence[int], max_new_tokens: int = 16,
               eos_id: Optional[int] = None,
               sampling: Optional[SamplingParams] = None) -> Request:
        req = self.make_request(prompt, max_new_tokens, eos_id,
                                sampling=sampling)
        with _span("submit", rid=req.rid, prompt_tokens=len(req.prompt),
                   queue_depth=len(self._queue)):
            with self._lock:
                # re-check under the lock: a close() racing this submit
                # has already drained the queue, and a request appended
                # after that drain would never complete (result() hangs
                # forever)
                if self._closed:
                    raise RuntimeError("engine is closed")
                if self.queue_limit is not None \
                        and len(self._queue) >= self.queue_limit:
                    # controller shed: sustained SLO breach capped the
                    # queue
                    raise RuntimeError(
                        f"queue at shed cap ({self.queue_limit}); "
                        f"engine {self.name!r} is shedding load")
                self._queue.append(req)
                depth = len(self._queue)
            req.trace_id = self.tracer.submit(req.rid)
            if _metrics.enabled():
                _M_QUEUE.set(depth, model=self.name)
        return req

    def queue_depth(self) -> int:
        with self._lock:
            return len(self._queue)

    def pending(self) -> bool:
        with self._lock:
            busy = bool(self._queue) or any(
                r is not None for r in self._slots)
        if busy or self._inflight is not None or self._unread_prefills:
            return True
        src = self.handoff_source
        return src is not None and src._handoff_peek() is not None

    def step(self) -> int:
        """ONE continuous-batching iteration: admit waiting requests into
        free slots (bucketed prefill each, shared-prefix pages forked,
        the first token read after the decode dispatch below),
        grow pages for sequences crossing a page boundary and fork any
        shared page about to be written (copy-on-write), preempting the
        youngest on pool exhaustion, then one fused decode dispatch, whose
        tokens the step reads before it returns only if one of them may
        end a request (the class docstring has the rule). Returns the
        number of tokens the dispatched iteration samples (0 = engine
        idle)."""
        with self._step_lock, \
                _span("step", iteration=self.stats["iterations"]):
            t0 = time.perf_counter()
            try:
                return self._iterate()
            finally:
                self.stats["step_wall_s"] += time.perf_counter() - t0

    def _iterate(self) -> int:
        # chaos: an armed `serving.wedge=N:delay` stalls the loop HERE,
        # before any progress is made — `wedged()` flips once the stall
        # outlives the liveness window (the watchdog-restart drill)
        try:
            _fault_site("serving.wedge")
        except Exception:
            pass  # delay/no-op kinds only; a wedge is slow, not dead
        # a staged weight swap lands at the iteration boundary: in-flight
        # requests keep their pages and decode the next token on the new
        # weights — no drain, no retrace (shapes/dtypes validated)
        if self._pending_swap is not None:
            self._apply_pending_swap()
        if self.handoff_source is not None:
            self._drain_handoff_source()
        with _span("admit"):
            self._admit()
        active_slots = [i for i, r in enumerate(self._slots)
                        if r is not None]
        if _metrics.enabled():
            _M_OCC.set(len(active_slots), model=self.name)
        if active_slots:
            with _span("capacity", active=len(active_slots)):
                self._ensure_capacity(active_slots)
            active_slots = [i for i, r in enumerate(self._slots)
                            if r is not None]  # capacity may have preempted
        if not active_slots:
            # an unread iteration can only hold requests that have ended
            self._drain()
            return 0
        produced = self._decode_iteration(active_slots)
        self._last_progress = time.monotonic()
        return produced

    def _note_introspection(self, active: int, iteration: int):
        """One bounded-ring snapshot per decode iteration, taken when its
        tokens are read: the live view /requests serves alongside the
        per-request phase breakdown."""
        with self._lock:
            depth = len(self._queue)
        used = self.cache.num_pages - 1 - self.allocator.free_pages
        self._introspect.append({
            "iteration": iteration,
            "ts": time.time(),
            "active": active,
            "lanes": self._decode_bucket(active),
            "occupancy": sum(r is not None for r in self._slots),
            "queue_depth": depth,
            "free_pages": self.allocator.free_pages,
            "used_pages": used,
            "cow_shared_pages": self.allocator.shared_page_count,
            "decode_mode": self.decode_mode,
        })

    def introspection(self, n: int = 32) -> List[dict]:
        return list(self._introspect)[-max(0, n):]

    def run_until_idle(self, max_iterations: int = 100000):
        for _ in range(max_iterations):
            if not self.pending():
                return
            self.step()
        raise RuntimeError("run_until_idle: iteration cap exceeded")

    def start(self, poll_s: float = 0.005):
        """Background decode loop: steps while work exists, naps when
        idle. close() joins it. An exception out of step() is FATAL for
        the engine (the cache may hold donated/invalid buffers): it is
        surfaced as a warning + failed requests instead of a silently
        dead thread that strands every client in result()."""
        if self._thread is not None:
            return
        self._loop_poll_s = poll_s

        def loop():
            while not self._closed and not self._restarting:
                try:
                    if self._pending_swap is not None and \
                            not self.pending():
                        self._apply_pending_swap()  # idle engines swap too
                    if not self.pending() or self.step() == 0:
                        time.sleep(poll_s)
                except Exception as e:  # noqa: BLE001 — see docstring
                    import warnings
                    err = f"{type(e).__name__}: {e}"
                    warnings.warn(
                        f"serving engine {self.name!r} decode loop died "
                        f"({err}); failing outstanding requests")
                    self._closed = True
                    self._fail_outstanding(f"engine decode loop died: "
                                           f"{err}")
                    return

        self._thread = threading.Thread(target=loop, daemon=True,
                                        name=f"serving-{self.name}")
        self._thread.start()

    def close(self):
        """Stop the engine. Outstanding (queued or mid-decode) requests
        FAIL with a clean 'engine closed' error — a client blocked in
        result() must never hang on a closed engine."""
        self._closed = True
        if self._thread is not None:
            self._thread.join(timeout=30)
            self._thread = None
        try:
            self._drain()
        except Exception:  # noqa: BLE001 — a dead device's tokens cannot
            pass           # be read: their requests fail below
        self._fail_outstanding("engine closed")

    def _fail_outstanding(self, error: str):
        with self._lock:
            leftovers = list(self._queue) + [r for r in self._slots
                                             if r is not None]
            self._queue.clear()
        # what is unread fails with the rest
        self._inflight, self._unread_prefills = None, []
        for req in leftovers:
            self._complete(req, "failed", error=error)

    # -- self-healing plane: hot-swap / restart / degradation -----------------
    def pool_bytes(self) -> int:
        """Device bytes the decode cache holds: the K/V page pools (every
        paged layer, K + V), the window layers' rings and the per-slot
        recurrent states. Pages are what `mem_budget_bytes` and
        `shrink_pool` can give back; the rings and the states are a fixed
        cost of `max_batch`."""
        return self.cache.pool_bytes() + self.cache.state_bytes()

    def device_counters(self) -> Dict:
        """What the model's layers counted on the device about the work
        they did (`PagedKVCache.counters`: an expert layer's assignments),
        fetched now, as NumPy. They ride in the decode step's donated
        cache and cost the loop nothing; THIS is a device fetch that
        waits for the step in flight, so call it at the ends of a window
        (a benchmark, a status page), never once an iteration."""
        self._drain()
        with self._dispatch_lock:
            return {k: np.asarray(v) for k, v in self.cache.counters.items()}

    def request_swap(self, params: Dict, buffers: Optional[Dict] = None, *,
                     step: Optional[int] = None, source: str = "manual",
                     rollback: bool = False, on_applied=None) -> dict:
        """Stage a replacement weight set; it rebinds atomically at the
        next decode-iteration boundary (`step()` / the idle loop). The
        arrays are validated against the live weights here — a missing
        key or a shape/dtype mismatch raises (nothing staged), so the
        fused executables can never retrace mid-swap. Returns the staged
        record; a second stage before apply replaces the first."""
        for k, live in self._params.items():
            cand = params.get(k)
            if cand is None:
                raise ValueError(f"swap rejected: missing parameter {k!r}")
            if tuple(cand.shape) != tuple(live.shape) \
                    or np.dtype(cand.dtype) != np.dtype(live.dtype):
                raise ValueError(
                    f"swap rejected: parameter {k!r} is "
                    f"{tuple(cand.shape)}/{np.dtype(cand.dtype)} but the "
                    f"live weights hold "
                    f"{tuple(live.shape)}/{np.dtype(live.dtype)}")
        if buffers is not None:
            for k, live in self._buffers.items():
                cand = buffers.get(k)
                if cand is not None \
                        and tuple(cand.shape) != tuple(live.shape):
                    raise ValueError(
                        f"swap rejected: buffer {k!r} shape "
                        f"{tuple(cand.shape)} != {tuple(live.shape)}")
        cand_params = {k: params[k] for k in self._params}
        if self.mesh is not None:
            # sharded engines replicate the candidate weights onto the
            # mesh at STAGE time (off the decode hot path): apply-time
            # rebind stays a pointer swap and the very next fused
            # dispatch sees consistently-placed inputs — a host-resident
            # candidate would otherwise retrigger placement mid-decode
            import jax
            rep = self._rep_sharding()
            cand_params = {k: jax.device_put(v, rep)
                           for k, v in cand_params.items()}
            if buffers is not None:
                buffers = {k: jax.device_put(v, rep)
                           for k, v in buffers.items()}
        pend = {"params": cand_params,
                "buffers": buffers, "step": step, "source": source,
                "rollback": bool(rollback), "on_applied": on_applied,
                "staged_ts": time.time()}
        with self._swap_lock:
            self._pending_swap = pend
        _events.emit("serving_swap", severity="info", action="stage",
                     model=self.name, to_step=step, source=source,
                     rollback=bool(rollback))
        return pend

    def _apply_pending_swap(self) -> Optional[dict]:
        with self._swap_lock:
            pend, self._pending_swap = self._pending_swap, None
        if pend is None:
            return None
        self._drain()
        from_step = self.weights_step
        t0 = time.perf_counter()
        with self._dispatch_lock:
            self._prev_weights = (self._params, self._buffers,
                                  self.weights_step)
            self._params = pend["params"]
            if pend["buffers"] is not None:
                self._buffers = dict(self._buffers, **pend["buffers"])
            self.weights_step = pend["step"]
        pause_s = time.perf_counter() - t0
        self.stats["swaps"] += 1
        action = "rollback" if pend["rollback"] else "swap"
        self.last_swap = {"action": action, "step": pend["step"],
                          "from_step": from_step, "pause_s": pause_s,
                          "ts": time.time(), "source": pend["source"],
                          "in_flight": sum(r is not None
                                           for r in self._slots)}
        if _metrics.enabled():
            outcome = "rolled_back" if pend["rollback"] else "applied"
            _M_SWAP_TOTAL.inc(1.0, model=self.name, outcome=outcome)
            _M_SWAP_PAUSE.observe(pause_s, model=self.name)
            _M_SWAP_STEP.set(-1 if pend["step"] is None else pend["step"],
                             model=self.name)
        _events.emit("serving_swap",
                     severity="warn" if pend["rollback"] else "info",
                     action=action, model=self.name,
                     from_step=from_step, to_step=pend["step"],
                     pause_s=round(pause_s, 6), source=pend["source"],
                     in_flight=sum(r is not None for r in self._slots))
        cb = pend.get("on_applied")
        if cb is not None:
            try:
                cb(self.last_swap)
            except Exception:  # noqa: BLE001 — observer must not kill decode
                pass
        return self.last_swap

    def rollback_weights(self, *, source: str = "rollback") -> dict:
        """Stage the previous weight set back in (post-swap regression
        response). Raises when no swap has happened yet."""
        if self._prev_weights is None:
            raise RuntimeError("no previous weights to roll back to")
        params, buffers, step = self._prev_weights
        return self.request_swap(params, buffers, step=step,
                                 source=source, rollback=True)

    def run_canary(self, probe_ids, params: Optional[Dict] = None,
                   buffers: Optional[Dict] = None) -> float:
        """Mean-token perplexity of the fixed probe batch under the
        given weights (default: the live weights) — the hot-swap canary
        score. Serializes with decode via the dispatch lock (the probe
        is a full forward with temporarily-rebound model state)."""
        from ..jit import _swapped_state
        params = self._params if params is None else params
        buffers = self._buffers if buffers is None else buffers
        ids = np.asarray(probe_ids, np.int32)
        if ids.ndim != 2 or ids.shape[1] < 2:
            raise ValueError("probe batch must be (B, T>=2) token ids")
        inp, lbl = Tensor(ids[:, :-1]), Tensor(ids[:, 1:])
        with self._dispatch_lock:
            with tape_mod.no_grad(), _swapped_state(self.model, params,
                                                    buffers):
                loss = self.model.loss(inp, lbl)
        nll = float(np.asarray(loss.data))
        try:
            return math.exp(nll)  # a confidently-wrong push overflows
        except OverflowError:     # float exp — that IS the verdict
            return float("inf")

    def last_progress_age(self) -> float:
        """Seconds since the last completed decode iteration (the
        /healthz serving-liveness signal)."""
        return time.monotonic() - self._last_progress

    def restart(self, reason: str = "wedged",
                join_timeout: float = 15.0,
                term: Optional[int] = None) -> dict:
        """Watchdog restart: stop the decode loop, requeue every
        in-flight request through the PREEMPTION path (trace ids and
        generated prefixes preserved — recompute-style resume), rebuild
        the KV plane (cache, allocator, prefix registry), and relaunch
        the loop if one was running. Queued requests are untouched.
        Raises if the loop won't stop inside `join_timeout` (the caller
        records a failed decision rather than corrupting live state).

        `term` is the issuing controller's fencing token: a restart
        ordered by a DEPOSED leader (term below the process high-water
        mark) raises ControllerFencedError before touching any state —
        `term=None` (operator / pre-HA caller) always passes."""
        from ..distributed.fleet.leader import check_term
        check_term(term, policy="serving_restart")
        if self._closed:
            raise RuntimeError("engine is closed")
        was_running = self._thread is not None
        self._restarting = True
        try:
            t = self._thread
            if t is not None:
                t.join(join_timeout)
                if t.is_alive():
                    raise RuntimeError(
                        f"decode loop did not stop within {join_timeout}s")
                self._thread = None
            self._drain()
            requeued = 0
            for req in [r for r in self._slots if r is not None]:
                self._preempt(req)
                requeued += 1
            leaked = self.allocator.outstanding()
            reserved = self.allocator.reserved_pages
            self._prefix = _PrefixCache(self.page_size,
                                        lookahead=self._drafts)
            self.cache = self.model.init_cache(
                self.max_batch, self.max_len, page_size=self.page_size,
                num_pages=self.cache.num_pages)
            self.allocator = PageAllocator(self.cache.num_pages,
                                           on_release=self._prefix.drop_page)
            if reserved:
                self.allocator.reserve(reserved)  # keep the shrink in force
            self._reset_tables()
            self._cur_tokens[:] = 0
            self.stats["restarts"] += 1
            self._last_progress = time.monotonic()
        finally:
            self._restarting = False
        if _metrics.enabled():
            _M_RESTARTS.inc(1.0, model=self.name, reason=reason)
        _events.emit("serving_restart", model=self.name, reason=reason,
                     requeued=requeued, leaked_pages=len(leaked),
                     restarted_thread=was_running)
        if was_running:
            self.start(self._loop_poll_s)
        return {"requeued": requeued, "leaked_pages": len(leaked),
                "restarted_thread": was_running}

    def set_queue_limit(self, limit: Optional[int],
                        term: Optional[int] = None):
        """Controller shed actuation: cap (or uncap) queue admission.
        `term` fences a deposed leader's stale shed/unshed (see
        :meth:`restart`)."""
        from ..distributed.fleet.leader import check_term
        check_term(term, policy="serving_shed")
        self.queue_limit = None if limit is None else max(1, int(limit))

    def suspend(self, reason: str = "memory_pressure",
                retry_after_s: Optional[float] = None):
        """Refuse new admissions (EngineSuspended carries Retry-After);
        queued and in-flight work keeps draining."""
        if retry_after_s is None:
            retry_after_s = env_float("PADDLE_TPU_SERVING_RETRY_AFTER_SEC",
                                      5.0)
        self._suspended = {"reason": reason,
                           "retry_after_s": float(retry_after_s),
                           "ts": time.time()}
        if _metrics.enabled():
            _M_SUSPENDED.set(1, model=self.name)

    def resume_admissions(self):
        self._suspended = None
        if _metrics.enabled():
            _M_SUSPENDED.set(0, model=self.name)

    def shrink_pool(self, frac: float = 0.5) -> int:
        """Park up to `frac` of the pool's pages (taken from the free
        list) out of circulation — the first memory-pressure degradation
        rung. Returns pages actually parked (live pages never move)."""
        self._drain()   # a request that ended in it gives its pages back
        target = max(1, int((self.cache.num_pages - 1) * frac))
        return self.allocator.reserve(target)

    def restore_pool(self) -> int:
        """Return every parked page to the free list (pressure cleared)."""
        return self.allocator.release_reserved()

    # -- disaggregated prefill/decode handoff ---------------------------------
    def admit_handoff(self, handoff) -> bool:
        """Decode-side admission of a prefill worker's KV payload
        (inference/disagg.py): allocate pages for the prefilled context,
        scatter the per-layer page payload into the pools in ONE donated
        dispatch (pow2 page-count buckets — padding rows land on the
        null page), point the slot's block table at them, and resume
        decode from the worker's first sampled token. Returns False with
        the payload untouched when no slot or pages are free right now
        (the pipeline retries next tick); True when admitted OR when the
        request already finished at the prefill stage."""
        req = handoff.request
        if req.state != "queued":
            return True  # single-token request finished at prefill
        if None in self._slots:
            self._drain()   # a slot is taken only with every token read
        # KV covers everything BEFORE the worker's sampled token
        ctx = len(req.prompt) + len(req.generated) - 1
        n_pages = -(-ctx // self.page_size)
        with self._lock:
            if self._closed:
                return False
            free = [i for i, r in enumerate(self._slots) if r is None]
            if not free:
                return False
            pages = self.allocator.alloc(n_pages)
            if pages is None:
                return False  # pool exhausted: wait for frees
            slot = free[0]
            req.slot, req.pages, req.state = slot, list(pages), "running"
            self._slots[slot] = req
        self._note_pool_watermark()
        self._point_slot(slot, pages)
        # no program of this engine wrote the length: the next refresh
        # carries it
        self._context_lens[slot] = ctx
        self._lens_dirty = True
        # scatter ids padded to the payload's pow2 bucket with page 0
        pad = int(handoff.k_payload[0].shape[0])
        ids = np.zeros((pad,), np.int32)
        ids[:n_pages] = pages
        # the worker committed the payload to ITS device; re-place onto
        # this engine's placement (replicated over the mesh under TP)
        # so the inject dispatch sees consistently-located inputs
        import jax
        target = self._rep_sharding() if self.mesh is not None \
            else next(iter(self.cache.k_pages[0].devices()))
        k_payload = jax.device_put(handoff.k_payload, target)
        v_payload = jax.device_put(handoff.v_payload, target)
        with self._dispatch_lock:
            self.cache.k_pages, self.cache.v_pages = self._inject_jit(
                self.cache.k_pages, self.cache.v_pages,
                k_payload, v_payload, ids)
        self._cur_tokens[slot] = req.generated[-1]
        if req.admitted_ts is None:
            req.admitted_ts = time.monotonic()
            self.slo.observe("queue_wait",
                             req.admitted_ts - req.submitted_ts)
        wait_s = time.monotonic() - handoff.produced_ts
        self.stats["handoffs"] += 1
        if _metrics.enabled():
            _M_HANDOFF_WAIT.observe(wait_s, model=self.name)
            _M_HANDOFF_BYTES.inc(float(handoff.nbytes), model=self.name)
        self.slo.observe("handoff_wait", wait_s)
        if self.share_prefix:
            tokens = (req.prompt + req.generated[:-1])[:ctx]
            self._prefix.register(tokens, pages)
        # no tracer.admitted here: the prefill WORKER owns the queued ->
        # prefill transition; the handoff wait lands in the decode span
        # via reqtrace's contiguous attribution
        self._emit_admission(req, handoff.bucket, ctx)
        return True

    def _drain_handoff_source(self):
        """Admit queued handoffs until slots/pages run out — called at
        the top of step() so payload injection always happens on the
        decode thread, never racing the donated decode dispatch."""
        src = self.handoff_source
        while True:
            h = src._handoff_peek()
            if h is None or not self.admit_handoff(h):
                break
            src._handoff_pop(h)

    # -- internals ------------------------------------------------------------
    def _bucket_for(self, n: int) -> int:
        for b in self.prefill_buckets:
            if b >= n:
                return b
        return self.prefill_buckets[-1]

    def _decode_bucket(self, n: int) -> int:
        for b in self.decode_buckets:
            if b >= n:
                return b
        return self.decode_buckets[-1]

    def _note_pool_watermark(self):
        if self.allocator.free_pages < self.stats["min_free_pages"]:
            self.stats["min_free_pages"] = self.allocator.free_pages

    def _admit(self):
        """Per-iteration admission: fill every free slot whose prompt the
        page pool can cover right now. A prompt whose prefix is already
        resident (prefix cache hit) FORKS the matching pages instead of
        allocating + recomputing them; prefill then skips the K/V
        scatter below the shared length."""
        if self._queue and None in self._slots:
            # somebody can be admitted: read what is in flight first, so
            # that the prefill's own read waits for nothing else. Only an
            # end of sequence or an arrival from outside meets an unread
            # iteration with a free slot: a request's last token by length
            # is read in the step that dispatched it
            self._drain()
        while True:
            with self._lock:
                if not self._queue:
                    break
                free = [i for i, r in enumerate(self._slots) if r is None]
                if not free:
                    break
                req = self._queue[0]
                # admission prompt = original prompt + any tokens already
                # generated before a preemption (recompute-style resume)
                tokens = req.prompt + req.generated
                n_pages = -(-len(tokens) // self.page_size)
                shared_pages: List[int] = []
                shared_len = 0
                if self.share_prefix:
                    shared_pages, shared_len = self._prefix.lookup(tokens)
                new_pages = self.allocator.alloc(n_pages - len(shared_pages))
                if new_pages is None:
                    break  # pool exhausted: wait for frees
                self.allocator.fork(shared_pages)
                pages = shared_pages + new_pages
                self._queue.popleft()
                slot = free[0]
                req.slot, req.pages, req.state = slot, pages, "running"
                req.shared_tokens = shared_len
                self._slots[slot] = req
                depth = len(self._queue)
            # stamped right after the pop: queue wait ends here, and the
            # prefill span below opens on the same instant
            if req.admitted_ts is None:
                req.admitted_ts = time.monotonic()
                self.slo.observe("queue_wait",
                                 req.admitted_ts - req.submitted_ts)
            bucket = self._bucket_for(len(tokens))
            requeue = req.preemptions > 0
            with _span("prefill", rid=req.rid, trace_id=req.trace_id or 0,
                       bucket=bucket, prompt_tokens=len(tokens),
                       shared_tokens=shared_len, requeue=int(requeue),
                       queue_wait_us=int(
                           1e6 * (req.admitted_ts - req.submitted_ts))):
                self._prefill(req, slot, tokens, pages, shared_len, bucket,
                              requeue)
            if _metrics.enabled():
                _M_QUEUE.set(depth, model=self.name)
            if not self._may_run_ahead([req]):
                # its first token is its last: read now, so that the slot
                # it frees is free for this very admission loop
                self._read_prefills()

    def _prefill(self, req: Request, slot: int, tokens: List[int],
                 pages: List[int], shared_len: int, bucket: int,
                 requeue: bool):
        """One admission past the queue: block-table row and padded ids
        on the host, the bucketed prefill program. The first token stays
        unread, on the device and in the slot's row of `_last_tokens`,
        until `_read_prefills`."""
        if shared_len:
            self.stats["shared_admissions"] += 1
            self.stats["prefix_hit_tokens"] += shared_len
        self._note_pool_watermark()
        self.tracer.admitted(req.rid, bucket=bucket,
                             prompt_tokens=len(tokens),
                             shared_tokens=shared_len,
                             requeue=requeue)
        sp = req.sampling
        with _span("prefill.build"):
            self._point_slot(slot, pages)
            ids = np.zeros((1, bucket), np.int32)
            ids[0, :len(tokens)] = tokens
            scalars = np.array([slot, len(tokens), shared_len, sp.top_k,
                                req.seed, len(req.generated)], np.int32)
            floats = np.array([sp.temperature, sp.top_p], np.float32)
        self._observe_site(f"prefill:{self.name}", [ids, scalars, floats])
        from ..profiler import compile_watch as _cw
        prev = _cw.push_entry("to_static", f"serving_prefill:{self.name}")
        # the three NumPy arguments travel with the call itself
        transfers = self._count_transfers(3)
        seq = self.stats["launches"]
        try:
            # dispatch lock: a concurrent canary evaluation rebinds the
            # model's parameter state while it traces — never interleave
            # that with a prefill/decode trace
            with _span("prefill.dispatch", seq=seq, transfers=transfers), \
                    self._dispatch_lock:
                self._refresh_tables()
                nxt, self.cache, self._last_tokens = self._prefill_jit(
                    self._params, self._buffers, self.cache,
                    self._last_tokens, ids, scalars, floats)
        finally:
            _cw.pop_entry(prev)
        self.stats["launches"] += 1
        self._context_lens[slot] = len(tokens)   # as the program set it
        self.stats["prefills"] += 1
        # rows the program computed for tokens, and for the bucket's padding
        self.stats["prefill_tokens"] += len(tokens)
        self.stats["prefill_padded_tokens"] += bucket - len(tokens)
        if self.share_prefix:
            self._prefix.register(tokens, pages)
        # until it is read the token is the device's alone
        req.unread += 1
        self._cur_tokens[slot] = -1
        self._unread_prefills.append((nxt, req, seq, bucket, len(tokens)))

    def _read_prefills(self):
        """Read and book the first token of every admission not read yet,
        in the order of their launches. `behind` on the span, and
        `stats["prefill_reads_behind"]`, say that a later program was
        dispatched before the read: the device had it queued behind the
        prefill while the host waited for the token."""
        while self._unread_prefills:
            nxt, req, seq, bucket, prompt_len = self._unread_prefills.pop(0)
            behind = int(self.stats["launches"] > seq + 1)
            t0 = time.perf_counter()
            with _span("prefill.fetch", seq=seq, behind=behind):
                got = np.asarray(nxt)
            self.stats["read_wait_s"] += time.perf_counter() - t0
            self.stats["prefill_reads_behind"] += behind
            req.unread -= 1
            if req.state != "running":
                continue   # failed while its token was unread
            if self._drafts:
                req.drafts.append((len(req.generated) + 1, int(got[1])))
            self.tracer.prefill_done(req.rid)
            if req.first_token_ts is None:
                req.first_token_ts = time.monotonic()
                if _metrics.enabled() and req.ttft_s is not None:
                    _M_TTFT.observe(req.ttft_s, model=self.name,
                                    path=self.decode_mode)
                if req.ttft_s is not None:
                    self.slo.observe("ttft", req.ttft_s)
            self._emit_admission(req, bucket, prompt_len)
            self._record_token(req, int(got[0]))

    def _alloc_one_or_preempt(self, req: Request) -> Optional[int]:
        """One fresh page for `req`, preempting the youngest runner on a
        dry pool. None => `req` itself was preempted or failed (caller
        must stop touching it)."""
        while True:
            got = self.allocator.alloc(1)
            if got is not None:
                self._note_pool_watermark()
                return got[0]
            if self._inflight is not None or self._unread_prefills:
                # an end of sequence among the unread tokens frees pages,
                # and may be `req`'s own
                self._drain()
                if req.state != "running":
                    return None
                continue
            victim = self._youngest_running()
            running = sum(r is not None for r in self._slots)
            if victim is None or (victim is req and running == 1):
                # sole runner with a dry pool: submit-time validation
                # bounds TOTAL need, so this is an external consumer of
                # the pool — fail loudly rather than preempt-requeue-wedge
                self._complete(req, "failed",
                               error="KV page pool exhausted")
                return None
            self._preempt(victim)
            if victim is req:
                return None

    def _ensure_capacity(self, active_slots: List[int]):
        """Every active sequence about to write position `ctx` needs
        (a) the page ctx // page_size allocated — grow by one where the
        boundary was crossed — and (b) EXCLUSIVE ownership of the page
        it writes into: a shared (refcount > 1) write page is forked
        copy-on-write — one donated dispatch copies the page across
        every layer's pools, the block table repoints, and the other
        sharers keep the original. Preempts the youngest request when
        the pool is dry. A drafting model's iteration writes TWO rows,
        and where one is unread the host knows the first of them to
        within one: every page from the nearest row it may write to the
        furthest is grown and owned."""
        from ..ops.pallas import paged_attention as _pa
        ps, per = self.page_size, 1 + self._drafts
        for slot in list(active_slots):
            req = self._slots[slot]
            if req is None:
                continue
            # by tokens DISPATCHED: an unread iteration has written its
            # position already (`unread` counts the most it may have)
            ctx = len(req.prompt) + len(req.generated) + req.unread
            # the rows this dispatch may write: from the one after the
            # fewest tokens the unread iteration yields to the draft's
            # after the most (an unread prefill yields exactly one)
            first = ctx - 1 - self._drafts * (req.unread // per)
            last = ctx - 1 + self._drafts
            # never past what `make_request` held the pool to
            need = min((last + 1) // ps + 1,
                       -(-(len(req.prompt) + req.max_new_tokens) // ps))
            dead = False
            while len(req.pages) < need:
                page = self._alloc_one_or_preempt(req)
                if page is None:
                    dead = True
                    break
                req.pages.append(page)
                self._block_tables[slot, len(req.pages) - 1] = page
                self._tables_dirty = True
            if dead or self._slots[slot] is not req:
                continue
            # copy-on-write: the page(s) receiving this iteration's K/V
            # write (without drafts: position ctx-1 = the token sampled
            # last iteration)
            for write_idx in range(first // ps, last // ps + 1):
                if write_idx >= len(req.pages):
                    break
                old = req.pages[write_idx]
                if not self.allocator.is_shared(old):
                    continue
                fresh = self._alloc_one_or_preempt(req)
                if fresh is None:
                    break
                self.cache.k_pages, self.cache.v_pages = _pa.cow_copy_pages(
                    self.cache.k_pages, self.cache.v_pages, old, fresh)
                self._block_tables[slot, write_idx] = fresh
                self._tables_dirty = True
                req.pages[write_idx] = fresh
                self.allocator.free([old])  # drop this holder's shared ref
                self.stats["cow_copies"] += 1

    def _youngest_running(self) -> Optional[Request]:
        running = [r for r in self._slots if r is not None]
        if not running:
            return None
        return max(running, key=lambda r: r.submitted_ts)

    def _lane_arrays(self, active_slots: List[int]):
        """Gather the active slots into W bucketed lanes (W = smallest
        decode bucket covering the active count), packed as the decode
        program takes them: int32 [6, W] (tokens, or -1 for "the one the
        unread iteration sampled for this slot"; slot map, lane-active,
        top-k, seeds, steps; for a drafting model a seventh row, the
        standing drafts) and float32 [2, W] (temperature, top-p).
        Padding lanes carry the slot sentinel `max_batch` (clamp-gather +
        drop-scatter in forward_decode) and greedy sampling params (so an
        all-greedy batch keeps the sampler's argmax fast path)."""
        n = len(active_slots)
        W = self._decode_bucket(n)
        lanes_i = np.zeros((6 + self._drafts, W), np.int32)
        lanes_f = np.zeros((2, W), np.float32)
        tokens, slot_map, lane_active, top_k, seeds, steps = lanes_i[:6]
        temp, top_p = lanes_f
        slot_map[:] = self.max_batch
        top_p[:] = 1.0
        for i, slot in enumerate(active_slots[:W]):
            req = self._slots[slot]
            sp = req.sampling
            slot_map[i] = slot
            tokens[i] = self._cur_tokens[slot]
            lane_active[i] = 1
            temp[i] = sp.temperature
            top_k[i] = sp.top_k
            top_p[i] = sp.top_p
            seeds[i] = req.seed
            steps[i] = len(req.generated) + req.unread
            if self._drafts:
                lanes_i[6, i] = self._cur_drafts[slot]
        return W, lanes_i, lanes_f

    def _decode_iteration(self, active_slots: List[int]) -> int:
        self._maybe_audit_once()
        # chaos: an armed `serving.decode=N:delay` sleeps here, inflating
        # TTFT/TPOT exactly like a slow device would (the SLO-breach drill)
        try:
            _fault_site("serving.decode")
        except Exception:
            pass  # only delay/no-op kinds make sense here; ignore others
        with _span("lanes", lanes=self._decode_bucket(len(active_slots)),
                   active=len(active_slots)):
            W, lanes_i, lanes_f = self._lane_arrays(active_slots)
        # per-bucket watchdog site: ONE signature per lane width is the
        # zero-retrace steady-state contract
        self._observe_site(f"decode:{self.name}:w{W}", [lanes_i, lanes_f])
        from ..profiler import compile_watch as _cw
        prev = _cw.push_entry("to_static", f"serving_decode:{self.name}")
        t0 = time.perf_counter()
        # what this iteration hands to the device: the tables if a row
        # changed, here, and the two lane arrays, which travel as NumPy
        # with the call itself (cheaper than a `device_put` each)
        with _span("upload", transfers=self._count_transfers(2)):
            self._refresh_tables()
            args = (self._params, self._buffers, self.cache,
                    self._last_tokens, lanes_i, lanes_f)
        in_flight = self._inflight
        seq = self.stats["launches"]
        try:
            # see _prefill: canary serialization
            with _span("dispatch", seq=seq,
                       iteration=self.stats["iterations"],
                       ahead=int(in_flight is not None)), \
                    self._dispatch_lock:
                if self.decode_mode == "fused":
                    nxt, self.cache, self._last_tokens = \
                        self._fused_jit(*args)
                else:
                    # eager A/B baseline: identical math, per-op dispatch
                    nxt, self.cache, self._last_tokens = \
                        self._fused_step_fn(*args)
        finally:
            _cw.pop_entry(prev)
        # the program bumped each active lane's length: so does the host
        # (by how much a drafting model's did, `_read` will know)
        if not self._drafts:
            self._context_lens[active_slots] += 1
        reqs = [self._slots[slot] for slot in active_slots]
        for req in reqs:
            req.unread += 1 + self._drafts
        # until this iteration is read its tokens are the device's alone
        self._cur_tokens[active_slots] = -1
        self._inflight = (nxt, reqs, W, self.stats["iterations"], seq)
        self.stats["iterations"] += 1
        self.stats["launches"] += 1
        self.stats["ahead_iterations"] += in_flight is not None
        if self._walk_span and not self._drafts:
            # one layer's walk of this iteration; a padding lane is idle
            self._count_page_walk(self._context_lens[active_slots],
                                  W - len(reqs))
        self.stats["decode_wall_s"] += time.perf_counter() - t0
        # the admissions' first tokens, while the device runs this
        # iteration behind their prefills
        self._read_prefills()
        if in_flight is not None:
            self._read(in_flight)
        if not self._may_run_ahead(reqs):
            self.stats["drained_for_length"] += 1
            self._drain()
        return len(reqs)

    def _count_page_walk(self, lengths, idle_lanes: int):
        """One paged layer's walk over lanes of these lengths."""
        from ..ops.pallas.paged_attention import page_group_counts
        live, walked = page_group_counts(lengths, self._walk_span)
        self.stats["page_groups_live"] += live
        self.stats["page_groups_walked"] += walked + idle_lanes

    @staticmethod
    def _may_run_ahead(reqs: List[Request]) -> bool:
        """Whether the iteration just dispatched for `reqs` may stay
        unread while the next one is dispatched: only if none of its
        tokens is a request's last by length (`unread` counts the MOST
        tokens an unread iteration may yield: two of a drafting model).
        That token frees a slot, and whoever takes the slot should find
        the device idle, not one iteration ahead."""
        return all(len(r.generated) + r.unread < r.max_new_tokens
                   for r in reqs if r.state == "running")

    def _drain(self):
        """Read and book the unread prefills and iteration, if there are
        any. Whatever takes, frees or moves a slot outside `_read` calls
        this first."""
        with self._step_lock:
            self._read_prefills()
            in_flight, self._inflight = self._inflight, None
            if in_flight is not None:
                self._read(in_flight)

    def _read(self, in_flight: tuple):
        """Fetch one dispatched iteration's tokens and book each to the
        REQUEST its lane was dispatched for (the slot may be somebody
        else's by now). A request that has ended since, by an end of
        sequence in the iteration before, drops its token. A drafting
        model's lane brings one or two tokens, booked in order: what
        follows an end of sequence or the budget's last is dropped."""
        nxt, reqs, W, iteration, seq = in_flight
        t0 = time.perf_counter()
        with _span("fetch", seq=seq, iteration=iteration):
            nxt_np = np.asarray(nxt)  # device sync: the iteration boundary
        waited = time.perf_counter() - t0
        self.stats["decode_wall_s"] += waited
        self.stats["read_wait_s"] += waited
        newest = self._inflight is None
        per = 1 + self._drafts
        if self._drafts:
            emitted = [nxt_np[i, :nxt_np[i, 2]].tolist()
                       for i in range(len(reqs))]
            self._book_drafts(reqs, nxt_np, W)
            args = {"lanes": W, "seq": seq,
                    "tokens": sum(len(toks) for toks in emitted)}
        else:
            emitted = [[int(tok)] for tok in nxt_np[:len(reqs)]]
            args = {"lanes": W}
        with _span("bookkeep", **args):
            produced = 0
            for i, req in enumerate(reqs):
                req.unread -= per
                if req.state != "running":
                    continue
                slot, toks = req.slot, self._kept(req, emitted[i])
                self.tracer.decode_iteration(req.rid, bucket=W,
                                             path=self.decode_mode,
                                             tokens=len(toks))
                for tok in toks:
                    self._record_token(req, tok)
                produced += len(toks)
                if req.state != "running":
                    continue
                if newest:
                    self._cur_tokens[slot] = toks[-1]
                if self._drafts:
                    req.drafts.append((len(req.generated),
                                       int(nxt_np[i, 3])))
                    self._cur_drafts[slot] = nxt_np[i, 3]
            self.stats["decode_tokens"] += produced
            self.stats["discarded_tokens"] += sum(map(len, emitted)) \
                - produced
            if _metrics.enabled():
                # re-publish occupancy AFTER completions so a drained
                # batch reads 0 even when no further step() runs
                _M_OCC.set(sum(r is not None for r in self._slots),
                           model=self.name)
            self._note_introspection(len(reqs), iteration + 1)

    @staticmethod
    def _kept(req: Request, toks: List[int]) -> List[int]:
        """What of an iteration's tokens the request takes: up to its
        budget, and nothing after an end of sequence."""
        toks = toks[:req.max_new_tokens - len(req.generated)]
        if req.eos_id >= 0 and req.eos_id in toks:
            toks = toks[:toks.index(req.eos_id) + 1]
        return toks

    def _book_drafts(self, reqs: List[Request], nxt_np, W: int):
        """What the host could not know at the dispatch of a drafting
        model's iteration: how far each lane's context moved (1, or 2
        where its draft was accepted), hence the page groups the paged
        layers walked (two rows a lane, at the context's length and one
        more) and the drafts verified and accepted."""
        live = [i for i, r in enumerate(reqs)
                if r.state == "running" and self._slots[r.slot] is r]
        slots = [reqs[i].slot for i in live]
        if self._walk_span:
            before = self._context_lens[slots]
            self._count_page_walk(
                np.concatenate([before + 1, before + 2]),
                2 * (W - len(live)))
        self._context_lens[slots] += nxt_np[live, 2]
        self.stats["draft_tokens"] += len(reqs)
        self.stats["accepted_tokens"] += int((nxt_np[:len(reqs), 2] == 2)
                                             .sum())

    def _record_token(self, req: Request, tok: int):
        req.generated.append(tok)
        if _metrics.enabled():
            # per-token goodput (prefill's first token included)
            _M_GOODPUT.inc(1.0, model=self.name)
        if req.eos_id >= 0 and tok == req.eos_id:
            self._complete(req, "eos")
        elif len(req.generated) >= req.max_new_tokens:
            self._complete(req, "length")

    def _complete(self, req: Request, reason: str,
                  error: Optional[str] = None):
        """Free the request's slot + pages; reason eos|length|failed."""
        self._release_slot(req)
        req.finish_reason = reason
        req.done_ts = time.monotonic()
        req.state = "failed" if reason == "failed" else "done"
        req.error = error
        if reason != "failed":
            self.stats["completed"] += 1
            if _metrics.enabled() and req.tpot_s is not None:
                _M_TPOT.observe(req.tpot_s, model=self.name,
                                path=self.decode_mode)
            if req.tpot_s is not None:
                self.slo.observe("tpot", req.tpot_s)
            self.slo.observe("e2e", req.done_ts - req.submitted_ts)
        self.tracer.complete(req.rid, reason, error=error)
        self._emit_eviction(req, reason)
        req._done.set()

    def _preempt(self, req: Request):
        """Recompute-style preemption: pages freed (shared pages only
        DECREF — a page another request still references never returns
        to the pool), request requeued with its generated prefix as part
        of the next admission's prompt."""
        self._drain()   # its unread token is part of that prefix
        if req.state != "running":
            return      # it ended in the iteration just read
        self._release_slot(req)
        self.tracer.preempted(req.rid)
        req.state = "queued"
        req.slot = None
        req.preemptions += 1
        self.stats["preemptions"] += 1
        hook = self.on_preempt_requeue
        if hook is not None:
            # disaggregated pipeline: the recompute-style resume re-runs
            # prefill (prompt + generated prefix), so route the request
            # back to the PREFILL stage instead of this engine's queue
            hook(req)
        else:
            with self._lock:
                self._queue.appendleft(req)
                depth = len(self._queue)
            if _metrics.enabled():
                _M_QUEUE.set(depth, model=self.name)
        self._emit_eviction(req, "preempted")

    def _release_slot(self, req: Request):
        slot = req.slot
        if slot is not None and self._slots[slot] is req:
            self._slots[slot] = None
            self._cur_tokens[slot] = 0
            # host only: the zeroed row rides on the next refresh, and
            # the device's stale length is never read (no lane names an
            # idle slot; prefill overwrites it on the next admission)
            self._point_slot(slot)
            self._context_lens[slot] = 0
        self.allocator.free(req.pages)
        req.pages = []

    # -- events ---------------------------------------------------------------
    def _emit_admission(self, req: Request, bucket: int, prompt_len: int):
        _events.emit(
            "serving_admission", model=self.name, request=req.rid,
            slot=req.slot, prompt_len=prompt_len, bucket=bucket,
            queue_wait_s=round(time.monotonic() - req.submitted_ts, 4),
            preemptions=req.preemptions,
            shared_tokens=req.shared_tokens,
            free_pages=self.allocator.free_pages)

    def _emit_eviction(self, req: Request, reason: str):
        _events.emit(
            "serving_eviction",
            severity="warn" if reason in ("preempted", "failed") else "info",
            model=self.name, request=req.rid, reason=reason,
            generated=len(req.generated),
            free_pages=self.allocator.free_pages)

    # -- introspection / HTTP serving surface ---------------------------------
    def requests_snapshot(self, n: int = 50) -> Dict:
        """The `/requests` endpoint payload: live + recently-completed
        per-request phase breakdowns plus the per-iteration engine
        introspection ring."""
        snap = self.tracer.snapshot(n)
        with self._lock:
            snap["queue_depth"] = len(self._queue)
        snap["occupancy"] = sum(r is not None for r in self._slots)
        snap["cache"] = self.cache_snapshot()
        snap["introspection"] = self.introspection(n)
        return snap

    def cache_snapshot(self) -> Dict:
        """The decode cache by kind, for `status()` and `/requests`: the
        page pools (layers, K/V heads, pages used / free / parked, bytes),
        the per-slot recurrent states (layers, bytes a slot, slots in use)
        and how many layers hold nothing."""
        d = self.cache.describe()
        free, parked = self.allocator.free_pages, self.allocator.reserved_pages
        return {
            "cacheless_layers": d["cacheless_layers"],
            "pages": {"layers": d["kv_layers"], "kv_heads": d["num_kv_heads"],
                      "page_size": d["page_size"],
                      "total": d["num_pages"] - 1, "free": free,
                      "parked": parked,
                      "used": d["num_pages"] - 1 - free - parked,
                      "bytes_per_page": d["page_bytes"],
                      "bytes": d["pool_bytes"] - d["window_bytes"]},
            "window": {"layers": d["window_layers"], "tokens": d["window"],
                       "slots": d["slots"], "bytes": d["window_bytes"]},
            "state": {"layers": d["state_layers"], "shape": d["state_shape"],
                      "conv_shape": d["conv_state_shape"],
                      "bytes_per_slot": d["state_bytes_per_slot"],
                      "slots": d["slots"],
                      "slots_in_use": sum(r is not None
                                          for r in self._slots),
                      "bytes": d["state_bytes"]},
        }

    def wedged(self, stall_after: Optional[float] = None) -> bool:
        """True when the engine holds work but has not completed a decode
        iteration for `stall_after` seconds (default: the /healthz stall
        threshold, PADDLE_TPU_HEALTH_STALL_SEC) — the shed signal
        /generate turns into a 503 instead of hanging a client."""
        if stall_after is None:
            stall_after = env_float("PADDLE_TPU_HEALTH_STALL_SEC", 300.0)
        if not self.pending():
            return False
        if self._closed:
            return True
        return (time.monotonic() - self._last_progress) > stall_after

    def generate(self, prompt: Sequence[int], max_new_tokens: int = 16,
                 sampling: Optional[SamplingParams] = None,
                 timeout: float = 120.0) -> Dict:
        """Synchronous one-call inference for the `/generate` endpoint:
        submit, (drive the loop inline when no background thread runs),
        wait, and return an endpoint-serializable result."""
        req = self.submit(prompt, max_new_tokens=max_new_tokens,
                          sampling=sampling)
        if self._thread is None:
            self.run_until_idle()
        tokens = req.result(timeout=timeout)
        return {
            "request": req.rid,
            "trace_id": req.trace_id,
            "model": self.name,
            "tokens": tokens,
            "finish_reason": req.finish_reason,
            "preemptions": req.preemptions,
            "ttft_s": req.ttft_s,
            "tpot_s": req.tpot_s,
            "e2e_s": (req.done_ts - req.submitted_ts
                      if req.done_ts is not None else None),
        }

    # -- status ---------------------------------------------------------------
    def status(self) -> Dict:
        with self._lock:
            return {
                "model": self.name,
                "max_batch": self.max_batch,
                "max_len": self.max_len,
                "page_size": self.page_size,
                "num_pages": self.cache.num_pages,
                "free_pages": self.allocator.free_pages,
                "cache": self.cache_snapshot(),
                "queue_depth": len(self._queue),
                "occupancy": sum(r is not None for r in self._slots),
                "prefill_buckets": list(self.prefill_buckets),
                "decode_buckets": list(self.decode_buckets),
                "decode_mode": self.decode_mode,
                "tp_degree": self.tp_degree(),
                "tp_axis": self.tp_axis if self.mesh is not None else None,
                "share_prefix": self.share_prefix,
                "prefix_entries": len(self._prefix),
                "priority": self.priority,
                "mem_budget_bytes": self.mem_budget_bytes,
                "budget_capped_pages": self._budget_capped,
                "reserved_pages": self.allocator.reserved_pages,
                "queue_limit": self.queue_limit,
                "suspended": dict(self._suspended) if self._suspended
                             else None,
                "weights_step": self.weights_step,
                "last_swap": dict(self.last_swap) if self.last_swap
                             else None,
                "stats": dict(self.stats),
                # of a drafting model: the share of verified drafts that
                # were the main model's own token
                "draft_acceptance": (
                    self.stats["accepted_tokens"]
                    / self.stats["draft_tokens"]
                    if self.stats["draft_tokens"] else None),
            }
