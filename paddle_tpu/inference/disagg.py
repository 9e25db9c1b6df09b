"""Disaggregated prefill/decode serving: a two-stage pipeline with
explicit KV-page handoff.

Co-locating compute-bound prefill with bandwidth-bound decode makes
TTFT and TPOT fight each other: one long prompt's prefill stalls every
in-flight request's next token for the whole forward pass. Splitting
the stages onto separate device groups (the DistServe/Splitwise shape,
and the heter-PS prepare-pipeline pattern: one group PRODUCES KV, the
other CONSUMES it) bounds that interference to the handoff cost:

* :class:`PrefillWorker` — owns one device OUTSIDE the decode group, a
  private single-slot paged cache, and a device-local replica of the
  serving weights (refreshed when the engine hot-swaps). It runs the
  bucketed prefill + first-token sample there and extracts the written
  K/V pages into a :class:`KVHandoff` payload (page count padded to a
  power-of-two bucket, so extraction and decode-side injection each
  compile one executable per bucket for the life of the pipeline);
* :class:`KVHandoff` — the unit moved between stages: the request, the
  per-layer page payloads, and the produce timestamp that becomes the
  ``serving_handoff_wait_seconds`` observation (and the ``handoff_wait``
  SLO signal) at admission;
* :class:`DisaggPipeline` — the two-stage continuous-batching loop:
  queued requests dispatch to idle prefill workers, finished payloads
  queue on the handoff plane (``serving_handoff_depth``), and the
  decode engine admits them into free slots via
  ``ServingEngine.admit_handoff`` — pages allocated, payload scattered
  in ONE donated dispatch, decode resumed from the worker's first
  sampled token. Per-stage busy counts land on
  ``serving_stage_occupancy{stage=prefill|decode}``.

Preemption stays recompute-style end to end: the engine's
``on_preempt_requeue`` hook routes an evicted request back to the
PREFILL stage (its next admission re-prefills prompt + generated
prefix), so pool pressure on the decode side never wedges the pipeline.

Worker fault tolerance (PR 20): every worker heartbeats through the
pipeline (``beat()`` around each prefill), and the DECODE side reaps —
``_handoff_peek`` runs at the top of every engine step, so a worker
whose beat went silent past ``worker_ttl_s`` (or that raised, including
the ``disagg.prefill`` chaos site) is retired there: its in-flight
request requeues to the surviving workers with its ORIGINAL trace id,
the queue drains to the decode engine's own colocated prefill when no
worker survives, and a fresh worker respawns into the slot (the PR-3
DataLoader respawn contract: bounded respawns per slot, a loud event +
``disagg_worker_restarts_total`` each time). Requeues are bounded per
request (``max_attempts`` dispatches); exhaustion fails the request
loudly through ``Request.result()`` — never a silent hang.

Tokens are bit-exact vs the co-located engine: the worker runs the
identical prefill math (same bucket, same in-graph sampling draw at the
same step counter) and the injected pages are byte-identical to the
ones prefill would have written in place. TP decode composes — the
payload replicates onto the decode mesh at admission and the scatter
runs under the pools' head sharding.
"""
from __future__ import annotations

import threading
import time
import warnings
from collections import deque
from typing import List, Optional, Sequence

import numpy as np

from ..fault import site as _fault_site
from ..framework import tape as tape_mod
from ..framework.tensor import Tensor
from ..profiler import events as _events
from ..profiler import metrics as _metrics
from .sampling import SamplingParams, sample_logits
from .serving import (ServingEngine, Request, _M_HANDOFF_DEPTH, _M_QUEUE,
                      _M_STAGE_OCC, _M_TTFT)

__all__ = ["KVHandoff", "PrefillWorker", "DisaggPipeline"]

_REG = _metrics.default_registry()
_M_W_RESTARTS = _REG.counter(
    "disagg_worker_restarts_total",
    "prefill workers respawned into their slot after an error or a "
    "missed-heartbeat death (bounded per slot; past the cap the slot "
    "is disabled and its load reroutes)")
_M_REQUEUE = _REG.counter(
    "disagg_requeue_total",
    "requests rerouted after losing their prefill worker, by reason "
    "(worker_error: the prefill raised / worker_dead: the worker's "
    "heartbeat went silent past the TTL / colocated: no surviving "
    "worker — the decode engine prefills it itself)")


def _pow2_pad(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p


def _extract_pages_impl(k_pages, v_pages, page_ids):
    """Gather the per-layer pages a prefill just wrote into a dense
    payload [P_pad, page_size, H*D] (a page as the pools store it, heads
    folded). Padding ids are the null page 0 —
    its garbage rows scatter back onto page 0 at the decode side."""
    return ([kp[page_ids] for kp in k_pages],
            [vp[page_ids] for vp in v_pages])


class KVHandoff:
    """One prefilled request crossing the prefill->decode boundary."""

    __slots__ = ("request", "k_payload", "v_payload", "bucket",
                 "produced_ts", "worker")

    def __init__(self, request: Request, k_payload, v_payload,
                 bucket: int, worker: int):
        self.request = request
        self.k_payload = k_payload
        self.v_payload = v_payload
        self.bucket = int(bucket)
        self.worker = int(worker)
        self.produced_ts = time.monotonic()

    @property
    def nbytes(self) -> int:
        return int(sum(int(k.nbytes) + int(v.nbytes)
                       for k, v in zip(self.k_payload, self.v_payload)))


class PrefillWorker:
    """One prefill device: private single-slot paged cache + a device-
    local weights replica. ``prefill(req)`` runs the bucketed prefill
    and the first-token sample on THIS device and returns the KVHandoff
    (or None when the request finished at the prefill stage)."""

    def __init__(self, engine: ServingEngine, device, wid: int = 0):
        import jax

        self.engine = engine
        self.device = device
        self.wid = int(wid)
        self.busy = False
        #: liveness plane (all guarded by the PIPELINE's lock): `alive`
        #: drops when the worker errors or its heartbeat goes silent;
        #: `retired` marks the object replaced in its slot — a wedged
        #: prefill that eventually returns must DISCARD its result (the
        #: request was already requeued by the reaper); `current` is the
        #: in-flight request the reaper steals on death
        self.alive = True
        self.retired = False
        self.current: Optional[Request] = None
        self.last_beat = time.monotonic()
        model = engine.model
        pages_per_seq = -(-engine.max_len // engine.page_size)
        # null page + exactly one sequence's worth of pages; the block
        # table row is FIXED at [1..pages_per_seq] for the worker's life
        cache = model.init_cache(1, engine.max_len,
                                 page_size=engine.page_size,
                                 num_pages=1 + pages_per_seq,
                                 sharded=False)
        self._page_row = np.arange(1, pages_per_seq + 1, dtype=np.int32)
        import jax.numpy as jnp
        cache.block_tables = cache.block_tables.at[0].set(
            jnp.asarray(self._page_row))
        self.cache = jax.device_put(cache, device)
        self._params = None
        self._buffers = None
        self._seen_step = object()  # != any weights_step -> first refresh
        # worker-private executables: one prefill per prompt bucket, one
        # page extraction per pow2 page-count bucket. The cache donates
        # (pools update in place every prefill); extraction is a pure
        # gather and must NOT donate — the pools are reused next request.
        self._prefill_jit = jax.jit(self._prefill_fn, donate_argnums=(2,))
        self._extract_jit = jax.jit(_extract_pages_impl)

    def _prefill_fn(self, params, buffers, cache, ids, slot, length,
                    write_start, temp, top_k, top_p, seed, step):
        from ..jit import _swapped_state
        model = self.engine.model
        with tape_mod.no_grad(), _swapped_state(model, params, buffers):
            # use_tp=False: the private cache is unsharded regardless of
            # the decode mesh — prefill is compute-bound and runs whole
            logits, cache = model.forward_prefill(
                Tensor(ids), cache, slot, length, write_start=write_start,
                use_tp=False)
        nxt = sample_logits(logits.data, temp, top_k, top_p, seed, step)
        return nxt, cache

    def _refresh_weights(self):
        """Device-local weights replica, re-pulled whenever the engine's
        live weights changed (hot-swap / rollback): `weights_step` is
        the swap plane's version marker. A mesh-replicated source
        gathers onto this worker's single device transparently."""
        import jax
        eng = self.engine
        step = eng.weights_step
        if self._params is not None and step == self._seen_step:
            return
        self._params = jax.device_put(dict(eng._params), self.device)
        self._buffers = jax.device_put(dict(eng._buffers), self.device)
        self._seen_step = step

    def beat(self):
        self.last_beat = time.monotonic()

    def prefill(self, req: Request) -> Optional[KVHandoff]:
        import jax.numpy as jnp
        eng = self.engine
        self.beat()
        # chaos: `disagg.prefill` kills this worker mid-prefill (error
        # kinds surface as a worker death — requeue + respawn; delay
        # kinds wedge it past the heartbeat TTL for the reaper drill)
        _fault_site("disagg.prefill")
        if self.retired:
            # reaped while wedged (an injected delay past the TTL): the
            # request was already requeued elsewhere — abort before
            # touching it, or its tokens would be recorded twice
            raise RuntimeError("prefill worker reaped mid-dispatch")
        self._refresh_weights()
        tokens = req.prompt + req.generated
        bucket = eng._bucket_for(len(tokens))
        ids = np.zeros((1, bucket), np.int32)
        ids[0, :len(tokens)] = tokens
        if req.admitted_ts is None:
            req.admitted_ts = time.monotonic()
            eng.slo.observe("queue_wait",
                            req.admitted_ts - req.submitted_ts)
        eng.tracer.admitted(req.rid, bucket=bucket,
                            prompt_tokens=len(tokens), shared_tokens=0,
                            requeue=req.preemptions > 0)
        eng._observe_site(f"disagg_prefill:{eng.name}:w{self.wid}", [ids])
        sp = req.sampling
        from ..profiler import compile_watch as _cw
        prev = _cw.push_entry("to_static", f"disagg_prefill:{eng.name}")
        try:
            # the dispatch lock serializes TRACING against the engine
            # (model-state rebinds must not interleave); dispatch is
            # async, so the device-sync below overlaps with decode
            with eng._dispatch_lock:
                nxt, self.cache = self._prefill_jit(
                    self._params, self._buffers, self.cache,
                    jnp.asarray(ids), np.int32(0),
                    np.int32(len(tokens)), np.int32(0),
                    jnp.full((1,), sp.temperature, jnp.float32),
                    jnp.full((1,), sp.top_k, jnp.int32),
                    jnp.full((1,), sp.top_p, jnp.float32),
                    jnp.full((1,), req.seed, jnp.int32),
                    jnp.full((1,), len(req.generated), jnp.int32))
        finally:
            _cw.pop_entry(prev)
        self.beat()  # liveness proven through the dispatch itself
        if self.retired:
            # the reaper fired while the dispatch was in flight and the
            # request is being re-prefilled: recording this late token
            # would corrupt the resumed sequence
            raise RuntimeError("prefill worker reaped mid-dispatch")
        tok = int(np.asarray(nxt)[0])
        eng.tracer.prefill_done(req.rid)
        now = time.monotonic()
        if req.first_token_ts is None:
            req.first_token_ts = now
            if _metrics.enabled() and req.ttft_s is not None:
                _M_TTFT.observe(req.ttft_s, model=eng.name,
                                path=eng.decode_mode)
            if req.ttft_s is not None:
                eng.slo.observe("ttft", req.ttft_s)
        # counted apart from stats["prefills"]: that one counts prefills
        # the DECODE engine ran itself, and under disaggregation it must
        # stay 0 (the bench gate pins decode_prefills == 0 on it)
        eng.stats["worker_prefills"] += 1
        eng._record_token(req, tok)
        if req.state != "queued":
            return None  # finished (or failed) at the prefill stage
        n_pages = -(-len(tokens) // eng.page_size)
        pad = _pow2_pad(n_pages)
        gather = np.zeros((pad,), np.int32)
        gather[:n_pages] = self._page_row[:n_pages]
        k_pay, v_pay = self._extract_jit(
            self.cache.k_pages, self.cache.v_pages, jnp.asarray(gather))
        return KVHandoff(req, k_pay, v_pay, bucket=bucket, worker=self.wid)


class DisaggPipeline:
    """Two-stage continuous batching over one decode engine plus N
    prefill workers. Drive it synchronously (`submit` then
    `run_until_idle`, tests/bench) or threaded (`start()` spawns one
    loop per prefill worker, a handoff drainer, and the engine's decode
    loop; `close()` joins everything).

    `prefill_devices` defaults to devices OUTSIDE the engine's TP mesh
    (the disaggregation claim: prefill compute never steals decode
    bandwidth); when none are free it falls back to sharing — the
    pipeline semantics (and the A/B bench) still hold."""

    def __init__(self, engine: ServingEngine, *,
                 prefill_devices=None, num_workers: int = 1,
                 max_attempts: int = 3, worker_ttl_s: float = 10.0,
                 max_worker_restarts: int = 3):
        import jax

        if getattr(engine.model, "draft_tokens", 0):
            from ..models.decode_cache import DraftingUnsupported
            raise DraftingUnsupported(
                "disaggregated prefill/decode (DisaggPipeline)",
                "a hand-off of the standing draft and of the drafting "
                "module's rows beside the K/V pages (a prefill worker "
                "runs the main model alone)",
                draft_tokens=int(engine.model.draft_tokens))
        if engine.cache.has_state:
            from ..models.decode_cache import StateLayersUnsupported
            d = engine.cache.describe()
            raise StateLayersUnsupported(
                "disaggregated prefill/decode (DisaggPipeline)",
                "a hand-off of the slot's recurrent and convolution state "
                "beside its K/V pages (KVHandoff carries pages only)",
                kv_layers=d["kv_layers"], state_layers=d["state_layers"])
        if engine.cache.has_window:
            from ..models.decode_cache import WindowLayersUnsupported
            d = engine.cache.describe()
            raise WindowLayersUnsupported(
                "disaggregated prefill/decode (DisaggPipeline)",
                "a hand-off of the slot's window rings beside its K/V "
                "pages (KVHandoff carries the pages a block table names)",
                kv_layers=d["kv_layers"], window_layers=d["window_layers"])
        self.engine = engine
        #: per-request dispatch bound: a request whose prefill keeps
        #: losing its worker is failed LOUDLY through result() after
        #: `max_attempts` dispatches — never parked forever
        self.max_attempts = max(1, int(max_attempts))
        #: heartbeat TTL: a busy worker silent this long is reaped by
        #: the decode side (its jit is wedged or its thread died)
        self.worker_ttl_s = float(worker_ttl_s)
        #: respawns allowed per worker slot (the PR-3 DataLoader
        #: respawn contract); past the cap the slot is disabled
        self.max_worker_restarts = max(0, int(max_worker_restarts))
        self._attempts: dict = {}   # rid -> dispatches so far
        self._restarts: dict = {}   # wid -> respawns so far
        if prefill_devices is None:
            taken = set()
            if engine.mesh is not None:
                taken = {d for d in np.asarray(engine.mesh.devices).flat}
            prefill_devices = [d for d in jax.devices()
                               if d not in taken] or list(jax.devices())
        self.workers: List[PrefillWorker] = [
            PrefillWorker(engine, prefill_devices[i % len(prefill_devices)],
                          wid=i)
            for i in range(max(1, int(num_workers)))]
        self._queue: "deque[Request]" = deque()
        self._handoffs: "deque[KVHandoff]" = deque()
        self._lock = threading.Lock()
        self._threads: List[threading.Thread] = []
        self._running = False
        # decode-side preemption re-enters the PREFILL stage (the
        # recompute resume re-runs prefill over prompt + generated);
        # the engine drains our handoff queue at the top of every
        # step() via the peek/pop protocol — injection stays on the
        # decode thread, never racing the donated decode dispatch
        engine.on_preempt_requeue = self._on_preempt
        engine.handoff_source = self

    # -- admission ------------------------------------------------------------
    def submit(self, prompt: Sequence[int], max_new_tokens: int = 16,
               eos_id: Optional[int] = None,
               sampling: Optional[SamplingParams] = None) -> Request:
        eng = self.engine
        req = eng.make_request(prompt, max_new_tokens, eos_id,
                               sampling=sampling)
        with self._lock:
            if eng.queue_limit is not None \
                    and len(self._queue) >= eng.queue_limit:
                raise RuntimeError(
                    f"queue at shed cap ({eng.queue_limit}); "
                    f"engine {eng.name!r} is shedding load")
            self._queue.append(req)
            depth = len(self._queue)
        req.trace_id = eng.tracer.submit(req.rid)
        if _metrics.enabled():
            _M_QUEUE.set(depth, model=eng.name)
        return req

    def _on_preempt(self, req: Request):
        with self._lock:
            self._queue.appendleft(req)
            depth = len(self._queue)
        if _metrics.enabled():
            _M_QUEUE.set(depth, model=self.engine.name)

    # -- worker fault tolerance -----------------------------------------------
    def _reap_dead_workers(self):
        """Decode-side death detection: runs at the top of every engine
        step (via ``_handoff_peek``). A busy worker whose heartbeat went
        silent past ``worker_ttl_s`` is retired — its in-flight request
        requeued, a replacement respawned into the slot — and with no
        surviving worker the queue drains to colocated prefill."""
        now = time.monotonic()
        victims = []
        with self._lock:
            for w in self.workers:
                if not w.alive or w.retired or not w.busy:
                    continue
                stall = now - w.last_beat
                if stall <= self.worker_ttl_s:
                    continue
                w.alive = False
                w.retired = True
                req, w.current = w.current, None
                victims.append((w, req, stall))
        for w, req, stall in victims:
            err = f"no heartbeat for {stall:.1f}s (ttl {self.worker_ttl_s}s)"
            self._respawn(w, "worker_dead", err)
            self._requeue(req, "worker_dead", err)
        self._drain_to_colocated()

    def _on_worker_error(self, w: PrefillWorker, req: Request, exc):
        """A prefill raised (including the ``disagg.prefill`` chaos
        site): the worker is dead — requeue its request, respawn."""
        with self._lock:
            if w.retired:
                return  # the reaper got here first and took the request
            w.alive = False
            w.retired = True
            w.busy = False
            w.current = None
        err = f"{type(exc).__name__}: {exc}"
        self._respawn(w, "worker_error", err)
        self._requeue(req, "worker_error", err)
        self._drain_to_colocated()

    def _respawn(self, w: PrefillWorker, cause: str, error=None):
        """Fresh worker into the dead one's slot, same device, bounded
        per slot. Threaded mode also spawns its loop thread."""
        n = self._restarts.get(w.wid, 0) + 1
        self._restarts[w.wid] = n
        eng = self.engine
        if n > self.max_worker_restarts:
            warnings.warn(
                f"disagg prefill worker {w.wid} ({eng.name!r}) died "
                f"{n} times ({cause}); slot disabled")
            _events.emit("disagg_worker_restart", severity="warn",
                         model=eng.name, worker=w.wid, restarts=n,
                         cause=cause, respawned=False, error=error)
            return
        try:
            nw = PrefillWorker(eng, w.device, wid=w.wid)
        except Exception as e:  # noqa: BLE001 — a sick device must not
            warnings.warn(      # take the whole pipeline down with it
                f"disagg prefill worker {w.wid} respawn failed "
                f"({type(e).__name__}: {e}); slot disabled")
            return
        with self._lock:
            for i, cur in enumerate(self.workers):
                if cur is w:
                    self.workers[i] = nw
                    break
            else:
                return  # slot already replaced by a racing respawn
        if _metrics.enabled():
            _M_W_RESTARTS.inc()
        _events.emit("disagg_worker_restart", severity="warn",
                     model=eng.name, worker=w.wid, restarts=n,
                     cause=cause, respawned=True, error=error)
        if self._running and not eng._closed:
            self._spawn_worker_thread(nw)

    def _requeue(self, req: Optional[Request], reason: str, error=None):
        """Bounded reroute of a request that lost its prefill worker —
        trace id untouched (set once at submit). Exhaustion fails the
        request loudly; with no surviving worker it reroutes to the
        decode engine's own colocated prefill."""
        if req is None:
            return
        eng = self.engine
        attempts = self._attempts.get(req.rid, 0)
        if attempts >= self.max_attempts:
            self._attempts.pop(req.rid, None)
            eng._complete(req, "failed", error=(
                f"disagg prefill gave up after {attempts} attempts "
                f"(last: {reason}" + (f": {error}" if error else "") + ")"))
            return
        if _metrics.enabled():
            _M_REQUEUE.inc(reason=reason)
        with self._lock:
            alive = any(w.alive for w in self.workers)
            if alive:
                self._queue.appendleft(req)
                depth = len(self._queue)
        if alive:
            if _metrics.enabled():
                _M_QUEUE.set(depth, model=eng.name)
            return
        self._to_colocated(req)

    def _to_colocated(self, req: Request):
        """Last resort: hand the request to the decode engine's OWN
        queue — it prefills it itself (stats["prefills"] counts it),
        original trace id preserved."""
        eng = self.engine
        self._attempts.pop(req.rid, None)
        with eng._lock:
            eng._queue.append(req)
            depth = len(eng._queue)
        if _metrics.enabled():
            _M_QUEUE.set(depth, model=eng.name)

    def _drain_to_colocated(self):
        """With NO surviving worker, queued requests would strand —
        reroute every one to colocated prefill (reason="colocated")."""
        with self._lock:
            if any(w.alive for w in self.workers):
                return
            stranded = list(self._queue)
            self._queue.clear()
        for req in stranded:
            if _metrics.enabled():
                _M_REQUEUE.inc(reason="colocated")
            self._to_colocated(req)

    # -- handoff-source protocol (consumed by ServingEngine.step) -------------
    def _handoff_peek(self) -> Optional[KVHandoff]:
        # the decode thread calls this at the top of EVERY step: it is
        # the pipeline's reaper tick — worker death is detected and
        # repaired here even when no handoff is pending
        self._reap_dead_workers()
        with self._lock:
            return self._handoffs[0] if self._handoffs else None

    def _handoff_pop(self, h: KVHandoff):
        with self._lock:
            if self._handoffs and self._handoffs[0] is h:
                self._handoffs.popleft()
            depth = len(self._handoffs)
        if _metrics.enabled():
            _M_HANDOFF_DEPTH.set(depth, model=self.engine.name)

    # -- synchronous drive ----------------------------------------------------
    def step(self) -> int:
        """One pipeline tick: dispatch queued requests to idle prefill
        workers, drain finished payloads into the decode batch, run one
        decode iteration. Returns tokens produced by the decode stage."""
        work = []
        with self._lock:
            for w in self.workers:
                if not self._queue:
                    break
                if w.busy or not w.alive:
                    continue
                w.busy = True
                req = self._queue.popleft()
                w.current = req
                self._attempts[req.rid] = \
                    self._attempts.get(req.rid, 0) + 1
                work.append((w, req))
            if _metrics.enabled():
                _M_QUEUE.set(len(self._queue), model=self.engine.name)
        for w, req in work:
            try:
                h = w.prefill(req)
            except Exception as e:  # noqa: BLE001 — a worker death is a
                self._on_worker_error(w, req, e)  # repairable event
                continue
            self._finish_dispatch(w, req, h)
        self._drain_to_colocated()
        # engine.step() drains the handoff queue first (peek/pop), then
        # admits + decodes — injection happens on THIS thread here
        produced = self.engine.step()
        self._publish_occupancy()
        return produced

    def _finish_dispatch(self, w: PrefillWorker, req: Request,
                         h: Optional[KVHandoff]) -> bool:
        """Atomically (vs the reaper) complete one dispatch: a worker
        retired MID-PREFILL had its request requeued already — its late
        result must be dropped, or the request would run twice (once
        re-prefilled, once from this stale handoff). Returns False when
        the result was dropped."""
        with self._lock:
            if w.retired:
                return False
            w.busy = False
            w.current = None
        self._attempts.pop(req.rid, None)
        if h is not None:
            self._enqueue_handoff(h)
        return True

    def _enqueue_handoff(self, h: KVHandoff):
        with self._lock:
            self._handoffs.append(h)
            depth = len(self._handoffs)
        if _metrics.enabled():
            _M_HANDOFF_DEPTH.set(depth, model=self.engine.name)

    def _publish_occupancy(self):
        if not _metrics.enabled():
            return
        busy = sum(w.busy for w in self.workers
                   if w.alive and not w.retired)
        active = sum(r is not None for r in self.engine._slots)
        _M_STAGE_OCC.set(busy, model=self.engine.name, stage="prefill")
        _M_STAGE_OCC.set(active, model=self.engine.name, stage="decode")

    def pending(self) -> bool:
        with self._lock:
            staged = bool(self._queue) or bool(self._handoffs)
            # a dead worker stuck busy must not read as pending work —
            # its request was (or will be, next reap) requeued
            busy = any(w.busy for w in self.workers
                       if w.alive and not w.retired)
        return staged or busy or self.engine.pending()

    def run_until_idle(self, max_iterations: int = 100000):
        for _ in range(max_iterations):
            if not self.pending():
                return
            self.step()
        raise RuntimeError("run_until_idle: iteration cap exceeded")

    # -- threaded drive -------------------------------------------------------
    def start(self, poll_s: float = 0.005):
        """Background mode: one loop per prefill worker, one handoff
        drainer, and the engine's own decode loop."""
        if self._running:
            return
        self._running = True
        self._poll_s = poll_s
        self.engine.start(poll_s)

        def occupancy_loop():
            # the engine's own decode loop drains the handoff queue;
            # this thread only keeps the per-stage gauges fresh
            while self._running and not self.engine._closed:
                self._publish_occupancy()
                time.sleep(max(poll_s, 0.01))

        for w in self.workers:
            self._spawn_worker_thread(w)
        t = threading.Thread(target=occupancy_loop, daemon=True,
                             name="disagg-occupancy")
        t.start()
        self._threads.append(t)

    def _spawn_worker_thread(self, w: PrefillWorker):
        """One loop per worker OBJECT: a respawned slot gets a fresh
        thread; the retired object's loop exits on its own."""
        poll_s = getattr(self, "_poll_s", 0.005)

        def worker_loop():
            while self._running and not self.engine._closed \
                    and not w.retired:
                w.beat()  # idle liveness: an empty queue is not a wedge
                with self._lock:
                    req = self._queue.popleft() if self._queue else None
                    if req is not None:
                        w.busy = True
                        w.current = req
                        self._attempts[req.rid] = \
                            self._attempts.get(req.rid, 0) + 1
                if req is None:
                    time.sleep(poll_s)
                    continue
                try:
                    h = w.prefill(req)
                except Exception as e:  # noqa: BLE001 — a worker death
                    self._on_worker_error(w, req, e)  # is repairable
                    return  # this worker object is retired; loop ends
                if not self._finish_dispatch(w, req, h):
                    return  # reaped mid-prefill: result dropped

        t = threading.Thread(target=worker_loop, daemon=True,
                             name=f"disagg-prefill-{w.wid}")
        t.start()
        self._threads.append(t)

    def close(self):
        self._running = False
        for t in self._threads:
            t.join(timeout=30)
        self._threads = []
        self.engine.on_preempt_requeue = None
        self.engine.handoff_source = None
        self.engine.close()
        with self._lock:
            leftovers = list(self._queue)
            self._queue.clear()
            self._handoffs.clear()
        for req in leftovers:
            self.engine._complete(req, "failed", error="pipeline closed")

    # -- status ---------------------------------------------------------------
    def status(self) -> dict:
        with self._lock:
            return {
                "stages": {
                    "prefill": {"workers": len(self.workers),
                                "alive": sum(w.alive for w in self.workers),
                                "busy": sum(w.busy for w in self.workers
                                            if w.alive and not w.retired),
                                "restarts": dict(self._restarts),
                                "devices": [str(w.device)
                                            for w in self.workers]},
                    "decode": {"occupancy": sum(
                        r is not None for r in self.engine._slots),
                        "tp_degree": self.engine.tp_degree()},
                },
                "queue_depth": len(self._queue),
                "handoff_depth": len(self._handoffs),
                "handoffs": self.engine.stats.get("handoffs", 0),
                "worker_prefills": self.engine.stats.get(
                    "worker_prefills", 0),
            }
