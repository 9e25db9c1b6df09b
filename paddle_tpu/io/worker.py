"""Multiprocess DataLoader workers with shared-memory tensor transport.

Reference: `_DataLoaderIterMultiProcess`
(/root/reference/python/paddle/fluid/dataloader/dataloader_iter.py:338) +
worker.py + the mmap shared-memory allocator
(`paddle/fluid/memory/allocation/mmap_allocator.cc`): worker processes pull
index batches from per-worker queues, decode+collate, and pass result
tensors through shared memory so only (name, shape, dtype) descriptors
cross the pipe.

TPU adaptation: workers are SPAWNED (a forked child of a process that
already initialized the TPU runtime is unsafe) with JAX forced to CPU —
workers only produce host numpy; the consumer's prefetch thread does the
single `jax.device_put` per batch (BufferedReader's role).
"""
from __future__ import annotations

import multiprocessing as mp
import os
import queue as pyqueue
import threading
import time
import warnings
from dataclasses import dataclass
from multiprocessing import shared_memory
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ..profiler import metrics as _metrics_mod

_REG = _metrics_mod.default_registry()
_M_WORKER_RESTARTS = _REG.counter(
    "dataloader_worker_restarts_total",
    "dead DataLoader worker processes respawned mid-epoch, by exitcode")
_M_WORKER_LOST = _REG.counter(
    "dataloader_worker_lost_total",
    "iterable-mode workers that died and could not be respawned, by "
    "exitcode (their shard is lost; the loader degraded to fewer workers)")

_SENTINEL = "__end__"

# bound lazily on first batch (dataloader imports this module)
_record_fetch_wait = None

_worker_info = None


class WorkerInfo:
    """Visible inside a worker process (reference dataloader/worker.py
    get_worker_info): lets an IterableDataset shard its stream explicitly.
    num_workers/id describe this loader's pool; dataset is the worker's
    copy."""

    def __init__(self, id: int, num_workers: int, dataset):
        self.id = id
        self.num_workers = num_workers
        self.dataset = dataset


def get_worker_info():
    """None in the main process; WorkerInfo inside a DataLoader worker."""
    return _worker_info


@dataclass
class _ShmArray:
    """Descriptor that crosses the worker->consumer pipe."""
    name: str
    shape: tuple
    dtype: str


def _to_shm(obj, segments: List[shared_memory.SharedMemory]):
    """numpy leaves -> shared memory descriptors (structure preserved)."""
    if isinstance(obj, np.ndarray) and obj.nbytes > 0:
        shm = shared_memory.SharedMemory(create=True, size=obj.nbytes)
        dst = np.ndarray(obj.shape, obj.dtype, buffer=shm.buf)
        dst[...] = obj
        segments.append(shm)
        return _ShmArray(shm.name, obj.shape, str(obj.dtype))
    if isinstance(obj, (list, tuple)):
        return type(obj)(_to_shm(v, segments) for v in obj)
    if isinstance(obj, dict):
        return {k: _to_shm(v, segments) for k, v in obj.items()}
    return obj


def _from_shm(obj):
    """Descriptors -> numpy copies (then the segment can be unlinked)."""
    if isinstance(obj, _ShmArray):
        shm = shared_memory.SharedMemory(name=obj.name)
        try:
            src = np.ndarray(obj.shape, np.dtype(obj.dtype), buffer=shm.buf)
            out = np.array(src)  # own copy; free the segment eagerly
        finally:
            shm.close()
            try:
                shm.unlink()
            except FileNotFoundError:
                pass
        return out
    if isinstance(obj, (list, tuple)):
        return type(obj)(_from_shm(v) for v in obj)
    if isinstance(obj, dict):
        return {k: _from_shm(v) for k, v in obj.items()}
    return obj


def _tensor_to_numpy(obj):
    # Tensors cannot cross process boundaries; flatten to numpy in-worker
    from ..framework.tensor import Tensor
    if isinstance(obj, Tensor):
        return np.asarray(obj.data)
    if isinstance(obj, (list, tuple)):
        return type(obj)(_tensor_to_numpy(v) for v in obj)
    if isinstance(obj, dict):
        return {k: _tensor_to_numpy(v) for k, v in obj.items()}
    return obj


def _worker_fault_site(worker_id: int):
    """Per-batch fault site: `dataloader.worker<N>` (and the generic
    `dataloader.worker`). A `:kill` spec clause makes this worker vanish
    mid-epoch like an OOM-kill — the consumer must detect the corpse and
    respawn. Spawned workers inherit PADDLE_TPU_FAULT_SPEC via os.environ."""
    from ..fault import site
    site("dataloader.worker")
    site(f"dataloader.worker{worker_id}")


def _worker_loop(dataset, collate_fn, index_queue, result_queue,
                 worker_id: int, init_fn, use_shared_memory: bool,
                 iterable_mode: bool, batch_size: int, drop_last: bool,
                 num_workers: int, suppress_faults: bool = False):
    """Worker process entry (reference dataloader/worker.py _worker_loop)."""
    # a worker never takes the chip its trainer parent holds: this runs
    # first in a freshly spawned process (no backend yet), and the env var
    # carries the choice to anything the worker itself starts
    import jax
    os.environ["JAX_PLATFORMS"] = "cpu"
    jax.config.update("jax_platforms", "cpu")
    if suppress_faults:  # a RESPAWNED worker must not re-die on the same
        from ..fault import default_injector  # armed kill clause forever
        default_injector().reset()
    global _worker_info
    _worker_info = WorkerInfo(worker_id, num_workers, dataset)
    try:
        if init_fn is not None:
            init_fn(worker_id)
        if iterable_mode:
            # Sharding contract (same as the reference/torch): a worker-aware
            # dataset checks get_worker_info() in __iter__ and yields only
            # its own shard — then the modulo filter below sees an already-
            # disjoint stream and num_workers==1-like behavior. A naive
            # deterministic iterable is modulo-sharded here; a NON-
            # deterministic iterable without worker awareness will overlap
            # shards (documented limitation, as in the reference).
            aware = getattr(dataset, "worker_aware", False)
            buf = []
            for i, sample in enumerate(iter(dataset)):
                if not aware and i % num_workers != worker_id:
                    continue
                buf.append(sample)
                if len(buf) == batch_size:
                    _worker_fault_site(worker_id)
                    _emit(collate_fn(buf), result_queue, use_shared_memory,
                          batch_idx=-1)
                    buf = []
            if buf and not drop_last:
                _emit(collate_fn(buf), result_queue, use_shared_memory,
                      batch_idx=-1)
            result_queue.put((_SENTINEL, worker_id))
            return
        while True:
            item = index_queue.get()
            if item is None:
                result_queue.put((_SENTINEL, worker_id))
                return
            batch_idx, indices = item
            _worker_fault_site(worker_id)
            batch = collate_fn([dataset[i] for i in indices])
            _emit(batch, result_queue, use_shared_memory, batch_idx)
    except KeyboardInterrupt:
        pass
    except Exception as e:  # surface to the consumer
        import traceback
        result_queue.put(("__error__",
                          f"worker {worker_id}: "
                          f"{traceback.format_exc(limit=8)}\n{e!r}"))


def _emit(batch, result_queue, use_shared_memory: bool, batch_idx: int):
    batch = _tensor_to_numpy(batch)
    if use_shared_memory:
        segments: List[shared_memory.SharedMemory] = []
        desc = _to_shm(batch, segments)
        result_queue.put((batch_idx, desc))
        for shm in segments:  # consumer unlinks; worker just closes its map
            shm.close()
    else:
        result_queue.put((batch_idx, batch))


class MultiprocessIter:
    """Order-preserving multi-worker iterator (reference
    `_DataLoaderIterMultiProcess`): round-robin index dispatch, reorder
    buffer on receive, eager refill to keep prefetch_factor batches in
    flight per worker."""

    def __init__(self, loader):
        self.loader = loader
        ctx = mp.get_context("spawn")
        self._nw = loader.num_workers
        self._iterable = not hasattr(loader, "batch_sampler") or \
            loader.batch_sampler is None
        # Bounded result queue: back-pressure for the iterable path (whose
        # workers would otherwise decode the whole epoch ahead — every
        # undelivered shared-memory batch is a live /dev/shm segment).
        window = max(2, loader.prefetch_factor) * self._nw
        self._result_q = ctx.Queue(maxsize=window + self._nw)
        # ONE shared index queue: workers pull as they finish, which load-
        # balances without per-worker bookkeeping. Map-style dispatch is
        # additionally FLOW-CONTROLLED to the same window.
        self._index_q = ctx.Queue()
        if not self._iterable:
            self._batches = list(iter(loader.batch_sampler))
            self._cursor = 0
            for _ in range(window):
                self._dispatch_one()
        self._ctx = ctx
        self._workers = []
        for wid in range(self._nw):
            self._workers.append(self._spawn_worker(wid))

        self._reorder: Dict[int, Any] = {}
        self._next_idx = 0
        self._finished_workers = 0
        self._sentinel_wids = set()  # workers that finished cleanly
        self._lost_wids = set()      # iterable-mode corpses (shard lost)
        self._restarts = 0
        self._max_restarts = getattr(loader, "worker_max_restarts", 2)
        self._shutdown_done = False

    def _spawn_worker(self, wid: int, suppress_faults: bool = False):
        w = self._ctx.Process(
            target=_worker_loop,
            args=(self.loader.dataset, self.loader.collate_fn,
                  self._index_q, self._result_q, wid,
                  self.loader.worker_init_fn, self.loader.use_shared_memory,
                  self._iterable, self.loader.batch_size,
                  self.loader.drop_last, self._nw, suppress_faults),
            daemon=True)
        w.start()
        return w

    def _dispatch_one(self):
        # NO mid-epoch EOF tokens: workers idle on the index queue once the
        # epoch is dispatched and exit on the None sent by _shutdown(). A
        # None circulating mid-epoch would race crash recovery — a dead
        # worker's consumed token is unobservable, and its respawn could
        # pop a stale None ahead of the re-dispatched batches and exit.
        if self._cursor < len(self._batches):
            self._index_q.put((self._cursor,
                               list(self._batches[self._cursor])))
            self._cursor += 1

    def __iter__(self):
        return self

    def __next__(self):
        global _record_fetch_wait
        if _record_fetch_wait is None:  # deferred once: dodges import cycle
            from .dataloader import _record_fetch_wait
        t0 = time.perf_counter()
        batch = self._next_impl()
        _record_fetch_wait(time.perf_counter() - t0)
        return batch

    def _next_impl(self):
        timeout = self.loader.timeout or None
        if self._iterable:
            while self._finished_workers < self._nw:
                kind, payload = self._get(timeout)
                if kind == "__recovered__":
                    continue  # re-check the finished-workers condition
                if kind == _SENTINEL:
                    self._finished_workers += 1
                    self._sentinel_wids.add(payload)
                    continue
                if kind == "__error__":
                    self._shutdown()
                    raise RuntimeError(payload)
                return self._finalize(payload)
            self._shutdown()
            raise StopIteration

        while True:
            if self._next_idx in self._reorder:
                batch = self._reorder.pop(self._next_idx)
                self._next_idx += 1
                return self._finalize(batch)
            if self._next_idx >= len(self._batches):
                self._shutdown()
                raise StopIteration
            kind, payload = self._get(timeout)
            if kind == "__recovered__":
                continue  # recovery re-dispatched; poll again
            if kind == "__error__":
                self._shutdown()
                raise RuntimeError(payload)
            if kind == _SENTINEL:
                self._finished_workers += 1
                self._sentinel_wids.add(payload)
                continue
            if kind < self._next_idx or kind in self._reorder:
                # duplicate from crash-recovery re-dispatch (both a live
                # worker and a respawn processed it): drop, free its shm
                self._release(payload)
                continue
            self._reorder[kind] = payload  # kind is a batch index
            self._dispatch_one()           # keep the in-flight window full

    def _get(self, timeout):
        """Poll with liveness checks: a worker killed by the kernel (OOM,
        segfault) posts nothing, and an infinite blocking get would hang the
        trainer forever. Dead workers are detected and RESPAWNED (map-style:
        their lost batches are re-dispatched) up to `worker_max_restarts`
        times; iterable-mode corpses degrade to fewer workers with a
        warning, since a restarted stream would replay its whole shard."""
        import time as _time
        deadline = None if not timeout else _time.monotonic() + timeout
        while True:
            try:
                return self._result_q.get(timeout=1.0)
            except pyqueue.Empty:
                pass
            # A dead worker that never posted its end-of-stream sentinel
            # left a hole: its dispatched batches can never arrive. This
            # covers nonzero exits (OOM-kill, segfault) AND sys.exit(0)
            # inside user dataset code. Only act once the queue is drained —
            # its already-posted results are still in flight.
            crashed = [wid for wid, w in enumerate(self._workers)
                       if w.exitcode is not None
                       and wid not in self._sentinel_wids
                       and wid not in self._lost_wids]
            if crashed and self._result_q.empty():
                self._recover_workers(crashed)
                # hand control back so _next_impl re-checks its end
                # conditions (e.g. every remaining worker is now finished)
                return ("__recovered__", None)
            if deadline is not None and _time.monotonic() >= deadline:
                self._shutdown()
                raise RuntimeError(
                    f"DataLoader timed out after {timeout}s waiting for "
                    f"workers (alive: "
                    f"{[w.is_alive() for w in self._workers]})")

    def _recover_workers(self, crashed):
        """Respawn dead workers or degrade; raises when out of budget."""
        codes = {wid: self._workers[wid].exitcode for wid in crashed}
        if self._restarts + len(crashed) > self._max_restarts:
            # budget exhausted (worker_max_restarts=0 = the old fail-fast)
            self._shutdown()
            raise RuntimeError(
                f"DataLoader worker(s) died without finishing (exitcodes "
                f"{codes}) and the restart budget "
                f"(worker_max_restarts={self._max_restarts}) is exhausted — "
                "possibly OOM-killed or dataset code called exit(); reduce "
                "batch size or num_workers")
        if self._iterable:
            # an iterable worker's stream position died with it: respawning
            # would replay its whole shard, so degrade to fewer workers and
            # let the epoch finish short (documented, warned, counted —
            # each lost shard consumes one unit of the restart budget)
            for wid in crashed:
                self._restarts += 1
                self._lost_wids.add(wid)
                self._finished_workers += 1
                warnings.warn(
                    f"DataLoader worker {wid} died (exitcode "
                    f"{codes[wid]}); its remaining shard is lost — "
                    f"continuing with {self._nw - len(self._lost_wids)} "
                    "worker(s)")
                if _metrics_mod.enabled():
                    _M_WORKER_LOST.inc(exitcode=codes[wid])
            return
        for wid in crashed:
            self._restarts += 1
            warnings.warn(
                f"DataLoader worker {wid} died (exitcode {codes[wid]}); "
                f"respawning (restart {self._restarts}/{self._max_restarts})")
            # fault injection stays disarmed in the replacement: a :kill
            # spec clause would otherwise re-kill every respawn forever
            self._workers[wid] = self._spawn_worker(wid, suppress_faults=True)
            if _metrics_mod.enabled():
                _M_WORKER_RESTARTS.inc(exitcode=codes[wid])
        # re-dispatch every dispatched-but-unreceived batch: the corpse's
        # in-flight work is somewhere in that set. Live workers may still
        # deliver some of them — duplicates are dropped on receive.
        for idx in range(self._next_idx, self._cursor):
            if idx not in self._reorder:
                self._index_q.put((idx, list(self._batches[idx])))

    def _finalize(self, payload):
        data = _from_shm(payload) if self.loader.use_shared_memory else payload
        from ..framework.tensor import Tensor
        import jax

        def to_tensor(a):
            if isinstance(a, np.ndarray):
                arr = jax.device_put(a) if self.loader.use_buffer_reader \
                    else a
                return Tensor(arr)
            return a
        return jax.tree_util.tree_map(
            to_tensor, data,
            is_leaf=lambda x: isinstance(x, np.ndarray))

    def _release(self, payload):
        """Unlink shared-memory segments of an undelivered batch."""
        if isinstance(payload, _ShmArray):
            try:
                shm = shared_memory.SharedMemory(name=payload.name)
                shm.close()
                shm.unlink()
            except FileNotFoundError:
                pass
        elif isinstance(payload, (list, tuple)):
            for v in payload:
                self._release(v)
        elif isinstance(payload, dict):
            for v in payload.values():
                self._release(v)

    def _drain_results(self):
        while True:
            try:
                kind, payload = self._result_q.get_nowait()
            except (pyqueue.Empty, OSError, ValueError):
                break
            if kind not in (_SENTINEL, "__error__"):
                self._release(payload)

    def _shutdown(self):
        if self._shutdown_done:
            return
        self._shutdown_done = True
        if not self._iterable:
            for _ in self._workers:
                try:
                    self._index_q.put(None)
                except Exception:
                    pass
        # interleave draining with joining: a worker blocked on the bounded
        # result queue can only exit once its pending put lands
        import time as _time
        deadline = _time.monotonic() + 5
        while _time.monotonic() < deadline and \
                any(w.is_alive() for w in self._workers):
            self._drain_results()
            for w in self._workers:
                w.join(timeout=0.1)
        for w in self._workers:
            if w.is_alive():
                w.terminate()
        # drop in-flight batches: their shm segments would otherwise leak
        # for the life of the process (abandoned epochs, worker errors)
        for payload in self._reorder.values():
            self._release(payload)
        self._reorder.clear()
        self._drain_results()

    def __del__(self):
        try:
            self._shutdown()
        except Exception:
            pass
