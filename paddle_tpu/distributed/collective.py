"""Collective communication API.

Reference: `python/paddle/distributed/collective.py` (all_reduce/all_gather/
broadcast/reduce/scatter/alltoall/send/recv over `ProcessGroup`,
`/root/reference/paddle/fluid/distributed/collective/ProcessGroup.h:53`) and
the static-graph `c_*` ops (`/root/reference/paddle/fluid/operators/collective/`).

TPU-native translation: a `Group` is a (Mesh, axis-names) view — no comm
init, no ring_id, no NCCL uniqueId exchange. Each collective has two paths:

* **SPMD path** (inside `shard_map`/`pjit` tracing): lowers to the XLA
  collective over ICI — `lax.psum`, `lax.all_gather`, `lax.ppermute`,
  `lax.all_to_all`. This is the hot path; it is what the parallel layers use.
* **Eager path** (plain `Tensor` outside a trace): wraps the op in a
  one-shot `shard_map` over the group's mesh so per-device shards behave
  like per-rank buffers. A replicated input is treated as every "rank"
  holding the same value (so all_reduce multiplies by group size — identical
  to N real ranks all holding x).

Multi-host: `jax.distributed.initialize` (done by `init_parallel_env`) makes
the same mesh span hosts; nothing here changes — the mesh is the cluster.
"""
from __future__ import annotations

import os
import threading
import time
from typing import List, Optional, Sequence, Tuple, Union

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from ..cost_model import array_bytes as _array_bytes
from ..framework.tensor import Tensor
from ..profiler import events as _events_mod
from ..profiler import metrics as _metrics_mod

_REG = _metrics_mod.default_registry()
_M_COLL_CALLS = _REG.counter(
    "collective_calls_total",
    "eager collective launches by kind and link class (ici/dcn)")
_M_COLL_BYTES = _REG.counter(
    "collective_bytes_total",
    "estimated per-device bytes moved by eager collectives, by kind, "
    "attributed to the slowest link the group's mesh axes cross "
    "(cluster-mapper pricing)")
_M_COLL_TIMEOUT = _REG.counter(
    "collective_timeout_total",
    "eager collectives that exceeded the deadline (or hit the armed "
    "collective.timeout fault site), by kind and group")
_M_COLL_SECONDS = _REG.histogram(
    "collective_seconds",
    "eager collective wall time (launch through completion of the guarded "
    "thunk) by kind — the step-diagnosis 'collective' signal; traced/SPMD "
    "collectives run inside compiled programs and are not timed here")


class CollectiveTimeoutError(RuntimeError):
    """An eager collective exceeded its deadline instead of completing.

    Raised (instead of hanging) when `PADDLE_TPU_COLLECTIVE_TIMEOUT` is set
    and the launch+completion of an eager collective outlives it — the
    classic symptom of a peer host that died mid-rendezvous — or when the
    `collective.timeout` fault site is armed (chaos testing). Names the
    group and this process's rank so the stuck member is identifiable from
    any host's log.

    Recovery contract: restart the PROCESS (the supervisor's `supervise`
    argv mode), not just the train loop. Python cannot cancel the
    abandoned watchdog thread, and if the fleet was slow rather than dead
    its collective can still complete later — re-entering training in the
    same process (`ElasticSupervisor.run`) risks that stale completion
    interleaving an unmatched collective into the next generation and
    desyncing cross-rank ordering."""

    def __init__(self, kind: str, group: "Group", rank: int,
                 timeout: float, detail: str = ""):
        msg = (f"collective {kind!r} over group {group.name!r} "
               f"(axes {group.axis_names}, {group.nranks} ranks) "
               f"did not complete within {timeout:g}s on process rank {rank}")
        if detail:
            msg += f" ({detail})"
        super().__init__(msg)
        self.kind = kind
        self.group_name = group.name
        self.rank = rank
        self.timeout = timeout


class ReduceOp:
    """Reduction kinds (reference `distributed/collective.py` ReduceOp)."""
    SUM = 0
    MAX = 1
    MIN = 2
    PROD = 3
    AVG = 4


def _reduce_fn(op):
    return {ReduceOp.SUM: lax.psum, ReduceOp.MAX: lax.pmax,
            ReduceOp.MIN: lax.pmin}.get(op)


class Group:
    """A communication group = a named-axis view of a Mesh."""

    _next_id = 0

    def __init__(self, mesh: Mesh, axis_names: Tuple[str, ...],
                 ranks: Optional[List[int]] = None, name: str = ""):
        self.mesh = mesh
        self.axis_names = tuple(axis_names)
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        self.nranks = int(np.prod([sizes[a] for a in self.axis_names]))
        self.ranks = ranks if ranks is not None else list(range(self.nranks))
        self.name = name or "_".join(self.axis_names)
        self.id = Group._next_id
        Group._next_id += 1

    @property
    def axis(self) -> Union[str, Tuple[str, ...]]:
        return self.axis_names[0] if len(self.axis_names) == 1 \
            else self.axis_names

    @property
    def world_size(self) -> int:
        return self.nranks

    @property
    def rank(self) -> int:
        return 0  # per-device rank is lax.axis_index(self.axis) in-trace

    def get_group_rank(self, rank: int) -> int:
        return self.ranks.index(rank) if rank in self.ranks else -1

    def process_group(self):
        return self

    def __repr__(self):
        return (f"Group(id={self.id}, axes={self.axis_names}, "
                f"nranks={self.nranks})")


_default_group: Optional[Group] = None
_groups_by_id = {}


def _world_mesh() -> Mesh:
    from .topology import get_hybrid_communicate_group
    hcg = get_hybrid_communicate_group()
    if hcg is not None:
        return hcg.mesh
    devs = np.array(jax.devices())
    return Mesh(devs, ("world",))


def set_default_group(group: Group):
    global _default_group
    _default_group = group
    _groups_by_id[group.id] = group


def _get_default_group() -> Group:
    global _default_group
    if _default_group is None:
        mesh = _world_mesh()
        _default_group = Group(mesh, tuple(mesh.axis_names), name="default")
        _groups_by_id[_default_group.id] = _default_group
    return _default_group


def _resolve(group) -> Group:
    if group is None:
        return _get_default_group()
    if isinstance(group, Group):
        return group
    if isinstance(group, int):
        return _groups_by_id[group]
    raise TypeError(f"not a group: {group!r}")


def get_group(gid: int = 0) -> Group:
    return _groups_by_id.get(gid, _get_default_group())


def new_group(ranks=None, backend=None, timeout=None,
              axis_name: Optional[str] = None) -> Group:
    """Create a group. TPU semantics: a group over a mesh axis. `ranks` is
    accepted for API parity; when given without `axis_name` the group spans
    the whole default mesh (single-controller has no per-rank comm setup)."""
    mesh = _world_mesh()
    if axis_name is not None:
        g = Group(mesh, (axis_name,))
    else:
        g = Group(mesh, tuple(mesh.axis_names), ranks=ranks)
    _groups_by_id[g.id] = g
    return g


def is_initialized() -> bool:
    return _default_group is not None


def destroy_process_group(group=None):
    global _default_group
    if group is None:
        _default_group = None
        _groups_by_id.clear()


# ---------------------------------------------------------------------------
# tracer detection + eager shard_map wrapper
# ---------------------------------------------------------------------------
def _is_tracer(x) -> bool:
    return isinstance(x, jax.core.Tracer)


def _unwrap(t):
    return t.data if isinstance(t, Tensor) else t


def _spec_of(arr, mesh) -> P:
    sh = getattr(arr, "sharding", None)
    if isinstance(sh, NamedSharding) and sh.mesh.shape == mesh.shape:
        return sh.spec
    return P()


def _proc_rank() -> int:
    try:
        return int(jax.process_index())
    except Exception:
        return 0


def _deadline_seconds() -> float:
    """0 = guard disabled (the default: zero overhead, unchanged async
    dispatch). Set `PADDLE_TPU_COLLECTIVE_TIMEOUT` (seconds) to bound every
    eager collective: launch + completion run on a watchdog thread and a
    blown deadline raises CollectiveTimeoutError instead of hanging.

    The deadline covers the WHOLE thunk — including shard_map tracing and
    XLA compilation the first time a shape is seen — so size it to cover a
    cold-start compile (tens of seconds on a pod), not just the wire time:
    a too-tight value turns a healthy first-step compile into a false
    dead-peer diagnosis that burns an elastic restart."""
    from ..utils.envparse import env_float
    return env_float("PADDLE_TPU_COLLECTIVE_TIMEOUT", 0.0)


def _timed_out(kind: str, group: Group):
    if _metrics_mod.enabled():
        _M_COLL_TIMEOUT.inc(kind=kind, group=group.name)
    _events_mod.emit("collective_timeout", severity="error",
                     collective=kind, group=group.name, rank=_proc_rank())


class _GuardWorker:
    """A long-lived watchdog thread serving guarded eager collectives,
    instead of a spawn+join per call (thread creation on the per-op eager
    path costs ~100us and churns native stacks). A `None` job is the exit
    sentinel (surplus workers retire instead of idling forever)."""

    def __init__(self):
        import queue
        self.jobs: "queue.SimpleQueue" = queue.SimpleQueue()
        self.thread = threading.Thread(target=self._loop, daemon=True,
                                       name="collective-guard-worker")
        self.thread.start()

    def _loop(self):
        while True:
            job = self.jobs.get()
            if job is None:
                return
            thunk, box, done = job
            try:
                r = thunk()
                jax.block_until_ready(r)  # deadline covers completion, not
                box["v"] = r              # just the async enqueue
            except BaseException as e:
                box["e"] = e
            done.set()


_guard_worker: Optional[_GuardWorker] = None
_guard_worker_lock = threading.Lock()
_guard_worker_spawns = 0  # regression-test hook: reuse means this is flat


def _run_on_guard_worker(thunk, timeout: float):
    """Run `thunk` on a pooled watchdog worker, bounded by `timeout`.
    Returns the result box, or None on deadline.

    Check-out/check-in: the ONE pooled worker is taken exclusively for the
    job's duration, so sequential guarded collectives (the only real
    pattern — they come from the train loop) reuse a single thread, while
    a concurrent caller finding the pool empty gets its own fresh worker
    and its deadline never includes another caller's thunk. On return, the
    worker goes back to the pool (or retires if the pool refilled). A
    timed-out worker is simply ABANDONED — never checked back in — because
    its thread may be wedged inside the hung collective and Python cannot
    cancel it; abandoning it can never touch a healthy worker another
    thread is using."""
    global _guard_worker, _guard_worker_spawns
    with _guard_worker_lock:
        w = _guard_worker
        _guard_worker = None  # checked out (exclusive) while running
        if w is None or not w.thread.is_alive():
            w = _GuardWorker()
            _guard_worker_spawns += 1
    box: dict = {}
    done = threading.Event()
    w.jobs.put((thunk, box, done))
    if not done.wait(timeout):
        return None  # abandoned: may still be executing the hung thunk
    with _guard_worker_lock:
        if _guard_worker is None:
            _guard_worker = w  # back in the pool for the next call
        else:
            w.jobs.put(None)  # pool refilled concurrently: retire this one
    return box


def _guard_collective(kind: str, group: Group, thunk):
    """Run one eager collective under the timeout contract.

    Only the EAGER entry points funnel through here — traced/SPMD
    collectives execute inside compiled programs where XLA owns scheduling
    (a hang there surfaces via the runtime's own deadline, not Python).
    The `collective.timeout` fault site lets chaos tests simulate the hang
    without a real dead peer."""
    from ..fault import InjectedFault, InjectedIOError, site as _fault_site
    try:
        _fault_site("collective.timeout")
    except (TimeoutError, InjectedFault, InjectedIOError) as e:
        # every injected kind at this site models the same thing — a hung
        # collective — so the bare spec `collective.timeout=1` (default
        # kind=error) must surface as the typed timeout too, not escape as
        # a raw InjectedFault that skips the metric
        _timed_out(kind, group)
        raise CollectiveTimeoutError(kind, group, _proc_rank(), 0.0,
                                     detail="injected fault") from e
    timeout = _deadline_seconds()
    if timeout <= 0:
        if not _metrics_mod.enabled():
            return thunk()
        t0 = time.perf_counter()
        try:
            return thunk()
        finally:
            _M_COLL_SECONDS.observe(time.perf_counter() - t0, kind=kind)
    t0 = time.perf_counter()
    box = _run_on_guard_worker(thunk, timeout)
    if box is not None and _metrics_mod.enabled():
        _M_COLL_SECONDS.observe(time.perf_counter() - t0, kind=kind)
    if box is None:
        # the worker is abandoned, not cancelled (Python can't), so a
        # slow-but-alive fleet may still complete this collective later:
        # recover by restarting the process, not the loop — see the
        # CollectiveTimeoutError docstring
        _timed_out(kind, group)
        raise CollectiveTimeoutError(kind, group, _proc_rank(), timeout)
    if "e" in box:
        raise box["e"]
    return box["v"]


def _eager(group: Group, fn, *arrs, out_specs=None, kind: str = "collective"):
    """Run `fn` (which uses lax collectives over group.axis) via shard_map."""
    in_specs = tuple(_spec_of(a, group.mesh) for a in arrs)
    if out_specs is None:
        out_specs = in_specs[0]
    return _guard_collective(
        kind, group,
        lambda: shard_map(fn, mesh=group.mesh, in_specs=in_specs,
                          out_specs=out_specs, check_vma=False)(*arrs))


def _group_link(g: Group) -> str:
    """'ici' or 'dcn': the slowest link class the group's mesh axes cross,
    via the auto-parallel cluster mapper (PR-1 pricing). Slice topology off
    a real multislice job comes from `PADDLE_TPU_NUM_SLICES`; default is one
    slice, so everything is ICI. A bad env value or mapper failure falls
    back to 'ici' but is LOGGED once — a silent fallback would zero the
    dcn breakdown on exactly the multislice jobs it exists for."""
    cached = getattr(g, "_link_class", None)
    if cached is not None:
        return cached
    import logging
    import os
    log = logging.getLogger("paddle_tpu.collective")
    link = "ici"
    from ..utils.envparse import env_int
    # garbled -> single-slice fallback (all ici link attribution)
    n_slices = env_int("PADDLE_TPU_NUM_SLICES", 1)
    if n_slices > 1:
        try:
            from .auto_parallel.cluster import Cluster, Mapper
            ndev = int(np.prod(g.mesh.devices.shape))
            cluster = Cluster(n_slices=n_slices,
                              chips_per_slice=max(1, ndev // n_slices))
            mesh_dims = dict(zip(g.mesh.axis_names, g.mesh.devices.shape))
            links = Mapper(cluster).axis_links(mesh_dims)
            if any(links.get(a) == "dcn" for a in g.axis_names):
                link = "dcn"
        except Exception as e:
            log.warning("cluster mapper failed for group %s (%s: %s); "
                        "collective link attribution falls back to ici",
                        g.name, type(e).__name__, e)
    g._link_class = link
    return link


def _account(kind: str, group: Group, *arrs):
    """Count one eager collective into the metrics registry (traced/SPMD
    collectives execute inside compiled programs and are priced by the
    planner's HLO walk instead — counting the trace would be once-ever)."""
    if not _metrics_mod.enabled():
        return
    try:
        link = _group_link(group)
        _M_COLL_CALLS.inc(kind=kind, link=link)
        _M_COLL_BYTES.inc(sum(_array_bytes(a) for a in arrs),
                          kind=kind, link=link)
    except Exception:
        pass


def _eager_acct(kind: str, group: Group, fn, *arrs, out_specs=None):
    _account(kind, group, *arrs)
    return _eager(group, fn, *arrs, out_specs=out_specs, kind=kind)


def _wrap_like(t, arr):
    if isinstance(t, Tensor):
        t.data = arr
        return t
    return arr


# ---------------------------------------------------------------------------
# collectives
# ---------------------------------------------------------------------------
def all_reduce(tensor, op=ReduceOp.SUM, group=None, sync_op=True,
               use_calc_stream=False):
    """In-place all-reduce (reference `collective.py` all_reduce /
    `c_allreduce_sum_op`). Returns the tensor (task.wait() is a no-op: XLA
    async collectives are scheduled by the compiler).

    SEMANTICS (single-controller!): the tensor is treated as N per-rank
    values laid out over the group's mesh axis — exactly N real processes
    calling the NCCL op in the reference. Two consequences:

    * a tensor whose data is SHARDED over the group axis reduces the
      per-shard values, matching the reference rank-for-rank (the case
      that matters in real pipelines — see tests);
    * a REPLICATED tensor is "the same value on every rank", so SUM
      multiplies it by group size — identical to N ranks all-reducing
      equal values. If you want the identity here, you wanted broadcast
      (or no collective at all), not all_reduce.
    """
    g = _resolve(group)
    x = _unwrap(tensor)
    red = _reduce_fn(op)

    def f(a):
        if red is not None:
            return red(a, g.axis)
        if op == ReduceOp.AVG:
            return lax.pmean(a, g.axis)
        # PROD via exp/sum-of-logs is lossy; use all_gather+prod
        ga = lax.all_gather(a, g.axis, axis=0)
        return jnp.prod(ga, axis=0)

    out = f(x) if _is_tracer(x) else _eager_acct("all_reduce", g, f, x)
    return _wrap_like(tensor, out)


def all_gather(tensor_list, tensor=None, group=None, sync_op=True, axis=0):
    """reference: all_gather(tensor_list, tensor). Also usable
    functionally: `out = all_gather(None, x)` returns the stacked array."""
    if tensor is None and not isinstance(tensor_list, list):
        tensor_list, tensor = None, tensor_list
    g = _resolve(group)
    x = _unwrap(tensor)

    def f(a):
        return lax.all_gather(a, g.axis, axis=0)

    if _is_tracer(x):
        out = f(x)
    else:
        # gathered result is identical on every device -> replicated output
        out = _eager_acct("all_gather", g, f, x, out_specs=P())
    if isinstance(tensor_list, list):
        for i in range(g.nranks):
            tensor_list.append(Tensor(out[i]) if isinstance(tensor, Tensor)
                               else out[i])
        return tensor_list
    res = out if axis == 0 else None
    if axis != 0:
        res = jnp.concatenate([out[i] for i in range(out.shape[0])], axis=axis) \
            if not _is_tracer(x) else jnp.concatenate(
                jnp.split(out, g.nranks, axis=0), axis=axis + 1)[0]
    return Tensor(res) if isinstance(tensor, Tensor) else res


def all_gather_object(object_list, obj, group=None):
    # single-controller: every "rank" holds the same python object
    g = _resolve(group)
    object_list.extend([obj] * g.nranks)
    return object_list


def broadcast(tensor, src=0, group=None, sync_op=True):
    """Broadcast from group-rank `src` (reference `c_broadcast_op`)."""
    g = _resolve(group)
    x = _unwrap(tensor)

    def f(a):
        ga = lax.all_gather(a, g.axis, axis=0)
        return ga[src]

    out = f(x) if _is_tracer(x) else _eager_acct("broadcast", g, f, x)
    return _wrap_like(tensor, out)


def reduce(tensor, dst=0, op=ReduceOp.SUM, group=None, sync_op=True):
    """On TPU SPMD every device computes the reduction (same cost over ICI);
    non-dst ranks keep the reduced value too (superset of reference
    semantics — documented divergence)."""
    return all_reduce(tensor, op=op, group=group)


def scatter(tensor, tensor_list=None, src=0, group=None, sync_op=True):
    g = _resolve(group)
    if tensor_list is not None:
        stacked = jnp.stack([_unwrap(t) for t in tensor_list], axis=0)

        def f(_):
            i = lax.axis_index(g.axis)
            return lax.dynamic_index_in_dim(stacked, i, axis=0,
                                            keepdims=False)

        x = _unwrap(tensor)
        out = f(x) if _is_tracer(x) else _eager_acct("scatter", g, f, x)
        return _wrap_like(tensor, out)
    raise ValueError("scatter requires tensor_list on TPU SPMD")


def reduce_scatter(tensor, tensor_or_list, op=ReduceOp.SUM, group=None,
                   sync_op=True):
    """reference `c_reducescatter_op`: reduce then shard along dim 0."""
    g = _resolve(group)
    if isinstance(tensor_or_list, (list, tuple)):
        x = jnp.concatenate([_unwrap(t) for t in tensor_or_list], axis=0)
    else:
        x = _unwrap(tensor_or_list)

    def f(a):
        return lax.psum_scatter(a, g.axis, scatter_dimension=0, tiled=True)

    if _is_tracer(x):
        out = f(x)
    else:
        spec = _spec_of(x, g.mesh)

        def f_eager(a):
            # drop the rank axis so each device's shard is its rank tensor
            if len(spec) > 0 and spec[0] is not None and a.shape[0] == 1:
                a = a[0]
            return f(a)

        out = _eager_acct("reduce_scatter", g, f_eager, x,
                          out_specs=P(g.axis))
    return _wrap_like(tensor, out)


def alltoall(in_tensor_list, out_tensor_list=None, group=None, sync_op=True):
    """reference `alltoall_op` (MoE global_scatter/gather ancestor)."""
    g = _resolve(group)
    if isinstance(in_tensor_list, (list, tuple)):
        x = jnp.stack([_unwrap(t) for t in in_tensor_list], axis=0)
    else:
        x = _unwrap(in_tensor_list)  # leading dim == nranks

    def f(a):
        # a: [nranks, ...] local; exchange chunk i -> rank i
        return lax.all_to_all(a, g.axis, split_axis=0, concat_axis=0,
                              tiled=False)

    if _is_tracer(x):
        out = f(x)
    else:
        spec = _spec_of(x, g.mesh)
        out = _eager_acct("alltoall", g, f, x, out_specs=spec)
    if isinstance(out_tensor_list, list):
        for i in range(g.nranks):
            out_tensor_list.append(Tensor(out[i]))
        return out_tensor_list
    return Tensor(out) if isinstance(in_tensor_list, Tensor) else out


alltoall_single = alltoall


def send(tensor, dst=0, group=None, sync_op=True):
    raise NotImplementedError(
        "point-to-point send/recv do not exist on TPU SPMD; use "
        "paddle_tpu.distributed.p2p.ppermute (pipeline engine) — XLA "
        "collective-permute replaces NCCL send/recv "
        "(reference operators/collective/partial_send_op.cc)")


recv = send
isend = send
irecv = send


def ppermute(x, group=None, perm=None):
    """collective_permute: the TPU replacement for PP send/recv pairs."""
    g = _resolve(group)
    if perm is None:  # ring shift by +1
        n = g.nranks
        perm = [(i, (i + 1) % n) for i in range(n)]
    arr = _unwrap(x)

    def f(a):
        return lax.ppermute(a, g.axis, perm)

    out = f(arr) if _is_tracer(arr) else _eager_acct("ppermute", g, f, arr)
    return Tensor(out) if isinstance(x, Tensor) else out


def barrier(group=None):
    """Device barrier: a tiny psum forces a sync point."""
    g = _resolve(group)
    x = jnp.zeros((), jnp.float32)
    _eager(g, lambda a: lax.psum(a, g.axis), x,
           kind="barrier").block_until_ready()


def wait(tensor, group=None, use_calc_stream=True):
    x = _unwrap(tensor)
    if not _is_tracer(x):
        x.block_until_ready()
    return tensor


def stream_synchronize():
    (jnp.zeros(()) + 0).block_until_ready()


# in-trace rank/size helpers (SPMD analogue of get_rank inside layers)
def axis_rank(group=None):
    g = _resolve(group)
    return lax.axis_index(g.axis)


def get_world_size_in_group(group=None) -> int:
    return _resolve(group).nranks


# ---------------------------------------------------------------------------
# paddle.distributed.split — sharded linear/embedding helper
# (reference collective.py:1436)
# ---------------------------------------------------------------------------
def split(x, size, operation, axis=0, num_partitions=1, gather_out=True,
          weight_attr=None, bias_attr=None, name=None):
    from .meta_parallel import parallel_layers as _pl
    if operation == "linear":
        layer_cls = _pl.ColumnParallelLinear if axis == 1 \
            else _pl.RowParallelLinear
        layer = layer_cls(size[0], size[1], weight_attr=weight_attr,
                          has_bias=bias_attr is not False,
                          gather_output=gather_out,
                          input_is_parallel=False)
        return layer(x)
    if operation == "embedding":
        layer = _pl.VocabParallelEmbedding(size[0], size[1],
                                           weight_attr=weight_attr)
        return layer(x)
    raise ValueError(f"unsupported split operation {operation!r}")
