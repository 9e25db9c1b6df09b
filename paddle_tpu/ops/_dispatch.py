"""Op dispatch: pure-array impls -> eager Tensor ops with autograd + AMP.

TPU-native analog of the reference's kernel dispatch stack
(`/root/reference/paddle/phi/core/kernel_factory.h:230` KernelFactory,
`paddle/fluid/imperative/tracer.cc:172` TraceOp, and the AMP autocast hook at
`tracer.cc:222-240`): one registry of pure functions over `jax.Array`s serves
both eager mode (this wrapper: unwrap -> optional autocast -> `jax.vjp` ->
tape record) and compiled programs (the impls are called directly under
`jit`). There is no backend enum — XLA is the one backend; `jax.vjp` replaces
the generated GradNodes.
"""
from __future__ import annotations

import functools
import os
import threading
import types
from collections import OrderedDict
from typing import Any, Callable, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..cost_model import (op_bytes_estimate as _op_bytes_estimate,
                          op_flops_estimate as _op_flops_estimate)
from ..fault.inject import (DeviceOOMError, InjectedFault, InjectedIOError,
                            InjectedTimeout, default_injector)
from ..framework import dtype as dtype_mod
from ..framework import tape as tape_mod
from ..framework.tensor import Tensor
from ..profiler import compile_watch as _compile_watch
from ..profiler import device_time as _device_time
from ..profiler import events as _events_mod
from ..profiler import metrics as _metrics_mod
from ..profiler import xplane as _xplane
from ..profiler.recorder import HostSpan, get_recorder, now_ns
from ..profiler.watchdog import get_watchdog

# op-level observability (tentpole PR 2): per-op call/byte counters are
# always-on (gated by PADDLE_TPU_METRICS), per-op HostSpans only while a
# Profiler RECORD window has the recorder enabled.
_REG = _metrics_mod.default_registry()
_M_OP_CALLS = _REG.counter("op_calls_total",
                           "eager op dispatches by op name")
_M_OP_BYTES = _REG.counter(
    "op_bytes_total",
    "estimated bytes touched per eager op (inputs+outputs, metadata-based)")
_M_OP_FLOPS = _REG.counter(
    "op_flops_total",
    "estimated FLOPs per eager op (exact for the matmul family, "
    "one-per-element otherwise — cost_model.op_flops_estimate)")
_M_OP_TIME = _REG.histogram(
    "op_time_seconds",
    "host-side eager dispatch latency by op (RECORD windows only; includes "
    "async-dispatch enqueue, not device completion)")
_M_CACHE_EVENTS = _REG.counter(
    "eager_cache_events_total",
    "eager jit-cache lookups by result (hit/miss/bypass)")
_M_DEVICE_OOM = _REG.counter(
    "device_oom_total",
    "eager ops that exhausted device memory (XLA RESOURCE_EXHAUSTED or the "
    "armed device.alloc fault site), by op")
_M_OP_DEVICE_TIME = _REG.histogram(
    "op_device_seconds",
    "device-side execution time by op and src (RECORD windows only; "
    "src=measured under PADDLE_TPU_DEVICE_TIME=sync, else a roofline "
    "estimate — see profiler/device_time.py)")
_op_recorder = get_recorder()
_fault_injector = default_injector()

# impl registry: name -> pure fn (for compiled/functional callers and tests)
KERNELS: Dict[str, Callable] = {}

# When non-None, every op call is recorded into the active static Program
# instead of executing eagerly (reference: static mode appends an OpDesc to
# the current Block, `python/paddle/fluid/framework.py` Block.append_op).
# Set/cleared by paddle_tpu.static.
GRAPH_BUILDER = None


def kernel(name: str):
    """Register a pure-array kernel (phi `PD_REGISTER_KERNEL` equivalent)."""
    def deco(fn):
        KERNELS[name] = fn
        fn._op_name = name
        return fn
    return deco


def _unwrap(x) -> jax.Array:
    if isinstance(x, Tensor):
        return x.data
    if isinstance(x, jax.Array):
        return x
    a = np.asarray(x)
    if a.dtype == np.float64 and dtype_mod.get_default_dtype() != jnp.dtype(jnp.float64):
        a = a.astype(dtype_mod.get_default_dtype())
    return jnp.asarray(a)


def _wants_grad(x) -> bool:
    return (isinstance(x, Tensor) and not x.stop_gradient
            and (dtype_mod.is_floating(x.data.dtype)
                 or dtype_mod.is_complex(x.data.dtype)))


# ---------------------------------------------------------------------------
# Eager op cache: jitted fwd + vjp executables per (op, shapes, dtypes, attrs)
#
# The reference's dygraph hot path is a C++ tracer dispatching a pre-compiled
# kernel in microseconds (`/root/reference/paddle/fluid/imperative/tracer.cc:172`,
# perf-tested in `paddle/fluid/eager/tests/performance_tests/`). Our eager
# path ran `jax.vjp` per op call — two fresh traces, milliseconds — which made
# every small-op workload (the PS trainer, eager UX on a chip) dispatch-bound
# (SURVEY §7 hard part #1). This cache stages each (op, static attrs, input
# avals) combination ONCE into two jitted executables:
#
#   fwd(*arrs) -> outs                      (the op itself)
#   bwd(arrs, cots) -> grads[diff slots]    (jax.vjp inside jit)
#
# The bwd executable re-derives the forward from the primals instead of
# threading residuals between two jits (a closure can't cross a jit
# boundary); XLA dead-code-eliminates whatever the transpose doesn't need —
# for matmul/conv-style ops the recompute vanishes entirely, for
# normalize/softmax-style ops it is a cheap fused reduction.
#
# Keying: most impls are defined PER CALL inside their Python API function,
# so function identity is useless — but their __code__ object is the same
# constant across calls. The key is (code, defaults, closure cells, static
# kwargs, input avals), with every captured value restricted to an allowlist
# of immutables; anything else (a baked-in RNG key array, a captured Layer)
# makes the call uncacheable and it takes the original re-trace path, which
# preserves per-call semantics like fresh dropout masks. A key must be seen
# TWICE before it is staged, so one-shot shapes never pay a compile.
# ---------------------------------------------------------------------------
_CACHE_MAX = 4096
_JITTED_TYPE = type(jax.jit(lambda: 0))
_eager_cache: "OrderedDict[Any, Any]" = OrderedDict()   # key -> entry|None
_eager_seen: "OrderedDict[Any, bool]" = OrderedDict()   # first-sight keys
_UNCACHEABLE = object()

_cache_stats = {"hit": 0, "miss": 0, "bypass": 0}


class _CacheEntry:
    __slots__ = ("fwd", "bwd", "prim", "diff_idx", "n_in")

    def __init__(self, impl, kwargs, arrs):
        def prim(*a):
            out = impl(*a, **kwargs)
            return out if isinstance(out, tuple) else (out,)

        diff_idx = tuple(
            i for i, a in enumerate(arrs)
            if dtype_mod.is_floating(a.dtype) or dtype_mod.is_complex(a.dtype))

        def bwd_fn(arrs_, cots):
            def of_diff(diff):
                full = list(arrs_)
                for i, v in zip(diff_idx, diff):
                    full[i] = v
                return prim(*full)
            _, vjp = jax.vjp(of_diff, tuple(arrs_[i] for i in diff_idx))
            (gs,) = vjp(cots)
            return gs

        self.prim = prim
        self.fwd = jax.jit(prim)
        self.bwd = jax.jit(bwd_fn)
        self.diff_idx = diff_idx
        self.n_in = len(arrs)

    def make_vjp(self, arrs):
        def vjp_fn(cots, _arrs=arrs, _self=self):
            try:
                gs = _self.bwd(_arrs, tuple(cots))
            except Exception:
                # impl's backward needs concrete values (it traced fine
                # under jax.vjp, whose primals are concrete) — re-trace
                # eagerly for this call
                _, eager_vjp = jax.vjp(_self.prim, *_arrs)
                return eager_vjp(tuple(cots))
            full = [None] * _self.n_in
            for i, g in zip(_self.diff_idx, gs):
                full[i] = g
            return full
        return vjp_fn


def _keyable(v):
    """Normalize a captured/static value for the cache key; raise TypeError
    for anything whose equality doesn't guarantee identical op behavior."""
    if v is None or isinstance(v, (bool, int, float, str, bytes, complex,
                                   slice, type, np.dtype)):
        return v
    if isinstance(v, (types.FunctionType, types.BuiltinFunctionType,
                      types.MethodType, functools.partial, np.generic,
                      jax.custom_vjp, jax.custom_jvp, _JITTED_TYPE)):
        return v  # identity-hashed; module-level helpers are stable
    if isinstance(v, (tuple, list)):
        return tuple(_keyable(x) for x in v)
    if isinstance(v, dict):
        return tuple(sorted((k, _keyable(x)) for k, x in v.items()))
    raise TypeError(f"uncacheable value {type(v)}")


def _entry_key(impl, kwargs, arrs):
    try:
        cells = impl.__closure__
        captured = (tuple(c.cell_contents for c in cells) if cells else ())
        key = (impl.__code__,
               _keyable(impl.__defaults__ or ()),
               _keyable(impl.__kwdefaults__ or {}),
               _keyable(captured),
               _keyable(kwargs),
               tuple((a.shape, a.dtype, bool(getattr(a, "weak_type", False)))
                     for a in arrs))
        hash(key)
        return key
    except Exception:
        return None


def _cache_event(result: str):
    _cache_stats[result] += 1
    if _metrics_mod.enabled():
        _M_CACHE_EVENTS.inc(result=result)


def _cache_lookup(impl, kwargs, arrs, name=None):
    """Return a _CacheEntry, or None to take the re-trace path."""
    if not _EAGER_CACHE_FLAG.value:
        return None
    key = _entry_key(impl, kwargs, arrs)
    if key is None:
        _cache_event("bypass")
        return None
    entry = _eager_cache.get(key)
    if entry is not None:
        _eager_cache.move_to_end(key)
        if entry is _UNCACHEABLE:
            _cache_event("bypass")
            return None
        _cache_event("hit")
        return entry
    if key not in _eager_seen:
        # first sighting: don't pay a compile for what may never recur.
        # The watchdog diffs this signature against the op's previous one —
        # a retrace event here names the shape/dtype/attr that changed.
        _eager_seen[key] = True
        if len(_eager_seen) > 2 * _CACHE_MAX:
            _eager_seen.popitem(last=False)
        _cache_event("miss")
        if name is not None:
            get_watchdog().observe("eager", name, arrs, static=kwargs,
                                   count_hit=False)
        return None
    try:
        entry = _CacheEntry(impl, kwargs, arrs)
    except Exception:
        entry = _UNCACHEABLE
    _eager_cache[key] = entry
    if len(_eager_cache) > _CACHE_MAX:
        _eager_cache.popitem(last=False)
    _cache_event("miss")
    if entry is not _UNCACHEABLE and name is not None and \
            os.environ.get("PADDLE_TPU_AUDIT", "").strip().lower() == "all":
        # PADDLE_TPU_AUDIT=all: vet each newly cached eager program once
        # (the compile decision point — every later call is a cache hit)
        from .. import analysis
        analysis.maybe_audit("eager", name, entry.prim, tuple(arrs))
    return None if entry is _UNCACHEABLE else entry


def _mark_uncacheable(impl, kwargs, arrs):
    key = _entry_key(impl, kwargs, arrs)
    if key is not None:
        _eager_cache[key] = _UNCACHEABLE


def _try_cached_fwd(impl, kwargs, arrs, name):
    """Attempt the cached jitted forward; (entry, outs) on success, else
    (None, None) — the impl needs CONCRETE values (float()/np conversions
    work under jax.vjp, whose primals are concrete, but not under jit), so
    the key is blacklisted and the caller re-runs eagerly, re-raising any
    genuine op error."""
    entry = _cache_lookup(impl, kwargs, arrs, name)
    if entry is None:
        return None, None
    try:
        outs = entry.fwd(*arrs)
    except Exception:
        _mark_uncacheable(impl, kwargs, arrs)
        return None, None
    if _nan_check_on():
        _check_nan_inf(name, outs)
    return entry, outs


def clear_eager_cache():
    _eager_cache.clear()
    _eager_seen.clear()


def call(impl: Callable, tensors: Sequence[Any], kwargs: Optional[dict] = None,
         name: Optional[str] = None, nondiff: bool = False,
         override_arrs: Optional[tuple] = None):
    """Run `impl(*arrays, **kwargs)` with eager autograd bookkeeping.

    `tensors` are the (potentially differentiable) data inputs; `kwargs` are
    static attributes closed over the vjp. Returns Tensor or tuple of Tensors
    (matching impl's return structure). `override_arrs`, when given, supplies
    the VALUES for the first len(override_arrs) inputs in place of their
    current `.data` — the tensors still provide tape connectivity (used by
    create_graph replay, which must see the RECORDED primal even if an
    optimizer has since rebound the parameter's data).
    """
    kwargs = kwargs or {}
    name = name or getattr(impl, "_op_name", impl.__name__)
    if GRAPH_BUILDER is not None:
        return GRAPH_BUILDER(impl, tensors, kwargs, name)
    if override_arrs is not None:
        arrs = tuple(override_arrs) + tuple(
            _unwrap(t) for t in tensors[len(override_arrs):])
    else:
        arrs = tuple(_unwrap(t) for t in tensors)

    arrs = _maybe_autocast(name, arrs)

    requires = (not nondiff and tape_mod.grad_enabled()
                and any(_wants_grad(t) for t in tensors))

    # observability fast-exit: with metrics disabled and no RECORD window the
    # instrumented path is skipped entirely (one attr read + two bool tests).
    # Tracer inputs also bypass it: an op re-entered during a to_static /
    # TrainStep trace executes per compiled run, not per Python call, so
    # counting it would inject one model's worth of phantom "eager
    # dispatches" per (re)trace (same rule as collective.py's eager gate)
    tracing = _op_recorder.enabled
    if any(isinstance(a, jax.core.Tracer) for a in arrs):
        # in-trace re-entry executes per compiled run, not per call: no
        # eager allocation happens here, so no OOM guard either
        return _execute(impl, kwargs, arrs, tensors, name, requires)
    if not tracing and not _metrics_mod.enabled():
        return _execute_guarded(impl, kwargs, arrs, tensors, name, requires)
    t0 = now_ns() if tracing else 0  # clock reads only feed spans/histogram
    if tracing and _xplane.annotating():
        # an xplane capture session is recording: put this op's name in the
        # device trace so xplane.correlate can hand its measured backend
        # time back to the span below
        with jax.profiler.TraceAnnotation(name):
            result = _execute_guarded(impl, kwargs, arrs, tensors, name,
                                      requires)
    else:
        result = _execute_guarded(impl, kwargs, arrs, tensors, name, requires)
    t1 = now_ns() if tracing else 0
    outs = result if isinstance(result, tuple) else (result,)
    nbytes = _op_bytes_estimate(
        arrs, [o.data for o in outs if isinstance(o, Tensor)])
    flops = _op_flops_estimate(name, arrs)
    if _metrics_mod.enabled():
        _M_OP_CALLS.inc(op=name)
        _M_OP_BYTES.inc(nbytes, op=name)
        _M_OP_FLOPS.inc(flops, op=name)
        if tracing:
            _M_OP_TIME.observe((t1 - t0) / 1e9, op=name)
    if tracing:
        # device-vs-host split: host span = dispatch latency; device time
        # is measured (sync mode) or roofline-estimated per op
        dev_ns, dev_src = _device_time.attribute(
            [o.data for o in outs if isinstance(o, Tensor)],
            flops, nbytes, t0)
        if dev_ns is not None and _metrics_mod.enabled():
            _M_OP_DEVICE_TIME.observe(dev_ns / 1e9, op=name, src=dev_src)
        stack = _op_recorder.span_stack()
        _op_recorder.push(HostSpan(
            name=name, start_ns=t0, end_ns=t1, tid=threading.get_ident(),
            event_type="Operator", parent=stack[-1] if stack else None,
            args={"shapes": [list(getattr(a, "shape", ())) for a in arrs],
                  "dtypes": [str(getattr(a, "dtype", "?")) for a in arrs],
                  "bytes_est": nbytes},
            device_ns=dev_ns, device_src=dev_src))
    return result


def _looks_like_oom(e: BaseException) -> bool:
    s = str(e)
    return ("RESOURCE_EXHAUSTED" in s or "Out of memory" in s
            or "out of memory" in s)


def _oom_error(name, arrs, detail: str) -> DeviceOOMError:
    try:
        nbytes = int(_op_bytes_estimate(arrs, []))
    except Exception:
        nbytes = 0
    if _metrics_mod.enabled():
        _M_DEVICE_OOM.inc(op=name)
    _events_mod.emit("device_oom", severity="error", op=name,
                     bytes_est=nbytes)
    return DeviceOOMError(name, nbytes, detail)


def _execute_guarded(impl, kwargs, arrs, tensors, name, requires):
    """The allocator boundary: every eager op's output buffers are
    allocated inside this call, so this is where device OOM becomes a typed
    error. XLA RESOURCE_EXHAUSTED failures — and anything the armed
    `device.alloc` fault site injects — surface as DeviceOOMError naming
    the op and its byte estimate (+ `device_oom_total{op=}`) instead of a
    raw XlaRuntimeError string."""
    try:
        # site() itself is a single dict truthiness check when unarmed
        _fault_injector.site("device.alloc")
    except (InjectedFault, InjectedTimeout, InjectedIOError) as e:
        raise _oom_error(name, arrs, str(e)) from e
    try:
        return _execute(impl, kwargs, arrs, tensors, name, requires)
    except DeviceOOMError:
        raise
    except Exception as e:
        if _looks_like_oom(e):
            raise _oom_error(name, arrs, str(e)) from e
        raise


def _execute(impl, kwargs, arrs, tensors, name, requires):
    """The uninstrumented op body: cached-or-traced forward + tape record.
    Labels the thread's compile-attribution entry as `eager:<op>` for the
    duration, so any XLA compile triggered here (cache staging, jax.vjp,
    lazy jnp jits) is attributed to this op (two attr writes when nothing
    compiles)."""
    _cw_prev = _compile_watch.push_entry("eager", name)
    try:
        return _execute_body(impl, kwargs, arrs, tensors, name, requires)
    finally:
        _compile_watch.pop_entry(_cw_prev)


def _execute_body(impl, kwargs, arrs, tensors, name, requires):
    if requires:
        entry, outs = _try_cached_fwd(impl, kwargs, arrs, name)
        if entry is not None:
            vjp_fn = entry.make_vjp(arrs)
            prim_fn = entry.prim
        else:
            def tup_impl(*a):
                out = impl(*a, **kwargs)
                return out if isinstance(out, tuple) else (out,)
            outs, vjp_fn = jax.vjp(tup_impl, *arrs)
            prim_fn = tup_impl
            if _nan_check_on():
                _check_nan_inf(name, outs)
        out_tensors = tuple(Tensor(o, stop_gradient=False) for o in outs)
        in_refs = [t if isinstance(t, Tensor) else None for t in tensors]
        # prim_fn/in_arrs make the node replayable for create_graph (double
        # grad re-linearizes through a fresh jax.vjp — see tape._relinearize)
        tape_mod.record(vjp_fn, in_refs, out_tensors, name=name,
                        prim_fn=prim_fn, in_arrs=arrs)
        return out_tensors[0] if len(out_tensors) == 1 else out_tensors
    else:
        # no-grad (inference/eval) eager path rides the same cache: jitted
        # forward, with the identical concreteness fallback. A genuine
        # 1-tuple op output collapses to a single Tensor here, matching the
        # grad path's long-standing convention.
        entry, outs = _try_cached_fwd(impl, kwargs, arrs, name)
        if entry is not None:
            out_tensors = tuple(Tensor(o, stop_gradient=True) for o in outs)
            return out_tensors[0] if len(out_tensors) == 1 else out_tensors
        out = impl(*arrs, **kwargs)
        if _nan_check_on():
            _check_nan_inf(name, out if isinstance(out, tuple) else (out,))
        if isinstance(out, tuple):
            out_tensors = tuple(Tensor(o, stop_gradient=True) for o in out)
            # 1-tuple collapse must match the cached hit above — an op's
            # return structure may not change once the cache warms
            return out_tensors[0] if len(out_tensors) == 1 else out_tensors
        return Tensor(out, stop_gradient=True)


# ---------------------------------------------------------------------------
# NaN/Inf numerical sanitizer (reference: FLAGS_check_nan_inf →
# CheckOpHasNanOrInfInDygraph, framework/details/nan_inf_utils.h:44).
# Routed through the training-health plane (profiler/health.py): the first
# bad op output emits a `tensor_health` event naming op + layer path +
# shape/dtype + bad-value kind before the (reference-semantics) crash.
# ---------------------------------------------------------------------------
from ..framework import flags as _flags_mod  # noqa: E402  (imports os only)
from ..profiler import health as _health_mod  # noqa: E402

_NAN_FLAG = _flags_mod._REGISTRY["FLAGS_check_nan_inf"]
_EAGER_CACHE_FLAG = _flags_mod._REGISTRY["FLAGS_eager_op_cache"]


def _nan_check_on() -> bool:
    return _NAN_FLAG.value


def _check_nan_inf(name: str, outs):
    for i, o in enumerate(outs):
        if not isinstance(o, jax.Array):
            continue
        if isinstance(o, jax.core.Tracer):
            continue  # under jit: the TrainStep's in-graph sentinel (or
            # the PADDLE_TPU_DEBUG_NANS escape hatch) covers compiled code
        if (dtype_mod.is_floating(o.dtype) or dtype_mod.is_complex(o.dtype)):
            if not bool(jnp.all(jnp.isfinite(o))):
                # failure path only: two more tiny fetches to name the kind
                kind = "nan" if bool(jnp.any(jnp.isnan(o))) else "inf"
                rec = _health_mod.note_bad_tensor(
                    op=name, output_index=i, shape=tuple(o.shape),
                    dtype=str(o.dtype), kind=kind)
                where = f" in layer '{rec['layer']}'" if rec.get("layer") \
                    else ""
                raise FloatingPointError(
                    f"Operator '{name}' output {i} contains {kind}{where} "
                    f"(shape {tuple(o.shape)}, dtype {o.dtype}). Enabled by "
                    f"FLAGS_check_nan_inf.")


def _multi_out(impl):
    return getattr(impl, "_multi_out", False)


# ---------------------------------------------------------------------------
# AMP autocast (reference: imperative/amp_auto_cast.h allow/block lists)
# ---------------------------------------------------------------------------
_amp_state = {"enabled": False, "dtype": jnp.bfloat16, "level": "O1",
              "custom_white": set(), "custom_black": set()}

# ops that are numerically safe & fast in bf16 (MXU-bound)
AMP_WHITE = {"matmul", "conv2d", "conv1d", "conv3d", "conv2d_transpose",
             "linear", "bmm", "mm", "einsum", "addmm"}
# ops that must run in fp32
AMP_BLACK = {"softmax_with_cross_entropy", "cross_entropy", "log_softmax",
             "mean", "sum", "norm", "exp", "log", "logsumexp", "var", "std",
             "layer_norm", "batch_norm"}


def amp_state():
    return _amp_state


def _maybe_autocast(name: str, arrs: tuple):
    st = _amp_state
    if not st["enabled"]:
        return arrs
    amp_dtype = st["dtype"]
    white = (AMP_WHITE | st["custom_white"]) - st["custom_black"]
    black = (AMP_BLACK | st["custom_black"]) - st["custom_white"]
    if name in white:
        return tuple(a.astype(amp_dtype)
                     if dtype_mod.is_floating(a.dtype) and a.dtype != amp_dtype else a
                     for a in arrs)
    if name in black:
        return tuple(a.astype(jnp.float32)
                     if a.dtype in (jnp.dtype(jnp.float16), jnp.dtype(jnp.bfloat16)) else a
                     for a in arrs)
    return arrs
