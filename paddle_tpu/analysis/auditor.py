"""Static program auditor: perf-hazard analysis over jaxpr + StableHLO.

The phi layer of the survey ships kernels behind a registry that can be
audited before anything runs; this is the JAX analogue. The auditor
traces (never executes) a program at its jit entry point and inspects
two artifacts:

* the **closed jaxpr** — op-level dtype flow, named-scope attribution
  (the PR-11 ``jax.named_scope`` metadata rides each equation's
  ``source_info.name_stack``), closure-captured constants, collective
  primitives;
* the **lowered StableHLO text** — the donation/aliasing table XLA
  actually accepted (``tf.aliasing_output`` / ``jax.buffer_donor`` arg
  attributes) vs what the caller requested (``Lowered.args_info``).

Checks (see findings.py for severity semantics):

1. **donation** — large (>= ``PADDLE_TPU_AUDIT_DONATE_MIN_BYTES``,
   default 1 MiB) input buffers that are dead after the step (an output
   of identical shape/dtype exists — the update pattern) but were not
   donated; and donations the caller requested that XLA rejected (no
   aliasing entry in the lowered text).
2. **dtype** — f64 anywhere (TPU-hostile); in a bf16-dominant region,
   f32 matmuls/convs and large silent float upcasts at op boundaries,
   attributed to the originating layer via named scopes.
3. **sharding** — collectives whose estimated per-step bytes exceed
   ``PADDLE_TPU_AUDIT_COLLECTIVE_BUDGET_MB``; and (via
   :func:`audit_sharding`) large params whose NamedSharding resolves to
   full replication while the mesh has a usable axis.
4. **bloat** — oversized constants baked into the program (host arrays
   captured by closure instead of passed as args,
   ``PADDLE_TPU_AUDIT_CONST_MIN_BYTES``) and retrace-risk static args.
5. **relayout** (only where the caller names the buffers, `relayout_of`;
   it COMPILES) — whole-buffer ``copy`` instructions of a donated
   buffer's shape in the optimized HLO: the device's default layout for
   the shape is not the one the program works in, so the buffer is
   re-laid out on the way in and out of every call although donation
   "succeeded" (:func:`pool_relayout_report`).

Apart from that check nothing here compiles or runs device code — it is
trace-time analysis that works on CPU CI, which is the point: every compiled TrainStep and
serving executable is vetted before a single device step. Runtime
integration is opt-in via ``PADDLE_TPU_AUDIT`` (``1``/``on`` audits the
compiled entry points — TrainStep, to_static, serving; ``all`` adds the
eager jit cache; each (entry, name) site is audited once per process).
"""
from __future__ import annotations

import math
import re
import threading
import warnings
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from ..utils.envparse import env_float, env_int, env_str
from .findings import AuditReport, Finding

__all__ = ["audit_program", "audit_collectives_by_link", "audit_sharding",
           "pool_relayout_report", "maybe_audit", "enabled", "AUDIT_ENV",
           "reset_seen"]

AUDIT_ENV = "PADDLE_TPU_AUDIT"

#: float widths for the upcast lattice (ml_dtypes bf16 has itemsize 2)
_FLOAT_ORDER = {"bfloat16": 2, "float16": 2, "float32": 4, "float64": 8}

#: primitives that move bytes across chips (the sharding-budget check),
#: by the names jax 0.9.0 traces: under shard_map's varying-axes typing a
#: `lax.psum` arrives as `psum_invariant` and an all-gather of a varying
#: value as `all_gather_invariant`
_COLLECTIVE_PRIMS = ("psum", "psum_invariant", "all_gather",
                     "all_gather_invariant", "reduce_scatter", "all_to_all",
                     "ragged_all_to_all", "ppermute", "pmax", "pmin")

#: primitives whose compute dtype defines the "model region" and whose
#: f32 appearance inside a bf16 region is the classic AMP leak
_MATMUL_PRIMS = ("dot_general", "conv_general_dilated")


def _min_donate_bytes() -> int:
    return env_int("PADDLE_TPU_AUDIT_DONATE_MIN_BYTES", 1 << 20)


def _min_const_bytes() -> int:
    return env_int("PADDLE_TPU_AUDIT_CONST_MIN_BYTES", 1 << 20)


def _min_upcast_bytes() -> int:
    return env_int("PADDLE_TPU_AUDIT_UPCAST_MIN_BYTES", 1 << 20)


def _collective_budget_bytes() -> float:
    return env_float("PADDLE_TPU_AUDIT_COLLECTIVE_BUDGET_MB",
                     16 * 1024.0) * (1 << 20)


def _link_budget_bytes(link: str) -> float:
    """Per-link budgets: DCN is ~15x slower per chip than ICI, so the
    same byte count that is fine intra-slice is a hazard across slices."""
    if link == "dcn":
        return env_float("PADDLE_TPU_AUDIT_COLLECTIVE_BUDGET_DCN_MB",
                         1024.0) * (1 << 20)
    return env_float("PADDLE_TPU_AUDIT_COLLECTIVE_BUDGET_ICI_MB",
                     16 * 1024.0) * (1 << 20)


def enabled(entry: str) -> bool:
    """Is runtime auditing armed for this jit entry point?
    PADDLE_TPU_AUDIT: unset/0 = off; 1/on/trace = compiled entry points
    (train_step, to_static, serving_*); all = those plus the eager jit
    cache (every new eager op signature pays one extra trace)."""
    raw = (env_str(AUDIT_ENV, "") or "").strip().lower()
    if raw in ("", "0", "false", "off", "no"):
        return False
    if raw == "all":
        return True
    return entry != "eager"


# -- aval plumbing -----------------------------------------------------------

def _aval_nbytes(aval) -> int:
    try:
        size = int(np.prod(aval.shape)) if aval.shape else 1
        return size * aval.dtype.itemsize
    except Exception:
        return 0


def _dtype_name(dtype) -> str:
    try:
        return np.dtype(dtype).name
    except Exception:
        return str(dtype)


def _is_float(dtype) -> bool:
    return _dtype_name(dtype) in _FLOAT_ORDER


def _walk_eqns(jaxpr) -> Iterable[Tuple[Any, str]]:
    """Yield (eqn, scope) over `jaxpr` and every sub-jaxpr (pjit bodies,
    custom_vjp calls, scan/while/cond branches). `scope` is the
    named-scope path from the equation's source info — the PR-11
    attribution channel."""
    for eqn in jaxpr.eqns:
        try:
            scope = str(eqn.source_info.name_stack)
        except Exception:
            scope = ""
        yield eqn, scope
        for sub in _sub_jaxprs(eqn):
            for inner, inner_scope in _walk_eqns(sub):
                yield inner, (inner_scope or scope)


def _sub_jaxprs(eqn) -> List[Any]:
    out = []
    for v in eqn.params.values():
        core = getattr(v, "jaxpr", None)  # ClosedJaxpr
        if core is not None and hasattr(core, "eqns"):
            out.append(core)
        elif hasattr(v, "eqns"):          # bare Jaxpr
            out.append(v)
        elif isinstance(v, (tuple, list)):
            for x in v:
                core = getattr(x, "jaxpr", None)
                if core is not None and hasattr(core, "eqns"):
                    out.append(core)
                elif hasattr(x, "eqns"):
                    out.append(x)
    return out


def _flat_arg_labels(args_info) -> List[str]:
    """One human label per flattened argument, from tree paths."""
    import jax
    flat, _ = jax.tree_util.tree_flatten_with_path(args_info)
    labels = []
    for path, _leaf in flat:
        labels.append(jax.tree_util.keystr(path) or "arg")
    return labels


# -- lowered-text parsing ----------------------------------------------------

# the attr dict may hold quoted values containing `}` (mhlo.sharding =
# "{devices=[2,1]<=[2]}" on sharded lowerings) — consume quoted strings
# atomically so the dict match doesn't truncate before the aliasing attr
_ARG_RE = re.compile(
    r"%arg(\d+):\s*tensor<[^>]*>\s*(\{(?:[^{}\"]|\"[^\"]*\")*\})?")


def _main_signature(text: str) -> str:
    """The argument list of the public @main func in StableHLO text
    (paren-balanced slice; `loc(...)` attributes nest parens)."""
    m = re.search(r"func\.func\s+(?:public\s+)?@main\s*\(", text)
    if not m:
        return ""
    i = m.end()
    depth = 1
    j = i
    while j < len(text) and depth:
        c = text[j]
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
        j += 1
    return text[i:j - 1]


def accepted_donations(lowered_text: str) -> set:
    """Flat arg indices whose lowering carries an aliasing/donation
    attribute — the donations XLA actually accepted."""
    sig = _main_signature(lowered_text)
    out = set()
    for m in _ARG_RE.finditer(sig):
        attrs = m.group(2) or ""
        if "tf.aliasing_output" in attrs or "jax.buffer_donor" in attrs:
            out.add(int(m.group(1)))
    return out


# -- the checks --------------------------------------------------------------

def _check_donation(report: AuditReport, flat_args, labels,
                    requested: set, accepted: set, out_avals):
    min_bytes = _min_donate_bytes()
    # outputs aliased by an ACCEPTED donation are consumed: they cannot
    # also justify flagging a second same-shaped input as dead
    out_pool: Dict[Tuple[tuple, str], int] = {}
    for aval in out_avals:
        key = (tuple(aval.shape), _dtype_name(aval.dtype))
        out_pool[key] = out_pool.get(key, 0) + 1
    for i in sorted(requested):
        if i >= len(flat_args):
            continue
        aval = flat_args[i]
        key = (tuple(aval.shape), _dtype_name(aval.dtype))
        if out_pool.get(key):
            out_pool[key] -= 1
    for i, aval in enumerate(flat_args):
        nbytes = _aval_nbytes(aval)
        key = (tuple(aval.shape), _dtype_name(aval.dtype))
        if i in requested:
            if i not in accepted:
                report.add(Finding(
                    check="donation", severity="high",
                    code="donation-rejected",
                    message=(f"donation of {key[1]}{list(aval.shape)} was "
                             f"requested but XLA's lowering carries no "
                             f"aliasing entry for it — the buffer is "
                             f"copied anyway"),
                    param=labels[i] if i < len(labels) else f"arg{i}",
                    nbytes=nbytes,
                    fix_hint=("make an output alias-compatible (same "
                              "shape/dtype) or drop the donation")))
            continue
        if nbytes < min_bytes:
            continue
        if out_pool.get(key):
            out_pool[key] -= 1
            report.add(Finding(
                check="donation", severity="high",
                code="undonated-large-input",
                message=(f"{key[1]}{list(aval.shape)} (~{nbytes >> 20} MiB) "
                         f"is replaced by a same-shaped output each step "
                         f"but is not donated — XLA must double-buffer "
                         f"it"),
                param=labels[i] if i < len(labels) else f"arg{i}",
                nbytes=nbytes,
                fix_hint="add this argument to donate_argnums"))


def _check_dtype(report: AuditReport, jaxpr):
    min_upcast = _min_upcast_bytes()
    # model-region dtype = the dominant float dtype by matmul/conv
    # OUTPUT bytes (elementwise ops follow whatever the matmuls feed)
    region_bytes: Dict[str, int] = {}
    f64_scopes: Dict[str, int] = {}
    upcasts: Dict[Tuple[str, str, str], Tuple[int, int]] = {}
    f32_matmuls: Dict[str, Tuple[int, int]] = {}
    for eqn, scope in _walk_eqns(jaxpr):
        prim = eqn.primitive.name
        if prim in _MATMUL_PRIMS:
            # a matmul COMPUTES in its widest float operand dtype (XLA
            # upcasts mixed operands); outputs may legitimately be wider
            # (f32 accumulation), so the region is operand-defined
            in_fl = [v.aval for v in eqn.invars
                     if hasattr(v, "aval")
                     and _is_float(getattr(v.aval, "dtype", None))]
            if in_fl:
                dt = max((_dtype_name(a.dtype) for a in in_fl),
                         key=lambda d: _FLOAT_ORDER[d])
                region_bytes[dt] = region_bytes.get(dt, 0) + sum(
                    _aval_nbytes(a) for a in in_fl)
        for v in eqn.outvars:
            aval = getattr(v, "aval", None)
            if aval is None or not _is_float(getattr(aval, "dtype", None)):
                continue
            if _dtype_name(aval.dtype) == "float64":
                f64_scopes[scope] = f64_scopes.get(scope, 0) + 1
        if prim == "convert_element_type":
            try:
                src = eqn.invars[0].aval.dtype
                dst = eqn.params.get("new_dtype")
            except Exception:
                continue
            if not (_is_float(src) and _is_float(dst)):
                continue
            if _FLOAT_ORDER[_dtype_name(dst)] <= _FLOAT_ORDER[
                    _dtype_name(src)]:
                continue
            nbytes = _aval_nbytes(eqn.outvars[0].aval)
            if nbytes < min_upcast:
                continue
            key = (scope, _dtype_name(src), _dtype_name(dst))
            n, total = upcasts.get(key, (0, 0))
            upcasts[key] = (n + 1, total + nbytes)
    # region = bf16/f16 when narrow-float matmuls carry a meaningful
    # share of the compute (>= 20% of matmul bytes): the model INTENDS
    # mixed precision there, so wide matmuls are leaks. Judging by the
    # dominant dtype alone would let one big f32 leak redefine the
    # region and hide itself.
    total_mm = sum(region_bytes.values())
    narrow = sum(region_bytes.get(d, 0) for d in ("bfloat16", "float16"))
    if total_mm and narrow >= 0.2 * total_mm:
        region = "bfloat16" if region_bytes.get("bfloat16", 0) >= \
            region_bytes.get("float16", 0) else "float16"
    elif region_bytes:
        region = max(region_bytes, key=region_bytes.get)
    else:
        region = None
    if region in ("bfloat16", "float16"):
        # second pass: wide-OPERAND matmuls inside the narrow region.
        # Output dtype is deliberately ignored: f32 accumulation from
        # bf16 operands (preferred_element_type) is good practice, not a
        # leak — the MXU rate is set by what the operands are.
        for eqn, scope in _walk_eqns(jaxpr):
            if eqn.primitive.name not in _MATMUL_PRIMS:
                continue
            in_dts = [_dtype_name(v.aval.dtype) for v in eqn.invars
                      if hasattr(v, "aval")
                      and _is_float(getattr(v.aval, "dtype", None))]
            if in_dts and all(_FLOAT_ORDER[d] > _FLOAT_ORDER[region]
                              for d in in_dts):
                n, total = f32_matmuls.get(scope, (0, 0))
                f32_matmuls[scope] = (
                    n + 1, total + _aval_nbytes(eqn.outvars[0].aval))
    for scope, n in sorted(f64_scopes.items()):
        report.add(Finding(
            check="dtype", severity="high", code="f64-compute",
            message=(f"{n} op(s) compute in float64 — TPUs emulate f64 "
                     f"at a fraction of peak and double every buffer"),
            scope=scope,
            fix_hint="cast to float32/bfloat16 (or keep jax_enable_x64 "
                     "off)"))
    for (scope, src, dst), (n, total) in sorted(upcasts.items()):
        sev = "medium" if region in ("bfloat16", "float16") else "low"
        report.add(Finding(
            check="dtype", severity=sev, code="silent-upcast",
            message=(f"{n} convert(s) {src}->{dst} totalling "
                     f"~{total >> 20} MiB at op boundaries"),
            scope=scope, nbytes=total,
            fix_hint=(f"keep the region in {region or src}: check the "
                      f"layer's param/activation dtypes at this scope")))
    for scope, (n, total) in sorted(f32_matmuls.items()):
        report.add(Finding(
            check="dtype", severity="medium", code="f32-matmul-in-bf16",
            message=(f"{n} float32 matmul/conv op(s) (~{total >> 20} MiB "
                     f"out) inside a {region} model region — the MXU "
                     f"runs these at half rate"),
            scope=scope, nbytes=total,
            fix_hint="cast the operands (amp_dtype / maybe_cast) at this "
                     "scope"))


def _check_collectives(report: AuditReport, jaxpr):
    budget = _collective_budget_bytes()
    if budget <= 0:
        return
    per_scope: Dict[Tuple[str, str], int] = {}
    total = 0
    for eqn, scope in _walk_eqns(jaxpr):
        prim = eqn.primitive.name
        if prim not in _COLLECTIVE_PRIMS:
            continue
        nbytes = max(
            sum(_aval_nbytes(v.aval) for v in eqn.invars
                if hasattr(v, "aval")),
            sum(_aval_nbytes(v.aval) for v in eqn.outvars))
        total += nbytes
        key = (scope, prim)
        per_scope[key] = per_scope.get(key, 0) + nbytes
    if total > budget:
        top = sorted(per_scope.items(), key=lambda kv: -kv[1])[:3]
        detail = ", ".join(f"{prim}@{scope or '<root>'}"
                           f"~{b >> 20}MiB" for (scope, prim), b in top)
        report.add(Finding(
            check="sharding", severity="high",
            code="collective-budget-exceeded",
            message=(f"collectives move ~{total >> 20} MiB per step, over "
                     f"the {int(budget) >> 20} MiB budget "
                     f"(top: {detail})"),
            nbytes=total,
            fix_hint=("shard the offending tensors further, fuse "
                      "collectives, or raise "
                      "PADDLE_TPU_AUDIT_COLLECTIVE_BUDGET_MB")))


def _check_bloat(report: AuditReport, consts, static_args=None):
    min_bytes = _min_const_bytes()
    small_total = 0
    for i, c in enumerate(consts):
        # sized from shape x dtype: the closed jaxpr's consts are typed
        # literal wrappers (jax 0.9.0), which carry no `nbytes`
        shape = tuple(getattr(c, "shape", ()) or ())
        dtype = _dtype_name(getattr(c, "dtype", "?"))
        nbytes = (math.prod(shape) * np.dtype(c.dtype).itemsize
                  if hasattr(c, "dtype") else 0)
        if nbytes >= min_bytes:
            report.add(Finding(
                check="bloat", severity="high", code="baked-constant",
                message=(f"{dtype}{list(shape)} (~{nbytes >> 20} MiB) is "
                         f"baked into the executable as a constant — a "
                         f"host array captured by closure is re-uploaded "
                         f"with every executable that embeds it"),
                param=f"const{i}", nbytes=nbytes,
                fix_hint="pass the array as an argument (or a donated "
                         "buffer) instead of capturing it"))
        else:
            small_total += nbytes
    if small_total >= 4 * min_bytes:
        report.add(Finding(
            check="bloat", severity="medium", code="constant-accretion",
            message=(f"{len(consts)} captured constants total "
                     f"~{small_total >> 20} MiB (each under the "
                     f"baked-constant threshold)"),
            nbytes=small_total,
            fix_hint="thread recurring host state as arguments"))
    for name, val in (static_args or {}).items():
        risky = isinstance(val, float) or (
            isinstance(val, (tuple, list)) and len(val) > 16)
        if risky:
            report.add(Finding(
                check="bloat", severity="low", code="retrace-risk-static",
                message=(f"static arg {name!r} = {type(val).__name__} — "
                         f"every distinct value recompiles the program "
                         f"(floats/high-cardinality values churn)"),
                param=str(name),
                fix_hint="make it a traced argument or quantize its "
                         "value space"))


#: HLO's names of the dtypes a K/V pool is stored in
_HLO_DTYPE = {"float32": "f32", "bfloat16": "bf16", "float16": "f16"}


def pool_relayout_report(compiled, pools: Sequence) -> Dict[str, int]:
    """What a COMPILED program does to buffers it should update in place.

    `pools` are arrays (or anything with `.shape`, `.dtype` and
    optionally `.sharding`) such as a serving engine's K/V page pools.
    Returns `pool_relayout_copies`, the number of `copy` instructions in
    the optimized HLO whose result has a pool's per-device shape and
    dtype, with `temp_size_in_bytes` of the program and `pool_bytes` of
    the largest pool on one device. A program that holds the pools in
    one layout from argument to result has no such copy, and a `temp`
    smaller than one pool. Works on a program compiled for a described
    topology (no chip) as on a live one."""
    shapes, pool_bytes = set(), 0
    for p in pools:
        shape = tuple(p.shape)
        sharding = getattr(p, "sharding", None)
        if sharding is not None:
            shape = tuple(sharding.shard_shape(shape))
        dt = np.dtype(p.dtype)
        shapes.add(f"{_HLO_DTYPE.get(dt.name, dt.name)}"
                   f"[{','.join(map(str, shape))}]")
        pool_bytes = max(pool_bytes, math.prod(shape) * dt.itemsize)
    text = compiled.as_text()
    copies = sum(len(re.findall(
        r"= %s(?:\{[^}]*\})? copy\(" % re.escape(s), text)) for s in shapes)
    return {"pool_relayout_copies": copies,
            "temp_size_in_bytes": int(
                compiled.memory_analysis().temp_size_in_bytes),
            "pool_bytes": int(pool_bytes)}


def _check_relayout(report: AuditReport, compiled, pools):
    found = pool_relayout_report(compiled, pools)
    report.pool_relayout_copies = found["pool_relayout_copies"]
    report.temp_size_in_bytes = found["temp_size_in_bytes"]
    if not report.pool_relayout_copies:
        return
    report.add(Finding(
        check="donation", severity="high", code="pool-relayout-copy",
        message=(f"the compiled program copies a whole donated pool "
                 f"({found['pool_bytes'] >> 20} MiB) "
                 f"{report.pool_relayout_copies} time(s) per call: its "
                 f"default device layout is not the layout the program "
                 f"works in (temp {report.temp_size_in_bytes >> 20} MiB)"),
        nbytes=found["pool_bytes"],
        fix_hint="store the buffer in a shape whose default layout is "
                 "row-major (fold the trailing dims to a multiple of 128)"))


# -- entry points ------------------------------------------------------------

def audit_program(fn, args: Sequence, kwargs: Optional[dict] = None, *,
                  donate_argnums: Sequence[int] = (),
                  static_args: Optional[dict] = None,
                  relayout_of: Optional[Sequence] = None,
                  name: str = "program", entry: str = "offline",
                  emit: bool = True) -> AuditReport:
    """Trace `fn(*args, **kwargs)` and audit the program statically.

    `donate_argnums` are the TOP-LEVEL argument positions the caller
    donates (exactly what it passes to jax.jit) — the auditor compares
    them against the aliasing table XLA accepted. Findings are emitted
    to events/metrics unless `emit=False`. Never executes the program.
    `relayout_of` names buffers the program should update in place (a
    serving engine's page pools): the program is then also COMPILED and
    the report carries `pool_relayout_copies` and `temp_size_in_bytes`.
    """
    import jax

    kwargs = kwargs or {}
    report = AuditReport(name=name, entry=entry)

    with warnings.catch_warnings():
        # the rejected-donation warning is re-raised as a typed finding
        warnings.simplefilter("ignore")
        # ONE trace serves both artifacts: Traced.jaxpr carries the
        # closed jaxpr (with captured consts) and .lower() reuses the
        # trace — tracing twice doubled audit cost at every entry point
        traced = jax.jit(
            fn, donate_argnums=tuple(donate_argnums)).trace(*args, **kwargs)
        closed = traced.jaxpr
        lowered = traced.lower()
    text = lowered.as_text()

    flat_info, _ = jax.tree_util.tree_flatten(lowered.args_info)
    flat_avals = [getattr(i, "aval", i) for i in flat_info]
    requested = {i for i, info in enumerate(flat_info)
                 if bool(getattr(info, "donated", False))}
    labels = _flat_arg_labels(lowered.args_info)
    out_avals = [v.aval for v in closed.jaxpr.outvars]

    _check_donation(report, flat_avals, labels, requested,
                    accepted_donations(text), out_avals)
    _check_dtype(report, closed.jaxpr)
    _check_collectives(report, closed.jaxpr)
    _check_bloat(report, closed.consts, static_args)
    if relayout_of:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            _check_relayout(report, lowered.compile(), relayout_of)

    if emit:
        report.emit()
    return report


def audit_collectives_by_link(fn, args: Sequence,
                              kwargs: Optional[dict] = None, *,
                              donate_argnums: Sequence[int] = (),
                              cluster=None, name: str = "program",
                              entry: str = "collectives",
                              emit: bool = True) -> AuditReport:
    """Per-link (ici/dcn) collective-bytes budget over the COMPILED
    program. `audit_program`'s jaxpr check only sees explicit collective
    primitives; the collectives of a GSPMD/shard_map-partitioned program
    (the TP decode path) are inserted by the partitioner, so this check
    compiles (nothing executes — XLA donation is a compile-time aliasing
    hint) and prices the optimized HLO's collectives by the link class
    their replica groups actually cross, via the cluster mapper's
    slice-major topology. Budgets:
    ``PADDLE_TPU_AUDIT_COLLECTIVE_BUDGET_ICI_MB`` (default 16 GiB) and
    ``_DCN_MB`` (default 1 GiB); the cluster shape comes from
    ``PADDLE_TPU_NUM_SLICES`` (single-slice clusters bill everything to
    ici) unless an explicit `cluster` is passed. The report carries the
    measured totals on ``report.link_bytes``."""
    import jax

    from ..distributed.auto_parallel.cluster import Cluster, Mapper

    kwargs = kwargs or {}
    if cluster is None:
        ndev = jax.device_count()
        n_slices = max(1, env_int("PADDLE_TPU_NUM_SLICES", 1))
        cluster = Cluster(n_slices=n_slices,
                          chips_per_slice=max(1, ndev // n_slices))
    report = AuditReport(name=name, entry=entry)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        compiled = jax.jit(
            fn, donate_argnums=tuple(donate_argnums)).lower(
                *args, **kwargs).compile()
    ici, dcn = Mapper(cluster).collective_bytes_by_link(compiled)
    for link, nbytes, bw in (("ici", ici, cluster.ici_bw),
                             ("dcn", dcn, cluster.dcn_bw)):
        budget = _link_budget_bytes(link)
        if budget <= 0 or nbytes <= budget:
            continue
        report.add(Finding(
            check="sharding", severity="high",
            code=f"collective-budget-exceeded-{link}",
            message=(f"compiled collectives move ~{int(nbytes) >> 20} MiB "
                     f"per step over {link} "
                     f"(~{nbytes / bw * 1e3:.2f} ms at "
                     f"{bw / 1e9:.0f} GB/s), over the "
                     f"{int(budget) >> 20} MiB {link} budget"),
            nbytes=int(nbytes),
            fix_hint=(f"reshard so the traffic rides a faster link, fuse "
                      f"collectives, or raise "
                      f"PADDLE_TPU_AUDIT_COLLECTIVE_BUDGET_"
                      f"{link.upper()}_MB")))
    report.link_bytes = {"ici": float(ici), "dcn": float(dcn)}
    if emit:
        report.emit()
    return report


def audit_sharding(params: Dict[str, Any],
                   mesh_axes: Optional[Dict[str, int]] = None, *,
                   name: str = "params", entry: str = "offline",
                   min_bytes: Optional[int] = None,
                   emit: bool = True) -> AuditReport:
    """Audit a param tree's shardings: a large param whose NamedSharding
    resolves to full replication while the mesh has a usable (>1) axis
    that divides one of its dims is memory the fleet pays `world` times.

    `params` leaves may be jax.Arrays (sharding read off the array) or
    (shape, dtype, partition-spec) triples for metadata-level audits —
    which is what CPU CI uses, since a single-device process cannot
    build a >1 mesh. `mesh_axes` maps axis name -> size; when None it is
    read from the first NamedSharding leaf's mesh."""
    import jax

    report = AuditReport(name=name, entry=entry)
    if min_bytes is None:
        min_bytes = env_int("PADDLE_TPU_AUDIT_REPLICATED_MIN_BYTES",
                            1 << 20)

    flat, _ = jax.tree_util.tree_flatten_with_path(
        params, is_leaf=lambda x: isinstance(x, tuple) and len(x) == 3)
    leaves = []
    axes = dict(mesh_axes or {})
    for path, leaf in flat:
        label = jax.tree_util.keystr(path) or "param"
        if isinstance(leaf, tuple) and len(leaf) == 3:
            shape, dtype, spec = leaf
            leaves.append((label, tuple(shape), np.dtype(dtype), spec))
            continue
        sharding = getattr(leaf, "sharding", None)
        spec = getattr(sharding, "spec", None)
        mesh = getattr(sharding, "mesh", None)
        if mesh is not None and not axes:
            axes = dict(mesh.shape)
        leaves.append((label, tuple(leaf.shape), np.dtype(leaf.dtype),
                       spec))
    usable = {ax: n for ax, n in axes.items() if int(n) > 1}
    if usable:
        for label, shape, dtype, spec in leaves:
            nbytes = int(np.prod(shape)) * dtype.itemsize if shape else \
                dtype.itemsize
            if nbytes < min_bytes:
                continue
            spec_parts = tuple(spec) if spec is not None else ()
            if any(p is not None for p in spec_parts):
                continue  # sharded on at least one dim
            fitting = [ax for ax, n in usable.items()
                       if any(d % int(n) == 0 and d >= int(n)
                              for d in shape)]
            if not fitting:
                continue
            report.add(Finding(
                check="sharding", severity="high",
                code="replicated-param",
                message=(f"{dtype.name}{list(shape)} (~{nbytes >> 20} "
                         f"MiB) is fully replicated though mesh "
                         f"axis(es) {fitting} could shard it — every "
                         f"chip holds a full copy"),
                param=label, nbytes=nbytes,
                fix_hint=(f"give it a PartitionSpec over "
                          f"{fitting[0]!r}")))
    if emit:
        report.emit()
    return report


# -- runtime hook ------------------------------------------------------------

_seen_lock = threading.Lock()
_seen: set = set()


def reset_seen():
    """Test hook: allow a site to be re-audited in this process."""
    with _seen_lock:
        _seen.clear()


def maybe_audit(entry: str, name: str, fn, args: Sequence,
                kwargs: Optional[dict] = None, *,
                donate_argnums: Sequence[int] = ()) -> Optional[AuditReport]:
    """Audit a jit entry point once per (entry, name) when
    PADDLE_TPU_AUDIT arms it. Swallows every failure — an auditor bug
    must never take down the training step it vets."""
    if not enabled(entry):
        return None
    key = (entry, name)
    with _seen_lock:
        if key in _seen:
            return None
        _seen.add(key)
    try:
        return audit_program(fn, args, kwargs, donate_argnums=donate_argnums,
                             name=name, entry=entry)
    except Exception as e:  # noqa: BLE001 — by contract
        warnings.warn(f"program audit of {entry}:{name} failed "
                      f"({type(e).__name__}: {e}); skipping")
        return None
