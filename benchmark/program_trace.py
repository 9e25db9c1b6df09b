"""The program's own spans and scopes, read back from the traced window.

`tracing.py` times every layer from outside, by the benchmark's `bench.*`
spans. This reader goes below them: the `pt.*` spans the program opens
inside `ServingEngine.step` and `TrainStep.__call__`
(`paddle_tpu/profiler/utils.RecordEvent`; PERF.md section 3 names each
span and argument with the metric that reads it), and the `named_scope`s
its compiled programs carry. One trace, one pass, parsed once a process;
the first read prints one line, `PROGRAM_SPANS {json}`.

How a `TPU v5 lite` trace of jax 0.9.0 / libtpu 0.0.34 carries them
(looked at by hand, PR 25; `tests/data/` keeps a small recorded one):

* a `TraceAnnotation(name, **kw)` is an event `name` on the line of its
  thread in the plane `/host:CPU`, its keyword arguments the event's
  stats, on the device planes' clock;
* an `XLA Ops` event's own stats are `device_offset_ps`,
  `device_duration_ps` and `Time Scale Multiplier`: no scope. The scope
  path (`jit(step)/jvp(attention)/jit(prim)/dot_general:`) is the stat
  `tf_op` of the operation's *event metadata* in the device plane, beside
  `source` (file:line) and `program_id`. `jax.profiler.ProfileData` does
  not show metadata stats, and the installation's one generated
  `xplane_pb2` sits inside tensorflow, which this process does not
  import: they are scanned from the file's bytes (protobuf wire format,
  `xplane.proto`; 0.3 s for a 25 MB trace);
* `tf_op` is the compiled operation's `op_name`. With
  `jax_include_full_tracebacks_in_locations` off (the program's
  `place_caches` turns it off, for a stable compile-cache key) jax 0.9.0
  gives the path only to operations traced inside an inner `jit` (the
  program's per-op `jit(prim)`, a kernel's own jit); one traced directly
  in the step under a `named_scope` arrives bare (`sub:`): the whole
  optimizer does. Such operations count as carrying no scope; the
  optimizer's share alone also accepts an operation whose `source` lies
  in `paddle_tpu/optimizer/`.
"""
from __future__ import annotations

import bisect
import glob
import json
import os
import re
from collections import defaultdict

from benchmark import tracing, yardstick

SPAN_PREFIX = "pt."   # the program's `profiler.utils.SPAN_PREFIX`
OUTSIDE = "outside-program-spans"
PREFILL_PROGRAM = "jit__prefill_fn"
# the host work that stands between one decode program and the next
LAUNCH_SPANS = ("pt.engine.capacity", "pt.engine.lanes", "pt.engine.upload",
                "pt.engine.dispatch")
# the scopes of models/gpt.py and jit.TrainStep, as one path component
SCOPE = re.compile(
    r"(?:^|[/(])(attention|mlp|ln|embed|logits|loss|optimizer)(?=[/)]|:|$)")
OPTIMIZER_SOURCE = "/paddle_tpu/optimizer/"

_summary = None   # of the newest trace: parsed once a process


# ---- the trace file -> plain lists -------------------------------------

def _fields(buf, pos: int, end: int):
    """(field number, wire type, value) of one protobuf message; a
    length-delimited value is its (start, end) in `buf`."""
    while pos < end:
        key = shift = 0
        while True:
            b = buf[pos]
            pos += 1
            key |= (b & 0x7F) << shift
            shift += 7
            if b < 0x80:
                break
        kind = key & 7
        if kind in (0, 2):
            value = shift = 0
            while True:
                b = buf[pos]
                pos += 1
                value |= (b & 0x7F) << shift
                shift += 7
                if b < 0x80:
                    break
            if kind == 2:
                value, pos = (pos, pos + value), pos + value
        elif kind in (1, 5):
            width = 8 if kind == 1 else 4
            value, pos = (pos, pos + width), pos + width
        else:
            raise ValueError(f"wire type {kind} in a trace file")
        yield key >> 3, kind, value


def op_table(data: bytes) -> dict:
    """`<program>/<op>` -> [tf_op, source] from the event metadata of
    the device planes (xplane.proto: XSpace.planes=1; XPlane.name=2,
    event_metadata=4, stat_metadata=5; a map entry's value=2;
    XEventMetadata.name=2, stats=5; XStatMetadata.id=1, name=2;
    XStat.metadata_id=1, uint64_value=3, int64_value=4, str_value=5).
    The program is found by the operation's `program_id`, which its
    module's name ends in: `jit_step(5542903849823142240)`."""
    buf = memoryview(data)

    def text(span):
        return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")

    table = {}
    for number, _, plane in _fields(buf, 0, len(buf)):
        if number != 1:
            continue
        name, metadata, stat_names = None, [], {}
        for number, _, value in _fields(buf, *plane):
            if number == 2:
                name = text(value)
            elif number == 4:
                metadata.append(value)
            elif number == 5:
                for number, _, entry in _fields(buf, *value):
                    if number == 2:
                        parts = dict((n, v) for n, _, v
                                     in _fields(buf, *entry))
                        stat_names[parts[1]] = text(parts[2])
        if not name or not tracing.DEVICE_PLANE.match(name):
            continue
        operations, programs = [], {}
        for entry in metadata:
            for number, _, value in _fields(buf, *entry):
                if number != 2:
                    continue
                op, stats = None, {}
                for number, kind, field in _fields(buf, *value):
                    if number == 2:
                        op = text(field)
                    elif number == 5:
                        stat = dict((n, v) for n, _, v
                                    in _fields(buf, *field))
                        key = stat_names.get(stat.get(1))
                        if key in ("tf_op", "source"):
                            stats[key] = text(stat[5]) if 5 in stat else ""
                        elif key == "program_id":
                            stats[key] = (stat.get(3, stat.get(4, 0))
                                          & 0xFFFFFFFFFFFFFFFF)
                if op is None:
                    continue
                module = re.match(r"^(.*)\((\d+)\)$", op)
                if module and "program_id" not in stats:
                    programs[int(module.group(2))] = module.group(1)
                elif "tf_op" in stats or "source" in stats:
                    operations.append((op, stats))
        for op, stats in operations:
            program = programs.get(stats.get("program_id"), "no-module")
            short = op.split(" = ")[0].lstrip("%")
            table[f"{program}/{short}"] = [stats.get("tf_op", ""),
                                           stats.get("source", "")]
    return table


def read_file(path: str) -> dict:
    """The part of a trace this reader needs, in plain lists (what
    `tests/data/` records): `devices` as `tracing.read_planes` gives them,
    `spans` = [name, start_ns, duration_ns, thread, {argument: value}] for
    every `pt.*` and `bench.*` event of every host line, `ops` =
    `op_table`."""
    import jax
    with open(path, "rb") as f:
        data = f.read()
    profile = jax.profiler.ProfileData.from_serialized_xspace(data)
    spans = []
    for plane in profile.planes:
        if plane.name != tracing.HOST_PLANE:
            continue
        for line in plane.lines:
            spans.extend(
                [e.name, float(e.start_ns), float(e.duration_ns), line.name,
                 dict(e.stats)]
                for e in line.events
                if e.name.startswith((SPAN_PREFIX, tracing.SPAN_PREFIX)))
    return {"devices": tracing.read_planes(profile)["devices"],
            "spans": spans, "ops": op_table(data)}


# ---- plain lists -> numbers --------------------------------------------

def nest(spans):
    """Spans of ONE thread, as (start, end, name, ...) tuples -> (parents,
    pieces): `parents[i]` is the index of the span directly around span i
    (None at the top), `pieces` cuts the thread's time into (start, end,
    i) labelled by the INNERMOST span open there. A span's self time is
    the sum of its pieces: its duration less what its children cover. A
    child that outlasts its parent (clock jitter) is cut to it."""
    order = sorted(range(len(spans)),
                   key=lambda i: (spans[i][0], -spans[i][1]))
    parents, pieces, stack = [None] * len(spans), [], []
    cursor = 0.0

    def close_until(t):
        nonlocal cursor
        while stack and stack[-1][0] <= t:
            end, i = stack.pop()
            if end > cursor:
                pieces.append((cursor, end, i))
                cursor = end

    for i in order:
        start, end = spans[i][0], spans[i][1]
        close_until(start)
        if stack:
            end = min(end, stack[-1][0])
            parents[i] = stack[-1][1]
            if start > cursor:
                pieces.append((cursor, start, stack[-1][1]))
        cursor = start
        stack.append((end, i))
    close_until(float("inf"))
    return parents, pieces


def scope_of(tf_op: str):
    found = SCOPE.search(tf_op or "")
    return found.group(1) if found else None


def reduce(planes: dict):
    """Everything the metrics read, from one trace's plain lists; None
    for a trace without a device plane AND without `pt.*` spans."""
    spans = planes.get("spans", [])
    window = [(s, s + d) for n, s, d, *_ in spans
              if n == tracing.WINDOW_SPAN]
    devices = {k: v for k, v in planes.get("devices", {}).items() if v}
    program_spans = [s for s in spans if s[0].startswith(SPAN_PREFIX)]
    if not devices and not program_spans:
        return None
    if window:
        lo, hi = window[0]
    elif devices:
        lo = min(s for ev in devices.values() for _, s, _ in ev)
        hi = max(s + d for ev in devices.values() for _, s, d in ev)
    else:
        lo = min(s[1] for s in program_spans)
        hi = max(s[1] + s[2] for s in program_spans)
    out = {"window_s": (hi - lo) / 1e9}

    # ---- host: the thread that holds most of the program's span time
    by_line = defaultdict(list)
    for name, start, dur, line, args in program_spans:
        if lo <= start and start + dur <= hi:
            by_line[line].append((start, start + dur, name, args))
    self_ns, dur_ns = defaultdict(list), defaultdict(list)
    main, main_top = None, -1.0
    nested = {}
    for line, items in by_line.items():
        parents, pieces = nest(items)
        nested[line] = (items, parents, pieces)
        own = [0.0] * len(items)
        for s, e, i in pieces:
            own[i] += e - s
        for i, (s, e, name, _) in enumerate(items):
            self_ns[name].append(own[i])
            dur_ns[name].append(e - s)
        top = sum(e - s for (s, e, *_), p in zip(items, parents)
                  if p is None)
        if top > main_top:
            main, main_top = line, top
    out["span_counts"] = {k: len(v) for k, v in sorted(dur_ns.items())}
    out["span_ms_p25"] = {k: yardstick.quantile(v, 0.25) / 1e6
                          for k, v in sorted(dur_ns.items())}
    out["span_ms_p50"] = {k: yardstick.median(v) / 1e6
                          for k, v in sorted(dur_ns.items())}
    out["self_ms_p50"] = {k: yardstick.median(v) / 1e6
                          for k, v in sorted(self_ns.items())}
    out.update(_host_metrics(nested))

    # ---- the first chip: idle by innermost span, device seconds by
    # scope and by program
    if devices:
        events = devices[sorted(devices)[0]]
        out.update(_idle_by_span(events, lo, hi, nested.get(main)))
        out.update(_device_seconds(events, planes.get("ops", {}), lo, hi))
    return out


def _idle_by_span(events, lo, hi, thread) -> dict:
    """Every instant of [lo, hi] in which no operation ran goes to the
    innermost span of `thread` (`nest`'s items, parents, pieces) open
    then, or to `OUTSIDE`."""
    busy = tracing.union([(s, s + d) for _, s, d in events], lo, hi)
    edges = [lo] + [x for piece in busy for x in piece] + [hi]
    gaps = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    idle = defaultdict(float)
    if thread is not None:
        items, _, pieces = thread       # pieces come in order of time
        starts = [p[0] for p in pieces]
        for g0, g1 in gaps:
            i = max(bisect.bisect_right(starts, g0) - 1, 0)
            while i < len(pieces) and pieces[i][0] < g1:
                cover = min(pieces[i][1], g1) - max(pieces[i][0], g0)
                if cover > 0:
                    idle[items[pieces[i][2]][2]] += cover
                i += 1
    idle_ns = sum(b - a for a, b in gaps)
    idle[OUTSIDE] = max(idle_ns - sum(idle.values()), 0.0)
    return {"idle_s": idle_ns / 1e9,
            "busy_s": sum(e - s for s, e in busy) / 1e9,
            "idle_s_by_span": {k: v / 1e9 for k, v in sorted(
                idle.items(), key=lambda kv: -kv[1])}}


def _device_seconds(events, ops: dict, lo, hi) -> dict:
    """Seconds of the operations inside [lo, hi] by scope and by program,
    the operations without a scope, and the shares the metrics read."""
    by_scope, by_program = defaultdict(float), defaultdict(float)
    unscoped = defaultdict(float)
    optimizer = total = 0.0
    for key, s, d in events:
        inside = min(s + d, hi) - max(s, lo)
        if inside <= 0:
            continue
        total += inside
        program = key.split("/", 1)[0]
        by_program[program] += inside
        tf_op, source = ops.get(key, ("", ""))
        scope = scope_of(tf_op)
        by_scope[scope or "no-scope"] += inside
        if scope is None:
            unscoped[f"{program}/{tf_op or 'no-metadata'}"] += inside
        if scope == "optimizer" or (scope is None
                                    and OPTIMIZER_SOURCE in source):
            optimizer += inside

    def ranked(d, n=None):
        return {k: v / 1e9 for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:n]}

    out = {"device_op_s": total / 1e9,
           "device_s_by_scope": ranked(by_scope),
           "device_s_by_program": ranked(by_program, 8),
           "unscoped_top": ranked(unscoped, 8)}
    if total:
        out["prefill_device_pct"] = 100.0 * by_program.get(
            PREFILL_PROGRAM, 0.0) / total
    # a trace whose operations carry no metadata at all (another backend,
    # an older runtime) says nothing about scopes
    if total and any(tf for tf, _ in ops.values()):
        out["scope_attributed_pct"] = 100.0 * (
            1.0 - by_scope.get("no-scope", 0.0) / total)
        out["optimizer_device_pct"] = 100.0 * optimizer / total
    return out


def _host_metrics(nested: dict) -> dict:
    """The numbers read from the spans alone, over every thread."""
    waits, prefills, host, prepare = [], [], [], []
    for items, parents, _ in nested.values():
        fetch = [0.0] * len(items)      # seconds of `*.fetch` below a span
        decoded = [False] * len(items)  # a decode program was dispatched
        dispatch = [0.0] * len(items)   # `pt.train.dispatch` directly below
        for i, (s, e, name, args) in enumerate(items):
            up = parents[i]
            if name == "pt.train.dispatch" and up is not None:
                dispatch[up] += e - s
            while up is not None:
                if name.endswith(".fetch"):
                    fetch[up] += e - s
                elif name == "pt.engine.dispatch":
                    decoded[up] = True
                up = parents[up]
        for i, (s, e, name, args) in enumerate(items):
            if name == "pt.engine.prefill":
                prefills.append(e - s)
                if not args.get("requeue") and "queue_wait_us" in args:
                    waits.append(float(args["queue_wait_us"]) * 1e3)
            elif name == "pt.engine.step" and decoded[i]:
                host.append(e - s - fetch[i])
            elif name == "pt.train.call":
                prepare.append(e - s - dispatch[i])
    out = {}
    if waits:
        out["queue_wait_p95_ms"] = yardstick.quantile(waits, 0.95) / 1e6
    if prefills:
        out["prefill_ms"] = yardstick.median(prefills) / 1e6
    if host:
        out["engine_host_ms"] = yardstick.median(host) / 1e6
    if prepare:
        # the lower quartile, as `train_dispatch_ms`: a call made while
        # the device's queue is full waits a step inside its first
        # device-touching line, which is in `pt.train.prepare`
        out["train_prepare_ms"] = yardstick.quantile(prepare, 0.25) / 1e6
    return out


def idle_group(span: str) -> str:
    """Which of the three serving idle metrics an idle instant inside
    `span` counts for: `launch` (what stands between one decode program
    and the next), `admit` (`pt.engine.admit` and everything under it),
    `other` (bookkeeping, the fetches, the step's self time and whatever
    lies outside the program's spans: the benchmark's own loop)."""
    if span in LAUNCH_SPANS:
        return "launch"
    if span == "pt.engine.admit" or span.startswith("pt.engine.prefill"):
        return "admit"
    return "other"


def idle_pct(summary, group: str):
    """Idle seconds of `group` as a share of the window; None without a
    device plane or without `pt.*` spans in the trace."""
    if not summary or not summary.get("span_counts") \
            or "idle_s_by_span" not in summary:
        return None
    seconds = sum(v for k, v in summary["idle_s_by_span"].items()
                  if idle_group(k) == group)
    return 100.0 * seconds / summary["window_s"]


# ---- the newest trace of this checkout ---------------------------------

def newest_trace():
    """The newest trace under this checkout's `.bench_trace/`: a traced run
    reads its metrics right after it wrote its own window there."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    files = glob.glob(os.path.join(
        root, ".bench_trace", "*", "plugins", "profile", "*", "*.xplane.pb"))
    return max(files, key=os.path.getmtime) if files else None


def summary():
    """`reduce` of the traced window this run took (`newest_trace`),
    parsed once; the first read prints the `PROGRAM_SPANS` line. None
    where there is no trace."""
    global _summary
    if _summary is None:
        path = newest_trace()
        _summary = (reduce(read_file(path)) if path else None) or {}
        if _summary:
            print("PROGRAM_SPANS " + json.dumps(
                {"trace": os.path.basename(os.path.dirname(path)),
                 **_summary}), flush=True)
    return _summary or None
