"""Each program the serving engine launches, from its call on the host to
its execution on the device to the read of its tokens.

`program_trace.py` gives an idle instant of the device to the span the
host STOOD in. With a decode iteration in flight behind the host that is
not the cause: idle inside `pt.engine.bookkeep` is the host's fault only
if nothing was on its way to the device. This reader joins every
`pt.engine.dispatch` / `pt.engine.prefill.dispatch` span to the execution
it launched and to the `pt.engine.fetch` / `.prefill.fetch` span that read
it, by the launch number `seq` all of them carry (PR 35), and reads from
the join what overlap in time cannot say. One trace, one pass, parsed once
a process; the first read prints one line, `LAUNCHES {json}`.

**The join.** The executions of `jit__fused_step_fn` and `jit__prefill_fn`
on the first chip's `XLA Modules` line against the call spans in order of
`seq`. On a `TPU v5 lite` trace of jax 0.9.0 / libtpu 0.0.34 (looked at by
hand, PR 35) a module event carries a `run_id`, and so does one host event:
the runtime's `DoEnqueueProgram`, on a runtime thread's line of
`/host:CPU`, 0.6-1.8 ms after the call span opened (up to 0.1 ms after it
closed). So an execution belongs to the call span open at, or last opened
before, the enqueue of its run: an identifier from the device's line to
the host's, then containment on ONE clock. A trace without such an event
is joined by order, one stream, first in first out, the alignment within
two programs of the trace's start picked by causality. Either way the
join is VERIFIED by the sequence of program names (decode, decode,
prefill, decode, ...), which both sides have: every pair agrees, the
pairs run one to one in order, and at most two programs stay unjoined at
each edge of the trace (it starts before `bench.window` opens; the
window's are kept after the join). A mismatch, a call span without `seq`
or more left over leave every metric out (`None`) with a `reason` on the
line; never a note: a traced run whose join failed is still a correct
run. A program without a span (a page copy, an injection) is skipped.

**The clock.** For each joined program three things cannot happen: its
execution starts before its call span opened, or before the runtime
enqueued it; its read span closes before its execution ended. So the
offset d of the device's line against the host's lies in
[max_k(max(call_start_k, enqueue_k) - exec_start_k), min_k(read_end_k -
exec_end_k)]. Both bounds are reported; if 0 lies outside them the
device's line is shifted by the least amount that restores causality
before anything is attributed; if the bounds cross, no offset does and the
join is refused. (closed32's first trace read [1.487, 2.020] ms: the
device's line is drawn one and a half milliseconds EARLY, among spans of
0.05-0.9 ms.)

**Per program:** `launch_lag` = execution start - call span start;
`read_tail` = read span end - execution end; `gap_before` = execution start
- end of the execution before it (any program of the chip).

**Per idle instant t** of the first chip inside the window (the busy union
that `serve_device_idle_pct` uses): let P be the next joined execution to
start after t and c its call span's start. t >= c -> **call** (the jit
call's own Python, its NumPy arguments' transfer, the launch in the
runtime). Else nothing is on its way to the device and the host's innermost
`pt.*` span at t decides: inside `pt.engine.fetch` or `.prefill.fetch` ->
**read** (the device has finished what it had, the host still waits for
tokens); anything else, or no span -> **host**. After the trace's last
joined execution the span alone decides. The three sum to the idle share.
"""
from __future__ import annotations

import bisect
import json
import os
from collections import defaultdict

from benchmark import program_trace, tracing, yardstick

DECODE, PREFILL = "decode", "prefill"
PROGRAMS = {"jit__fused_step_fn": DECODE,
            program_trace.PREFILL_PROGRAM: PREFILL}
CALL_SPANS = {"pt.engine.dispatch": DECODE,
              "pt.engine.prefill.dispatch": PREFILL}
READ_SPANS = {"pt.engine.fetch": DECODE,
              "pt.engine.prefill.fetch": PREFILL}
EDGE = 2                  # programs that may straddle an edge of the trace
BACK_TO_BACK_NS = 50e3    # a gap under this is no gap: the queue fed it
CAUSES = ("call", "read", "host")
INF = float("inf")

_summary = None   # of the newest trace: parsed once a process


# ---- the trace file -> plain lists -------------------------------------

def read_launches(path: str):
    """(modules, enqueues) of a trace file: `modules` = [program, start_ns,
    duration_ns, run_id or None] for every event of the first chip's
    `XLA Modules` line, the program without its id (`jit__prefill_fn(123)`
    -> `jit__prefill_fn`); `enqueues` = [run_id, start_ns] for every host
    event that carries a `run_id` (the runtime's `DoEnqueueProgram`, on
    the host's clock)."""
    import jax
    profile = jax.profiler.ProfileData.from_file(path)
    modules, enqueues = [], []
    chips = sorted((p for p in profile.planes
                    if tracing.DEVICE_PLANE.match(p.name)),
                   key=lambda p: p.name)
    for plane in chips:
        lines = {line.name: line for line in plane.lines}
        if tracing.OP_LINE not in lines:
            continue                    # `read_planes` skips it too
        for e in (lines[tracing.MODULE_LINE].events
                  if tracing.MODULE_LINE in lines else ()):
            run_id = dict(e.stats).get("run_id")
            modules.append([e.name.split("(")[0], float(e.start_ns),
                            float(e.duration_ns),
                            None if run_id is None else int(run_id)])
        break
    for plane in profile.planes:
        if plane.name != tracing.HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith((program_trace.SPAN_PREFIX,
                                      tracing.SPAN_PREFIX)):
                    continue
                run_id = dict(e.stats).get("run_id")
                if run_id is not None:
                    enqueues.append([int(run_id), float(e.start_ns)])
    return modules, enqueues


def read_file(path: str) -> dict:
    """`program_trace.read_file`'s plain lists plus `modules` and
    `enqueues` (`read_launches`): what `tests/data/` records."""
    modules, enqueues = read_launches(path)
    return {**program_trace.read_file(path), "modules": modules,
            "enqueues": enqueues}


# ---- plain lists -> the join --------------------------------------------

def _refused(reason: str, **said) -> dict:
    return {"reason": reason, **said}


def _by_kind(kinds) -> dict:
    out = {DECODE: 0, PREFILL: 0}
    for k in kinds:
        out[k] += 1
    return out


def _pairs_by_run_id(calls, execs, enqueued):
    """(call, execution) index pairs: an execution belongs to the call
    span open at, or last opened before, the instant the runtime enqueued
    its run (both on the host's clock; the enqueue comes from a runtime
    thread 0.6-1.8 ms after the span opened and up to 0.1 ms after it
    closed: chip trace, PR 35)."""
    opened = [c["call"] for c in calls]
    pairs = []
    for j, (_, _, _, run_id) in enumerate(execs):
        at = enqueued.get(run_id)
        i = -1 if at is None else bisect.bisect_right(opened, at) - 1
        if i >= 0:
            pairs.append((i, j))
    return pairs


def _pairs_by_order(calls, execs, reads):
    """(call, execution) index pairs, first in first out. Up to EDGE
    executions at the trace's start belong to calls made before it; among
    the alignments whose program names agree over the whole overlap,
    causality picks (a join off by one program breaks it by an
    iteration's time)."""
    best = None
    for skip in range(min(EDGE, len(execs) - 1) + 1):
        n = min(len(calls), len(execs) - skip)
        pairs = [(i, skip + i) for i in range(n)]
        if any(calls[i]["kind"] != execs[j][2] for i, j in pairs):
            continue
        lo, hi = _clock_bounds(calls, execs, pairs, reads, {})
        if best is None or hi - lo > best[0]:
            best = (hi - lo, pairs)
    return best[1] if best else []


def _clock_bounds(calls, execs, pairs, reads, enqueued):
    """[lo, hi] of the device clock's offset: no execution starts before
    its call span opened, nor before the runtime enqueued it; no read
    span closes before its execution ended."""
    lo = max(max(calls[i]["call"], enqueued.get(execs[j][3], -INF))
             - execs[j][0] for i, j in pairs)
    hi = min((reads[calls[i]["seq"]][2] - execs[j][1]
              for i, j in pairs if calls[i]["seq"] in reads), default=INF)
    return lo, hi


def join(planes: dict) -> dict:
    """Calls, reads and executions of the whole trace, joined. Returns
    `programs` (one dict each: seq, kind, ahead, call, call_end, enqueue
    or None, start, end, read, read_end or None, prev_end: the end of the
    execution before it or None), how they were joined (`joined_by`), the
    clock's `bounds_us`, the `shift_us` applied to the device's line
    (already in `start`, `end`, `prev_end`), the counts; or `reason` where
    the join is refused."""
    calls, reads = [], {}
    for name, start, dur, _, args in planes.get("spans", []):
        if name in CALL_SPANS:
            if "seq" not in args:
                return _refused(f"a {name} span carries no `seq`: the "
                                f"program numbers no launch")
            calls.append({"seq": int(args["seq"]), "kind": CALL_SPANS[name],
                          "ahead": int(args.get("ahead", 0)),
                          "call": start, "call_end": start + dur})
        elif name in READ_SPANS and "seq" in args:
            if int(args["seq"]) in reads:
                return _refused(f"launch {args['seq']} is read twice")
            reads[int(args["seq"])] = (READ_SPANS[name], start, start + dur)
    if not calls:
        return _refused("no call span in the trace")
    calls.sort(key=lambda c: c["call"])
    seqs = [c["seq"] for c in calls]
    if seqs != list(range(seqs[0], seqs[0] + len(seqs))):
        return _refused("the call spans' `seq` are not consecutive in "
                        "order of start", first_seq=seqs[0])
    modules = sorted((s, s + d, name, run_id) for name, s, d, run_id
                     in planes.get("modules", []))
    execs = [(s, e, PROGRAMS[name], run_id) for s, e, name, run_id
             in modules if name in PROGRAMS]
    said = {"calls": _by_kind(c["kind"] for c in calls),
            "executions": _by_kind(x[2] for x in execs)}
    if not execs:
        return _refused("no execution of the engine's programs on the "
                        "chip's `XLA Modules` line", **said)
    enqueued = {}
    for run_id, at in planes.get("enqueues", []):
        enqueued[run_id] = min(at, enqueued.get(run_id, at))
    if any(x[3] in enqueued for x in execs):
        said["joined_by"] = "run_id"
        pairs = _pairs_by_run_id(calls, execs, enqueued)
    else:
        said["joined_by"] = "order"
        pairs = _pairs_by_order(calls, execs, reads)

    # whichever way they were joined: the names agree, nothing is joined
    # twice or out of order, and what is left over sits at the edges
    n = len(pairs)
    first = pairs[0] if pairs else (0, 0)
    left = {"executions_of_calls_before_the_trace": first[1],
            "executions_after_the_last_call": len(execs) - first[1] - n,
            "calls_before_the_first_execution": first[0],
            "calls_without_an_execution": len(calls) - first[0] - n}
    if (not pairs or pairs != [(first[0] + k, first[1] + k)
                               for k in range(n)]
            or any(calls[i]["kind"] != execs[j][2] for i, j in pairs)
            or max(left.values()) > EDGE):
        return _refused("the executions' program names do not follow the "
                        f"call spans' one to one within {EDGE} programs "
                        f"of the trace's edges", unjoined=left, **said)
    lo, hi = _clock_bounds(calls, execs, pairs, reads, enqueued)
    bounds = [lo / 1e3, hi / 1e3]
    if lo > hi:
        return _refused("no offset of the device's clock puts every "
                        "execution after its call and before the end of "
                        "its read", bounds_us=bounds, **said)
    shift = 0.0 if lo <= 0.0 <= hi else (lo if lo > 0.0 else hi)

    ends = [m[1] for m in modules]          # of every program of the chip
    starts = [m[0] for m in modules]
    programs = []
    for i, j in pairs:
        s, e, kind, _ = execs[j]
        read = reads.get(calls[i]["seq"])
        if read is not None and read[0] != kind:
            return _refused(f"launch {calls[i]['seq']} is a {kind} call "
                            f"read by a {read[0]} span", **said)
        # the execution before this one on the chip's line, of any program
        before = bisect.bisect_left(starts, s) - 1
        programs.append({
            **calls[i], "enqueue": enqueued.get(execs[j][3]),
            "start": s + shift, "end": e + shift,
            "read": read[1] if read else None,
            "read_end": read[2] if read else None,
            "prev_end": ends[before] + shift if before >= 0 else None})
    left["reads_of_launches_before_the_trace"] = sum(
        q < seqs[0] for q in reads)
    left["calls_without_a_read"] = sum(p["read"] is None for p in programs)
    return {"programs": programs, "bounds_us": bounds,
            "shift_us": shift / 1e3, "unjoined": left, **said}


# ---- the join -> numbers -------------------------------------------------

def _quartiles_ms(values):
    if not values:
        return None
    return {"n": len(values),
            **{f"p{int(100 * q)}": yardstick.quantile(values, q) / 1e6
               for q in (0.25, 0.5, 0.95)}}


def _quartiles_by_group(groups: dict) -> dict:
    return {k: _quartiles_ms(v) for k, v in sorted(groups.items())}


def _median_ms(values):
    return yardstick.median(values) / 1e6 if values else None


def reduce(planes: dict):
    """Everything the seven metrics and the `LAUNCHES` line read; None for
    a trace with neither call spans nor a device plane; `reason` (and no
    metric) where the join is refused."""
    spans = planes.get("spans", [])
    devices = {k: v for k, v in planes.get("devices", {}).items() if v}
    if not devices or not any(s[0] in CALL_SPANS for s in spans):
        return None
    window = [(s, s + d) for n, s, d, *_ in spans
              if n == tracing.WINDOW_SPAN]
    events = devices[sorted(devices)[0]]
    if window:
        lo, hi = window[0]
    else:
        lo = min(s for _, s, _ in events)
        hi = max(s + d for _, s, d in events)
    out = join(planes)
    out["window_s"] = (hi - lo) / 1e9
    if "reason" in out:
        return out
    programs, shift = out.pop("programs"), out["shift_us"] * 1e3

    # ---- per program, over the window's
    lag, tail, gap = defaultdict(list), defaultdict(list), defaultdict(list)
    enqueue = defaultdict(list)     # call opened -> run enqueued: one clock
    idle_lag, back_to_back, decodes, ahead = [], 0, 0, [0, 0]
    for p in programs:
        kinds = [p["kind"]] + ([f"{DECODE}.ahead{p['ahead']}"]
                               if p["kind"] == DECODE else [])
        if lo <= p["call"] <= hi:
            for k in kinds:
                lag[k].append(p["start"] - p["call"])
                if p["enqueue"] is not None:
                    enqueue[k].append(p["enqueue"] - p["call"])
            if p["kind"] == DECODE:
                ahead[p["ahead"]] += 1
            # the device idle and nothing queued when the call opened
            if p["prev_end"] is None or p["prev_end"] <= p["call"]:
                idle_lag.append(p["start"] - p["call"])
        if p["read_end"] is not None and lo <= p["read_end"] <= hi:
            for k in kinds:
                tail[k].append(p["read_end"] - p["end"])
        if lo <= p["start"] <= hi and p["prev_end"] is not None:
            for k in kinds:
                gap[k].append(p["start"] - p["prev_end"])
            if p["kind"] == DECODE:
                decodes += 1
                back_to_back += p["start"] - p["prev_end"] < BACK_TO_BACK_NS
    out["decode_read_tail_ms"] = _median_ms(tail[DECODE])
    out["prefill_read_tail_ms"] = _median_ms(tail[PREFILL])
    out["serve_launch_lag_ms"] = _median_ms(idle_lag)
    out["serve_back_to_back_pct"] = (100.0 * back_to_back / decodes
                                     if decodes else None)
    out["serve_ahead_pct"] = (100.0 * ahead[1] / sum(ahead)
                              if sum(ahead) else None)
    out["launch_lag_ms"] = _quartiles_by_group(lag)
    out["launch_lag_ms"]["device_idle_at_the_call"] = _quartiles_ms(idle_lag)
    out["enqueue_lag_ms"] = _quartiles_by_group(enqueue)
    out["read_tail_ms"] = _quartiles_by_group(tail)
    out["gap_before_ms"] = _quartiles_by_group(gap)

    # ---- per idle instant of the window
    out.update(_idle_by_cause(events, programs, spans, lo, hi, shift))
    return out


def _idle_by_cause(events, programs, spans, lo, hi, shift) -> dict:
    busy = tracing.union([(s + shift, s + d + shift) for _, s, d in events],
                         lo, hi)
    edges = [lo] + [x for piece in busy for x in piece] + [hi]
    gaps = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]

    # the thread that made the calls: its innermost span at an instant
    lines = defaultdict(int)
    for name, _, _, line, _ in spans:
        lines[line] += name in CALL_SPANS
    main = max(lines, key=lines.get)
    items = [(s, s + d, name) for name, s, d, line, _ in spans
             if line == main and name.startswith(program_trace.SPAN_PREFIX)]
    _, pieces = program_trace.nest(items)
    piece_starts = [p[0] for p in pieces]
    starts = [p["start"] for p in programs]

    idle = defaultdict(float)       # (cause, innermost span) -> ns

    def by_span(a, b, cause=None):
        """[a, b) to `cause`, or by the innermost span to read / host."""
        if b <= a:
            return
        covered = 0.0
        i = max(bisect.bisect_right(piece_starts, a) - 1, 0)
        while i < len(pieces) and pieces[i][0] < b:
            cover = min(pieces[i][1], b) - max(pieces[i][0], a)
            if cover > 0:
                name = items[pieces[i][2]][2]
                idle[cause or ("read" if name in READ_SPANS else "host"),
                     name] += cover
                covered += cover
            i += 1
        idle[cause or "host", program_trace.OUTSIDE] += b - a - covered

    for g0, g1 in gaps:
        j = bisect.bisect_right(starts, g0)   # first to start after g0
        a = g0
        while a < g1:
            if j >= len(programs):            # past the last execution
                by_span(a, g1)
                break
            b = min(max(programs[j]["start"], a), g1)
            c = min(max(programs[j]["call"], a), b)
            by_span(a, c)
            by_span(c, b, "call")
            a, j = b, j + 1

    window = hi - lo
    seconds = {cause: 0.0 for cause in CAUSES}
    for (cause, _), ns in idle.items():
        seconds[cause] += ns
    out = {f"serve_idle_cause_{cause}_pct": 100.0 * seconds[cause] / window
           for cause in CAUSES}
    out["idle_s"] = sum(b - a for a, b in gaps) / 1e9
    out["idle_s_by_cause_and_span"] = [
        [cause, span, ns / 1e9] for (cause, span), ns
        in sorted(idle.items(), key=lambda kv: -kv[1]) if ns > 0][:24]
    return out


# ---- the newest trace of this checkout -----------------------------------

def summary():
    """`reduce` of the traced window this run took
    (`program_trace.newest_trace`), parsed once; the first read prints
    the `LAUNCHES` line. None where there is nothing to read."""
    global _summary
    if _summary is None:
        path = program_trace.newest_trace()
        _summary = (reduce(read_file(path)) if path else None) or {}
        if _summary:
            print("LAUNCHES " + json.dumps(
                {"trace": os.path.basename(os.path.dirname(path)),
                 **_summary}), flush=True)
    return _summary or None


def metric(name: str):
    """One of the seven metrics of the newest trace; None where the trace
    has no launch numbers or the join was refused."""
    return (summary() or {}).get(name)
