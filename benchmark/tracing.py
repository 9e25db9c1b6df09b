"""Host spans of the benchmark's own loop, and the reduction of a
profiler trace to device busy time, top device operations and idle gaps
joined to those spans.

How a `TPU v5 lite` trace of jax 0.9.0 is laid out (looked at by hand,
PR 24; `tests/data/` keeps a small recorded one):

* one plane per chip named `/device:TPU:<n>`; its line `XLA Ops` holds one
  event per executed HLO operation (start and duration in ns), its lines
  `XLA Modules` and `Steps` hold whole programs — counting those too
  would count every operation twice;
* the plane `/host:CPU` holds one line per host thread; a
  `jax.profiler.TraceAnnotation` is an event of its name on the line of
  the thread that opened it, on the same clock as the device planes.
"""
from __future__ import annotations

import bisect
import contextlib
import glob
import os
import re
import shutil
import time
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


class Spans:
    """The benchmark's spans around its calls into each layer: written
    into the profiler's trace when one is being taken (so that idle gaps
    can be joined to them) and timed on the host's clock always."""

    def __init__(self):
        self.durations = defaultdict(list)

    @contextlib.contextmanager
    def span(self, name: str):
        import jax
        with jax.profiler.TraceAnnotation(SPAN_PREFIX + name):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.durations[name].append(time.perf_counter() - t0)

    def reset(self):
        self.durations.clear()


class Tracer:
    """Takes one profiler trace of a window into a directory inside the
    checkout and reduces it."""

    def __init__(self, directory: str):
        self.directory = directory

    @contextlib.contextmanager
    def window(self):
        import jax
        shutil.rmtree(self.directory, ignore_errors=True)
        os.makedirs(self.directory, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        # annotations only: no event per Python call, and none per runtime
        # call (level 2 made one TrainStep call read 48 ms against 7.5 ms
        # untraced; chip run, PR 24)
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.directory, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(WINDOW_SPAN):
                yield
        finally:
            jax.profiler.stop_trace()

    def reduce(self) -> dict:
        files = sorted(glob.glob(os.path.join(
            self.directory, "plugins", "profile", "*", "*.xplane.pb")))
        if not files:
            return {}
        return reduce_file(files[-1])


def reduce_file(path: str) -> dict:
    import jax
    return reduce_planes(read_planes(
        jax.profiler.ProfileData.from_file(path)))


def read_planes(profile) -> dict:
    """ProfileData -> {"devices": {plane: [(name, start_ns, dur_ns)]},
    "spans": [(name, start_ns, dur_ns)]} — the part of a trace the
    reduction needs, in plain lists (what `tests/data/` records). An
    operation's event is named by its whole HLO text; it is recorded as
    `<program>/<operation>`, the program being the `XLA Modules` event it
    ran inside (`jit_step(123)` -> `jit_step`), since every program has a
    `fusion.1` of its own."""
    devices, spans = {}, []
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {line.name: line for line in plane.lines}
            if OP_LINE not in lines:
                continue
            modules = sorted(
                (float(e.start_ns), float(e.start_ns + e.duration_ns),
                 e.name.split("(")[0])
                for e in lines[MODULE_LINE].events
            ) if MODULE_LINE in lines else []
            starts = [m[0] for m in modules]
            ops = []
            for e in lines[OP_LINE].events:
                start = float(e.start_ns)
                i = bisect.bisect_right(starts, start) - 1
                program = (modules[i][2] if i >= 0 and start < modules[i][1]
                           else "no-module")
                op = e.name.split(" = ")[0].lstrip("%")
                ops.append((f"{program}/{op}", start, float(e.duration_ns)))
            devices[plane.name] = ops
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                spans.extend(
                    (e.name, float(e.start_ns), float(e.duration_ns))
                    for e in line.events if e.name.startswith(SPAN_PREFIX))
    return {"devices": devices, "spans": spans}


def union(intervals, lo: float, hi: float):
    """Merged [start, end) pieces of `intervals` clipped to [lo, hi]."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce_planes(planes: dict) -> dict:
    """Busy seconds (union of device-op intervals inside the window,
    averaged over the chips that ran anything), the window's seconds, the
    device operations by summed seconds, and the idle gaps of the first
    chip summed by the benchmark span the host was in (the span that
    covers most of the gap; `outside-spans` where none does)."""
    devices = {k: v for k, v in planes["devices"].items() if v}
    if not devices:
        return {}
    spans = planes["spans"]
    win = [(s, s + d) for n, s, d in spans if n == WINDOW_SPAN]
    if win:
        lo, hi = win[0]
    else:  # no window span in the trace: first op start to last op end
        lo = min(s for ev in devices.values() for _, s, _ in ev)
        hi = max(s + d for ev in devices.values() for _, s, d in ev)
    by_op = defaultdict(float)
    busy_ns = []
    merged_first = None
    for name in sorted(devices):
        events = devices[name]
        merged = union([(s, s + d) for _, s, d in events], lo, hi)
        busy_ns.append(sum(e - s for s, e in merged))
        if merged_first is None:
            merged_first = merged
        for op, s, d in events:
            inside = min(s + d, hi) - max(s, lo)
            if inside > 0:
                by_op[op] += inside
    n = len(devices)
    # the loop's spans follow one another on one thread (only the window
    # span encloses others), so sorted by start their ends are sorted too
    host = sorted(((s, s + d, nm[len(SPAN_PREFIX):]) for nm, s, d in spans
                   if nm != WINDOW_SPAN))
    ends = [e for _, e, _ in host]
    gaps = defaultdict(float)
    edges = [lo] + [x for piece in merged_first for x in piece] + [hi]
    for g0, g1 in zip(edges[0::2], edges[1::2]):
        if g1 <= g0:
            continue
        best, best_cover = "outside-spans", 0.0
        i = bisect.bisect_right(ends, g0)
        while i < len(host) and host[i][0] < g1:
            cover = min(host[i][1], g1) - max(host[i][0], g0)
            if cover > best_cover:
                best, best_cover = host[i][2], cover
            i += 1
        gaps[best] += g1 - g0

    def ranked(d, share=1.0):
        return [[k, v * share / 1e9]
                for k, v in sorted(d.items(), key=lambda kv: -kv[1])]

    return {"window_s": (hi - lo) / 1e9,
            "busy_s": sum(busy_ns) / n / 1e9,
            "chips_traced": n,
            "device_ops": ranked(by_op, 1.0 / n),
            "idle_gaps": ranked(gaps)}


def idle_pct(run: dict):
    """1 - (union of device-operation intervals / traced window), in %."""
    trace = run.get("trace")
    if not trace or not trace.get("window_s"):
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
