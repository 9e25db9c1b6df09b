"""RecordEvent + result loading.

Reference parity: `python/paddle/profiler/utils.py:31` (RecordEvent
ContextDecorator), `:125` (load_profiler_result), `:153` (wrap_optimizers).
Each span is annotated into any active jax.profiler trace
(`jax.profiler.TraceAnnotation` — the XLA analog of nvtx ranges the
reference emits for CUPTI correlation), whoever started that trace, and
recorded to the host recorder while the program's own recorder is enabled.
"""
from __future__ import annotations

import json
import threading
from contextlib import ContextDecorator
from typing import Optional

import jax

from .recorder import HostSpan, get_recorder, now_ns

#: prefix of the span names the program itself opens (`pt.engine.step`,
#: `pt.train.call`): what a reader of a trace filters the host lines by
SPAN_PREFIX = "pt."


class TracerEventType:
    Operator = "Operator"
    Dataloader = "Dataloader"
    ProfileStep = "ProfileStep"
    UserDefined = "UserDefined"
    Forward = "Forward"
    Backward = "Backward"
    Optimization = "Optimization"
    Communication = "Communication"


class RecordEvent(ContextDecorator):
    """RAII profiling span (reference `utils.py:31` / C++ `RecordEvent`).

    Always opens a `TraceAnnotation` (well under a microsecond when no
    trace is being taken), so the span shows in a trace the program did
    not start itself; keyword arguments become the annotation's stats and
    `HostSpan.args`. With the recorder off nothing is pushed and no
    buffer is touched."""

    def __init__(self, name: str,
                 event_type: str = TracerEventType.UserDefined, **args):
        self.name = name
        self.event_type = event_type
        self.args = args
        self._start = None
        self._jax_ann = None
        self._pushed = False

    def begin(self):
        # the annotation first: what jax refuses leaves nothing half open
        self._jax_ann = jax.profiler.TraceAnnotation(self.name, **self.args)
        self._jax_ann.__enter__()
        rec = get_recorder()
        self._start = now_ns()
        if rec.enabled:
            rec.span_stack().append(self.name)
            self._pushed = True

    def end(self):
        if self._start is None:
            return
        rec = get_recorder()
        self._jax_ann.__exit__(None, None, None)
        self._jax_ann = None
        # pop even if the record window closed mid-span, else the thread's
        # stack leaks the entry and later spans get a stale parent
        if self._pushed:
            stack = rec.span_stack()
            if self.name in stack:
                stack.reverse()
                stack.remove(self.name)
                stack.reverse()
            self._pushed = False
        if rec.enabled:
            stack = rec.span_stack()
            parent = stack[-1] if stack else None
            rec.push(HostSpan(name=self.name, start_ns=self._start,
                              end_ns=now_ns(), tid=threading.get_ident(),
                              event_type=self.event_type, parent=parent,
                              args=self.args or None))
        self._start = None

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()
        return False


def load_profiler_result(filename: str):
    """Load a chrome-trace JSON exported by Profiler.export (`utils.py:125`)."""
    with open(filename) as f:
        return json.load(f)


def wrap_optimizers():
    """No-op for parity: optimizer.step is already spanned via RecordEvent in
    Profiler-enabled training loops (reference monkey-patches optimizers)."""
    return None
