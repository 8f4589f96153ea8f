"""Structured host-side tracing: spans, instants, Chrome-trace export
(DESIGN.md §12.1).

A `Tracer` records nested spans on the host's monotonic ns clock into a
bounded deque — no device syncs, no allocation beyond one tuple per span,
and a disabled tracer costs one attribute check and one profiler
annotation per span site, so the instrumentation can stay in the serving
and solver hot paths permanently.

Span taxonomy (DESIGN.md §12.1 — the names CI schema-checks for):

    admit          one request admitted (scheduler.submit)
    launch         one bucket dispatched: pad/stack/warm-start + the async
                   solve call (reason=full|deadline|flush)
    warm_start     cache lookups for one launch (hits recorded in args)
    harvest.block  the only blocking wait in the runtime
    complete       unpad + cache refill + delivery (parent of none)
    mh.place       coordinator placed a batch on a host
    route          router decision instant (path + full price table)
    trace:<entry>  solver (re)trace instant — nonzero steady-state count
                   is the regression the zero-retrace CI gate catches
    enet_path.prepare   standardize, grid and config of one `enet_path`
                        (`enet`, `enet_batch`) call
    enet_path.dispatch  the jitted solve's dispatch
    enet_path.unscale   coefficients back to the original scale

Export is Chrome-trace JSON (``chrome://tracing`` / Perfetto: "X" complete
events, µs timestamps). Every span, recorded or not, also enters a
`jax.profiler.TraceAnnotation`: while a jax profile is being taken (by an
operator, or by the benchmark's traced run) the spans appear on the
profile's own clock beside the device's operations, and while none is, the
annotation records nothing. A disabled tracer hands out that annotation
alone, one object per span.
"""
from __future__ import annotations

import collections
import json
import os
import threading
from typing import Optional

from jax.profiler import TraceAnnotation

from repro.obs import clock as _clock

__all__ = ["Tracer", "get_tracer", "enable_tracing", "disable_tracing"]


class _ProfilerSpan(TraceAnnotation):
    """The span a disabled tracer hands out: the profiler annotation alone
    (records nothing unless a profile is being taken), with the `args`
    attribute call sites may test."""

    __slots__ = ()
    args = None


class _Span:
    """Reusable context manager for one span — cheaper than a generator
    contextmanager on the per-request path."""

    __slots__ = ("tracer", "name", "args", "t0", "parent", "annot")

    def __init__(self, tracer: "Tracer", name: str, args: Optional[dict]):
        self.tracer = tracer
        self.name = name
        self.args = args
        self.t0 = None

    def __enter__(self):
        tr = self.tracer
        if not tr.enabled:
            return self
        stack = tr._stack()
        self.parent = stack[-1] if stack else None
        stack.append(self.name)
        self.annot = TraceAnnotation(self.name)
        self.annot.__enter__()
        self.t0 = _clock.monotonic_ns()
        return self

    def __exit__(self, *exc):
        tr = self.tracer
        if self.t0 is None:
            return False   # disabled at entry: nothing was opened
        dur = _clock.monotonic_ns() - self.t0
        self.annot.__exit__(*exc)
        stack = tr._stack()
        if stack and stack[-1] == self.name:
            stack.pop()
        if not tr.enabled:
            return False   # toggled off mid-span: record nothing
        tr._record("X", self.name, self.parent, self.t0, dur, self.args)
        return False


class Tracer:
    """Bounded in-memory span recorder with Chrome-trace export."""

    def __init__(self, *, capacity: int = 200_000) -> None:
        self.enabled = False
        self._spans: collections.deque = collections.deque(maxlen=capacity)
        self._counts: collections.Counter = collections.Counter()
        self._tls = threading.local()

    def _stack(self) -> list:
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        return stack

    def _record(self, phase, name, parent, t0_ns, dur_ns, args) -> None:
        self._counts[name] += 1
        self._spans.append((phase, name, parent,
                            threading.get_ident(), t0_ns, dur_ns, args))

    # -- control -----------------------------------------------------------

    def enable(self) -> "Tracer":
        self.enabled = True
        return self

    def disable(self) -> None:
        self.enabled = False

    def reset(self) -> None:
        self._spans.clear()
        self._counts.clear()

    # -- recording ---------------------------------------------------------

    def span(self, name: str, **args):
        if not self.enabled:
            return _ProfilerSpan(name)
        return _Span(self, name, args or None)

    def traced(self, name: Optional[str] = None):
        """Decorator form: ``@tracer.traced("phase")``."""
        def deco(fn):
            span_name = name or fn.__qualname__

            def wrapper(*a, **kw):
                with self.span(span_name):
                    return fn(*a, **kw)
            wrapper.__name__ = fn.__name__
            wrapper.__qualname__ = fn.__qualname__
            wrapper.__doc__ = fn.__doc__
            return wrapper
        return deco

    def instant(self, name: str, **args) -> None:
        if not self.enabled:
            return
        stack = self._stack()
        self._record("i", name, stack[-1] if stack else None,
                     _clock.monotonic_ns(), 0, args or None)

    # -- introspection / export --------------------------------------------

    def __len__(self) -> int:
        return len(self._spans)

    def counts(self) -> dict:
        """Span-name -> recorded occurrences (includes rolled-off spans)."""
        return dict(self._counts)

    def spans(self) -> list:
        return list(self._spans)

    def chrome_trace(self) -> dict:
        """The retained spans as a Chrome-trace/Perfetto JSON object."""
        pid = os.getpid()
        events = []
        for phase, name, parent, tid, t0_ns, dur_ns, args in self._spans:
            ev = {"ph": phase, "name": name, "cat": "repro",
                  "pid": pid, "tid": tid, "ts": t0_ns / 1e3}
            if phase == "X":
                ev["dur"] = dur_ns / 1e3
            else:
                ev["s"] = "t"
            ev["args"] = dict(args or {})
            if parent is not None:
                ev["args"]["parent"] = parent
            events.append(ev)
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def export(self, path: str) -> str:
        """Write the Chrome-trace JSON; returns the path written."""
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f, default=str)
        return path


_TRACER = Tracer()


def get_tracer() -> Tracer:
    """The process-default tracer every runtime component records into
    unless handed a private one."""
    return _TRACER


def enable_tracing() -> Tracer:
    return _TRACER.enable()


def disable_tracing() -> None:
    _TRACER.disable()
