"""Tracing spans: nestable wall-clock scopes with chrome-trace export.

This is the span half of the telemetry layer and the NEW HOME of the
profiler's event machinery: `paddle_tpu.profiler` now aliases
`_ProfState = _TraceState`, `_Event = SpanEvent` and
`RecordEvent = Span` (same objects, old names kept as a shim), so
host-side spans recorded through either API land in one table and one
chrome trace. Span categories (`CATEGORIES`) attribute wall time to
the phases the load suite and chaos runner care about — prefill /
decode / schedule on the serving side, checkpoint / restart / train on
the training side — instead of a flat op list.

Two sinks, one switch each. The in-process table (`events()`,
`export_chrome`, `profiler.summary`) fills while `enable()` is on: host
wall-clock, time.perf_counter on already-running host code. The
profiler's own trace needs no switch of ours: a span enters a
jax.profiler.TraceAnnotation whenever `annotate` is true and jax is
already imported, and the annotation records only while some caller has
a profiler session open (`jax.profiler.start_trace`), on the device
trace's clock. This module never imports jax itself, so `import
paddle_tpu.obs` and a span in a jax-free process stay jax-free.
"""
from __future__ import annotations

import json
import os
import sys
import threading
import time
from typing import List, Optional

__all__ = ["Span", "SpanEvent", "CATEGORIES", "enable", "disable",
           "is_enabled", "clear", "events", "export_chrome", "span"]

#: span categories used by instrument sites (docs/observability.md);
#: free-form strings are allowed, these are the cataloged ones
CATEGORIES = ("serving", "schedule", "prefill", "decode", "checkpoint",
              "restart", "train", "op", "deploy")


class SpanEvent:
    """One completed span (was profiler._Event)."""

    __slots__ = ("name", "start", "end", "tid", "depth", "cat", "args")

    def __init__(self, name, start, end, tid, depth, cat=None, args=None):
        self.name = name
        self.start = start
        self.end = end
        self.tid = tid
        self.depth = depth
        self.cat = cat
        self.args = args

    @property
    def duration(self) -> float:
        return self.end - self.start


class _TraceState:
    """Process-wide trace table (was profiler._ProfState — the profiler
    aliases this class, so `profiler._ProfState.enabled = True` and
    `obs.trace.enable()` flip the same bit). Class-attribute state, one
    lock; tls.depth gives nesting depth for the exported events."""

    enabled = False
    events: List[SpanEvent] = []
    t0 = 0.0
    lock = threading.Lock()
    tls = threading.local()
    trace_dir: Optional[str] = None
    op_hook_installed = False


def is_enabled() -> bool:
    return _TraceState.enabled


def enable() -> None:
    """Start recording spans (fresh table)."""
    if _TraceState.enabled:
        return
    with _TraceState.lock:
        _TraceState.events = []
        _TraceState.t0 = time.perf_counter()
    _TraceState.enabled = True


def disable() -> None:
    _TraceState.enabled = False


def clear() -> None:
    with _TraceState.lock:
        _TraceState.events = []
        _TraceState.t0 = time.perf_counter()


def events() -> List[SpanEvent]:
    with _TraceState.lock:
        return list(_TraceState.events)


class Span:
    """Scoped wall-clock span (was profiler.RecordEvent — that name is
    now an alias of this class, so the old serving/training call sites
    and the new obs ones record identically).

    Context manager or decorator. `cat` tags the chrome-trace category
    (see CATEGORIES). `args` given at construction reach both sinks: the
    profiler's annotation takes its scalar values as event stats on the
    clean name (`serving.decode` with stat `num_seqs`), the chrome
    trace the whole dict. `span.args` set inside the scope is read at
    end() and reaches the chrome trace only; `span.set_stats(**kw)` inside
    the scope reaches both. A string value must hold no comma: the
    profiler's encoding of an annotation's stats cuts it there ("4,32,16"
    arrives as 4). `annotate=False` skips the
    jax.profiler.TraceAnnotation for spans that must stay jax-free.
    """

    def __init__(self, name: str, cat: str = None, args: dict = None,
                 annotate: bool = True):
        self.name = name
        self.cat = cat
        self.args = args
        self.annotate = annotate
        self._t0 = None
        self._ann = None

    def begin(self):
        # the one place that decides whether to annotate: the profiler
        # session is the switch, and TraceAnnotation reads it itself
        jax = sys.modules.get("jax") if self.annotate else None
        if jax is not None:
            stats = {k: v for k, v in (self.args or {}).items()
                     if isinstance(v, (str, int, float))}
            self._ann = jax.profiler.TraceAnnotation(self.name, **stats)
            self._ann.__enter__()
        if _TraceState.enabled:
            self._t0 = time.perf_counter()
            depth = getattr(_TraceState.tls, "depth", 0)
            _TraceState.tls.depth = depth + 1

    def set_stats(self, **stats):
        """Stats known only inside the scope (a count the device returned
        with the result): merged into `args` for the chrome trace and
        appended to the open profiler annotation, so they land on the same
        event as the stats given at construction."""
        if not stats:
            return
        self.args = {**(self.args or {}), **stats}
        if self._ann is not None:
            self._ann.set_metadata(**stats)

    def end(self):
        if self._t0 is not None:
            t1 = time.perf_counter()
            _TraceState.tls.depth -= 1
            with _TraceState.lock:
                _TraceState.events.append(SpanEvent(
                    self.name, self._t0, t1,
                    threading.get_ident(), _TraceState.tls.depth,
                    self.cat, self.args))
            self._t0 = None
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()
        return False

    def __call__(self, fn):
        import functools

        @functools.wraps(fn)
        def wrapper(*a, **k):
            with Span(self.name, cat=self.cat, annotate=self.annotate):
                return fn(*a, **k)
        return wrapper


def span(name: str, cat: str = None, args: dict = None,
         annotate: bool = True) -> Span:
    """Convenience constructor: `with obs.span("x", cat="decode"): ...`"""
    return Span(name, cat=cat, args=args, annotate=annotate)


def export_chrome(path: str, extra_events=None) -> str:
    """Write recorded spans as chrome://tracing JSON (the substance of
    profiler.export_chrome_tracing, which now delegates here). ts/dur
    in microseconds relative to enable() time; category defaults to
    "op" for unlabeled spans. `extra_events` are pre-built chrome event
    dicts appended verbatim (obs.export adds gauge counter tracks)."""
    evs = events()
    trace = {"traceEvents": [
        dict({"name": e.name, "ph": "X", "cat": e.cat or "op",
              "ts": (e.start - _TraceState.t0) * 1e6,
              "dur": (e.end - e.start) * 1e6,
              "pid": os.getpid(), "tid": e.tid},
             **({"args": e.args} if e.args else {}))
        for e in evs
    ] + list(extra_events or [])}
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(path, "w") as f:
        json.dump(trace, f)
    return path
