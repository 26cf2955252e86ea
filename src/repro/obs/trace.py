"""Runtime span tracing: stdlib-only, contextvar-scoped, no-op when off.

The planner, the simulator-scored beam, the planner service, the kernel
pre-flight and the network runner are instrumented with `span` blocks. When
neither a tracer is installed nor the JAX profiler recording (the default),
``span(...)`` returns a shared no-op context manager — one module-global read,
one profiler check, plus an allocation-free ``with`` — so the instrumented hot
paths pay effectively nothing (the ``obs`` benchmark section measures the
ceiling and ``benchmarks/run.py check`` enforces it at <= 5% of the planserve
smoke stream).

While the JAX profiler records (``jax.profiler.trace`` / ``start_trace``),
every ``span`` block also opens a ``jax.profiler.TraceAnnotation`` of the same
name, its attributes as TraceMe metadata, so the span lands in the
``.xplane.pb`` on the clock of the device's operations. JAX is never imported
from here: until the program has imported ``jax.profiler`` no profiler can be
recording, and the check costs a dictionary lookup; after, it is
``TraceAnnotation.is_enabled()``.

Garbage-collector pauses are spans too: a ``gc.callbacks`` hook, installed
when this module is imported, opens `GC_SPAN` (attribute ``generation``) at a
collection's start and closes it at its stop, through `span`, so it records
only while a tracer or the profiler is on.

When a `Tracer` is installed (`enable()` / the `tracing()` context manager),
every ``span`` block records a `SpanRecord` carrying wall-clock start/
duration, its parent span (tracked through a `contextvars.ContextVar`, so
nesting is correct across generators and threads), and free-form attributes.
Records export to Chrome/Perfetto trace-event JSON via
`repro.obs.export.spans_to_trace`.

`Stopwatch` is the sanctioned wall-clock interval primitive everywhere
outside ``benchmarks/`` (lint rule RPL104 forbids ad-hoc
``time.perf_counter()`` timing): it measures an interval and, when a name is
given and tracing is on, records the same interval as a span.
"""

from __future__ import annotations

import contextlib
import contextvars
import gc
import itertools
import sys
import threading
import time
from dataclasses import dataclass
from types import TracebackType
from typing import Any, Iterator, Optional

__all__ = ["SpanRecord", "Tracer", "Stopwatch", "span", "enabled",
           "enable", "disable", "get_tracer", "tracing", "GC_SPAN"]

GC_SPAN = "obs.gc"


@dataclass(frozen=True)
class SpanRecord:
    """One finished span: a named wall-clock interval with attributes."""

    name: str
    cat: str                 # coarse subsystem: "plan" | "sim" | "serve" | ...
    t0_s: float              # perf_counter seconds at entry
    dur_s: float
    span_id: int
    parent_id: Optional[int]
    thread_id: int
    attrs: tuple[tuple[str, Any], ...]


class Tracer:
    """Collects `SpanRecord`\\ s; thread-safe, append-only.

    ``record()`` admits externally timed intervals (the planner service uses
    it to emit virtual-clock request spans); ``span`` blocks go through the
    module-level `span()` entry point.
    """

    def __init__(self) -> None:
        self.spans: list[SpanRecord] = []
        self._ids = itertools.count(1)
        # Reentrant: a collection can start inside ``record`` and record its
        # `GC_SPAN` from the same thread.
        self._lock = threading.RLock()

    def __len__(self) -> int:
        return len(self.spans)

    def next_id(self) -> int:
        return next(self._ids)

    def record(self, name: str, t0_s: float, dur_s: float, *,
               cat: str = "repro", span_id: Optional[int] = None,
               parent_id: Optional[int] = None,
               attrs: tuple[tuple[str, Any], ...] = ()) -> SpanRecord:
        rec = SpanRecord(
            name=name, cat=cat, t0_s=t0_s, dur_s=dur_s,
            span_id=self.next_id() if span_id is None else span_id,
            parent_id=parent_id, thread_id=threading.get_ident(),
            attrs=attrs)
        with self._lock:
            self.spans.append(rec)
        return rec

    def clear(self) -> None:
        with self._lock:
            self.spans.clear()


# Current span id, scoped through contextvars so nesting survives generators
# and is correct per-thread / per-async-task.
_CURRENT: contextvars.ContextVar[Optional[int]] = \
    contextvars.ContextVar("repro_obs_current_span", default=None)

# The installed tracer, read once per ``span()`` call.
_TRACER: Optional[Tracer] = None

# jax.profiler.TraceAnnotation, once the program has imported it.
_ANNOTATION: Any = None


def _find_annotation() -> Any:
    """``jax.profiler.TraceAnnotation`` once the program has imported
    ``jax.profiler``, else None: no profiler can record before then. Looked
    up, never imported, so that a collection during JAX's own import never
    re-enters it."""
    global _ANNOTATION
    _ANNOTATION = getattr(sys.modules.get("jax.profiler"), "TraceAnnotation",
                          None)
    return _ANNOTATION


class _NoopSpan:
    """Shared, allocation-free ``with`` target for the disabled path."""

    __slots__ = ()
    recording = False

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, exc_type: Optional[type[BaseException]],
                 exc: Optional[BaseException],
                 tb: Optional[TracebackType]) -> None:
        return None

    def set(self, key: str, value: Any) -> None:
        return None


_NOOP = _NoopSpan()


class _Span:
    """A live span: records itself in the tracer on exit (when one is
    installed) and in the profiler's trace (when it records)."""

    __slots__ = ("_tracer", "name", "cat", "_attrs", "_t0", "_id", "_token",
                 "_annotation")
    recording = True

    def __init__(self, tracer: Optional[Tracer], name: str, cat: str,
                 attrs: dict[str, Any], profiled: bool) -> None:
        self._tracer = tracer
        self.name = name
        self.cat = cat
        self._attrs = attrs
        self._t0 = 0.0
        self._id = 0
        self._token: Optional[contextvars.Token[Optional[int]]] = None
        self._annotation: Any = _ANNOTATION(name, **attrs) if profiled \
            else None

    def set(self, key: str, value: Any) -> None:
        """Attach an attribute to the running span."""
        self._attrs[key] = value
        if self._annotation is not None:
            self._annotation.set_metadata(**{key: value})

    def __enter__(self) -> "_Span":
        if self._annotation is not None:
            self._annotation.__enter__()
        if self._tracer is not None:
            self._id = self._tracer.next_id()
            self._token = _CURRENT.set(self._id)
            self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type: Optional[type[BaseException]],
                 exc: Optional[BaseException],
                 tb: Optional[TracebackType]) -> None:
        if exc_type is not None:
            self.set("error", exc_type.__name__)
        if self._tracer is not None:
            dur = time.perf_counter() - self._t0
            token = self._token
            parent: Optional[int] = None
            if token is not None:
                parent = token.old_value if token.old_value \
                    is not contextvars.Token.MISSING else None
                _CURRENT.reset(token)
            self._tracer.record(self.name, self._t0, dur, cat=self.cat,
                                span_id=self._id, parent_id=parent,
                                attrs=tuple(self._attrs.items()))
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
        return None


def span(name: str, cat: str = "repro", **attrs: Any) -> "_Span | _NoopSpan":
    """Open a traced span; a shared no-op when tracing is disabled.

        with obs.span("plan_graph", cat="plan", graph=name):
            ...

    The span records into the installed `Tracer`, and into the JAX
    profiler's trace while it records. The disabled path (neither) is one
    global read, one profiler check and the shared `_NoopSpan` — safe to
    leave in hot control paths. A loop that opens many spans can check
    once: ``recording`` is False on the no-op, whose ``with`` costs less
    than a ``span()`` call.
    """
    tr = _TRACER
    annotation = _ANNOTATION or _find_annotation()
    profiled = annotation is not None and annotation.is_enabled()
    if tr is None and not profiled:
        return _NOOP
    return _Span(tr, name, cat, attrs, profiled)


# The collection in progress, when a tracer or the profiler was on at its
# start. Collections never overlap: the interpreter runs one at a time.
_GC_OPEN: "_Span | _NoopSpan" = _NOOP


def _on_gc(phase: str, info: dict[str, int]) -> None:
    """``gc.callbacks`` hook: a `GC_SPAN` span from a collection's start to
    its stop."""
    global _GC_OPEN
    if phase == "start":
        _GC_OPEN = span(GC_SPAN, cat="obs", generation=info["generation"])
        _GC_OPEN.__enter__()
    else:
        opened, _GC_OPEN = _GC_OPEN, _NOOP
        opened.__exit__(None, None, None)


gc.callbacks.append(_on_gc)


def enabled() -> bool:
    """True iff a tracer is installed (spans are being recorded)."""
    return _TRACER is not None


def get_tracer() -> Optional[Tracer]:
    return _TRACER


def enable(tracer: Optional[Tracer] = None) -> Tracer:
    """Install (and return) the active tracer."""
    global _TRACER
    _TRACER = tracer if tracer is not None else Tracer()
    return _TRACER


def disable() -> Optional[Tracer]:
    """Uninstall the active tracer and return it (spans stay readable)."""
    global _TRACER
    tr = _TRACER
    _TRACER = None
    return tr


@contextlib.contextmanager
def tracing(tracer: Optional[Tracer] = None) -> Iterator[Tracer]:
    """Scoped tracing: installs a tracer, restores the previous one on exit.

        with obs.tracing() as tr:
            plan_graph("resnet18")
        export.spans_to_trace(tr)
    """
    global _TRACER
    prev = _TRACER
    tr = tracer if tracer is not None else Tracer()
    _TRACER = tr
    try:
        yield tr
    finally:
        _TRACER = prev


class Stopwatch:
    """Measure one wall-clock interval (and span it, when named and traced).

        with Stopwatch() as sw:
            work()
        seconds, micros = sw.s, sw.us

    This is the repo's single ad-hoc timing primitive outside
    ``benchmarks/``: lint rule RPL104 forbids raw ``time.perf_counter()``
    calls elsewhere, so every wall-clock measurement is also a potential
    trace span.
    """

    __slots__ = ("name", "cat", "t0", "s", "_span")

    def __init__(self, name: Optional[str] = None, cat: str = "repro") -> None:
        self.name = name
        self.cat = cat
        self.t0 = 0.0
        self.s = 0.0
        self._span: "_Span | _NoopSpan | None" = None

    def __enter__(self) -> "Stopwatch":
        if self.name is not None:
            self._span = span(self.name, cat=self.cat)
            self._span.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type: Optional[type[BaseException]],
                 exc: Optional[BaseException],
                 tb: Optional[TracebackType]) -> None:
        self.s = time.perf_counter() - self.t0
        if self._span is not None:
            self._span.__exit__(exc_type, exc, tb)
            self._span = None
        return None

    @property
    def us(self) -> float:
        return self.s * 1e6

    @property
    def ms(self) -> float:
        return self.s * 1e3
