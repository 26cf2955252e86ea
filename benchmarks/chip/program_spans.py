"""The program's own spans in a traced run, on the clock of the device's operations.

The program (``repro.obs.span``) writes each span into the profiler's trace
as a host event of the same name while the profiler records: the network
runner's ``network.step``, ``kernel.preflight``, ``network.conv`` and
``network.glue``, and ``obs.gc`` around each garbage-collector pause. This
module reads those events from the traced run's one ``.xplane.pb``, once per
process, clipped to the ``bench.window`` span, and gives

  * a span name's self time: the duration of its events less the union of
    the program spans nested inside each (on the same host thread);
  * the device's idle time inside a span name: each device's idle intervals
    in the window intersected with the union of that name's events, summed
    over devices.

A program that writes no such span (an older one) leaves the names absent:
the readers then return ``None``.
"""

from __future__ import annotations

import dataclasses
import functools
import pathlib
import sys
from typing import Dict, List, Optional, Sequence, Tuple

from xplane import Event, Interval, TraceSummary, find_xplane, gaps, union

TRACE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".bench_trace"  # run.py's
RUNNER_SPANS = ("network.step", "kernel.preflight", "network.conv",
                "network.glue")


def gc_span() -> Optional[str]:
    """The program's span around a garbage-collector pause, as the program
    names it (``repro.obs.trace.GC_SPAN``); ``None`` for a program that
    spans no collections."""
    return getattr(sys.modules.get("repro.obs.trace"), "GC_SPAN", None)


def span_names() -> Tuple[str, ...]:
    """The program spans the readers ask for: the network runner's, and the
    garbage collector's where the program has one."""
    gc_name = gc_span()
    return RUNNER_SPANS + ((gc_name,) if gc_name else ())


def _length(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def _intersect(a: Sequence[Interval], b: Sequence[Interval]
               ) -> List[Interval]:
    """The overlap of two sorted lists of disjoint intervals."""
    out: List[Interval] = []
    i = j = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


@dataclasses.dataclass(frozen=True)
class ProgramSpans:
    """The program's span events, one sorted tuple per host thread."""

    threads: Tuple[Tuple[Event, ...], ...]

    def names(self) -> set:
        return {e.name for t in self.threads for e in t}

    def clipped(self, window: Interval) -> "ProgramSpans":
        lo, hi = window
        return ProgramSpans(tuple(
            tuple(Event(e.name, max(e.start_ns, lo), min(e.end_ns, hi))
                  for e in t if e.end_ns > lo and e.start_ns < hi)
            for t in self.threads))

    def self_s(self, names: Sequence[str]) -> float:
        """Seconds in the events named ``names``, each less the union of the
        program spans nested inside it."""
        total = 0.0
        for events in self.threads:
            for i, e in enumerate(events):
                if e.name not in names:
                    continue
                inner = []
                for j in range(i + 1, len(events)):
                    c = events[j]
                    if c.start_ns >= e.end_ns:
                        break
                    if c.end_ns <= e.end_ns:
                        inner.append((c.start_ns, c.end_ns))
                total += e.duration_ns - _length(union(inner))
        return total / 1e9

    def idle_inside_s(self, name: str, trace: TraceSummary) -> float:
        """Device seconds idle while some ``name`` event was open, summed
        over the devices of ``trace``."""
        held = union([(e.start_ns, e.end_ns) for t in self.threads
                      for e in t if e.name == name])
        return sum(_length(_intersect(gaps(trace.busy(d), trace.window),
                                      held))
                   for d in range(len(trace.devices))) / 1e9


def _sorted(events: List[Event]) -> Tuple[Event, ...]:
    # a parent sorts before the children it holds, even one that starts
    # with it
    return tuple(sorted(events, key=lambda e: (e.start_ns, -e.end_ns)))


@functools.lru_cache(maxsize=4)
def load(path: str, names: Tuple[str, ...]) -> ProgramSpans:
    """The events named ``names`` on the host planes of one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    threads: Dict[Tuple[str, int], List[Event]] = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for n, line in enumerate(plane.lines):
            for e in line.events:
                if e.name in names:
                    threads.setdefault((plane.name, n), []).append(
                        Event(e.name, e.start_ns, e.end_ns))
    return ProgramSpans(tuple(_sorted(t) for t in threads.values()))


def of_run(run) -> Optional[ProgramSpans]:
    """The program's spans in ``run``'s traced window; ``None`` when the run
    was not traced."""
    if run.trace is None:
        return None
    return load(str(find_xplane(TRACE_DIR)), span_names()).clipped(
        run.trace.window)


def per_step_ms(run, names: Sequence[str]) -> Optional[float]:
    """Self time of ``names`` in the window per step, in ms; ``None`` when
    the run was not traced or the program wrote none of them."""
    spans = of_run(run)
    if spans is None or not spans.names() & set(names) \
            or run.window.steps == 0:
        return None
    return 1e3 * spans.self_s(names) / run.window.steps
