"""Reduce one profiler trace (``.xplane.pb``) to what the per-layer metrics read.

A traced run wraps its measured window in the host span ``bench.window`` and
each call into a layer in a ``bench.*`` span of its own
(``jax.profiler.TraceAnnotation``). The profiler puts those host spans and the
device's operations on one clock, so this module can

  * take each device's operations (the ``XLA Ops`` line of every
    ``/device:TPU:<n>`` plane), clipped to the window;
  * merge them into the busy union, and find the idle gaps between;
  * name each gap by the innermost ``bench.*`` span that holds its midpoint:
    what the host was doing while the device waited;
  * sum the device time of the operations a predicate picks (a kernel's
    launches, or everything that is not a kernel).

On a TPU v5e an operation's event carries its whole HLO instruction,
``%conv2d_psum.1 = f32[32,16,3328]{...} custom-call(...)``. A Pallas kernel
is the custom call named after the jitted entry that launched it
(``psum_matmul.12``, ``conv2d_psum.1``); the glue around it is ``slice.0``,
``pad.0``, ``copy.4``, ``pad_bitcast_fusion`` and the like. `base_name`
keeps the instruction's name without its number, `label` adds the result's
shape, which tells one conv layer's launches from another's.
"""

from __future__ import annotations

import dataclasses
import pathlib
import re
from typing import Callable, Dict, List, Sequence, Tuple

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"

Interval = Tuple[float, float]


@dataclasses.dataclass(frozen=True)
class Event:
    """One operation or host span, in nanoseconds on the trace's clock."""

    name: str
    start_ns: float
    end_ns: float

    @property
    def duration_ns(self) -> float:
        return self.end_ns - self.start_ns


_HLO = re.compile(r"^%?(?P<name>[^\s=]+) = (?P<type>\S+) ")


def base_name(op: str) -> str:
    """``%conv2d_psum.12 = f32[...] custom-call(...)`` or ``conv2d_psum.12``
    -> ``conv2d_psum``; ``fusion`` stays."""
    m = _HLO.match(op)
    return re.sub(r"\.\d+$", "", m.group("name") if m else op)


def label(op: str) -> str:
    """`base_name` and the result's shape without its layout:
    ``conv2d_psum f32[32,16,3328]``."""
    m = _HLO.match(op)
    if not m:
        return base_name(op)
    return f"{base_name(op)} {re.sub(r'{[^}]*}', '', m.group('type'))}"


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merge overlapping or touching intervals; the result is sorted."""
    merged: List[List[float]] = []
    for start, end in sorted(intervals):
        if end <= start:
            continue
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(s, e) for s, e in merged]


def gaps(busy: Sequence[Interval], window: Interval) -> List[Interval]:
    """The parts of ``window`` that the sorted, disjoint ``busy`` leaves."""
    out: List[Interval] = []
    cursor = window[0]
    for start, end in busy:
        if start > cursor:
            out.append((cursor, min(start, window[1])))
        cursor = max(cursor, end)
        if cursor >= window[1]:
            break
    if cursor < window[1]:
        out.append((cursor, window[1]))
    return [(s, e) for s, e in out if e > s]


def _clip(events: Sequence[Event], window: Interval) -> List[Event]:
    lo, hi = window
    return [Event(e.name, max(e.start_ns, lo), min(e.end_ns, hi))
            for e in events if e.end_ns > lo and e.start_ns < hi]


@dataclasses.dataclass(frozen=True)
class TraceSummary:
    """The traced window, each device's operations in it, and the host's
    ``bench.*`` spans."""

    window: Interval
    devices: Tuple[Tuple[Event, ...], ...]
    spans: Tuple[Event, ...]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def busy(self, device: int) -> List[Interval]:
        return union([(e.start_ns, e.end_ns) for e in self.devices[device]])

    @property
    def busy_s(self) -> float:
        """Seconds in which some operation ran, averaged over the devices."""
        total = sum(e - s for d in range(len(self.devices))
                    for s, e in self.busy(d))
        return total / len(self.devices) / 1e9

    def op_seconds(self, pick: Callable[[str], bool]) -> float:
        """Device seconds of the operations whose name ``pick`` accepts,
        summed over operations and devices."""
        return sum(e.duration_ns for ops in self.devices for e in ops
                   if pick(e.name)) / 1e9

    def spans_at(self, times: Sequence[float]) -> List[str]:
        """For each of the sorted ``times``, the innermost ``bench.*`` span
        holding it (the window itself when no other does), or ``"outside
        bench spans"``. One sweep: the spans of one thread nest, so the
        latest-started span still open is the innermost."""
        order = sorted(self.spans, key=lambda s: (s.start_ns, -s.end_ns))
        names, stack, i = [], [], 0
        for t in times:
            while i < len(order) and order[i].start_ns <= t:
                stack.append(order[i])
                i += 1
            while stack and stack[-1].end_ns < t:
                stack.pop()
            names.append(stack[-1].name if stack else "outside bench spans")
        return names

    def idle_by_span(self) -> Dict[str, Tuple[float, int]]:
        """{host span: (idle device seconds inside it, gaps)}, over all
        devices, each gap named by the span that holds its midpoint."""
        out: Dict[str, Tuple[float, int]] = {}
        for d in range(len(self.devices)):
            idle = gaps(self.busy(d), self.window)
            for (s, e), name in zip(idle, self.spans_at(
                    [(s + e) / 2 for s, e in idle])):
                total, n = out.get(name, (0.0, 0))
                out[name] = (total + (e - s) / 1e9, n + 1)
        return out

    def breakdown(self, top: int = 10) -> Dict[str, List[List[object]]]:
        """The operations that took most device time (grouped by `label`)
        and the idle time by what the host was doing."""
        ops: Dict[str, float] = {}
        for dev in self.devices:
            for e in dev:
                key = label(e.name)
                ops[key] = ops.get(key, 0.0) + e.duration_ns / 1e9
        idle = self.idle_by_span()
        return {
            "device_ops": [[k, v] for k, v in
                           sorted(ops.items(), key=lambda kv: -kv[1])[:top]],
            "idle_gaps": [[f"{k} ({n} gaps)", v] for k, (v, n) in
                          sorted(idle.items(), key=lambda kv: -kv[1][0])[:top]],
        }


def load(path: "str | pathlib.Path") -> TraceSummary:
    """Read one ``.xplane.pb`` written by ``jax.profiler`` around a window
    that a single ``bench.window`` span marks."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    spans: List[Event] = []
    devices: List[Tuple[Event, ...]] = []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            spans += [Event(e.name, e.start_ns, e.end_ns)
                      for line in plane.lines for e in line.events
                      if e.name.startswith(SPAN_PREFIX)]
        elif DEVICE_PLANE.match(plane.name):
            devices.append(tuple(
                Event(e.name, e.start_ns, e.end_ns)
                for line in plane.lines if line.name == OPS_LINE
                for e in line.events))
    windows = [s for s in spans if s.name == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"{path}: expected one {WINDOW_SPAN!r} span, "
                         f"found {len(windows)}")
    if not devices:
        raise ValueError(f"{path}: no /device:TPU:<n> plane")
    window = (windows[0].start_ns, windows[0].end_ns)
    return TraceSummary(
        window=window,
        devices=tuple(tuple(_clip(d, window)) for d in devices),
        spans=tuple(sorted(spans, key=lambda s: s.start_ns)))


def find_xplane(trace_dir: "str | pathlib.Path") -> pathlib.Path:
    """The one ``.xplane.pb`` that ``jax.profiler.start_trace`` wrote."""
    found = sorted(pathlib.Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if len(found) != 1:
        raise FileNotFoundError(f"{trace_dir}: expected one .xplane.pb, "
                                f"found {len(found)}")
    return found[0]
