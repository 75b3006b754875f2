"""From a profiler trace to the per-layer numbers: the benchmark's
reduction, kept with it so that every run computes them alike.

The trace is read into plain :class:`Event` records: device events from
the ``XLA Modules`` and ``XLA Ops`` lines of each ``/device:TPU:<n>``
plane, and the harness's own host spans (``window``, ``warmup``,
``source_wait``, ``barrier``).  :class:`Window` then measures inside the
measured window only:

* busy time: the union of the intervals in which an op ran on a device
  (nested ops, such as a ``while`` and its body, count once), averaged
  over the devices; idle is the rest of the window;
* device time per module (a jitted function: ``jit_<name>(<hash>)`` in
  the trace) and per op;
* idle gaps, each named by the harness span that covers most of it, or
  ``host`` where none does: then the host was busy with the program's
  own work.
"""

from __future__ import annotations

import re
from dataclasses import dataclass
from pathlib import Path

__all__ = ["Event", "Window", "load", "module_name", "union"]

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
DEVICE_LINES = ("XLA Modules", "XLA Ops")
HOST_SPANS = ("window", "warmup", "source_wait", "barrier")
_MODULE = re.compile(r"^jit_(?P<name>.+?)(\(\d+\))?$")


@dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start: float  # seconds on the trace's clock
    end: float


def load(path: Path) -> list[Event]:
    """The events the reduction reads, from an ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    out = []
    for plane in data.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        if not device and not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            if device and line.name not in DEVICE_LINES:
                continue
            for e in line.events:
                if device or e.name in HOST_SPANS:
                    out.append(Event(plane.name, line.name, e.name,
                                     e.start_ns * 1e-9, e.end_ns * 1e-9))
    return out


def module_name(event_name: str) -> str:
    """``jit_insert_and_maintain(123)`` -> ``insert_and_maintain``."""
    m = _MODULE.match(event_name)
    return m.group("name") if m else event_name


def op_name(event_name: str) -> str:
    """An op's short name: the instruction name of HLO text."""
    return event_name.split(" = ")[0].lstrip("%")


def union(intervals) -> list[tuple[float, float]]:
    """Merge intervals into disjoint sorted ones."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _overlap(a: list[tuple[float, float]], b: list[tuple[float, float]]):
    """Total length of the intersection of two disjoint sorted lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(0.0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


class Window:
    """The measured window of a trace.

    ``open_delay`` and ``close_delay`` place the window on the trace's
    clock: seconds after the start of the harness's ``window`` span, which
    begins at the host time the window's first edge was read.
    """

    def __init__(self, events: list[Event], open_delay: float = 0.0,
                 close_delay: float | None = None):
        spans = [e for e in events if e.name == "window"
                 and not DEVICE_PLANE.match(e.plane)]
        if len(spans) != 1:
            raise ValueError(f"expected one window span, found {len(spans)}")
        anchor = spans[0]
        self.t0 = anchor.start + open_delay
        self.t1 = anchor.end if close_delay is None \
            else anchor.start + close_delay
        if self.t1 <= self.t0:
            raise ValueError("the window closes before it opens")
        self.devices = sorted({e.plane for e in events
                               if DEVICE_PLANE.match(e.plane)})
        if not self.devices:
            raise ValueError("no device plane in the trace")
        self._events = events
        self.host_spans: dict[str, list[tuple[float, float]]] = {}
        for e in events:
            if e.plane.startswith("/host:") and e.name in HOST_SPANS:
                self.host_spans.setdefault(e.name, []).append(
                    (e.start, e.end))
        for k, v in self.host_spans.items():
            self.host_spans[k] = union(self._clip(v))

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    def _clip(self, intervals):
        return [(max(a, self.t0), min(b, self.t1)) for a, b in intervals
                if b > self.t0 and a < self.t1]

    def _device(self, plane: str, line: str) -> list[Event]:
        return [e for e in self._events
                if e.plane == plane and e.line == line
                and e.end > self.t0 and e.start < self.t1]

    def busy(self, plane: str) -> list[tuple[float, float]]:
        ops = self._device(plane, "XLA Ops")
        return union(self._clip((e.start, e.end) for e in ops))

    @property
    def busy_s(self) -> float:
        """Device-busy seconds in the window, averaged over devices."""
        return sum(sum(b - a for a, b in self.busy(p))
                   for p in self.devices) / len(self.devices)

    @property
    def idle_s(self) -> float:
        return self.window_s - self.busy_s

    def gaps(self, plane: str | None = None) -> list[tuple[float, float]]:
        """The idle intervals of a device (the first by default)."""
        edges = [self.t0]
        for a, b in self.busy(plane or self.devices[0]):
            edges += [a, b]
        edges.append(self.t1)
        return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]

    def idle_outside(self, span: str) -> float:
        """Idle seconds (first device) not covered by host ``span``s."""
        gaps = self.gaps()
        return sum(b - a for a, b in gaps) - _overlap(
            gaps, self.host_spans.get(span, []))

    def module_seconds(self, names) -> float:
        """Device seconds of the modules named ``names``, averaged over
        devices."""
        names = set(names)
        total = 0.0
        for p in self.devices:
            for e in self._device(p, "XLA Modules"):
                if module_name(e.name) in names:
                    total += min(e.end, self.t1) - max(e.start, self.t0)
        return total / len(self.devices)

    def _top(self, line: str, key, k: int) -> list[list]:
        sums: dict[str, float] = {}
        for e in self._device(self.devices[0], line):
            name = key(e.name)
            sums[name] = sums.get(name, 0.0) + (
                min(e.end, self.t1) - max(e.start, self.t0))
        return [[n, s] for n, s in
                sorted(sums.items(), key=lambda x: -x[1])[:k]]

    def top_modules(self, k: int = 10) -> list[list]:
        return self._top("XLA Modules", module_name, k)

    def top_ops(self, k: int = 10) -> list[list]:
        return self._top("XLA Ops", op_name, k)

    def idle_gaps(self, k: int = 10) -> list[list]:
        """The longest idle gaps, each named by the host span covering
        most of it (``host`` where none covers any)."""
        named = []
        for a, b in self.gaps():
            best, cover = "host", 0.0
            for span, ivs in self.host_spans.items():
                if span == "window":
                    continue
                c = _overlap([(a, b)], ivs)
                if c > cover:
                    best, cover = span, c
            named.append([best, b - a])
        return sorted(named, key=lambda x: -x[1])[:k]

    def barriers_out_of_order(self, barrier: str) -> int:
        """Barrier modules that started before an earlier module ended:
        a barrier stamps completion only if the device runs in order."""
        bad = 0
        for p in self.devices:
            mods = sorted(self._device(p, "XLA Modules"),
                          key=lambda e: e.start)
            last_end = float("-inf")
            for e in mods:
                if module_name(e.name) == barrier and e.start < last_end:
                    bad += 1
                last_end = max(last_end, e.end)
        return bad
