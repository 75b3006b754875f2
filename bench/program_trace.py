"""The program's own marks in a profiler trace, read beside the harness's
reduction (``bench/tracing.py``), which this module extends and leaves
as it is.

The program marks its work two ways (``src/repro``):

* named scopes on the tick program's ops (``peel_gather``,
  ``peel_scatter``, ``peel_update`` inside each round; ``tick_prologue``,
  ``tick_append``, ``tick_seed``, ``tick_rounds``, ``tick_merge`` around
  them), which reach each device op through its HLO ``op_name``
  metadata;
* ``spade.*`` host spans (``jax.profiler.TraceAnnotation``) around the
  served loop's steps, recorded in the same profiler session as the
  device planes and so on the same clock.

:func:`load` reads what :func:`bench.tracing.load` reads, each device op
with its name-scope path (:class:`ScopedEvent`), plus the program's
``spade.*`` host spans.  :class:`ProgramWindow` is a
:class:`bench.tracing.Window` whose existing numbers are unchanged, with
three more:

* :meth:`ProgramWindow.scope_seconds`: device time of the ops under one
  scope (the union of their intervals, so a fusion and its nested ops
  count once);
* :meth:`ProgramWindow.idle_under`: device idle under the program's spans,
  outside a harness span;
* :meth:`ProgramWindow.idle_gaps`: a gap no harness span covers is named
  by the program span that covers most of it, ``host`` only where none
  does.
"""

from __future__ import annotations

from dataclasses import dataclass
from pathlib import Path

from bench.tracing import (DEVICE_LINES, DEVICE_PLANE, HOST_SPANS, Event,
                           Window, _overlap, union)

__all__ = ["PROGRAM_PREFIX", "ProgramWindow", "ScopedEvent", "load",
           "op_scopes"]

PROGRAM_PREFIX = "spade."
# the span around a whole tick: a gap is named by it only where none of
# the steps inside it covers any of the gap
PARENT_SPANS = ("spade.tick",)
# the stat of an op's event metadata that carries its HLO op_name
SCOPE_STAT = "tf_op"


@dataclass(frozen=True)
class ScopedEvent(Event):
    scope: str = ""  # the op's name-scope path, "" where it has none


def _varint(buf, pos: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, pos
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one protobuf message: a varint as an
    int, a length-delimited field as a ``memoryview``."""
    pos = 0
    while pos < len(buf):
        key, pos = _varint(buf, pos)
        wire = key & 7
        if wire == 0:
            val, pos = _varint(buf, pos)
        elif wire == 2:
            n, pos = _varint(buf, pos)
            val, pos = buf[pos:pos + n], pos + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            val, pos = buf[pos:pos + n], pos + n
        else:
            raise ValueError(f"protobuf wire type {wire} in a trace")
        yield key >> 3, val


def _map_values(entries):
    """The values of protobuf map entries (key 1, value 2)."""
    for entry in entries:
        for f, v in _fields(entry):
            if f == 2:
                yield v


def op_scopes(path: Path) -> dict[tuple[str, str], str]:
    """``(device plane, op event name) -> name-scope path``.

    :class:`jax.profiler.ProfileData` gives an event's own stats only,
    and a device op's ``op_name`` rides in a stat of its event metadata
    (``tf_op``, ``jit(f)/tick_rounds/.../peel_gather/gather:``), so this
    reads the ``XSpace`` protobuf itself: per plane (field 1) its name
    (2), event metadata (4: ``XEventMetadata`` name 2, stats 5) and stat
    metadata (5: ``XStatMetadata`` id 1, name 2); a stat (``XStat``) is
    metadata id 1 and a string value 5, or a reference 7 to a stat
    metadata whose name is the string.
    """
    out: dict[tuple[str, str], str] = {}
    for f, plane in _fields(memoryview(Path(path).read_bytes())):
        if f != 1:
            continue
        name, metas, stat_names = "", [], {}
        for pf, v in _fields(plane):
            if pf == 2:
                name = bytes(v).decode()
            elif pf == 4:
                metas.append(v)
            elif pf == 5:
                for sm in _map_values([v]):
                    fields = dict(_fields(sm))
                    stat_names[fields.get(1, 0)] = bytes(
                        fields.get(2, b"")).decode()
        if not DEVICE_PLANE.match(name):
            continue
        for md in _map_values(metas):
            op, scope = "", ""
            for mf, mv in _fields(md):
                if mf == 2:
                    op = bytes(mv).decode()
                elif mf == 5:
                    stat = dict(_fields(mv))
                    if stat_names.get(stat.get(1)) != SCOPE_STAT:
                        continue
                    if 5 in stat:
                        scope = bytes(stat[5]).decode()
                    elif 7 in stat:
                        scope = stat_names.get(stat[7], "")
            if scope:
                # "<op_name>:<op type>"; JAX leaves the type empty
                out[(name, op)] = scope.rpartition(":")[0] or scope
    return out


def load(path: Path) -> list[Event]:
    """What :func:`bench.tracing.load` keeps, each device op with its
    scope, and the program's ``spade.*`` host spans."""
    from jax.profiler import ProfileData

    scopes = op_scopes(path)
    data = ProfileData.from_file(str(path))
    out: list[Event] = []
    for plane in data.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        if not device and not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            if device and line.name not in DEVICE_LINES:
                continue
            for e in line.events:
                start, end = e.start_ns * 1e-9, (e.start_ns
                                                 + e.duration_ns) * 1e-9
                if device:
                    out.append(ScopedEvent(plane.name, line.name, e.name,
                                           start, end,
                                           scopes.get((plane.name, e.name),
                                                      "")))
                elif e.name in HOST_SPANS or e.name.startswith(
                        PROGRAM_PREFIX):
                    out.append(Event(plane.name, line.name, e.name, start,
                                     end))
    return out


def _intersect(a: list[tuple[float, float]], b: list[tuple[float, float]]):
    """The intersection of two disjoint sorted interval lists."""
    i = j = 0
    out = []
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


class ProgramWindow(Window):
    """A :class:`bench.tracing.Window` that also reads the program's
    scopes and spans."""

    def __init__(self, events: list[Event], open_delay: float = 0.0,
                 close_delay: float | None = None):
        super().__init__(events, open_delay, close_delay)
        spans: dict[str, list[tuple[float, float]]] = {}
        for e in events:
            if e.plane.startswith("/host:") and e.name.startswith(
                    PROGRAM_PREFIX):
                spans.setdefault(e.name, []).append((e.start, e.end))
        #: whether the program marked any span at all, in or out of the
        #: window: a program that predates its spans marks none
        self.has_program_spans = bool(spans)
        self.program_spans = {k: union(self._clip(v))
                              for k, v in spans.items()}

    def scope_seconds(self, component: str) -> float:
        """Device seconds of the ops whose name-scope path has
        ``component`` as one of its parts, averaged over devices."""
        total = 0.0
        for p in self.devices:
            ops = [e for e in self._device(p, "XLA Ops")
                   if component in getattr(e, "scope", "").split("/")]
            total += sum(b - a for a, b in union(
                self._clip((e.start, e.end) for e in ops)))
        return total / len(self.devices)

    def idle_under(self, prefix: str = PROGRAM_PREFIX,
                   outside: str = "source_wait") -> float:
        """Idle seconds (first device) under program spans whose name
        starts with ``prefix``, less what harness spans ``outside``
        cover."""
        under = union(iv for name, ivs in self.program_spans.items()
                      if name.startswith(prefix) for iv in ivs)
        idle = _intersect(self.gaps(), under)
        return sum(b - a for a, b in idle) - _overlap(
            idle, self.host_spans.get(outside, []))

    def idle_gaps(self, k: int = 10) -> list[list]:
        """The longest idle gaps, each named by the harness span covering
        most of it; where none covers any, by the program span covering
        most of it (a step inside a tick before the tick itself); ``host``
        where neither does."""
        named = []
        for a, b in self.gaps():
            best = None
            for group in (
                    {s: v for s, v in self.host_spans.items()
                     if s != "window"},
                    {s: v for s, v in self.program_spans.items()
                     if s not in PARENT_SPANS},
                    {s: v for s, v in self.program_spans.items()
                     if s in PARENT_SPANS}):
                cover = 0.0
                for span, ivs in group.items():
                    c = _overlap([(a, b)], ivs)
                    if c > cover:
                        best, cover = span, c
                if best is not None:
                    break
            named.append([best or "host", b - a])
        return sorted(named, key=lambda x: -x[1])[:k]
