"""The program's marks in a trace (``bench/program_trace.py``): scope
time, idle under the program's spans, gaps named by them, the readers
built on them and on the program's round counters, and the harness's
existing numbers left bit for bit as they were."""

from __future__ import annotations

from types import SimpleNamespace

import numpy as np
import pytest

from bench.harness import RunView
from bench.program_trace import ProgramWindow, ScopedEvent, op_scopes
from bench.spec import Bench
from bench.tracing import Window

from conftest import ROOT
from test_tracing import DEV, _recorded, host, small_trace

ROUNDS = "jit(insert_and_maintain)/tick_rounds/while/body/closed_call"
EXISTING = ("idle_share.backlog", "idle_share.open", "fused_tick_ms",
            "fused_tick_ms.open", "host_gap_ms.open")


def op(name, a, b, scope):
    return ScopedEvent(DEV, "XLA Ops", name, float(a), float(b), scope)


def marked_trace():
    """``small_trace`` with scopes on module A's ops and the program's
    spans: tick 0 over [9.8, 13.7] (dispatch [9.8, 10]; prep over the
    barrier gap [13.0, 13.5]), tick 1 over [13.7, 19.5] (read [13.7, 16]
    under the source wait, a weigh span [15.9, 16] and dispatch
    [16, 16.1]), and the drain over [19.5, 19.8]."""
    events = [e for e in small_trace() if e.line != "XLA Ops"
              or e.start < 10 or e.start >= 13]
    events += [
        op("while.3", 10, 13, "jit(insert_and_maintain)/tick_rounds/while"),
        op("%fusion.41 = pred[27491328] fusion(...)", 10.5, 11.5,
           f"{ROUNDS}/peel_gather/gather"),
        op("fusion.41", 11.5, 12.0, f"{ROUNDS}/peel_gather/gather"),
        op("sort.8", 12.0, 12.4, f"{ROUNDS}/peel_scatter/scatter-add"),
        op("fusion.57", 12.2, 12.6, f"{ROUNDS}/peel_scatter/scatter-add"),
        host("spade.tick", 9.8, 13.7),
        host("spade.dispatch", 9.8, 10.0),
        host("spade.prep", 13.0, 13.5),
        host("spade.tick", 13.7, 19.5),
        host("spade.read", 13.7, 16.0),
        host("spade.weigh", 15.9, 16.0),
        host("spade.dispatch", 16.0, 16.1),
        host("spade.drain", 19.5, 19.8),
    ]
    return events


def _varint(n):
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _pb(field, value):
    """One protobuf field: an int as a varint, else length-delimited."""
    if isinstance(value, int):
        return _varint(field << 3) + _varint(value)
    value = value.encode() if isinstance(value, str) else value
    return _varint(field << 3 | 2) + _varint(len(value)) + value


def _plane(name, ops, stat_names):
    """An ``XPlane``: ops are (metadata id, name, [(stat id, field,
    value)]); stat metadata maps id to name."""
    out = _pb(2, name)
    for mid, op_name, stats in ops:
        md = _pb(1, mid) + _pb(2, op_name) + b"".join(
            _pb(5, _pb(1, sid) + _pb(f, v)) for sid, f, v in stats)
        out += _pb(4, _pb(1, mid) + _pb(2, md))
    for sid, sname in stat_names.items():
        out += _pb(5, _pb(1, sid) + _pb(2, _pb(1, sid) + _pb(2, sname)))
    return out


def test_op_scopes_read_from_the_event_metadata(tmp_path):
    """The ``tf_op`` stat of each device op's event metadata, as a string
    or as a reference to a stat metadata name; other planes, stats and
    ops without it give nothing."""
    names = {3: "tf_op", 4: "flops", 9: f"{ROUNDS}/peel_scatter/sort:"}
    space = _pb(1, _plane("/device:TPU:0", [
        (7, "%fusion.54 = pred[8] fusion()", [
            (4, 4, 12), (3, 5, f"{ROUNDS}/peel_gather/gather:")]),
        (8, "%sort.8 = s32[8] sort()", [(3, 7, 9)]),
        (10, "%copy.1 = s32[8] copy()", [(4, 4, 0)]),
    ], names)) + _pb(1, _plane("/host:CPU", [
        (7, "spade.tick", [(3, 5, "not a device op:")])], names))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space)
    assert op_scopes(path) == {
        ("/device:TPU:0", "%fusion.54 = pred[8] fusion()"):
            f"{ROUNDS}/peel_gather/gather",
        ("/device:TPU:0", "%sort.8 = s32[8] sort()"):
            f"{ROUNDS}/peel_scatter/sort",
    }


def test_scope_seconds_is_a_union_over_path_components():
    w = ProgramWindow(marked_trace(), open_delay=1.0, close_delay=11.0)
    assert w.scope_seconds("peel_gather") == pytest.approx(1.5)
    # overlapping sort and fusion count once: [12.0, 12.6]
    assert w.scope_seconds("peel_scatter") == pytest.approx(0.6)
    assert w.scope_seconds("tick_rounds") == pytest.approx(3.0)
    # a component, not a substring
    assert w.scope_seconds("peel") == 0.0
    assert w.scope_seconds("scatter-add") == pytest.approx(0.6)
    # events without a scope (the harness's own loader) read nothing
    assert ProgramWindow(small_trace(), 1.0, 11.0).scope_seconds(
        "peel_gather") == 0.0


def test_idle_under_program_spans_outside_the_source_wait():
    w = ProgramWindow(marked_trace(), open_delay=1.0, close_delay=11.0)
    # gaps [13, 13.5], [13.6, 16], [19, 20]; spade.* covers [13, 13.7],
    # [13.7, 19.5] and [19.5, 19.8]: 0.5 + 2.4 + 0.8 = 3.7, less the
    # source wait over [13.6, 16]: 1.3
    assert w.idle_under("spade.", "source_wait") == pytest.approx(1.3)
    assert w.idle_under("spade.drain", "source_wait") == pytest.approx(0.3)
    assert w.idle_under("spade.", "no_such_span") == pytest.approx(3.7)


def test_gaps_named_by_program_spans_after_the_harness():
    w = ProgramWindow(marked_trace(), open_delay=1.0, close_delay=11.0)
    gaps = w.idle_gaps()
    # the harness's names keep precedence
    assert gaps[0][0] == "source_wait" and gaps[0][1] == pytest.approx(2.4)
    assert ["barrier", pytest.approx(0.5)] in gaps
    # [19, 20]: the tick covers [19, 19.5], the drain [19.5, 19.8]; a
    # step inside a tick names the gap before the tick does
    assert ["spade.drain", pytest.approx(1.0)] in gaps
    # no program span: as the harness names it
    bare = ProgramWindow(small_trace(), 1.0, 11.0)
    assert bare.idle_gaps() == Window(small_trace(), 1.0, 11.0).idle_gaps()
    # a gap under a tick and under none of its steps takes the tick's name
    only_tick = [e for e in marked_trace() if e.name != "spade.drain"]
    named = dict((n, s) for n, s in
                 ProgramWindow(only_tick, 1.0, 11.0).idle_gaps())
    assert named["spade.tick"] == pytest.approx(1.0)


def _existing(w):
    bench = Bench(ROOT)
    view = RunView(report=None, window_ticks=2, trace=w)
    return {m: bench.layer_reader(m).read(view) for m in EXISTING}


@pytest.mark.parametrize("trace", ["small", "recorded"])
def test_existing_readers_bit_identical(trace):
    """The same events, each op with a scope and the program's spans
    added, read alike by every existing reader and method."""
    if trace == "small":
        events, delays = small_trace(), (1.0, 11.0)
    else:
        fix, events = _recorded()
        delays = (fix["open_delay_s"], fix["close_delay_s"])
    t0 = min(e.start for e in events)
    marked = [op(e.name, e.start, e.end, f"{ROUNDS}/peel_gather/gather")
              if e.line == "XLA Ops" else e for e in events]
    marked += [host("spade.tick", t0, t0 + 30),
               host("spade.dispatch", t0, t0 + 0.001)]
    before = Window(events, *delays)
    after = ProgramWindow(marked, *delays)
    assert _existing(after) == _existing(before)
    assert after.busy_s == before.busy_s
    assert after.top_ops() == before.top_ops()
    assert after.top_modules() == before.top_modules()
    assert after.idle_outside("source_wait") == \
        before.idle_outside("source_wait")


def _reader(name):
    return Bench(ROOT).layer_reader(name)


def test_scope_readers_on_a_made_run():
    w = ProgramWindow(marked_trace(), open_delay=1.0, close_delay=11.0)
    view = RunView(report=None, window_ticks=2, trace=w)
    assert _reader("peel_gather_ms").read(view) == pytest.approx(750.0)
    assert _reader("peel_scatter_ms").read(view) == pytest.approx(300.0)
    assert _reader("loop_gap_ms.open").read(view) == pytest.approx(650.0)
    # a marked program whose scopes are gone is an error, not a 0
    unscoped = [e for e in marked_trace()
                if not isinstance(e, ScopedEvent)]
    lost = RunView(report=None, window_ticks=2,
                   trace=ProgramWindow(unscoped, 1.0, 11.0))
    for name in ("peel_gather_ms", "peel_scatter_ms"):
        with pytest.raises(LookupError):
            _reader(name).read(lost)
    no_tick = [e for e in marked_trace() if e.name != "spade.tick"]
    with pytest.raises(LookupError):
        _reader("loop_gap_ms.open").read(RunView(
            report=None, window_ticks=2,
            trace=ProgramWindow(no_tick, 1.0, 11.0)))
    # a program that marks nothing gives nothing; so does an untraced run
    bare = RunView(report=None, window_ticks=2,
                   trace=ProgramWindow(small_trace(), 1.0, 11.0))
    for name in ("peel_gather_ms", "peel_scatter_ms", "loop_gap_ms.open"):
        assert _reader(name).read(bare) is None
        assert _reader(name).read(RunView(report=None,
                                          window_ticks=2)) is None


def _report(rv, re, slots=100):
    return SimpleNamespace(round_vertices=None if rv is None
                           else np.asarray(rv),
                           round_edges=None if re is None
                           else np.asarray(re), edge_slots=slots)


def test_counter_readers_on_a_made_run():
    # three ticks of four rounds; the window holds the last two
    rv = [[9, 9, 9, 9], [50, 8, 0, 0], [40, 3, 1, 0]]
    re = [[90, 90, 90, 90], [60, 10, 0, 0], [50, 5, 5, 0]]
    view = RunView(report=_report(rv, re), window_ticks=2)
    assert _reader("dead_round_share").read(view) == pytest.approx(37.5)
    assert _reader("dead_slot_share").read(view) == pytest.approx(
        100 * (1 - 130 / 800))
    # a program without the counters gives nothing
    old = RunView(report=SimpleNamespace(n_ticks=3), window_ticks=2)
    assert _reader("dead_round_share").read(old) is None
    assert _reader("dead_slot_share").read(old) is None
    # an engine that kept none, or too few rows, is an error
    for bad in (_report(None, None, None), _report(rv[:1], re[:1])):
        for name in ("dead_round_share", "dead_slot_share"):
            with pytest.raises(LookupError):
                _reader(name).read(RunView(report=bad, window_ticks=2))


def _recorded_program():
    import gzip
    import json
    from pathlib import Path

    from bench.tracing import Event

    path = Path(__file__).parent / "data" / "backlog_program_trace.json.gz"
    with gzip.open(path, "rt") as f:
        fix = json.load(f)
    events = [op(n, a * 1e-9, b * 1e-9, sc) if p.startswith("/device")
              and ln == "XLA Ops" else Event(p, ln, n, a * 1e-9, b * 1e-9)
              for p, ln, n, a, b, sc in fix["events"]]
    return fix, ProgramWindow(events, fix["open_delay_s"],
                              fix["close_delay_s"])


def test_recorded_program_trace():
    """A ``grab4.backlog`` window recorded on a TPU v5 lite with the
    program's scopes and spans: scope time against a brute-force count
    on a fine grid, the rounds' scopes covering the ``while`` op, and no
    idle gap left to ``host``."""
    fix, w = _recorded_program()
    ops = [e for e in w._events if e.line == "XLA Ops"]
    grid = np.linspace(w.t0, w.t1, 400_001)[:-1] + w.window_s / 800_000
    for scope in ("peel_gather", "peel_scatter"):
        covered = np.zeros(grid.shape, bool)
        for e in ops:
            if scope in e.scope.split("/"):
                covered |= (grid >= e.start) & (grid < e.end)
        s = w.scope_seconds(scope)
        assert s == pytest.approx(covered.mean() * w.window_s, abs=2e-3)
        assert s == pytest.approx(fix["expect"][f"{scope}_s"], abs=1e-9)
    view = RunView(report=None, window_ticks=2, trace=w)
    peel = _reader("peel_gather_ms").read(view) \
        + _reader("peel_scatter_ms").read(view)
    loop = 1e3 * sum(e.end - e.start for e in ops
                     if e.name.lstrip("%").startswith("while")) / 2
    assert 0.9 * loop <= peel <= loop
    assert all(n != "host" for n, s in w.idle_gaps(1000) if s >= 1e-4)
    assert _reader("loop_gap_ms.open").read(view) \
        <= _reader("host_gap_ms.open").read(view)
