"""The trace reduction (``bench/tracing.py``) on small traces whose
answers are known: busy/idle as a union, per-module sums, idle gaps named
by the harness span they fall in, and the barrier-order check."""

from __future__ import annotations

import pytest

from bench.tracing import Event, Window, module_name, op_name, union

DEV = "/device:TPU:0"
HOST = "/host:CPU"


def ev(name, a, b, line="XLA Ops", plane=DEV):
    return Event(plane, line, name, float(a), float(b))


def host(name, a, b):
    return ev(name, a, b, line="python", plane=HOST)


def small_trace():
    """Window [10, 20] on the trace clock (span from 9, opened 1 s late).

    Device: module A over [10, 13] with a nested while op [10, 13] and
    its body ops; a barrier module at [13.5, 13.6]; module B over
    [16, 19].  Host: the source waited over [13.6, 16]; nothing covers
    the gap [19, 20] but the end of the window.
    """
    return [
        host("window", 9, 20),
        host("source_wait", 13.6, 16),
        host("barrier", 13.0, 13.6),
        ev("jit_insert_and_maintain(123)", 10, 13, line="XLA Modules"),
        ev("while.3", 10, 13),
        ev("%fusion.41 = pred[27491328] fusion(...)", 10.5, 11.5),
        ev("fusion.41", 11.5, 12.0),
        ev("jit__barrier(7)", 13.5, 13.6, line="XLA Modules"),
        ev("add", 13.5, 13.6),
        ev("jit__slide_phase_a(9)", 16, 19, line="XLA Modules"),
        ev("sort.2", 16, 19),
        # outside the window: set-up, must not count
        ev("jit_bulk_peel(1)", 2, 9.5, line="XLA Modules"),
        ev("while.1", 2, 9.5),
    ]


def test_union_merges_nested_and_touching():
    assert union([(0, 3), (1, 2), (3, 4), (5, 6), (6, 6)]) == [(0, 4), (5, 6)]


def test_busy_idle_and_window_placement():
    w = Window(small_trace(), open_delay=1.0, close_delay=11.0)
    assert (w.t0, w.t1) == (10.0, 20.0)
    assert w.window_s == pytest.approx(10.0)
    # busy = [10,13] + [13.5,13.6] + [16,19]; nested ops counted once
    assert w.busy_s == pytest.approx(6.1)
    assert w.idle_s == pytest.approx(3.9)
    assert w.gaps() == [(13.0, 13.5), (13.6, 16.0), (19.0, 20.0)]


def test_module_sums_clip_to_window():
    w = Window(small_trace(), open_delay=1.0, close_delay=11.0)
    assert w.module_seconds(["insert_and_maintain"]) == pytest.approx(3.0)
    assert w.module_seconds(["_slide_phase_a", "_insert_phase_a"]) \
        == pytest.approx(3.0)
    assert w.module_seconds(["bulk_peel"]) == 0.0
    # a window opened at the span's start keeps the set-up's tail
    early = Window(small_trace(), open_delay=0.0, close_delay=11.0)
    assert early.module_seconds(["bulk_peel"]) == pytest.approx(0.5)


def test_idle_gaps_named_by_host_span():
    w = Window(small_trace(), open_delay=1.0, close_delay=11.0)
    gaps = w.idle_gaps()
    assert gaps[0][0] == "source_wait" and gaps[0][1] == pytest.approx(2.4)
    assert ["host", pytest.approx(1.0)] in gaps
    assert ["barrier", pytest.approx(0.5)] in gaps
    # host gap = idle that no source wait covers
    assert w.idle_outside("source_wait") == pytest.approx(1.5)


def test_top_ops_and_modules():
    w = Window(small_trace(), open_delay=1.0, close_delay=11.0)
    ops = dict((n, s) for n, s in w.top_ops())
    assert ops["fusion.41"] == pytest.approx(1.5)  # HLO text shortened
    assert ops["while.3"] == pytest.approx(3.0)
    mods = [n for n, _ in w.top_modules()]
    assert mods[:2] == ["insert_and_maintain", "_slide_phase_a"]


def test_barrier_order():
    w = Window(small_trace(), open_delay=1.0, close_delay=11.0)
    assert w.barriers_out_of_order("_barrier") == 0
    bad = small_trace() + [ev("jit__barrier(7)", 17, 17.1,
                              line="XLA Modules")]
    assert Window(bad, 1.0, 11.0).barriers_out_of_order("_barrier") == 1


def test_names():
    assert module_name("jit_insert_and_maintain(5512)") == \
        "insert_and_maintain"
    assert module_name("jit__phase_b_checked") == "_phase_b_checked"
    assert op_name("%sort.7 = (s32[2]) sort(...)") == "sort.7"


def test_window_requires_one_span_and_a_device():
    with pytest.raises(ValueError):
        Window([ev("while.1", 0, 1)])
    with pytest.raises(ValueError):
        Window([host("window", 0, 1)])


def _recorded():
    import gzip
    import json
    from pathlib import Path

    path = Path(__file__).parent / "data" / "backlog_window_trace.json.gz"
    with gzip.open(path, "rt") as f:
        fix = json.load(f)
    events = [Event(p, ln, n, a * 1e-9, b * 1e-9)
              for p, ln, n, a, b in fix["events"]]
    return fix, events


def test_recorded_chip_trace():
    """A window of ``grab4.backlog`` recorded on a TPU v5 lite: the union
    and the module sums against a brute-force count on a fine grid."""
    import numpy as np

    fix, events = _recorded()
    w = Window(events, fix["open_delay_s"], fix["close_delay_s"])
    assert w.window_s == pytest.approx(fix["expect"]["window_s"], abs=1e-9)
    grid = np.linspace(w.t0, w.t1, 400_001)[:-1] + w.window_s / 800_000
    covered = np.zeros(grid.shape, bool)
    for e in events:
        if e.plane == DEV and e.line == "XLA Ops":
            covered |= (grid >= e.start) & (grid < e.end)
    assert w.busy_s == pytest.approx(covered.mean() * w.window_s, abs=2e-3)
    assert w.busy_s == pytest.approx(fix["expect"]["busy_s"], abs=1e-9)
    mods = w.module_seconds(["insert_and_maintain"])
    assert mods == pytest.approx(fix["expect"]["insert_and_maintain_s"])
    assert 0 < mods <= w.busy_s <= w.window_s
    gaps = sum(b - a for a, b in w.gaps())
    assert gaps + w.busy_s == pytest.approx(w.window_s, abs=1e-9)
    assert w.barriers_out_of_order("_barrier") == 0
