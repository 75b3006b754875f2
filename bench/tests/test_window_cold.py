"""The windowed cold cell's readers, on made runs, and a small backlogged
window cell through the harness beside ``tiny-window.open`` (CPU)."""

from __future__ import annotations

import dataclasses
import json
import time
from types import SimpleNamespace

import numpy as np
import pytest

from bench import harness
from bench.harness import RunView, run_cell
from bench.spec import Bench
from bench.tracing import Event, Window

from conftest import ROOT, add_cell, tiny_configs

DEV = "/device:TPU:0"
CHECKS = {"final_g", "live_edges", "expired", "ticks", "detected", "benign",
          "suffix_edges", "tick_paths"}


def _reader(name):
    return Bench(ROOT).layer_reader(name)


def _report(rs, slots=100):
    return SimpleNamespace(round_slots=None if rs is None else np.asarray(rs),
                           edge_slots=slots)


def test_streamed_slot_share_window_on_a_made_run():
    # three ticks of four rounds; the window holds the last two, a
    # fallback tick down the ladder and a workset tick on a 16-slot bucket
    rs = [[100, 100, 100, 100], [100, 25, 0, 0], [16, 16, 0, 0]]
    read = _reader("streamed_slot_share.window").read
    assert read(RunView(report=_report(rs), window_ticks=2)) \
        == pytest.approx(100 * 157 / 800)
    # an engine that counts nothing (the field absent or left empty)
    # gives nothing
    assert read(RunView(report=_report(None, None), window_ticks=2)) is None
    assert read(RunView(report=SimpleNamespace(edge_slots=None),
                        window_ticks=2)) is None


@pytest.mark.parametrize("report, window_ticks", [
    (_report([[100, 25, 0, 0]]), 2),        # fewer rows than the window
    (_report([[100, 25, 0, 0]] * 3, 0), 2),  # no capacity
    (_report([[100, 25, 0, 0]] * 3), 0),    # an empty window
])
def test_streamed_slot_share_window_without_counts_is_an_error(
        report, window_ticks):
    with pytest.raises(LookupError):
        _reader("streamed_slot_share.window").read(
            RunView(report=report, window_ticks=window_ticks))


def _mod(name, a, b):
    return Event(DEV, "XLA Modules", name, float(a), float(b))


def _op(a, b):
    return Event(DEV, "XLA Ops", "fusion.1", float(a), float(b))


def _window(modules):
    """Window [10, 20]: the given modules with one op under each, and a
    set-up module before the window."""
    events = [Event("/host:CPU", "python", "window", 10.0, 20.0),
              _mod("jit_bulk_peel(1)", 2, 9), _op(2, 9)]
    for name, a, b in modules:
        events += [_mod(name, a, b), _op(a, b)]
    return Window(events, open_delay=0.0, close_delay=10.0)


TWO_TICKS = [("jit__insert_phase_a(4)", 10.0, 10.5),
             ("jit__phase_b(5)", 10.5, 13.5),
             ("jit__slide_phase_a(6)", 14.0, 14.25),
             ("jit__phase_b_checked(7)", 14.25, 17.25)]


def test_phase_and_idle_readers_on_a_made_trace():
    view = RunView(report=None, window_ticks=2, trace=_window(TWO_TICKS))
    assert _reader("phase_a_ms.window").read(view) == pytest.approx(375.0)
    assert _reader("phase_b_ms.window").read(view) == pytest.approx(3000.0)
    # busy 3.5 + 3.25 of the 10 s window
    assert _reader("idle_share.window").read(view) == pytest.approx(32.5)
    untraced = RunView(report=None, window_ticks=2)
    for name in ("phase_a_ms.window", "phase_b_ms.window",
                 "idle_share.window"):
        assert _reader(name).read(untraced) is None


@pytest.mark.parametrize("name, kept", [
    ("phase_a_ms.window", [m for m in TWO_TICKS if "phase_b" in m[0]]),
    ("phase_b_ms.window", [m for m in TWO_TICKS if "phase_a" in m[0]]),
])
def test_phase_readers_without_their_modules_are_an_error(name, kept):
    view = RunView(report=None, window_ticks=2, trace=_window(kept))
    with pytest.raises(LookupError):
        _reader(name).read(view)


def test_tiny_windowed_cold_cell(tiny_root, monkeypatch):
    """The cell's mix on a small window, backlogged: every check at its 0
    limit, the edge rate reported, and the slot reader reads the measured
    run's counters."""
    cold = json.loads((ROOT / "bench/traffic/cold.json").read_text())
    add_cell(tiny_root, tiny_configs()["tiny-window"],
             dict(cold, name="tiny-cold"), "tiny-window.cold")
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "grab4-window.cold" in m.get("workloads", ()):
            m["workloads"].append("tiny-window.cold")
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(spec))
    bench = Bench(tiny_root)
    cell = bench.cell("tiny-window.cold")
    assert {m.name for m in cell.per_layer} == {
        "phase_a_ms.window", "phase_b_ms.window", "idle_share.window",
        "streamed_slot_share.window"}

    views = []

    @dataclasses.dataclass
    class Recorded(RunView):
        def __post_init__(self):
            views.append(self)

    monkeypatch.setattr(harness, "RunView", Recorded)
    out = run_cell(bench, "tiny-window.cold", 2**31 + 5, 0.5, False,
                   time.perf_counter(), require_tpu=False)
    assert out["correct"] is True
    assert set(out["checks"]) == CHECKS
    assert set(out["metrics"]) == {"edges_per_s", "setup_s"}
    (view,) = views
    window = cell.config["engine"]["window_ticks"]
    assert view.window_ticks > window
    assert view.report.n_expired_edges > 0
    share = _reader("streamed_slot_share.window").read(view)
    assert 0 < share <= 100
