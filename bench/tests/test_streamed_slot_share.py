"""The reader of the staged rounds' slot counter, on made reports."""

from __future__ import annotations

from types import SimpleNamespace

import numpy as np
import pytest

from bench.harness import RunView
from bench.spec import Bench

from conftest import ROOT


def _read(report, window_ticks=2):
    return Bench(ROOT).layer_reader("streamed_slot_share").read(
        RunView(report=report, window_ticks=window_ticks))


def _report(rs, slots=100):
    return SimpleNamespace(round_slots=None if rs is None else np.asarray(rs),
                           edge_slots=slots)


def test_streamed_slot_share_on_a_made_run():
    # three ticks of four rounds; the window holds the last two
    rs = [[100, 100, 100, 100], [100, 25, 0, 0], [100, 25, 25, 0]]
    assert _read(_report(rs)) == pytest.approx(100 * 275 / 800)
    # a program without the counter gives nothing
    assert _read(SimpleNamespace(round_edges=np.zeros((3, 4)),
                                 edge_slots=100)) is None


@pytest.mark.parametrize("report, window_ticks", [
    (_report(None, None), 2),               # an engine that kept none
    (_report([[100, 25, 0, 0]]), 2),        # fewer rows than the window
    (_report([[100, 25, 0, 0]] * 3, 0), 2),  # no capacity
    (_report([[100, 25, 0, 0]] * 3), 0),    # an empty window
])
def test_streamed_slot_share_without_counts_is_an_error(report,
                                                        window_ticks):
    with pytest.raises(LookupError):
        _read(report, window_ticks)
