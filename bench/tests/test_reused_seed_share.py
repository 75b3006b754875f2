"""The reader of the warm seed's reuse counter, on made reports."""

from __future__ import annotations

from types import SimpleNamespace

import numpy as np
import pytest

from bench.harness import RunView
from bench.spec import Bench

from conftest import ROOT


def _read(report, window_ticks=4):
    return Bench(ROOT).layer_reader("reused_seed_share").read(
        RunView(report=report, window_ticks=window_ticks))


def _report(sr):
    return SimpleNamespace(seed_reused=None if sr is None else np.asarray(sr))


def test_reused_seed_share_on_a_made_run():
    # six ticks; the window holds the last four, three of them reused
    assert _read(_report([0, 0, 1, 0, 1, 1])) == pytest.approx(75.0)
    assert _read(_report([1] * 6)) == 100.0


def test_a_program_without_the_counter_gives_nothing():
    assert _read(SimpleNamespace(round_slots=np.zeros((6, 4)))) is None


@pytest.mark.parametrize("report, window_ticks", [
    (_report(None), 4),           # an engine that kept none
    (_report([1, 1]), 4),         # fewer rows than the window
    (_report([1] * 6), 0),        # an empty window
])
def test_reused_seed_share_without_counts_is_an_error(report, window_ticks):
    with pytest.raises(LookupError):
        _read(report, window_ticks)
