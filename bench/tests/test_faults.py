"""``correct`` comes out false when the timed path is broken underneath.

Each fault is planted in the program's tick functions as the served loop
calls them (``repro.serve.spade_service``), and the rest of a run goes
as the benchmark drives it, without its look for a chip: a tick that
returns its state unchanged, half of each batch left out, and an answer
altered where it is produced (the best density, one float32 step up).
Every tiny cell takes every fault: the fused engine backlogged
(``tiny.backlog``) and open loop (``tiny.open``), and the predictive
workset engine under a sliding window (``tiny-window.open``).  The
exchange between chips is not a fault these one-chip cells can have.

The control: the reference computed in bfloat16, the precision below the
float32 the configurations state, put in the program's place.
"""

from __future__ import annotations

import dataclasses
import time

import jax.numpy as jnp
import pytest

import repro.serve.spade_service as served
from bench.harness import run_cell
from bench.spec import Bench
from repro.core.incremental import WorksetTickInfo

CELLS = {"tiny.backlog": ("insert_and_maintain",),
         "tiny.open": ("insert_and_maintain",),
         "tiny-window.open": ("insert_and_maintain_predictive",
                              "slide_and_maintain_predictive")}


def _unchanged(orig):
    def tick(state, *args, **kw):
        if "predictor" in kw:
            return state, WorksetTickInfo(0, 0, 64, 64, False, True, False)
        return state
    return tick


def _half_batch(orig):
    def tick(state, *args, **kw):
        args = list(args)
        valid = args[-1]  # (..., src, dst, c, valid)
        args[-1] = valid & (jnp.arange(valid.shape[0]) < valid.shape[0] // 2)
        return orig(state, *args, **kw)
    return tick


def _altered(orig):
    def tick(state, *args, **kw):
        out = orig(state, *args, **kw)
        st, info = out if isinstance(out, tuple) else (out, None)
        st = dataclasses.replace(
            st, best_g=jnp.nextafter(st.best_g, jnp.float32(jnp.inf)))
        return st if info is None else (st, info)
    return tick


FAULTS = {"unchanged": _unchanged, "half_batch": _half_batch,
          "altered": _altered}


def _run(root, cell, **kw):
    seconds = 0.5 if cell == "tiny.backlog" else 1.0
    return run_cell(Bench(root), cell, 2**31 + 77, seconds, False,
                    time.perf_counter(), require_tpu=False, **kw)


@pytest.mark.parametrize("cell", sorted(CELLS))
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_not_correct(tiny_root, monkeypatch, cell, fault):
    for name in CELLS[cell]:
        monkeypatch.setattr(served, name, FAULTS[fault](getattr(served, name)))
    out = _run(tiny_root, cell)
    assert out["correct"] is False
    assert any(c["value"] > c["limit"] for c in out["checks"].values())


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_control_in_lower_precision_is_not_correct(tiny_root, cell):
    out = _run(tiny_root, cell, control="bfloat16")
    assert out["correct"] is False
    assert out["checks"]["final_g"]["value"] > 0


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_sound_run_is_correct(tiny_root, cell):
    out = _run(tiny_root, cell)
    assert out["correct"] is True
    assert all(c["value"] == 0 for c in out["checks"].values())
