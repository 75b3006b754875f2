"""Share of the edge slots full-buffer peel rounds would read that the
fused engine's rounds streamed, over the window's ticks, backlog cell:
sum(round_slots) / (rounds x edge_slots).

Counters ``DeviceServiceReport.round_slots`` (``[n_ticks, max_rounds]``,
the edge slots each round streamed, 0 where no round ran, counted by the
tick program itself, ``core/peel.py``) and ``edge_slots`` (the edge
buffer's capacity).  Moves ``edges_per_s``.

A report without the field comes from a program that keeps no such
counter, and gives nothing.  A report that has it but holds no counts
for the window's ticks is an error: the metric would otherwise vanish
unseen."""

import numpy as np


def read(run):
    if not hasattr(run.report, "round_slots"):
        return None
    rs, slots = run.report.round_slots, run.report.edge_slots
    if rs is None or not slots or len(rs) < run.window_ticks \
            or not run.window_ticks:
        raise LookupError("no round counters for the window's ticks")
    rows = np.asarray(rs, np.float64)[-run.window_ticks:]
    return 100.0 * rows.sum() / (rows.size * slots)
