"""Share of the edge slots the fused engine's peel rounds stream that
hold no live edge of the restricted set, over the window's ticks,
backlog cell: 1 - sum(round_edges) / (rounds x edge_slots).

Counters ``DeviceServiceReport.round_edges`` (``[n_ticks, max_rounds]``,
the restricted set's live edges at the start of each round, counted by
the tick program itself, ``core/peel.py``) and ``edge_slots`` (the edge
buffer's capacity, which every round reads whole).  Moves
``edges_per_s``.

A report without the fields comes from a program that keeps no round
counters, and gives nothing.  A report that has them but holds no counts
for the window's ticks is an error: the metric would otherwise vanish
unseen."""

import numpy as np


def read(run):
    if not hasattr(run.report, "round_edges"):
        return None
    re, slots = run.report.round_edges, run.report.edge_slots
    if re is None or not slots or len(re) < run.window_ticks \
            or not run.window_ticks:
        raise LookupError("no round counters for the window's ticks")
    rows = np.asarray(re, np.float64)[-run.window_ticks:]
    return 100.0 * (1.0 - rows.sum() / (rows.size * slots))
