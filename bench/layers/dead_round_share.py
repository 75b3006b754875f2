"""Share of the fused engine's peel rounds that started with an empty
restricted set, over the window's ticks, backlog cell.

Counter ``DeviceServiceReport.round_vertices`` (``[n_ticks,
max_rounds]``, counted by the tick program itself, ``core/peel.py``):
the restricted set's active vertices at the start of each round.  A
round that starts empty peels nothing, yet streams the whole edge
buffer.  Moves ``edges_per_s``.

A report without the field comes from a program that keeps no round
counters, and gives nothing.  A report that has it but holds no counts
for the window's ticks is an error: the metric would otherwise vanish
unseen."""

import numpy as np


def read(run):
    if not hasattr(run.report, "round_vertices"):
        return None
    rv = run.report.round_vertices
    if rv is None or len(rv) < run.window_ticks or not run.window_ticks:
        raise LookupError("no round counters for the window's ticks")
    rows = np.asarray(rv)[-run.window_ticks:]
    return 100.0 * float((rows == 0).sum()) / rows.size
