"""Share of the edge slots full-buffer peel rounds would read that the
workset engine's rounds streamed, over the window's ticks, windowed cell:
sum(round_slots) / (rounds x edge_slots).

Counters ``DeviceServiceReport.round_slots`` (``[n_ticks, max_rounds]``:
the slots each round of phase B streamed, a size of the staged rounds'
ladder on a fallback tick and of the edge bucket's on a workset tick, 0
where no round ran) and ``edge_slots`` (the edge buffer's capacity).
Moves ``edges_per_s``.

A report without the field, or whose engine left it empty, comes from a
program that counts nothing there, and gives nothing.  A report that
holds counts, but not for each of the window's ticks, is an error: the
metric would otherwise vanish unseen."""

import numpy as np


def read(run):
    rs = getattr(run.report, "round_slots", None)
    if rs is None:
        return None
    slots = run.report.edge_slots
    if not slots or len(rs) < run.window_ticks or not run.window_ticks:
        raise LookupError("no round counters for the window's ticks")
    rows = np.asarray(rs, np.float64)[-run.window_ticks:]
    return 100.0 * rows.sum() / (rows.size * slots)
