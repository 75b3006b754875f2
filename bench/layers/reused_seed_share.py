"""Share of the window's ticks whose warm re-peel read its seed from the
maintained weights, full-buffer engines (fused tick, phase B's fallback):
100 x mean(seed_reused).

Counter ``DeviceServiceReport.seed_reused`` (``[n_ticks]``: 1 where the
tick's affected suffix held every live vertex, so the seed skipped the
two [E] gathers and the two [E]-to-[V] segment sums, 0 elsewhere; counted
by the tick program itself, ``core/peel.py``).  Moves ``edges_per_s``.

A report without the field comes from a program that keeps no such
counter, and gives nothing.  A report that has it but holds no counts
for the window's ticks is an error: the metric would otherwise vanish
unseen."""

import numpy as np


def read(run):
    if not hasattr(run.report, "seed_reused"):
        return None
    sr = run.report.seed_reused
    if sr is None or len(sr) < run.window_ticks or not run.window_ticks:
        raise LookupError("no seed counters for the window's ticks")
    return 100.0 * float(np.mean(np.asarray(sr, np.float64)
                                 [-run.window_ticks:]))
