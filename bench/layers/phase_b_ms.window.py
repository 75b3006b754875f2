"""Device time of the workset engine's phase B, per window tick,
windowed cell.

Modules ``_phase_b`` and ``_phase_b_checked`` (``core/incremental.py``):
the warm re-peel, over the gathered workset or, where the suffix outgrows
the largest bucket, over the whole edge buffer down the staged rounds'
ladder, and the merge into the state.  Moves ``edges_per_s``.  A traced
window in which neither module ran is an error: a program's name
changed, and the metric would otherwise vanish unseen."""

MODULES = ("_phase_b", "_phase_b_checked")


def read(run):
    if run.trace is None or not run.window_ticks:
        return None
    s = run.trace.module_seconds(MODULES)
    if s <= 0:
        raise LookupError(f"no module of {MODULES} ran in the window")
    return 1e3 * s / run.window_ticks
