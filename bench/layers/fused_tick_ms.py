"""Device time of the fused engine's tick program, per window tick,
backlog cell.

Module ``insert_and_maintain`` (``core/incremental.py``): append, the
affected-suffix bookkeeping, the warm bulk re-peel over the full edge
buffer and the merge, in one program.  Moves ``edges_per_s``.  A traced
window in which the module never ran is an error: the program's name
changed, and the metric would otherwise vanish unseen."""

MODULES = ("insert_and_maintain",)


def read(run):
    if run.trace is None or not run.window_ticks:
        return None
    s = run.trace.module_seconds(MODULES)
    if s <= 0:
        raise LookupError(f"no module of {MODULES} ran in the window")
    return 1e3 * s / run.window_ticks
