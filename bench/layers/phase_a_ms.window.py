"""Device time of the workset engine's phase A, per window tick,
windowed cell.

Modules ``_insert_phase_a`` and ``_slide_phase_a``
(``core/incremental.py``): the affected-suffix bookkeeping, the expiry of
the oldest tick (slide ticks; scope ``slide_expire``), the append
(``slide_append``) and the count of the suffix that picks phase B's path
(``workset_count``).  Moves ``edges_per_s``.  A traced window in which
neither module ran is an error: a program's name changed, and the metric
would otherwise vanish unseen."""

MODULES = ("_insert_phase_a", "_slide_phase_a")


def read(run):
    if run.trace is None or not run.window_ticks:
        return None
    s = run.trace.module_seconds(MODULES)
    if s <= 0:
        raise LookupError(f"no module of {MODULES} ran in the window")
    return 1e3 * s / run.window_ticks
