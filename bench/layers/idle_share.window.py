"""Device idle share of the window, windowed cell: 1 - (union of the
intervals in which an op ran on the device) / window.  Moves
``edges_per_s``: with every window edge due at once, idle time is the
host's own work between the workset engine's two programs and between
ticks (the predictor's count read, batch preparation, dispatch)."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * run.trace.idle_s / run.trace.window_s
