"""Device idle share of the window, backlog cell: 1 - (union of the
intervals in which an op ran on the device) / window.  Moves
``edges_per_s``: idle time is time the edge buffer is not being peeled."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * run.trace.idle_s / run.trace.window_s
