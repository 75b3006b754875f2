"""Device idle share of the window, open-loop cell: 1 - (union of the
intervals in which an op ran on the device) / window.  Moves
``latency_p95_ms``: below the knee most idle time is waiting for a tick's
arrivals, and what is left is the host's own work between ticks."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * run.trace.idle_s / run.trace.window_s
