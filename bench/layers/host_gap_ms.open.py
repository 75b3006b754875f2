"""Device idle time per window tick that falls outside the harness's
``source_wait`` spans: the device waits on the host's own work (batch
preparation, dispatch, transfers, the serving loop), not on arrivals.
Moves ``latency_p95_ms``: it sits on every tick's critical path."""


def read(run):
    if run.trace is None or not run.window_ticks:
        return None
    return 1e3 * run.trace.idle_outside("source_wait") / run.window_ticks
