"""Device idle time per window tick under the served loop's own
``spade.*`` spans and outside the harness's ``source_wait`` spans,
open-loop cell: the device waits on a named step of the loop (reading,
batch preparation and transfers, weighting, dispatch), not on arrivals.
The inside view of the layer ``host_gap_ms.open`` sees from outside.
Moves ``latency_p95_ms``.

Reads a :class:`bench.program_trace.ProgramWindow`.  A trace with no
``spade.*`` span comes from a program that marks nothing, and gives
nothing; a marked program with no ``spade.tick`` span in the window is
an error: the span was renamed or lost, and the metric would otherwise
read 0 unseen."""


def read(run):
    if run.trace is None or not run.trace.has_program_spans:
        return None
    if not run.trace.program_spans.get("spade.tick") or not run.window_ticks:
        raise LookupError("no spade.tick span in the window")
    return 1e3 * run.trace.idle_under("spade.", "source_wait") \
        / run.window_ticks
