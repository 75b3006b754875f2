"""Device time of the peel rounds' gathers, per window tick, backlog
cell: the ops under the ``peel_gather`` scope (``core/peel.py``,
``_round_step``: the [V]-by-[E] gathers of the peel mask and the
edge-liveness update), as the union of their intervals.  Moves
``edges_per_s``.

Reads a :class:`bench.program_trace.ProgramWindow`.  A trace with no
``spade.*`` span comes from a program that marks nothing, and gives
nothing; a marked program whose window holds no op under the scope is an
error: the scope was renamed or lost, and the metric would otherwise
read 0 unseen."""

SCOPE = "peel_gather"


def read(run):
    if run.trace is None or not run.trace.has_program_spans:
        return None
    s = run.trace.scope_seconds(SCOPE)
    if s <= 0 or not run.window_ticks:
        raise LookupError(f"no op under the scope {SCOPE!r} in the window")
    return 1e3 * s / run.window_ticks
