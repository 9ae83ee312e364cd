"""Shared by the readers of the program's own span tracer
(`babble_tpu/obs/trace.py`): its cumulative (count, seconds) per span name,
taken between the first and the last checkpoint inside the window. The
program checkpoints on entry to and return from every `Core.run_consensus`,
so a window holds whole consensus calls and the inserts between them, and
count and time are read at the same boundary."""


def window_totals(reading):
    """{span name: (count, seconds)} over `reading.window` from the one live
    tracer that has checkpoints there; None when the program has no such
    tracer (a program from before the span tree), none of its tracers has
    two checkpoints in the window, or more than one has."""
    try:
        from babble_tpu.obs.trace import live_tracers
    except ImportError:
        return None
    t0, t1 = reading.window
    found = [totals for totals in
             (tracer.totals_between(t0, t1) for tracer in live_tracers())
             if totals]
    return found[0] if len(found) == 1 else None
