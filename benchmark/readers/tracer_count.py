"""How often one span name of the program's own tracer occurred in the
window. A name that never occurred reads 0; None only when there is no
tracer to read. spec: {"span": <span name>} (`name` is the metric's own)."""

from benchmark.readers._tracer import window_totals


def read(reading, spec):
    totals = window_totals(reading)
    if not totals:
        return None
    return totals.get(spec["span"], (0, 0.0))[0]
