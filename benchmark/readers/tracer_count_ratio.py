"""Counts of the program's own tracer, one sum over another, times `scale`:
scale * (summed counts of `num`) / (summed counts of `den`), over the window
and from the program's totals. None when there is no tracer to read or the
divisor's names never occurred.
spec: {"num": [<span name>, ...], "den": [<span name>, ...], "scale": <number>}."""

from benchmark.readers._tracer import window_totals


def read(reading, spec):
    totals = window_totals(reading)
    if not totals:
        return None

    def count(names):
        return sum(totals.get(name, (0, 0.0))[0] for name in names)

    den = count(spec["den"])
    if not den:
        return None
    return spec.get("scale", 1) * count(spec["num"]) / den
