"""Milliseconds in spans of the program's own tracer, over the count of one
of its span names: 1e3 * (seconds of `spans` - seconds of `minus`) / count
of `per`, all three over the window and from the program's totals, so the
divisor is counted where the time is.
spec: {"spans": [<span name>, ...], "minus": [<span name>, ...], "per": <span name>}."""

from benchmark.readers._tracer import window_totals


def read(reading, spec):
    totals = window_totals(reading)
    if not totals:
        return None
    count = totals.get(spec["per"], (0, 0.0))[0]
    if not count:
        return None

    def seconds(names):
        return sum(totals.get(name, (0, 0.0))[1] for name in names)

    return 1e3 * (seconds(spec["spans"]) - seconds(spec.get("minus", []))) / count
