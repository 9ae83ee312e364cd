"""Milliseconds in one of the benchmark's own spans, over a count.
spec: {"span": <span name>, "per": <counter name>}. Taken over the window."""


def read(reading, spec):
    t0, t1 = reading.window
    _, seconds = reading.rec.span_seconds(spec["span"], t0, t1 + 1e-9)
    per = reading.counters.get(spec["per"], 0)
    if not per:
        return None
    return seconds * 1e3 / per
