"""One counter of the entry over another, times `scale`.
spec: {"num": <counter>, "den": <counter>, "scale": <number>}."""


def read(reading, spec):
    den = reading.counters.get(spec["den"], 0)
    if not den or spec["num"] not in reading.counters:
        return None
    return reading.counters[spec["num"]] * spec.get("scale", 1) / den
