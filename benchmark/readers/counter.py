"""One counter of the entry, as it is. spec: {"counter": <counter>}."""


def read(reading, spec):
    return reading.counters.get(spec["counter"])
