"""Device milliseconds of the named programs in the traced slice, over the
spans of one kind that started in it.
spec: {"programs": <regex on program names>, "per_span": <span name>}."""

from benchmark.readers._traced import program_seconds, spans_in_slice


def read(reading, spec):
    seconds = program_seconds(reading, spec)
    count = spans_in_slice(reading, spec)
    if seconds is None or not count:
        return None
    return seconds * 1e3 / count
