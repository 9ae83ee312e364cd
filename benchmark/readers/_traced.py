"""Shared by the readers of the traced slice."""

import re


def program_seconds(reading, spec):
    """Device seconds in the traced slice of the programs whose names match
    spec["programs"] (a regular expression on the jitted function's name)."""
    tr = reading.trace
    if not tr:
        return None
    want = re.compile(spec["programs"])
    seconds = sum(s for name, s in tr["program_s"].items() if want.search(name))
    return seconds or None


def spans_in_slice(reading, spec):
    """How many of spec["per_span"] spans started inside the traced slice."""
    began, ended = reading.traced
    if began is None or ended is None:
        return 0
    count, _ = reading.rec.span_seconds(spec["per_span"], began, ended)
    return count
