"""Least time at the chip's HBM bandwidth for the bytes the algorithm
needs, over the device time the named programs took in the traced slice,
in percent. The bytes come from benchmark/rooflines/<spec["roofline"]>.py
and the bandwidth from benchmark/peaks.json.
spec: {"roofline": <module>, "programs": <regex>, "per_span": <span name>}."""

from benchmark.readers._traced import program_seconds, spans_in_slice


def read(reading, spec):
    seconds = program_seconds(reading, spec)
    syncs_traced = spans_in_slice(reading, spec)
    c = reading.counters
    if seconds is None or not syncs_traced or not c.get("syncs") or not reading.peaks:
        return None
    need = reading.roofline(spec["roofline"]).bytes_per_sync(
        c["validators"], c["sync_events"], c["rounds"] / c["syncs"])
    least = syncs_traced * need / reading.peaks["hbm_bytes_per_s"]
    return 100.0 * least / seconds
