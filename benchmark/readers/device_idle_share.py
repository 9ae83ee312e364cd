"""1 - (seconds in which an operation ran on the device) / (traced
window), in percent, from the profiler's trace; averaged over the chips
used."""


def read(reading, spec):
    tr = reading.trace
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
