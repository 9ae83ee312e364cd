"""Reduction of a `jax.profiler` trace to what the per-layer readers use.

Two steps, so that the arithmetic can be checked on a small recorded
sample (`benchmark/testdata/`) without the profiler:

1. `reduce_file(path)` reads an `.xplane.pb` with `jax.profiler.ProfileData`
   and keeps, per device plane, the events of the lines that hold XLA
   operations and whole XLA programs, and from the host plane the
   benchmark's own `bench.*` annotations. Times are nanoseconds on the
   trace's clock. The result is plain JSON.
2. `summarise(reduced)` computes from that: the traced window (the
   `bench.traced` annotation), the seconds in which an operation ran on
   each device (union of the operation intervals, clipped to the window),
   seconds per program name, the operations that took most time, and the
   idle time split by what the host was doing.

On a TPU the device planes are `/device:TPU:<n>`; the line `XLA Ops` has one
event per operation as it ran and `XLA Modules` one per launched program
(`jit_<name>(<fingerprint>)`). An operation that contains others (a while
loop and its body) appears with them, which is why busy time is a union of
intervals and never a sum.
"""

from __future__ import annotations

import bisect
import re
from typing import Dict, Iterable, List, Optional, Tuple

from benchmark.recorder import SPAN_PREFIX

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TRACED = SPAN_PREFIX + "traced"
NS = 1e-9

Interval = Tuple[float, float]


def reduce_file(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out: dict = {"devices": {}, "host_spans": [], "lines_seen": {}}
    for plane in data.planes:
        seen = out["lines_seen"].setdefault(plane.name, {})
        is_device = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            events = [(e.name, float(e.start_ns), float(e.duration_ns))
                      for e in line.events]
            seen[line.name] = len(events)
            if is_device and line.name in (OPS_LINE, MODULES_LINE):
                dev = out["devices"].setdefault(
                    plane.name, {"ops": [], "modules": []})
                if line.name == OPS_LINE:  # named by a whole HLO line each
                    dev["ops"].extend([op_name(n), s, d] for n, s, d in events)
                else:
                    dev["modules"].extend([n, s, d] for n, s, d in events)
            elif not is_device:
                out["host_spans"].extend(
                    [n, s, d] for n, s, d in events if n.startswith(SPAN_PREFIX))
    return out


def excerpt(reduced: dict, seconds: float) -> dict:
    """The first `seconds` of the traced window, for keeping as test data."""
    lo, end = window_of(reduced)
    launched = [s for dev in reduced["devices"].values()
                for _, s, _ in dev["modules"] if s >= lo]
    lo = min(launched, default=lo)  # from the first program launched in it
    hi = min(lo + seconds / NS, end)

    def cut(events):
        return [[n, max(s, lo), min(s + d, hi) - max(s, lo)]
                for n, s, d in events if s < hi and s + d > lo]

    out = dict(reduced)
    out["devices"] = {p: {k: cut(v) for k, v in dev.items()}
                      for p, dev in reduced["devices"].items()}
    out["host_spans"] = [[TRACED, lo, hi - lo]] + cut(
        [e for e in reduced["host_spans"] if e[0] != TRACED])
    return out


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Union of [start, end) intervals as a sorted list of disjoint ones."""
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    """The complement of disjoint sorted `busy` inside [lo, hi)."""
    out, at = [], lo
    for s, e in busy:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def op_name(event_name: str) -> str:
    """An operation's event carries its whole HLO line; keep the result's
    name: `%fusion.408 = s32[...] fusion(...)` -> `fusion.408`."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def program_name(event_name: str) -> str:
    """`jit_multi_step(1234)` -> `multi_step`."""
    name = event_name.split("(", 1)[0]
    return name[4:] if name.startswith("jit_") else name


def window_of(reduced: dict) -> Optional[Interval]:
    spans = [(s, s + d) for n, s, d in reduced["host_spans"] if n == TRACED]
    if spans:
        return min(s for s, _ in spans), max(e for _, e in spans)
    every = [(s, s + d) for dev in reduced["devices"].values()
             for n, s, d in dev["ops"] + dev["modules"]]
    if not every:
        return None
    return min(s for s, _ in every), max(e for _, e in every)


def summarise(reduced: dict, top: int = 10) -> Optional[dict]:
    """None when the trace holds no device operation."""
    window = window_of(reduced)
    devices = reduced["devices"]
    if window is None or not devices:
        return None
    lo, hi = window
    busy_s: Dict[str, float] = {}
    op_s: Dict[str, float] = {}
    program_s: Dict[str, float] = {}
    program_calls: Dict[str, int] = {}
    idle_by: Dict[str, float] = {}
    host = sorted(((n[len(SPAN_PREFIX):], s, s + d)
                   for n, s, d in reduced["host_spans"] if n != TRACED),
                  key=lambda span: span[1])
    host_starts = [s for _, s, _ in host]
    for plane, dev in devices.items():
        # where a plane has no operation line, a program's span stands in
        source = dev["ops"] or dev["modules"]
        busy = merge(clip(((s, s + d) for _, s, d in source), lo, hi))
        busy_s[plane] = sum(e - s for s, e in busy) * NS
        for n, s, d in dev["ops"]:
            for cs, ce in clip([(s, s + d)], lo, hi):
                k = op_name(n)
                op_s[k] = op_s.get(k, 0.0) + (ce - cs) * NS
        for n, s, d in dev["modules"]:
            for cs, ce in clip([(s, s + d)], lo, hi):
                p = program_name(n)
                program_s[p] = program_s.get(p, 0.0) + (ce - cs) * NS
                program_calls[p] = program_calls.get(p, 0) + 1
        for gs, ge in gaps(busy, lo, hi):
            covered = 0.0
            # the spans are one thread's, so disjoint and sorted by start
            k = max(bisect.bisect_right(host_starts, gs) - 1, 0)
            while k < len(host) and host[k][1] < ge:
                name, s, e = host[k]
                part = min(e, ge) - max(s, gs)
                if part > 0:
                    idle_by[name] = idle_by.get(name, 0.0) + part * NS
                    covered += part
                k += 1
            rest = (ge - gs) - covered
            if rest > 0:
                idle_by["between_spans"] = (
                    idle_by.get("between_spans", 0.0) + rest * NS)
    if not any(busy_s.values()):
        return None
    n_dev = len(devices)

    def ranked(table: Dict[str, float], scale: float = 1.0):
        rows = sorted(table.items(), key=lambda kv: -kv[1])[:top]
        return [[k, v * scale] for k, v in rows]

    return {
        "window_s": (hi - lo) * NS,
        "busy_s": sum(busy_s.values()) / n_dev,  # averaged over the chips used
        "busy_s_by_device": busy_s,
        "program_s": program_s,
        "program_calls": program_calls,
        "device_ops": ranked(op_s or program_s),
        "idle_gaps": ranked(idle_by, 1.0 / n_dev),
    }
