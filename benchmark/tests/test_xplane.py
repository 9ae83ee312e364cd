"""The trace reduction on recorded samples: busy and idle time and the sums
per program come out as counted by hand.

hand_trace.json (nanoseconds). Window = bench.traced = [500, 12000), 11,500.
Operations, clipped to the window: while.2 [1000, 5000) holds fusion.7
[1200, 2000) and fusion.8 [2500, 3500); copy.3 [6000, 6500); fusion.7
[9000, 11000); copy.9 [11800, 12200) -> [11800, 12000).
Union: 4000 + 500 + 2000 + 200 = 6,700 busy, so 4,800 idle: 41.739...%.
Per operation: while.2 4000, fusion.7 800 + 2000 = 2800, fusion.8 1000,
copy.3 500, copy.9 200.
Per program: multi_step [1000, 6500) 5500; _pack_results 2000;
convert_element_type 200 (clipped).
Idle gaps and the host span over each: [500, 1000) insert 500;
[5000, 6000) run_consensus 1000; [6500, 9000) run_consensus [6500, 7000)
500, insert [7000, 8500) 1500, run_consensus [8500, 9000) 500;
[11000, 11800) run_consensus [11000, 11500) 500, no span 300.
So run_consensus 2500, insert 2000, between_spans 300: 4,800 in all.
"""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import xplane  # noqa: E402

DATA = os.path.join(ROOT, "benchmark", "testdata")
NS = 1e-9


def load(name):
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


def test_hand_trace_sums():
    s = xplane.summarise(load("hand_trace.json"))
    assert s["window_s"] == pytest.approx(11500 * NS)
    assert s["busy_s"] == pytest.approx(6700 * NS)
    assert dict(s["device_ops"]) == pytest.approx({
        "while.2": 4000 * NS, "fusion.7": 2800 * NS, "fusion.8": 1000 * NS,
        "copy.3": 500 * NS, "copy.9": 200 * NS})
    assert [k for k, _ in s["device_ops"]][:2] == ["while.2", "fusion.7"]
    assert s["program_s"] == pytest.approx({
        "multi_step": 5500 * NS, "_pack_results": 2000 * NS,
        "convert_element_type": 200 * NS})
    assert s["program_calls"] == {"multi_step": 1, "_pack_results": 1,
                                  "convert_element_type": 1}
    assert dict(s["idle_gaps"]) == pytest.approx({
        "run_consensus": 2500 * NS, "insert": 2000 * NS,
        "between_spans": 300 * NS})
    idle = sum(v for _, v in s["idle_gaps"])
    assert idle == pytest.approx(s["window_s"] - s["busy_s"])


def test_no_device_operation_reads_as_nothing():
    red = load("hand_trace.json")
    red["devices"] = {"/device:TPU:0": {"ops": [], "modules": []}}
    assert xplane.summarise(red) is None
    red["devices"] = {}
    assert xplane.summarise(red) is None


def brute_busy(events, lo, hi):
    """Busy nanoseconds by a sweep over interval ends, written apart from
    xplane.merge: the time during which at least one interval is open."""
    marks = []
    for _, s, d in events:
        s, e = max(s, lo), min(s + d, hi)
        if e > s:
            marks += [(s, 1), (e, -1)]
    marks.sort()
    busy, depth, since = 0.0, 0, None
    for t, step in marks:
        if depth == 0 and step == 1:
            since = t
        depth += step
        if depth == 0:
            busy += t - since
    return busy


@pytest.mark.parametrize("name", sorted(
    f for f in os.listdir(DATA) if f.startswith("v5e_") and f.endswith(".json")))
def test_recorded_excerpt(name):
    """An excerpt of a real v5e trace (recorded by run.py with
    BENCH_KEEP_REDUCED): the union agrees with a sweep written apart, the
    programs are the live rung's, and idle plus busy make the window."""
    red = load(name)
    s = xplane.summarise(red)
    lo, hi = xplane.window_of(red)
    dev = next(iter(red["devices"].values()))
    assert s["busy_s"] == pytest.approx(brute_busy(dev["ops"], lo, hi) * NS)
    assert 0 < s["busy_s"] < s["window_s"]
    assert sum(v for _, v in s["idle_gaps"]) <= s["window_s"] - s["busy_s"] + 1e-12
    assert any(p in s["program_s"] for p in ("multi_step", "_step_full"))
    modules = sum(min(st + d, hi) - max(st, lo) for _, st, d in dev["modules"]
                  if min(st + d, hi) > max(st, lo))
    assert sum(s["program_s"].values()) == pytest.approx(modules * NS)
    # an operation runs inside a launched program: busy time cannot pass
    # the programs' own
    assert s["busy_s"] <= sum(s["program_s"].values()) * (1 + 1e-9)
