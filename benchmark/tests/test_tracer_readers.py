"""The two readers of the program's own span tracer against a hand-built
tracer: known totals and checkpoints give known metric values, and a
reader that finds nothing to read says None and does not raise. Run by hand:

    python3 -m pytest benchmark/tests -q -p no:cacheprovider
"""

import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from babble_tpu.obs.trace import SpanTracer  # noqa: E402
from benchmark.readers import tracer_count, tracer_ms_per  # noqa: E402


class HandClock:
    now = 0.0

    def monotonic(self):
        return self.now


def reading(t0, t1):
    return types.SimpleNamespace(window=(t0, t1))


@pytest.fixture
def tracer():
    """Three consensus calls at t = 100, 110, 120 on a clock of its own.
    Each: 500 inserts of 0.2 ms (0.05 verify, 0.1 fd) before it; inside it
    a 20 ms dispatch (15 stage, 4 launch), a 1 ms pack, 30 ms integrate and
    40 ms commit; the call lasts 100 ms, so 9 ms of it have no span. One
    block every call but the first, its frame built in 25 ms."""
    import gc

    import babble_tpu.obs.trace as trace_mod

    trace_mod._KEPT.clear()  # the last case's tracer stands in the same window
    gc.collect()
    clock = HandClock()
    tr = SpanTracer(clock=clock)
    for call in range(3):
        clock.now = 100.0 + 10 * call
        for _ in range(500):
            tr.add("insert.verify", 0.00005)
            tr.add("insert.fd", 0.0001)
            tr.add("insert", 0.0002)
        tr.checkpoint()
        with tr.span("core.run_consensus"):
            with tr.span("device.dispatch"):
                with tr.span("live.stage"):
                    clock.now += 0.015
                with tr.span("live.launch"):
                    clock.now += 0.004
                clock.now += 0.001
            with tr.span("live.pack"):
                clock.now += 0.001
            with tr.span("live.integrate"):
                clock.now += 0.030
            with tr.span("consensus.process_decided_rounds"):
                if call:
                    with tr.span("commit.frame"):
                        clock.now += 0.025
                    with tr.span("commit.block"):
                        clock.now += 0.005
                    clock.now += 0.010
                else:
                    clock.now += 0.040
            clock.now += 0.009
        tr.checkpoint()
    return tr


def spec_of(metric):
    with open(os.path.join(ROOT, "benchmark/layer_metrics", metric + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("metric,want", [
    ("stage_ms_per_sync", 15.0),
    ("launch_ms_per_sync", 5.0),
    ("integrate_ms_per_sync", 30.0),
    ("commit_host_ms_per_sync", 40.0),
    ("frame_build_ms_per_block", 25.0),
    ("sig_verify_ms_per_event", 0.05),
    ("fd_update_ms_per_event", 0.1),
    ("consensus_call_unattributed_ms_per_sync", 9.0),
    ("admissibility_ms_per_sync", 0.0),  # no such span: 0 ms over 3 calls
    ("sig_pool_ms_per_sync", 0.0),
])
def test_known_totals_give_known_values(tracer, metric, want):
    got = tracer_ms_per.read(reading(99.0, 130.0), spec_of(metric))
    assert got == pytest.approx(want, abs=1e-9)


def test_window_holds_whole_calls_and_the_inserts_between(tracer):
    # from the first call's entry to the last one's return: three calls,
    # and the inserts of the second and third sync only
    got = tracer.totals_between(99.0, 130.0)
    assert got["core.run_consensus"][0] == 3 and got["insert"][0] == 1000
    # a window that opens after the first call has returned: two calls
    spec = spec_of("frame_build_ms_per_block")
    assert tracer_ms_per.read(reading(105.0, 130.0), spec) == pytest.approx(25.0)
    assert tracer.totals_between(105.0, 130.0)["core.run_consensus"][0] == 2


def test_count_reads_zero_for_a_name_that_never_occurred(tracer):
    spec = spec_of("host_repaired_syncs")
    assert tracer_count.read(reading(99.0, 130.0), spec) == 0
    with tracer.span("live.host_repair"):
        pass
    tracer.clock.now = 129.0
    tracer.checkpoint()
    assert tracer_count.read(reading(99.0, 130.0), spec) == 1


@pytest.mark.parametrize("window", [
    (0.0, 50.0),      # no checkpoint inside
    (100.05, 100.2),  # one checkpoint inside (the first call's return)
])
def test_nothing_to_read_is_none(tracer, window):
    assert tracer_ms_per.read(reading(*window), spec_of("stage_ms_per_sync")) is None
    assert tracer_count.read(reading(*window), spec_of("host_repaired_syncs")) is None


def test_zero_divisor_and_two_tracers_are_none(tracer):
    spec = {"spans": ["live.stage"], "per": "no.such.span"}
    assert tracer_ms_per.read(reading(99.0, 130.0), spec) is None
    twin = SpanTracer(clock=tracer.clock)  # a second node in the process
    twin.add("insert", 0.001)
    twin.checkpoint()
    tracer.clock.now = 129.5
    twin.checkpoint()
    assert tracer_ms_per.read(reading(99.0, 130.0),
                              spec_of("stage_ms_per_sync")) is None


def test_a_program_without_the_span_tree_reads_none(tracer, monkeypatch):
    """The parent commit has no `live_tracers`: the reader says nothing."""
    import babble_tpu.obs.trace as trace_mod

    monkeypatch.delattr(trace_mod, "live_tracers")
    assert tracer_ms_per.read(reading(99.0, 130.0),
                              spec_of("stage_ms_per_sync")) is None
    assert tracer_count.read(reading(99.0, 130.0),
                             spec_of("host_repaired_syncs")) is None
