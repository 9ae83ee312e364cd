"""The collector's pauses as the program reads them (obs/gcpause.py): a
collection inside an open span leaves its totals, for a full one a ring
record under that span, and moves the two registry counters; the entry
takes no lock, so a collection set off inside the tracer or a counter does
not hang; a tracer that is never used again holds a bounded queue; and
nothing of it reaches an `Observability` on a virtual clock, so the
simulator's fingerprints do not see the collector."""

import gc
import logging
import sys
import threading

import pytest

from babble_tpu.common.clock import SystemClock
from babble_tpu.obs import Observability, gcpause, live_tracers
from babble_tpu.obs import trace as trace_mod
from babble_tpu.sim import SimClock, run_one

logging.getLogger("babble.sim").setLevel(logging.CRITICAL)

FINGERPRINTS = ("digest", "trace_fingerprint", "flightrec_fingerprint",
                "cluster_health_fingerprint", "provenance_fingerprint",
                "ledger_fingerprint")


def counters(obs):
    seconds = obs.counter("babble_gc_pause_seconds_total", labels=("generation",))
    collections = obs.counter("babble_gc_collections_total", labels=("generation",))
    return {g: (seconds.value(generation=g), collections.value(generation=g))
            for g in ("young", "full")}


def in_thread(fn, seconds=20.0):
    """Run fn on a thread of its own; False if it has not returned in time
    (a hang must fail the test, not the run)."""
    done = []
    t = threading.Thread(target=lambda: done.append(fn()), daemon=True)
    t.start()
    t.join(seconds)
    return bool(done)


@pytest.mark.parametrize("generation,name", [(2, "gc.full"), (0, "gc.young")])
def test_collection_inside_a_span_is_booked(generation, name):
    obs = Observability()
    before = counters(obs)
    with obs.span("outer") as outer:
        gc.collect(generation)
    totals = obs.tracer.totals()
    count, seconds = totals[name]
    assert count >= 1 and seconds > 0.0
    kind = name.split(".")[1]
    after = counters(obs)
    assert after[kind][1] - before[kind][1] == count
    assert after[kind][0] - before[kind][0] == pytest.approx(seconds)
    records = [s for s in obs.tracer.spans() if s.name.startswith("gc.")]
    if generation == 2:
        record = records[-1]  # the forced one (an automatic one may precede it)
        assert record.parent == outer.id
        assert record.attrs["generation"] == 2 and record.attrs["collected"] >= 0
        assert outer.start <= record.start
        assert record.start + record.duration <= outer.start + outer.duration
    else:
        assert not records  # a young collection is a total, no ring record


def test_every_watched_tracer_is_fed_and_one_entry_is_installed():
    a, b = Observability(), Observability()
    gc.collect()
    assert a.tracer.totals()["gc.full"] == b.tracer.totals()["gc.full"]
    assert a.tracer.totals()["gc.full"][0] >= 1
    assert gc.callbacks.count(gcpause._on_collection) == 1


def test_a_checkpoint_holds_the_pauses_before_it():
    obs = Observability()
    t0 = obs.clock.monotonic()
    obs.tracer.checkpoint()
    gc.collect()
    obs.tracer.checkpoint()  # the queue is booked before the copy
    window = obs.tracer.totals_between(t0, obs.clock.monotonic())
    assert window["gc.full"][0] >= 1


@pytest.mark.parametrize("holder", ["tracer", "counter"])
def test_collection_under_a_held_lock_does_not_hang(holder):
    obs = Observability()
    seconds = obs.counter("babble_gc_pause_seconds_total", labels=("generation",))
    lock = obs.tracer._lock if holder == "tracer" else seconds._lock

    def collect_inside():
        with lock:
            gc.collect()
        return True

    assert in_thread(collect_inside)
    assert obs.tracer.totals()["gc.full"][0] >= 1
    assert counters(obs)["full"][1] == obs.tracer.totals()["gc.full"][0]


def test_no_pause_is_lost_or_booked_twice_under_threads():
    """Eight threads set the collector off and use the tracer at once: what
    the tracer and the counters hold in the end is what an independent
    `gc.callbacks` entry counted, once each."""
    gc.disable()  # only forced collections run: none falls outside the count
    obs = Observability()
    seen = []

    def count(phase, info):
        if phase == "stop":
            seen.append(info["generation"])

    def work():
        for _ in range(150):
            gc.collect(0)
            obs.tracer.add("work", 0.0)
            obs.tracer.totals()
        return True

    interval = sys.getswitchinterval()
    gc.callbacks.append(count)
    try:
        sys.setswitchinterval(1e-5)
        threads = [threading.Thread(target=work, daemon=True) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60.0)
        assert not any(t.is_alive() for t in threads)
    finally:
        gc.callbacks.remove(count)
        sys.setswitchinterval(interval)
        gc.enable()
    totals = obs.tracer.totals()
    # a forced collection returns at once while another thread's runs
    assert 150 <= len(seen) <= 8 * 150 and set(seen) == {0}
    assert totals["work"][0] == 8 * 150
    assert totals["gc.young"][0] == len(seen) and "gc.full" not in totals
    booked = counters(obs)
    assert booked["young"][1] == len(seen)
    assert booked["young"][0] == pytest.approx(totals["gc.young"][1])


def test_an_unused_tracer_keeps_a_bounded_queue(monkeypatch):
    monkeypatch.setattr(trace_mod, "DEFERRED_CAPACITY", 3)
    obs = Observability()
    for _ in range(5):
        gc.collect()
    assert len(obs.tracer._deferred) == 3 and obs.tracer.deferred_dropped >= 2
    totals = obs.tracer.totals()
    assert sum(totals.get(name, (0, 0.0))[0]
               for name in ("gc.full", "gc.young")) == 3
    assert not obs.tracer._deferred


def test_virtual_clock_sees_nothing_of_the_collector():
    obs = Observability(clock=SimClock())
    with obs.span("outer"):
        gc.collect()
    assert set(obs.tracer.totals()) == {"outer"}
    assert not obs.tracer._deferred
    assert not [n for n in obs.registry.snapshot() if n.startswith("babble_gc_")]


def test_simulator_fingerprints_do_not_see_the_collector():
    Observability()  # the entry is installed, as in any process with a node
    quiet = run_one(11, plan="clean", n=4, until=None, target_block=3)
    threshold = gc.get_threshold()
    gc.set_threshold(50, 2, 2)  # collections all through the run
    fed = Observability()
    try:
        busy = run_one(11, plan="clean", n=4, until=None, target_block=3)
    finally:
        gc.set_threshold(*threshold)
    assert quiet["ok"] and busy["ok"]
    # the entry did run through it (a full collection also waits for the
    # long-lived heap to have grown by a quarter, so only the young are sure)
    assert fed.tracer.totals()["gc.young"][0] > 100
    for key in FINGERPRINTS:
        assert quiet[key] == busy[key], key
    for tracer in live_tracers():
        if not isinstance(tracer.clock, SystemClock):
            assert not [n for n in tracer.totals() if n.startswith("gc.")]
            assert not tracer._deferred
