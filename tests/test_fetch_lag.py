"""The pipelined fetch's lag (`tpu/live.py _run_pipelined`, `_note_wait`):
how many dispatches the live rung keeps in flight before it integrates the
oldest. XLA:CPU here, a small seeded stream handed sync by sync to an
observer `Core("tpu")` under a clock the test owns: a fetch waits what the
test says it waits, so the schedule is the rule's and not the host's.

What is held: with waits that read zero every result is integrated on the
call after its dispatch, whatever the cap; waits over `ASYNC_FETCH_MIN_S`
on three consecutive calls deepen the lag by one, as far as
`dispatch_queue_depth` and no further, and each step is counted
(`fetch.deepen`); a cap of 1 and a cap of 0 never leave a lag of one; a
queue that drains because nothing was staged starts again at one; the
rebase barrier and the flush leave nothing in flight at any depth; and the
blocks are the host engine's at every lag.
"""

import threading

import pytest

from babble_tpu.common.clock import Clock
from babble_tpu.hashgraph import InmemStore
from babble_tpu.node import Core
from babble_tpu.obs import Observability
from babble_tpu.tpu import live as live_mod

from test_live_spans import Blocks, handed, named, signed_stream

SYNC = 16
SLOW = 2 * live_mod.ASYNC_FETCH_MIN_S


class HeldClock(Clock):
    """Stands still but for what a fetch is told to wait."""

    def __init__(self):
        self.now = 100.0

    def monotonic(self):
        return self.now

    def sleep(self, seconds):
        self.now += seconds


@pytest.fixture(scope="module")
def stream():
    return signed_stream()


@pytest.fixture(scope="module")
def cpu_blocks(stream):
    return drive(stream, "cpu")[1]


def drive(stream, backend="tpu", cap=4, waits=lambda i: 0.0, after_call=None):
    """Hand the stream over in SYNC-event syncs under a HeldClock on which
    the i-th pipelined fetch waits `waits(i)` seconds. Returns the Core,
    its block bodies and the tracer's totals as they stood before the
    flush."""
    peers, key, signed = stream
    clock = HeldClock()
    blocks = Blocks()
    core = Core(0, key, peers, InmemStore(peers, 2000), commit_ch=blocks,
                consensus_backend=backend, dispatch_queue_depth=cap,
                obs=Observability(clock=clock))
    fetched = [0]
    real = live_mod._AsyncFetch

    class Waited(real):
        def result(self):
            clock.sleep(waits(fetched[0]))
            fetched[0] += 1
            return super().result()

    live_mod._AsyncFetch = Waited
    try:
        for lo in range(0, len(signed), SYNC):
            for ev in signed[lo:lo + SYNC]:
                core.insert_event(handed(ev), True)
            core.run_consensus()
            if after_call is not None:
                after_call(core)
        before_flush = core.hg.obs.tracer.totals()
        core.flush_device_dispatch()
    finally:
        live_mod._AsyncFetch = real
    if backend == "tpu":
        assert core.ladder_rung() == "live" and core.live_demotions == 0
    return core, blocks.bodies, before_flush


@pytest.fixture
def pipelined(monkeypatch):
    monkeypatch.setitem(live_mod.ENGINE_DEFAULTS, "async_fetch", True)


def integrations(core):
    return [r.fields for r in core.hg.obs.flightrec.records()
            if r.name == "live.integrate"]


def lags(core):
    return [s.attrs["lag_calls"]
            for s in named(core.hg.obs.tracer.spans(), "device.fetch")
            if s.attrs["discipline"] == "pipelined"]


def test_a_result_is_integrated_on_the_call_after_its_dispatch(
        pipelined, stream, cpu_blocks):
    depths = []
    core, blocks, totals = drive(
        stream, cap=4,
        after_call=lambda c: depths.append(len(c.device_engine.inflight)))
    eng = core.device_engine
    assert totals["fetch.pipelined"][0] == totals["fetch.lag"][0] > 10
    assert set(lags(core)[:-1]) == {1}  # the flush's own fetch lags no call
    assert "fetch.deepen" not in core.hg.obs.tracer.totals()
    assert eng.fetch_lag == 1 and set(depths[1:]) == {1}
    assert {r["lag"] for r in integrations(core)} == {1}
    assert not eng.inflight and blocks == cpu_blocks


@pytest.mark.parametrize("cap", [2, 4])
def test_waits_on_consecutive_calls_deepen_the_lag_to_the_cap(
        pipelined, stream, cpu_blocks, cap):
    seen = []
    core, blocks, totals = drive(
        stream, cap=cap, waits=lambda i: SLOW,
        after_call=lambda c: seen.append(
            (c.device_engine.fetch_lag,
             len(c.device_engine.inflight))))
    eng = core.device_engine
    assert eng.fetch_lag == cap
    assert totals["fetch.deepen"] == (cap - 1, 0.0)
    steps = [b[0] - a[0] for a, b in zip(seen, seen[1:])]
    assert set(steps) == {0, 1} and sum(steps) == cap - 1
    assert all(inflight <= lag <= cap for lag, inflight in seen)
    # three waits a step, and the calls that fill the deeper queue
    recorded = [r["lag"] for r in integrations(core)]
    assert recorded == sorted(recorded) and recorded[:2] == [1, 1]
    for depth in range(2, cap + 1):
        assert recorded.count(depth) >= 3 or depth == cap
    assert max(lags(core)) == cap
    assert totals["fetch.lag"][0] > totals["fetch.pipelined"][0]
    assert not eng.inflight and blocks == cpu_blocks


def test_a_fast_fetch_between_slow_ones_deepens_nothing(
        pipelined, stream, cpu_blocks):
    core, blocks, totals = drive(
        stream, cap=4, waits=lambda i: 0.0 if i % 3 == 2 else SLOW)
    assert core.device_engine.fetch_lag == 1
    assert "fetch.deepen" not in totals
    assert totals["fetch.pipelined"][0] == totals["fetch.lag"][0]
    assert blocks == cpu_blocks


@pytest.mark.parametrize("cap", [0, 1])
def test_a_cap_of_one_or_none_keeps_a_lag_of_one(
        pipelined, stream, cpu_blocks, cap):
    core, blocks, totals = drive(stream, cap=cap, waits=lambda i: SLOW)
    eng = core.device_engine
    assert eng.queue_depth == cap and eng.fetch_lag == 1
    assert "fetch.deepen" not in totals
    assert set(lags(core)[:-1]) == {1}
    assert totals["fetch.pipelined"][0] == totals["fetch.lag"][0] > 10
    assert blocks == cpu_blocks


def test_a_queue_drained_for_lack_of_traffic_starts_again_at_one(
        pipelined, stream):
    """Calls that staged nothing integrate the oldest dispatch; the lag
    shallows when the last one is in, not a call sooner."""
    after_idle = []

    def idle_once(core):
        eng = core.device_engine
        if eng.fetch_lag == 3 and len(eng.inflight) == 3 and not after_idle:
            for _ in range(3):
                core.run_consensus()
                after_idle.append((eng.fetch_lag, len(eng.inflight)))

    core, _, totals = drive(stream, cap=3, waits=lambda i: SLOW,
                            after_call=idle_once)
    # the first idle call integrates two: one because three ride, one
    # because it dispatched nothing
    assert after_idle == [(3, 1), (1, 0), (1, 0)]
    # and the waits that follow deepen it again
    assert core.device_engine.fetch_lag == 3
    assert totals["fetch.deepen"][0] == 4


@pytest.mark.parametrize("waits", [0.0, SLOW], ids=["lag1", "cap"])
def test_rebase_barrier_and_flush_leave_nothing_in_flight(
        pipelined, monkeypatch, stream, cpu_blocks, waits):
    monkeypatch.setitem(live_mod.ENGINE_DEFAULTS, "r_cap", 16)
    in_flight_at_rebase = []
    real = live_mod.LiveDeviceEngine.rebase

    def rebase(self):
        in_flight_at_rebase.append(len(self.inflight))
        return real(self)

    monkeypatch.setattr(live_mod.LiveDeviceEngine, "rebase", rebase)
    core, blocks, totals = drive(stream, cap=4, waits=lambda i: waits)
    eng = core.device_engine
    assert eng.rebases > 0 and set(in_flight_at_rebase) == {0}
    # a barrier's drain waits by design: it is no evidence, and keeps the lag
    assert eng.fetch_lag == (4 if waits else 1)
    assert totals.get("fetch.deepen", (0, 0.0))[0] == (3 if waits else 0)
    assert not eng.inflight and blocks == cpu_blocks


def test_the_synchronous_discipline_has_no_lag(monkeypatch, stream, cpu_blocks):
    monkeypatch.setitem(live_mod.ENGINE_DEFAULTS, "async_fetch", False)
    core, blocks, totals = drive(stream, cap=4, waits=lambda i: SLOW)
    assert totals["fetch.lag"][0] == 0 and "fetch.pipelined" not in totals
    assert "fetch.deepen" not in totals and not integrations(core)
    assert core.device_engine.fetch_lag == 1
    assert blocks == cpu_blocks


def test_a_flip_starts_the_lag_at_one(monkeypatch, stream, cpu_blocks):
    """An engine that flips by itself (three slow synchronous fetches)
    counts the pipelined waits anew: the flip's evidence deepens nothing."""
    clock_of = {}
    real_get = live_mod.jax.device_get

    def slow_get(x):
        # the synchronous fetch only: the reader thread's own get waits
        # through `drive`'s `result`
        if "clock" in clock_of and threading.current_thread().name != "live-fetch":
            clock_of["clock"].sleep(SLOW)
        return real_get(x)

    monkeypatch.setattr(live_mod.jax, "device_get", slow_get)
    flipped_at = []

    def watch(core):
        clock_of["clock"] = core.hg.obs.clock
        eng = core.device_engine
        if eng.async_fetch and not flipped_at:
            flipped_at.append((eng.calls, eng.fetch_lag, eng._slow_fetches))

    core, blocks, totals = drive(stream, cap=4, after_call=watch)
    assert flipped_at == [(4, 1, 0)]
    assert "fetch.deepen" not in totals
    assert set(lags(core)[:-1]) == {1}
    assert blocks == cpu_blocks
