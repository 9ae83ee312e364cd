"""The device ladder's protocol (`Core._run_ladder`), rung by rung: the
rung that stays attached across calls (`live`, or `mesh_queued` on a
two-device mesh) and the one-shot call below it. XLA:CPU here, a small
seeded stream handed to an observer `Core("tpu")`, faults injected at the
rung engine's constructor and serve step and at the one-shot call, which
otherwise hands the call to the host engine so that hundreds of calls
stay cheap.

What is held: an attached engine that raises is one demotion, recorded
with a backoff of 2, its re-attach is tried at +2, +4, ... calls capped at
64 and counted and recorded once when it holds; a first attach that fails
on anything but `GridUnsupported` is counted and warned of once, and is no
demotion; a one-shot call the grid cannot express sends the node to
`cpu_fallback`, retried at +2, +4, ... calls capped at 256, and heals
once. A fast-forward drops the attached engine as a demotion and the next
attach is a re-attach; the hashgraph mirrors the live engine for the
benchmark's entries while it is attached.
"""

import logging

import pytest

from babble_tpu.hashgraph import Block, Frame, InmemStore, Section
from babble_tpu.node import Core
from babble_tpu.tpu import dispatch as dispatch_mod
from babble_tpu.tpu import engine as engine_mod
from babble_tpu.tpu import live as live_mod
from babble_tpu.tpu.grid import GridUnsupported

from test_live_spans import Blocks, handed, signed_stream

SYNC = 40

# rung -> (Core knobs, engine class, the step a serve call always takes,
# the rung a call takes while the engine is down)
RUNGS = {
    "live": ({}, live_mod.LiveDeviceEngine, "advance", "one_shot"),
    "mesh_queued": ({"mesh_devices": 2}, dispatch_mod.MeshDispatchQueue,
                    "serve", "mesh"),
}


@pytest.fixture(scope="module")
def stream():
    return signed_stream()


class Ladder:
    """An observer Core with fault points on its rung and one-shot call."""

    def __init__(self, monkeypatch, stream, rung):
        self.rung = rung
        knobs, cls, step, self.below = RUNGS[rung]
        peers, key, self.signed = stream
        self.blocks = Blocks()
        self.core = Core(0, key, peers, InmemStore(peers, 2000),
                         commit_ch=self.blocks, consensus_backend="tpu",
                         **knobs)
        self.handed = 0
        self.attaches = []  # calls at which the rung engine was built
        self.one_shots = []  # calls at which the one-shot rung ran
        self.refuse = None  # what the engine's constructor raises
        self.step_error = None  # what its serve step raises
        self.one_shot_error = None  # what the one-shot call raises
        monkeypatch.setitem(live_mod.ENGINE_DEFAULTS, "async_fetch", False)
        real_init, real_step = cls.__init__, getattr(cls, step)

        def init(eng, *args, **kwargs):
            self.attaches.append(self.core._consensus_calls)
            if self.refuse is not None:
                raise self.refuse
            real_init(eng, *args, **kwargs)

        def serve_step(eng, *args, **kwargs):
            if self.step_error is not None:
                raise self.step_error
            return real_step(eng, *args, **kwargs)

        def one_shot(hg, d_max=None, mesh=None):
            self.one_shots.append(self.core._consensus_calls)
            if self.one_shot_error is not None:
                raise self.one_shot_error
            hg.run_consensus()

        monkeypatch.setattr(cls, "__init__", init)
        monkeypatch.setattr(cls, step, serve_step)
        monkeypatch.setattr(engine_mod, "run_consensus_device", one_shot)

    def call(self, events=0):
        """One consensus call, after handing over the next `events`."""
        for ev in self.signed[self.handed:self.handed + events]:
            self.core.insert_event(handed(ev), True)
        self.handed += events
        self.core.run_consensus()

    def until(self, call):
        while self.core._consensus_calls < call:
            self.call()

    def records(self, name):
        return [r.fields for r in self.core.hg.obs.flightrec.records()
                if r.name == name]

    def close(self):
        self.core.flush_device_dispatch()


@pytest.fixture
def ladder(request, monkeypatch, stream):
    lad = Ladder(monkeypatch, stream, request.param)
    yield lad
    lad.refuse = lad.step_error = lad.one_shot_error = None
    lad.close()


@pytest.mark.parametrize("ladder", list(RUNGS), indirect=True)
def test_a_fallen_engine_is_demoted_and_reattached(ladder):
    core = ladder.core
    rung = ladder.rung
    ladder.call(SYNC)
    assert core.ladder_rung() == rung and ladder.attaches == [1]
    assert core.device_consensus_runs == 1 and ladder.one_shots == []

    ladder.step_error = RuntimeError("device lost")
    ladder.call(SYNC)
    ladder.step_error = None
    assert core.live_demotions == 1 and core.ladder_rung() == ladder.below
    assert ladder.records("ladder.demote") == [
        {"rung": rung, "error": "RuntimeError", "backoff": 2}]
    assert ladder.one_shots == [2]

    # re-attaches refused quietly: tried at +2, +4, ... and no further
    # apart than 64 calls
    ladder.refuse = GridUnsupported("not yet")
    ladder.until(200)
    assert ladder.attaches == [1, 4, 8, 16, 32, 64, 128, 192]
    assert core.live_demotions == 1 and core.device_attach_failures == 0
    assert core.live_reattaches == 0 and core.ladder_rung() == ladder.below

    ladder.refuse = None
    ladder.until(256)
    assert ladder.attaches[-1] == 256 and core.ladder_rung() == rung
    assert core.live_reattaches == 1 and core.live_demotions == 1
    assert ladder.records("ladder.reattach") == [
        {"rung": rung, "demotions": 1}]
    assert ladder.records("ladder.demote") == [
        {"rung": rung, "error": "RuntimeError", "backoff": 2}]
    ladder.until(258)
    assert ladder.attaches[-1] == 256 and core.ladder_rung() == rung
    assert core.device_consensus_fallbacks == 0


@pytest.mark.parametrize("ladder", list(RUNGS), indirect=True)
def test_a_first_attach_that_fails_is_counted_once(ladder, caplog):
    core = ladder.core
    ladder.refuse = MemoryError("RESOURCE_EXHAUSTED")
    with caplog.at_level(logging.DEBUG, logger=core.logger.name):
        ladder.call(SYNC)
        assert core.device_attach_failures == 1
        assert core.live_demotions == 0 and ladder.records("ladder.demote") == []
        warned = [r for r in caplog.records if r.levelno >= logging.WARNING]
        assert len(warned) == 1 and "failed to attach" in warned[0].getMessage()
        assert ladder.one_shots == [1] and core.ladder_rung() == ladder.below

        # the retry two calls on fails again: counted, not warned of again
        ladder.until(3)
    assert ladder.attaches == [1, 3] and core.device_attach_failures == 2
    assert len([r for r in caplog.records if r.levelno >= logging.WARNING]) == 1
    assert core.live_demotions == 0 and core.live_reattaches == 0


@pytest.mark.parametrize("ladder", list(RUNGS), indirect=True)
def test_an_unsupported_grid_falls_to_the_host_and_heals(ladder):
    core = ladder.core
    ladder.refuse = GridUnsupported("rung refuses")
    ladder.one_shot_error = GridUnsupported("rolled window")
    ladder.call(SYNC)
    assert core.ladder_rung() == "cpu_fallback"
    assert core.device_consensus_fallbacks == 1 and core.device_heals == 0
    assert ladder.records("ladder.device_down") == [
        {"what": ("mesh" if ladder.below == "mesh" else "device")
         + " consensus", "error": "GridUnsupported", "backoff": 2}]

    # tried at +2, +4, ... and no further apart than 256 calls; the host
    # engine serves the calls between
    ladder.until(700)
    assert ladder.one_shots == [1, 3, 7, 15, 31, 63, 127, 255, 511]
    assert core.device_consensus_fallbacks == 9
    assert core.ladder_rung() == "cpu_fallback"
    assert len(ladder.records("ladder.device_down")) == 1

    ladder.one_shot_error = None
    ladder.until(767)
    assert ladder.one_shots[-1] == 767 and core.device_heals == 1
    assert core.ladder_rung() == ladder.below
    assert ladder.records("ladder.device_heal") == [
        {"heals": 1, "fallbacks": 9}]
    assert core.live_demotions == 0 and core.device_attach_failures == 0


@pytest.mark.parametrize("ladder", ["live"], indirect=True)
def test_a_fast_forward_demotes_the_attached_engine(ladder):
    """The engine's device state was built against the store a reset
    replaces: the fast-forward drops it, and counts that as a demotion;
    the attach two calls on counts as a re-attach, and the engine serves
    the syncs after it."""
    core = ladder.core
    for _ in range(5):
        ladder.call(SYNC)
    assert core.ladder_rung() == "live" and core.live_demotions == 0
    # the core's own anchor, copied as prepare_fast_forward copies a
    # donor's, with the section above it
    block = core.hg.store.get_block(core.get_last_block_index() - 1)
    frame = core.hg.get_frame(block.round_received())
    section = core.hg.get_section(frame.round)
    calls = core._consensus_calls
    core.apply_fast_forward(Block.from_json(block.to_json()),
                            Frame.from_json(frame.to_json()),
                            Section.from_json(section.to_json()))
    assert core.live_demotions == 1 and core.ladder_rung() == "one_shot"
    assert ladder.attaches == [1] and ladder.one_shots == [calls + 1]
    ladder.call()
    ladder.call()
    assert ladder.attaches == [1, calls + 2] and core.ladder_rung() == "live"
    assert core.live_demotions == 1 and core.live_reattaches == 1
    last, runs = core.get_last_block_index(), core.device_consensus_runs
    ladder.call(SYNC)
    ladder.call(SYNC)
    assert core.device_consensus_runs == runs + 2
    assert core.get_last_block_index() > last and ladder.one_shots == [calls + 1]


@pytest.mark.parametrize("ladder", ["live"], indirect=True)
def test_the_hashgraph_mirrors_the_live_engine(ladder):
    """`hg._live_device_engine` is what the benchmark's entries read of
    the live engine: the Core's handle while attached, None once it is
    dropped."""
    core = ladder.core
    ladder.call(SYNC)
    assert core.device_engine is not None
    assert core.hg._live_device_engine is core.device_engine
    ladder.step_error = RuntimeError("device lost")
    ladder.call(SYNC)
    assert core.device_engine is None and core.hg._live_device_engine is None
