"""A gossip stream whose adversarial third withholds its chains and reveals
them late, handed over in arrival order (`benchmark/traffic_adversarial.py`)
to an observer `Core("tpu")` on XLA:CPU: the live rung serves every sync,
the ones that carry a witness into an already decided round included, and
stamps and commits what the host engine and the plain reference do.

The syncs are sized so that one sync is one device program, `step` (32
events, one batch) or a `multi_step` train (100 events in 32-row batches):
the device then decides as often as the host engine does, and "a witness
landed in a decided round" means the same on both.
"""

import numpy as np
import pytest

from babble_tpu.tpu import live as live_mod
from benchmark import traffic as gen
from benchmark import traffic_adversarial as adversarial
from benchmark.entries import replay, replay_adversarial
from benchmark.reference import hashgraph as reference

# validators, events, span of an episode in own events
SIZES = [(8, 2400, "24-64"), (16, 4000, "12-48")]
# events a sync, Core knobs, the program a sync launches
SYNCS = [(32, {}, "step"), (100, {"dispatch_batch_rows": 32}, "multi_step")]


def withheld_stream(monkeypatch, n, events, seed, span, topology_seed=None):
    cfg = dict(validators=n, events=events, zipf_a=1.1, byzantine=n // 3,
               withhold_span=span, withhold_start_p=1 / 24,
               max_hidden=max(n // 8, 1))
    traffic = replay_adversarial.WithheldTraffic(cfg)
    monkeypatch.setattr(replay, "gen", traffic)
    stream = replay.Stream(n, events, seed, 1.1, 1, topology_seed)
    return stream, traffic.drawn


def sync_ranges(total, sync, lead_in=()):
    """[lo, hi) rows a sync: the lead-in's phases (events, events a sync),
    as a traffic file states them, then syncs of `sync` events."""
    lo = 0
    for events, size in tuple(lead_in) + ((total, sync),):
        for a, b in gen.syncs(min(events, total - lo), size):
            yield lo + a, lo + b
        lo = min(lo + events, total)


def drive(stream, backend, sync, knobs, lead_in=()):
    """The whole stream through a fresh observer Core. Returns the Core,
    its blocks, the stamps it left, syncs unserved, and the late witnesses
    it registered (witnesses divided into a round that was whole)."""
    handed = [stream.copy(ev) for ev in stream.signed]
    stamps = replay.CommitStamps()
    core = stream.core(backend, 50000, commit_ch=stamps, **knobs)
    late = []
    queue_round = core.hg.queue_round

    def spy(round_number, round_info, late_witness):
        if (late_witness and round_info.witnesses()
                and round_info.witnesses_decided()):
            late.append(round_number)
        return queue_round(round_number, round_info, late_witness)

    core.hg.queue_round = spy
    served = replay.Served(core)
    for lo, hi in sync_ranges(len(handed), sync, lead_in):
        for ev in handed[lo:hi]:
            core.insert_event(ev, True)
        core.run_consensus()
        if backend == "tpu":
            served.note()
    core.flush_device_dispatch()

    def stamp(v):
        return -1 if v is None else int(v)

    got = np.array([(stamp(ev.round), stamp(ev.lamport_timestamp),
                     stamp(ev.round_received)) for ev in handed])
    return core, stamps.blocks, got, served.unserved, late


@pytest.mark.parametrize("sync,knobs,program", SYNCS,
                         ids=[s[2] for s in SYNCS])
@pytest.mark.parametrize("seed", [1, 2])
@pytest.mark.parametrize("n,events,span", SIZES, ids=["v8", "v16"])
def test_live_rung_serves_late_witnesses(monkeypatch, n, events, span, seed,
                                         sync, knobs, program):
    monkeypatch.setitem(live_mod.ENGINE_DEFAULTS, "async_fetch", False)
    stream, drawn = withheld_stream(monkeypatch, n, events, seed, span)
    assert drawn.late.sum() > events // 20 and len(drawn.reveal_rows) > 3

    cpu, cpu_blocks, cpu_stamps, _, cpu_late = drive(stream, "cpu", sync, knobs)
    reopened = cpu.hg.obs.tracer.totals().get("fame.reopen", (0, 0.0))[0]
    # the stream holds what the test is about: witnesses that land in a
    # round already decided, some of them in a round already committed
    assert len(cpu_late) >= reopened > 0

    tpu, blocks, stamps, unserved, late = drive(stream, "tpu", sync, knobs)
    assert unserved == 0 and tpu.ladder_rung() == "live"
    assert (tpu.live_demotions, tpu.device_consensus_fallbacks,
            tpu.device_attach_failures) == (0, 0, 0)
    launched = {s.attrs["program"] for s in tpu.hg.obs.tracer.spans()
                if s.name == "live.launch"}
    assert program in launched

    # stamp for stamp and block for block: the host engine's ...
    assert (stamps == cpu_stamps).all()
    assert ([b.body.marshal() for _, b in blocks]
            == [b.body.marshal() for _, b in cpu_blocks])
    assert len(blocks) > 10
    # ... and the plain reference's
    want = reference.order(*replay.reference_inputs(stream, events))
    handed_over = [(b.index(), b.round_received(), b.transactions())
                   for _, b in blocks]
    assert replay.mismatches((stamps, handed_over), want) == {
        "events_mismatched": 0, "blocks_mismatched": 0}

    # what the device re-opened in place is what the host engine met
    totals = tpu.hg.obs.tracer.totals()
    spans = [s for s in tpu.hg.obs.tracer.spans()
             if s.name == "live.late_witness"]
    assert totals["live.late_witness"][0] == len(spans) > 0
    assert sum(s.attrs["witnesses"] for s in spans) == len(cpu_late)
    assert sorted(late) == sorted(cpu_late)
    assert totals["fame.reopen"][0] == reopened
    assert "live.host_repair" not in totals


@pytest.mark.parametrize("n,events,span", SIZES, ids=["v8", "v16"])
def test_reference_is_blind_to_arrival_order(monkeypatch, n, events, span):
    """The same DAG in the order it was made and in the order it arrived:
    the reference gives every event the same round, lamport timestamp and
    round received, and the same blocks."""
    stream, drawn = withheld_stream(monkeypatch, n, events, 3, span)
    made, row_there = adversarial.creation_order(drawn)
    assert (row_there != np.arange(events)).sum() > events // 20
    n_, creator, index, sp, op, sig_r, coin, txs = replay.reference_inputs(
        stream, events)
    arrived = reference.order(n_, creator, index, sp, op, sig_r, coin, txs)
    back = np.argsort(row_there)  # creation row -> arrival row

    def there(values):
        return [values[a] for a in back]

    created = reference.order(
        n_, made.creator, made.index, made.self_parent, made.other_parent,
        there(sig_r), there(coin), there(txs))
    for name in ("rounds", "lamport", "received"):
        assert (getattr(created, name)[row_there]
                == getattr(arrived, name)).all(), name
    assert created.blocks == arrived.blocks and len(arrived.blocks) > 10


def test_a_round_below_the_base_still_latches(monkeypatch):
    """What the round window cannot hold stays a demotion: with a round
    axis of 16 a rebase holds the base at most 4 rounds down, a chain
    withheld for 80-120 own events (8 to 12 rounds) starts below it, the
    state latches `stale`, and the ladder repairs the blocks."""
    monkeypatch.setitem(live_mod.ENGINE_DEFAULTS, "async_fetch", False)
    monkeypatch.setitem(live_mod.ENGINE_DEFAULTS, "r_cap", 16)
    stream, _ = withheld_stream(monkeypatch, 8, 4000, 1, "80-120")
    _, cpu_blocks, cpu_stamps, _, _ = drive(stream, "cpu", 100, {})
    tpu, blocks, stamps, unserved, _ = drive(stream, "tpu", 100, {})
    assert unserved > 0 and tpu.live_demotions > 0
    assert (stamps == cpu_stamps).all()
    assert ([b.body.marshal() for _, b in blocks]
            == [b.body.marshal() for _, b in cpu_blocks])


@pytest.mark.parametrize("n,events,span,cap,knobs", [
    (8, 1600, "24-64", 256, {}),
    (16, 2400, "12-48", 256, {"dispatch_batch_rows": 32}),
], ids=["v8-rows64", "v16-rows32"])
def test_a_batch_is_cut_at_the_update_cap(monkeypatch, n, events, span, cap,
                                          knobs):
    """A staging of 256 first-descendant updates a batch: the engine cuts
    its batches there (a revealed chain bursts past it; at 16 validators a
    32-row batch carries ~500) instead of demoting, counts them in the
    total `stage.cut`, and the decisions stay the host engine's."""
    monkeypatch.setitem(live_mod.ENGINE_DEFAULTS, "async_fetch", False)
    monkeypatch.setitem(live_mod.ENGINE_DEFAULTS, "upd_cap", cap)
    stream, _ = withheld_stream(monkeypatch, n, events, 1, span)
    _, cpu_blocks, cpu_stamps, _, _ = drive(stream, "cpu", 100, knobs)
    tpu, blocks, stamps, unserved, _ = drive(stream, "tpu", 100, knobs)
    spans = tpu.hg.obs.tracer.spans()
    staged = [s.attrs for s in spans if s.name == "live.stage"]
    batches = [s.attrs["batches"] for s in spans if s.name == "device.dispatch"]
    rows = knobs.get("dispatch_batch_rows", 64)
    # some sync carried more updates than one batch stages, and was cut
    assert max(a["fd_updates"] for a in staged) > 2 * cap
    assert max(batches) > -(-100 // rows)  # what 100 events are uncut
    assert unserved == 0 and tpu.live_demotions == 0
    assert (stamps == cpu_stamps).all()
    assert ([b.body.marshal() for _, b in blocks]
            == [b.body.marshal() for _, b in cpu_blocks])
    # the cuts at the staging are counted apart from the level table's and
    # handed to the tracer once a dispatch
    cut = tpu.hg.obs.tracer.totals()["stage.cut"][0]
    assert cut == tpu.hg._live_device_engine.update_cuts > 0


def test_width_128_agrees_with_the_reference(monkeypatch):
    """`v128-byz` as its cell hands it over, at the real width and the
    engine's own capacities: 128 validators of which 42 withhold (16 at
    once, 32-128 own events), the configuration's DAG, the traffic file's
    lead-in (2,048 events in 32-event syncs) and then 500-event syncs in
    32-row batches, for as long as it takes to commit (the first block
    comes 13,048 rows in) and to re-open a decided round. Every stamp and
    every block is the plain reference's; every sync is served."""
    monkeypatch.setitem(live_mod.ENGINE_DEFAULTS, "async_fetch", False)
    n, events = 128, 16048
    stream, drawn = withheld_stream(monkeypatch, n, events, 7, "32-128",
                                    topology_seed=103)
    assert drawn.late.sum() > events // 20
    tpu, blocks, stamps, unserved, late = drive(
        stream, "tpu", 500, {"dispatch_batch_rows": 32}, lead_in=[(2048, 32)])
    assert unserved == 0 and tpu.ladder_rung() == "live"
    assert (tpu.live_demotions, tpu.device_consensus_fallbacks,
            tpu.device_attach_failures) == (0, 0, 0)
    want = reference.order(*replay.reference_inputs(stream, events))
    handed_over = [(b.index(), b.round_received(), b.transactions())
                   for _, b in blocks]
    assert replay.mismatches((stamps, handed_over), want) == {
        "events_mismatched": 0, "blocks_mismatched": 0}
    assert len(blocks) >= 1 and len(blocks[0][1].transactions()) > 1000
    totals = tpu.hg.obs.tracer.totals()
    # the mechanism ran: a witness landed in a decided round, served in place
    assert len(late) > 0 and totals["fame.reopen"][0] > 0
    assert totals["live.late_witness"][0] > 0
    assert "live.host_repair" not in totals
    # the programs the cell launches, and the staging's cuts counted: at this
    # width a 32-row batch carries ~4,000 cells and now and then over 8,192
    eng = tpu.hg._live_device_engine
    assert totals["live.launch.step"][0] >= 60  # the attach is not in it
    assert totals["live.launch.train"][0] >= 28
    assert totals["stage.cut"][0] == eng.update_cuts
    assert totals["stage.cells"][0] == eng.cells_staged > 100 * events
