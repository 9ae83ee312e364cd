"""The span tree of one consensus call on the live device rung (XLA:CPU
here): a small seeded stream handed sync by sync to an observer
`Core("tpu")`, under the synchronous and the pipelined fetch discipline.

What is held: every live/device/commit span hangs under a
`core.run_consensus` root (but for the flush's), the `dispatch` id ties
one dispatch's launch, fetch and integration across calls, the totals
count what the program did (events inserted, blocks committed), one
interval is booked once, a reception the host rule refuses is counted,
frame building reads the rounds the engine stamped and derives none, and
the admissibility gate looks each round up once an integration.

The second level (PR 35): the four `live.integrate.*` spans are children
of their dispatch's `live.integrate` and sum to no more than it with the
gate's walk, the insert totals have `insert`'s count and sum to no more
than it, inside a window of checkpoints too, the two `commit.frame.*`
totals count a frame's events and roots, `obs.provenance` counts the cells
of the `prov.capture` marks, and the fetch discipline is a pair of counts.
"""

import pytest

from babble_tpu.crypto import derive_key, pub_key_bytes
from babble_tpu.hashgraph import Event, InmemStore, root_self_parent
from babble_tpu.node import Core
from babble_tpu.peers import Peer, Peers
from babble_tpu.tpu import engine as engine_mod
from babble_tpu.tpu import live as live_mod
from babble_tpu.tpu.grid import synthetic_grid

N, EVENTS, SYNC, SEED = 4, 400, 40, 23


def signed_stream():
    """(peers, observer key, signed events in creation order)."""
    grid = synthetic_grid(N, EVENTS, seed=SEED)
    by_pub = {}
    for i in range(N):
        key = derive_key(SEED * 1009 + i)
        by_pub["0x" + pub_key_bytes(key).hex().upper()] = key
    peers = Peers.from_slice([Peer(net_addr="", pub_key_hex=h) for h in by_pub])
    plist = peers.to_peer_slice()  # creator positions index the sorted slice
    keys = [by_pub[p.pub_key_hex] for p in plist]
    signed = []
    for i in range(grid.e):
        c = int(grid.creator[i])
        sp, op = int(grid.self_parent[i]), int(grid.other_parent[i])
        ev = Event(
            transactions=[f"tx{i}".encode()],
            parents=[signed[sp].hex() if sp >= 0 else root_self_parent(plist[c].id),
                     signed[op].hex() if op >= 0 else ""],
            creator=pub_key_bytes(keys[c]), index=int(grid.index[i]),
        )
        ev.sign(keys[c])
        signed.append(ev)
    return peers, keys[0], signed


def handed(ev):
    """A fresh copy, as a decoded wire event arrives (insert mutates)."""
    cp = Event(transactions=ev.body.transactions, parents=ev.body.parents,
               creator=ev.body.creator, index=ev.body.index)
    cp.signature = ev.signature
    return cp


class Blocks:
    def __init__(self):
        self.bodies = []

    def put(self, block):
        self.bodies.append(block.body.marshal())


def drive(backend):
    """Hand the stream over in SYNC-event syncs, then flush. Returns the
    Core, its committed block bodies and the last span id before the
    flush."""
    peers, key, signed = signed_stream()
    blocks = Blocks()
    core = Core(0, key, peers, InmemStore(peers, 2000), commit_ch=blocks,
                consensus_backend=backend)
    for lo in range(0, len(signed), SYNC):
        for ev in signed[lo:lo + SYNC]:
            core.insert_event(handed(ev), True)
        core.run_consensus()
    before_flush = max((s.id for s in core.hg.obs.tracer.spans()), default=0)
    core.flush_device_dispatch()
    return core, blocks.bodies, before_flush


@pytest.fixture(scope="module")
def cpu_run():
    core, blocks, _ = drive("cpu")
    return core, blocks


@pytest.fixture(scope="module")
def cpu_blocks(cpu_run):
    return cpu_run[1]


_RUNS = {}


@pytest.fixture(params=["sync", "pipelined"])
def run(request, monkeypatch):
    """One driven Core per discipline, built once and shared by the cases."""
    if request.param not in _RUNS:
        monkeypatch.setitem(live_mod.ENGINE_DEFAULTS, "async_fetch",
                            request.param == "pipelined")
        core, blocks, before_flush = drive("tpu")
        assert core.ladder_rung() == "live" and core.live_demotions == 0
        _RUNS[request.param] = (core, blocks, before_flush,
                                core.hg.obs.tracer.spans())
    return (request.param,) + _RUNS[request.param]


def named(spans, name):
    return [s for s in spans if s.name == name]


def test_spans_hang_under_run_consensus(run):
    discipline, core, _, before_flush, spans = run
    by_id = {s.id: s for s in spans}

    def root_of(sp):
        while sp.parent is not None:
            sp = by_id[sp.parent]
        return sp

    tree = [s for s in spans
            if s.name.startswith(("live.", "device.", "commit.",
                                  "consensus.process_"))]
    assert tree
    for sp in tree:
        if sp.id > before_flush:
            assert root_of(sp).name != "core.run_consensus"
        else:
            assert root_of(sp).name == "core.run_consensus", sp.name
    roots = named(spans, "core.run_consensus")
    assert [s.attrs["call"] for s in roots] == list(range(1, len(roots) + 1))
    assert {s.attrs["rung"] for s in roots} == {"live"}
    # a child lies inside its parent on the clock too
    for sp in tree:
        if sp.parent is not None:
            p = by_id[sp.parent]
            assert p.start <= sp.start
            assert sp.start + sp.duration <= p.start + p.duration + 1e-9
    # what each span hangs under
    parents = {(s.name, by_id[s.parent].name) for s in tree
               if s.parent is not None}
    assert ("live.stage", "device.dispatch") in parents
    assert ("live.launch", "device.dispatch") in parents
    assert ("live.admissible", "live.integrate") in parents
    assert ("commit.frame", "consensus.process_decided_rounds") in parents
    assert ("commit.block", "consensus.process_decided_rounds") in parents


def test_dispatch_id_ties_launch_fetch_and_integration(run):
    discipline, core, _, _, spans = run
    by_id = {s.id: s for s in spans}
    launched = {by_id[s.parent].attrs["dispatch"]
                for s in named(spans, "live.launch")}
    ids = {name: [s.attrs["dispatch"] for s in named(spans, name)]
           for name in ("device.dispatch", "live.pack", "device.fetch",
                        "live.integrate")}
    for name, seen in ids.items():
        assert len(seen) == len(set(seen)), name  # once in each
    # the attach dispatch (the first) packs the bootstrapped state: it has
    # no launch of its own; every other one has all four
    attach = ids["live.pack"][0]
    assert set(ids["live.pack"]) == set(ids["device.fetch"]) \
        == set(ids["live.integrate"]) == launched | {attach}
    assert set(ids["device.dispatch"]) == launched
    fetches = named(spans, "device.fetch")
    if discipline == "pipelined":
        assert {s.attrs["discipline"] for s in fetches[1:]} == {"pipelined"}
        assert max(s.attrs["lag_calls"] for s in fetches) >= 1
    else:
        assert {s.attrs["discipline"] for s in fetches} == {"sync"}
        assert {s.attrs["lag_calls"] for s in fetches} == {0}
    assert sum(s.attrs["rows"] for s in named(spans, "live.integrate")) == EVENTS
    assert sum(s.attrs["events"] for s in named(spans, "live.stage")) \
        == EVENTS - SYNC  # the first sync is bootstrapped, not staged


def test_totals_count_what_the_program_did(run, cpu_blocks):
    _, core, blocks, _, spans = run
    totals = core.hg.obs.tracer.totals()
    assert blocks and blocks == cpu_blocks
    assert totals["commit.block"][0] == len(blocks) == len(named(spans, "commit.block"))
    assert [s.attrs["index"] for s in named(spans, "commit.block")] \
        == list(range(len(blocks)))
    assert sum(s.attrs["txs"] for s in named(spans, "commit.block")) \
        == core.hg.consensus_transactions
    for name in ("insert", "insert.verify", "insert.fd"):
        assert totals[name][0] == EVENTS, name
    assert totals["insert"][1] >= totals["insert.verify"][1] + totals["insert.fd"][1]
    assert totals["core.run_consensus"][0] == EVENTS // SYNC
    assert "live.host_repair" not in totals
    hist = core.hg.obs.histogram("babble_consensus_pass_duration_seconds",
                                 labels=("phase",))
    assert hist.stats(phase="process_decided_rounds")[0] \
        == totals["consensus.process_decided_rounds"][0] > 0


def test_frames_read_the_stamped_rounds(run, cpu_blocks):
    """The device write-back's stamp is the memo of `Hashgraph.round`: no
    frame of the live rung derives a round, every root's rounds are read
    from stamps, and the bodies (frame hash included) stay the host
    engine's."""
    _, core, blocks, _, spans = run
    frames = named(spans, "commit.frame")
    assert len(frames) >= len(blocks) > 0  # a frame without events: no block
    assert [s.attrs["rounds_derived"] for s in frames] == [0] * len(frames)
    totals = core.hg.obs.tracer.totals()
    assert "round.derive" not in totals
    # at the least each frame's roots: their own, self- and other-parent's
    assert totals["round.stamp"][0] >= N * len(frames)
    assert totals["round.stamp"][1] == 0.0
    assert blocks == cpu_blocks


def test_host_engine_derives_in_divide_rounds_only(cpu_run):
    core, blocks = cpu_run
    tracer = core.hg.obs.tracer
    frames = named(tracer.spans(), "commit.frame")
    assert len(frames) >= len(blocks) > 0  # a frame without events: no block
    assert [s.attrs["rounds_derived"] for s in frames] == [0] * len(frames)
    totals = tracer.totals()
    # one derivation an event, but for those that hang on a root
    assert EVENTS - 2 * N <= totals["round.derive"][0] <= EVENTS
    assert "round.stamp" not in totals


def test_one_interval_is_booked_once(run):
    _, core, _, _, spans = run
    obs = core.hg.obs
    totals = obs.tracer.totals()
    cells = obs.devledger.snapshot()["cells"]
    advances = len(named(spans, "device.dispatch"))
    for component, span in (("stage", "live.stage"), ("fetch", "device.fetch"),
                            ("integrate", "live.integrate")):
        [cell] = [v for k, v in cells.items()
                  if k.startswith("live/dispatch/") and k.endswith("/" + component)]
        assert cell[0] == totals[span][0], component
        assert cell[1] == pytest.approx(totals[span][1], abs=1e-6)
    assert totals["live.stage"][0] == advances
    for metric, span in (("babble_device_dispatch_seconds", "device.dispatch"),
                         ("babble_device_fetch_seconds", "device.fetch")):
        count, seconds = obs.histogram(metric).stats()
        assert count == totals[span][0]
        assert seconds == pytest.approx(totals[span][1], abs=1e-9)


def test_checkpoints_bracket_every_call(run):
    _, core, _, _, spans = run
    tracer = core.hg.obs.tracer
    roots = named(spans, "core.run_consensus")
    first, last = roots[0], roots[-1]
    whole = tracer.totals_between(first.start - 1.0, last.start + last.duration + 1.0)
    assert whole["core.run_consensus"][0] == len(roots)
    # from the second call's entry: one call and its sync's inserts fewer
    later = tracer.totals_between(roots[1].start - 1e-4, last.start + last.duration + 1.0)
    assert later["core.run_consensus"][0] == len(roots) - 1
    assert later["insert"][0] == EVENTS - 2 * SYNC


INTEGRATE_CHILDREN = ("live.integrate.gate", "live.integrate.rounds",
                      "live.integrate.fame", "live.integrate.receptions")
INSERT_PARTS = ("insert.verify", "insert.lookup", "insert.coords",
                "insert.fd", "insert.listener")


@pytest.mark.parametrize("name", INTEGRATE_CHILDREN)
def test_integrate_child_hangs_under_its_dispatch(run, name):
    _, core, _, _, spans = run
    by_id = {s.id: s for s in spans}
    mine = named(spans, name)
    # every write-back of these runs is admitted: one of each a dispatch
    assert len(mine) == len(named(spans, "live.integrate")) > 0
    assert core.hg.obs.tracer.totals()[name][0] == len(mine)
    for sp in mine:
        parent = by_id[sp.parent]
        assert parent.name == "live.integrate"
        assert sp.attrs["dispatch"] == parent.attrs["dispatch"]


def test_integrate_children_sum_to_no_more_than_it(run):
    _, _, _, _, spans = run
    inside = {}
    for sp in spans:
        if sp.name in INTEGRATE_CHILDREN + ("live.admissible",
                                           "live.host_repair"):
            inside[sp.parent] = inside.get(sp.parent, 0.0) + sp.duration
    whole = named(spans, "live.integrate")
    assert set(inside) == {sp.id for sp in whole}
    for sp in whole:
        assert 0.0 < inside[sp.id] <= sp.duration + 1e-9


@pytest.mark.parametrize("name", INSERT_PARTS)
def test_insert_part_has_inserts_count(run, name):
    _, core, _, _, spans = run
    tracer = core.hg.obs.tracer
    totals = tracer.totals()
    # the listener is the live engine's, set when the first call attaches
    # it: the first sync's events had nobody listening
    want = EVENTS - SYNC if name == "insert.listener" else EVENTS
    assert totals[name][0] == want and totals[name][1] > 0.0
    # from the second call's entry: one call and its sync's inserts fewer,
    # as `insert` itself (test_checkpoints_bracket_every_call)
    roots = named(spans, "core.run_consensus")
    last = roots[-1]
    later = tracer.totals_between(roots[1].start - 1e-4,
                                  last.start + last.duration + 1.0)
    assert later[name][0] == later["insert"][0] == EVENTS - 2 * SYNC


def test_insert_parts_sum_to_no_more_than_insert(run, cpu_run):
    _, core, _, _, _ = run
    for totals in (core.hg.obs.tracer.totals(),
                   cpu_run[0].hg.obs.tracer.totals()):
        parts = sum(totals.get(name, (0, 0.0))[1] for name in INSERT_PARTS)
        assert 0.0 < parts <= totals["insert"][1]
    # nobody listens to the host engine's inserts
    assert "insert.listener" not in cpu_run[0].hg.obs.tracer.totals()
    assert cpu_run[0].hg.obs.tracer.totals()["insert.lookup"][0] == EVENTS


def test_inserts_reach_the_tracer_once_a_call():
    """The sums are kept in the Hashgraph and handed over by the next
    consensus call, before its entry checkpoint."""
    peers, key, signed = signed_stream()
    core = Core(0, key, peers, InmemStore(peers, 2000), commit_ch=Blocks(),
                consensus_backend="cpu")
    tracer = core.hg.obs.tracer
    for ev in signed[:SYNC]:
        core.insert_event(handed(ev), True)
    assert "insert" not in tracer.totals() and core.hg._inserts == SYNC
    core.run_consensus()
    assert tracer.totals()["insert"][0] == SYNC and core.hg._inserts == 0
    t0 = core.hg.obs.clock.monotonic()
    for ev in signed[SYNC:3 * SYNC]:
        core.insert_event(handed(ev), True)
    core.run_consensus()
    core.run_consensus()
    window = tracer.totals_between(t0, core.hg.obs.clock.monotonic())
    # the window opens at the second call's entry checkpoint, which the
    # second sync's inserts precede
    assert window["core.run_consensus"][0] == 2
    assert window.get("insert", (0, 0.0))[0] == 0
    assert tracer.totals()["insert"][0] == 3 * SYNC


@pytest.mark.parametrize("name,attr", [("commit.frame.events", "events"),
                                       ("commit.frame.roots", "roots_created")])
def test_frame_parts_count_the_frames(run, cpu_run, name, attr):
    for core in (run[1], cpu_run[0]):
        tracer = core.hg.obs.tracer
        totals = tracer.totals()
        frames = named(tracer.spans(), "commit.frame")
        assert totals[name][0] == sum(s.attrs[attr] for s in frames) > 0
        assert 0.0 < totals[name][1]
        assert totals["commit.frame.events"][1] + totals["commit.frame.roots"][1] \
            <= totals["commit.frame"][1]


def test_provenance_total_counts_the_captured_cells(run):
    _, core, _, _, _ = run
    marks = [m for m in core.hg.obs.provenance.to_json()["marks"]
             if m["name"] == "prov.capture"]
    count, seconds = core.hg.obs.tracer.totals()["obs.provenance"]
    assert count == sum(m["fields"]["cells"] for m in marks) > 0
    assert {m["fields"]["engine"] for m in marks} == {"live"}
    assert 0.0 < seconds <= core.hg.obs.tracer.totals()["live.integrate.rounds"][1]


def test_fetch_discipline_is_counted(run):
    discipline, core, _, _, spans = run
    totals = core.hg.obs.tracer.totals()
    fetches = named(spans, "device.fetch")
    pipelined = [s for s in fetches if s.attrs["discipline"] == "pipelined"]
    assert totals.get("fetch.pipelined", (0, 0.0))[0] == len(pipelined)
    assert totals["fetch.lag"] == (sum(s.attrs["lag_calls"] for s in fetches), 0.0)
    if discipline == "pipelined":
        assert len(pipelined) >= len(fetches) - 1 and totals["fetch.lag"][0] > 0
    else:
        assert not pipelined and totals["fetch.lag"][0] == 0


def test_refused_reception_is_counted_and_repaired(monkeypatch, cpu_blocks):
    """One fetched round-received moved past the newest decided round: the
    host rule refuses it, the host's reception pass runs in the device's
    place, and that is counted; blocks stay the CPU engine's."""
    monkeypatch.setitem(live_mod.ENGINE_DEFAULTS, "async_fetch", False)
    real = live_mod._unpack_results

    def unpack(packed, e_win, r_cap, n):
        out = list(real(packed, e_win, r_cap, n))
        received = out[3].copy()
        if received.max() >= 0:
            received[received == received.max()] += 1
        out[3] = received
        return tuple(out)

    monkeypatch.setattr(live_mod, "_unpack_results", unpack)
    core, blocks, _ = drive("tpu")
    obs = core.hg.obs
    repaired = obs.counter("babble_live_host_repaired_integrations_total").value()
    assert repaired > 0
    assert obs.tracer.totals()["live.host_repair"][0] == repaired
    assert core.ladder_rung() == "live"
    assert blocks == cpu_blocks


@pytest.mark.parametrize("discipline", ["sync", "pipelined"])
def test_gate_asks_each_round_once(discipline, monkeypatch, cpu_blocks):
    """`admissible.rounds` counts the gate's round look-ups: in every
    integration at most one for each distinct round its proposals cross
    (all of them when it admits), whatever the number of proposals."""
    monkeypatch.setitem(live_mod.ENGINE_DEFAULTS, "async_fetch",
                        discipline == "pipelined")
    real = engine_mod.admissible_receptions
    calls = []

    def gate(hg, round_infos, proposed):
        def lookups():
            return hg.obs.tracer.totals().get("admissible.rounds", (0, 0.0))[0]

        crossed = {i for h, rr in proposed
                   for i in range(hg.store.get_event(h).round + 1, rr + 1)}
        before = lookups()
        admitted = real(hg, round_infos, proposed)
        calls.append((len(proposed), len(crossed), lookups() - before, admitted))
        return admitted

    monkeypatch.setattr(engine_mod, "admissible_receptions", gate)
    core, blocks, _ = drive("tpu")
    assert core.ladder_rung() == "live" and core.live_demotions == 0
    assert blocks == cpu_blocks
    assert all(looked <= crossed for _, crossed, looked, _ in calls)
    assert all(looked == crossed for _, crossed, looked, ok in calls if ok)
    assert any(n > crossed > 0 for n, crossed, _, _ in calls)  # rounds are shared
    totals = core.hg.obs.tracer.totals()
    assert totals["admissible.rounds"] == (sum(c[2] for c in calls), 0.0)
    assert sum(s.attrs["proposed"] for s in named(
        core.hg.obs.tracer.spans(), "live.admissible")) == sum(c[0] for c in calls)
    assert "live.host_repair" not in totals


def test_rebase_has_a_span(monkeypatch, cpu_blocks):
    """A round axis too short for the stream: every rebase leaves a
    `live.rebase` span under the call that made it, carrying the new base."""
    monkeypatch.setitem(live_mod.ENGINE_DEFAULTS, "async_fetch", False)
    monkeypatch.setitem(live_mod.ENGINE_DEFAULTS, "r_cap", 16)
    core, blocks, _ = drive("tpu")
    spans = core.hg.obs.tracer.spans()
    by_id = {s.id: s for s in spans}
    rebases = [s for s in named(spans, "live.rebase") if s.attrs]
    eng = core.device_engine
    assert len(rebases) == eng.rebases > 0
    assert core.hg.obs.counter("babble_device_rebases_total").value() == eng.rebases
    assert [s.attrs["base"] for s in rebases] == sorted(s.attrs["base"] for s in rebases)
    assert rebases[-1].attrs["base"] == eng.round_base
    assert {by_id[s.parent].name for s in rebases} == {"core.run_consensus"}
    assert core.live_demotions == 0 and blocks == cpu_blocks
