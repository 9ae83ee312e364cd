"""`Hashgraph.insert_event` does each thing once (a validator's key parsed
once, a body marshalled and hashed once, parents and root fetched once) and
writes an event's first-descendant cells as ranges of one table
(`babble_tpu/hashgraph/coordinates.py`), not cell by cell down its
ancestors' chains; and leaves the cells, the listener's hashes, the rounds
and the blocks the insert it replaces left.

The yardstick is kept here: `PlainInsert`, `PlainInmemStore` and
`PlainSQLiteStore` are the insert and the stores' event accessors as they
stood before (every check re-fetching its parents, the walk going
`get_event` / mutate / `set_event`, the cells `(index, hash)` lists on the
events), so that a later change to either is still compared with the plain
loop and not with itself. Beside it a recount from the definition (`recount`):
validator p's first event whose last ancestor on E's chain is at or past E,
which asks no store and no walk.

The plain walk refreshes the store's recency on every ancestor it reads and
stops at one the store evicted; the table asks the store nothing. So under
a cache that evicts, the two leave the cache in another order and the table
writes on past an evicted ancestor: section (a) holds what must still agree
there (every cell the plain walk set, rounds, blocks).
"""

import functools
import json
import random

import numpy as np
import pytest

from babble_tpu import crypto
from babble_tpu.common import LRU, StoreErr, StoreErrType
from babble_tpu.hashgraph import (
    Block, Event, Frame, Hashgraph, InmemStore, SQLiteStore,
)
from babble_tpu.hashgraph import event as event_mod
from babble_tpu.hashgraph import sqlite_store
from babble_tpu.hashgraph.hashgraph import MAX_INT32
from benchmark.entries import replay, replay_adversarial


# ---------------------------------------------------------------------------
# the plain reference: insert and store accessors as they were
# ---------------------------------------------------------------------------


class PlainInmemStore(InmemStore):
    def get_event(self, key):
        res, ok = self.event_cache.get(key)
        if not ok:
            raise StoreErr("EventCache", StoreErrType.KEY_NOT_FOUND, key)
        return res

    def set_event(self, event):
        key = event.hex()
        _, ok = self.event_cache.get(key)
        if not ok:
            self._add_participant_event(event.creator(), key, event.index())
        self.event_cache.add(key, event)


class PlainSQLiteStore(SQLiteStore):
    def set_event(self, event):
        with self.db:
            row = self.db.execute(
                "SELECT topo_index FROM events WHERE hex = ?", (event.hex(),)
            ).fetchone()
            peer = self.inmem.participants().by_pub_key[event.creator()]
            last_known = self.inmem.participant_events_cache.known().get(peer.id, -1)
            if event.index() > last_known:
                self.inmem.set_event(event)
            else:
                self.inmem.event_cache.add(event.hex(), event)
            topo = row[0] if row else self._topo_counter
            if row is None:
                self._topo_counter += 1
            self.db.execute(
                "INSERT OR REPLACE INTO events VALUES (?, ?, ?, ?, ?, ?, ?)",
                (topo, event.hex(), event.creator(), event.index(),
                 json.dumps(event.to_store_json()), sqlite_store._hkey(event.hex()),
                 peer.id),
            )


def plain_verify(event):
    pub = crypto.pub_key_from_bytes(event.body.creator)
    r, s = crypto.decode_signature(event.signature)
    return crypto.verify(pub, event.body.hash(), r, s)


class PlainInsert(Hashgraph):
    """insert_event with every step fetching for itself."""

    def _check_self_parent(self, event):
        creator_last_known, _ = self.store.last_event_from(event.creator())
        if event.self_parent() != creator_last_known:
            raise ValueError("Self-parent not last known event by creator")

    def _check_other_parent(self, event, root=None):
        other_parent = event.other_parent()
        if other_parent == "":
            return
        try:
            self.store.get_event(other_parent)
            return
        except StoreErr:
            if other_parent in self.frozen_refs:
                return
            root = self.store.get_root(event.creator())
            other = root.others.get(event.hex())
            if other is not None and other.hash == other_parent:
                return
            raise ValueError("Other-parent not known")

    def _init_event_coordinates(self, event):
        n = len(self.participants)
        event.first_descendants = [(MAX_INT32, "")] * n
        sp = op = None
        try:
            sp = self.store.get_event(event.self_parent())
        except StoreErr:
            pass
        try:
            op = self.store.get_event(event.other_parent())
        except StoreErr:
            pass
        if sp is None and op is None:
            event.last_ancestors = [(-1, "")] * n
        elif sp is None:
            event.last_ancestors = list(op.last_ancestors)
        elif op is None:
            event.last_ancestors = list(sp.last_ancestors)
        else:
            event.last_ancestors = [
                a if a[0] >= b[0] else b
                for a, b in zip(sp.last_ancestors, op.last_ancestors)
            ]
        pos = self._pos_by_pubkey[event.creator()]
        coords = (event.index(), event.hex())
        event.first_descendants[pos] = coords
        event.last_ancestors[pos] = coords

    def _update_ancestor_first_descendant(self, event):
        pos = self._pos_by_pubkey[event.creator()]
        coords = (event.index(), event.hex())
        writes = []
        for _, ah in event.last_ancestors:
            while ah != "":
                try:
                    a = self.store.get_event(ah)
                except StoreErr:
                    break
                if a.first_descendants[pos][0] == MAX_INT32:
                    a.first_descendants[pos] = coords
                    self.store.set_event(a)
                    writes.append((ah, pos, coords[0]))
                    ah = a.self_parent()
                else:
                    break
        return writes

    def _set_wire_info(self, event):
        self_parent_index = -1
        other_parent_creator_id = -1
        other_parent_index = -1
        last_from, is_root = self.store.last_event_from(event.creator())
        if is_root and last_from == event.self_parent():
            root = self.store.get_root(event.creator())
            self_parent_index = root.self_parent.index
        else:
            self_parent = self.store.get_event(event.self_parent())
            self_parent_index = self_parent.index()
        if event.other_parent() != "":
            root = self.store.get_root(event.creator())
            other = root.others.get(event.hex())
            if other is not None and other.hash == event.other_parent():
                other_parent_creator_id = other.creator_id
                other_parent_index = other.index
            else:
                other_parent = self.store.get_event(event.other_parent())
                other_parent_creator_id = self.participants.by_pub_key[
                    other_parent.creator()
                ].id
                other_parent_index = other_parent.index()
        event.set_wire_info(
            self_parent_index, other_parent_creator_id, other_parent_index,
            self.participants.by_pub_key[event.creator()].id,
        )

    def insert_event(self, event, set_wire_info):
        if not plain_verify(event):
            raise ValueError("Invalid Event signature")
        self._check_self_parent(event)
        self._check_other_parent(event)
        event.topological_index = self.topological_index
        self.topological_index += 1
        if set_wire_info:
            self._set_wire_info(event)
        self._init_event_coordinates(event)
        self.store.set_event(event)
        fd_writes = self._update_ancestor_first_descendant(event)
        if self.insert_listener is not None:
            self.insert_listener(event, fd_writes)
        self.undetermined_events.append(event.hex())
        if event.is_loaded():
            self.pending_loaded_events += 1
        self.sig_pool.extend(event.block_signatures())


# ---------------------------------------------------------------------------
# streams
# ---------------------------------------------------------------------------

# validators -> events, span of a withheld episode in own events
SIZES = {4: (1200, "8-24"), 16: (2400, "12-48"), 64: (3000, "24-96"),
         128: (3000, "32-128")}
KINDS = ["honest", "withheld"]


@functools.lru_cache(maxsize=None)
def make_stream(n, kind):
    """Zipf-skewed gossip, or the same with a third of the validators
    withholding their chains and revealing them at once
    (`benchmark/traffic_adversarial.py`'s lifecycle, in arrival order)."""
    events, span = SIZES[n]
    if kind == "honest":
        return replay.Stream(n, events, 5, 1.1, 1)
    cfg = dict(validators=n, events=events, zipf_a=1.1, byzantine=n // 3,
               withhold_span=span, withhold_start_p=1 / 24,
               max_hidden=max(n // 8, 1))
    gen = replay.gen
    replay.gen = replay_adversarial.WithheldTraffic(cfg)
    try:
        return replay.Stream(n, events, 7, 1.1, 1)
    finally:
        replay.gen = gen


@pytest.fixture(scope="module")
def stream64():
    return make_stream(64, "honest")


@pytest.fixture(scope="module")
def stream8():
    return replay.Stream(8, 1200, 6, 1.1, 1)


def tracked(hg):
    """Record what the insert listener is handed. The plain walk hands
    (ancestor, column, value) triples; the insert hands the ancestors
    alone, column and value being the event's own creator position and
    index: spelled out here with the event's own, so that equality holds
    the ancestors, their order and both to the plain triples."""
    fed = []
    if isinstance(hg, PlainInsert):
        def listener(ev, writes):
            fed.append((ev.hex(), list(writes)))
    else:
        def listener(ev, cells):
            assert all(type(ah) is str for ah in cells)
            pos, index = hg.peer_position(ev.creator()), ev.index()
            fed.append((ev.hex(), [(ah, pos, index) for ah in cells]))
    hg.insert_listener = listener
    return fed


def outcome(hg, ev, set_wire_info):
    try:
        hg.insert_event(ev, set_wire_info)
    except (ValueError, StoreErr) as e:
        return type(e).__name__, str(e)
    return None


def wire_info(ev):
    b = ev.body
    return (b.self_parent_index, b.other_parent_creator_id,
            b.other_parent_index, b.creator_id)


def recount(hg, events):
    """{hash: first-descendant indices} of `events` (inserted, in order)
    from the definition: cell p of E (chain c, index j) is the index of
    p's first event whose last ancestor on chain c is at or past j."""
    n = len(hg.participants)
    chains = [[] for _ in range(n)]
    for ev in events:
        chains[hg.peer_position(ev.creator())].append(ev)
    last = [np.array([[a[0] for a in ev.last_ancestors] for ev in chain],
                     np.int64).reshape(len(chain), n) for chain in chains]
    index = [np.array([ev.index() for ev in chain] + [MAX_INT32], np.int64)
             for chain in chains]
    out = {}
    for ev in events:
        c, j = hg.peer_position(ev.creator()), ev.index()
        cells = []
        for p in range(n):
            at = last[p][:, c] >= j
            cells.append(int(index[p][at.argmax() if at.any() else -1]))
        out[ev.hex()] = cells
    return out


def frontier_of(hg, events):
    """[column p][chain c]: the highest index of chain c whose cell p is
    set, from the cells themselves; the index under the chain's oldest
    held event where none is."""
    n = len(hg.participants)
    front = np.tile(np.array(hg._coords._first, np.int64), (n, 1))
    for ev in events:
        c = hg.peer_position(ev.creator())
        for p, cell in enumerate(ev.first_descendants):
            if cell[0] != MAX_INT32:
                front[p, c] = max(front[p, c], ev.index())
    return front


def sampled_pairs(events, k, seed=11):
    rng = random.Random(seed)
    pairs = []
    for _ in range(k):
        y = rng.randrange(len(events) - 1)
        x = rng.randrange(y, min(len(events), y + 40 * 16))
        pairs.append((events[x].hex(), events[y].hex()))
    return pairs


def stamps(hg, events):
    out = []
    for signed in events:
        try:
            ev = hg.store.get_event(signed.hex())
        except StoreErr:
            out.append(None)
            continue
        out.append((ev.round, ev.lamport_timestamp, ev.round_received))
    return out


def bodies(hg):
    return [hg.store.get_block(i).body.marshal()
            for i in range(hg.store.last_block_index() + 1)]


# ---------------------------------------------------------------------------
# (a) the table against the plain walk: the listener's hashes, the cells,
# the frontier, strongly-see, rounds and blocks
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("n", sorted(SIZES))
def test_table_leaves_what_the_plain_walk_leaves(n, kind):
    """Nothing is evicted: every insert hands the listener the plain
    walk's ancestors in the plain walk's order, every event ends with the
    plain walk's cells (and the recount's), the frontier is what the cells
    say, strongly-see answers alike, and consensus every 200 events stamps
    and commits alike (4 and 16 validators commit blocks; a round is
    ~1,200 events at 64 and ~4,500 at 128)."""
    stream = make_stream(n, kind)
    peers = stream.peers
    new = Hashgraph(peers, InmemStore(peers, 50000))
    old = PlainInsert(peers, PlainInmemStore(peers, 50000))
    new_fed, old_fed = tracked(new), tracked(old)
    handed = []
    for i, signed in enumerate(stream.signed):
        a, b = stream.copy(signed), stream.copy(signed)
        assert outcome(new, a, True) is None and outcome(old, b, True) is None
        handed.append(a)
        assert new_fed[-1] == old_fed[-1], f"listener differs at event {i}"
        if i % 200 == 199:
            new.run_consensus()
            old.run_consensus()
    counted = recount(new, handed)
    for ev in handed:
        want = old.store.get_event(ev.hex())
        assert ev.first_descendants == want.first_descendants
        assert [c[0] for c in want.first_descendants] == counted[ev.hex()]
        assert ev.last_ancestors == want.last_ancestors
        assert wire_info(ev) == wire_info(want)
    la, fd = new.coordinate_rows(handed)
    assert fd.tolist() == [counted[ev.hex()] for ev in handed]
    assert la.tolist() == [[c[0] for c in ev.last_ancestors] for ev in handed]
    assert np.array_equal(new._coords.frontier, frontier_of(new, handed))
    for x, y in sampled_pairs(handed, 400):
        assert new.strongly_see(x, y) == old.strongly_see(x, y)
    assert stamps(new, handed) == stamps(old, handed)
    assert bodies(new) == bodies(old)
    if n <= 16:
        assert new.store.last_block_index() >= 3


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("n", sorted(SIZES))
def test_an_insert_writes_each_chains_range(n, kind):
    """What an insert by validator p hands its listener is, chain by chain
    in the validators' order and top down, chain c's events with indices in
    (frontier[p][c], last ancestor on c], p's own chain left out, and the
    frontier ends at the last ancestors: spelled out here from the frontier
    as it stood before the insert and a (chain, index) -> hash map the test
    keeps, with no table and no walk."""
    stream = make_stream(n, kind)
    peers = stream.peers
    hg = Hashgraph(peers, InmemStore(peers, 50000))
    fed = tracked(hg)
    hash_at = {}
    written = 0
    for signed in stream.signed:
        ev = stream.copy(signed)
        p = hg.peer_position(ev.creator())
        before = hg._coords.frontier[p].tolist()
        hg.insert_event(ev, True)
        hash_at[p, ev.index()] = ev.hex()
        want = []
        for c, (k, _) in enumerate(ev.last_ancestors):
            if c != p:
                want += [(hash_at[c, i], p, ev.index())
                         for i in range(k, before[c], -1)]
        assert fed[-1] == (ev.hex(), want)
        assert hg._coords.frontier[p].tolist() == [
            max(k, lo) for (k, _), lo in zip(ev.last_ancestors, before)]
        written += len(want)
    # towards a cell a column an insert, once the chains have met (3,000
    # events are a young graph at 128 validators)
    assert written > len(stream.signed) * n * 0.4


@pytest.mark.parametrize("set_wire_info", [True, False], ids=["wire", "nowire"])
@pytest.mark.parametrize("cache", [150, 1000, 1500, 50000])
def test_cells_are_the_recounts_whatever_the_cache_evicts(stream64, cache,
                                                         set_wire_info):
    """64 validators, a cache that evicts by recency alone (`pin_live`
    off) and no consensus, so that the table releases nothing. At 150
    entries chains lose their heads, parents' fetches miss and most of the
    stream is refused: a refused event leaves the chain map and the
    frontier alone, and the accepted ones end with the recount's cells
    though the store has evicted most of them. At 1,000 the plain walk's
    refreshes made chains lose their heads too; without them the cache
    evicts in insertion order and every event is inserted. At 1,500 every
    event is inserted over a cache that evicts half of them: the plain
    walk stops at an evicted ancestor and the table does not, so the
    listener is handed the plain walk's ancestors and, after them on a
    chain, those the plain walk could no longer read. At 50,000 nothing is
    evicted and the two agree event for event."""
    peers = stream64.peers
    new = Hashgraph(peers, InmemStore(peers, cache, pin_live=False))
    old = PlainInsert(peers, PlainInmemStore(peers, cache, pin_live=False))
    new_fed, old_fed = tracked(new), tracked(old)
    table = new._coords
    accepted, outcomes = [], []
    for i, signed in enumerate(stream64.signed):
        a = stream64.copy(signed)
        before = (table.frontier.copy(), list(table._held), list(table._last))
        got = outcome(new, a, set_wire_info)
        outcomes.append(got)
        if got is None:
            accepted.append(a)
        else:
            assert np.array_equal(table.frontier, before[0])
            assert (table._held, table._last) == before[1:]
        if cache >= 1500:
            assert got is None
            assert outcome(old, stream64.copy(signed), set_wire_info) is None
            (key, cells), (old_key, old_cells) = new_fed[-1], old_fed[-1]
            assert key == old_key
            if cache == 50000:
                assert cells == old_cells
            else:
                # chain by chain the plain walk's run is a prefix of the
                # table's: what follows it lies below an ancestor the plain
                # store had evicted
                rest = iter(cells)
                assert all(c in rest for c in old_cells), f"event {i}"
    assert table.base == 0 and table.top == new.topological_index
    counted = recount(new, accepted)
    la, fd = new.coordinate_rows(accepted)
    assert fd.tolist() == [counted[ev.hex()] for ev in accepted]
    assert np.array_equal(table.frontier, frontier_of(new, accepted))
    for ev in accepted:  # evicted or not: the event names its table
        assert [c[0] for c in ev.first_descendants] == counted[ev.hex()]
    inserted = len(accepted)
    refusals = {o[1].split(",")[0] for o in outcomes if o}
    assert len(new.store.event_cache) == min(inserted, cache)
    if cache == 150:
        assert 150 < inserted < len(stream64.signed)
        assert refusals >= {"Self-parent not last known event by creator",
                            "Other-parent not known"}
        # a self-parent gone from the store stops the wire info, not the
        # coordinates
        assert ("EventCache" in refusals) == set_wire_info
    else:
        assert inserted == len(stream64.signed)


@pytest.mark.parametrize("n,kind,cache", [(8, "honest", 120),
                                          (16, "honest", 1300),
                                          (4, "withheld", 64)])
def test_small_cache_evictions_and_released_rows(stream8, n, kind, cache):
    """The stores as a node builds them (`pin_live` on: only events that
    consensus has received and that left their chain's tail are evicted),
    consensus run every 40 events on both. The table releases its oldest
    rows in blocks, never one at or past the oldest event without a round
    received; what it released and the store still holds keeps its cells as
    a list. The two caches evict in another order (the walk refreshed
    what it read), and rounds, receptions and blocks are equal all the
    same; every cell the plain walk set, the table set."""
    stream = stream8 if n == 8 else make_stream(n, kind)
    peers = stream.peers
    new = Hashgraph(peers, InmemStore(peers, cache))
    old = PlainInsert(peers, PlainInmemStore(peers, cache))
    new_fed, old_fed = tracked(new), tracked(old)
    table = new._coords
    bases = set()
    for i, signed in enumerate(stream.signed):
        assert outcome(new, stream.copy(signed), True) is None
        assert outcome(old, stream.copy(signed), True) is None
        (key, cells), (old_key, old_cells) = new_fed[-1], old_fed[-1]
        rest = iter(cells)
        assert key == old_key and all(c in rest for c in old_cells)
        bases.add(table.base)
        if new.undetermined_events:
            oldest = new.store.get_event(new.undetermined_events[0])
            assert table.base <= oldest.topological_index
        if i % 40 == 39:
            new.run_consensus()
            old.run_consensus()
            assert stamps(new, stream.signed[: i + 1]) == stamps(
                old, stream.signed[: i + 1]) or (
                new.store.event_cache.keys() != old.store.event_cache.keys())
    assert len(bases) >= 2 and table.base > len(stream.signed) // 4
    assert table.top <= table.fd.shape[1] < len(stream.signed)
    assert bodies(new) == bodies(old) and len(bodies(new)) > 3
    released = 0
    for key in new.store.event_cache.keys():
        ev, _ = new.store.event_cache.peek(key)
        cells = ev.first_descendants
        if table.slot_of(ev) < 0:
            released += 1
            assert ev.round_received is not None
        want, there = old.store.event_cache.peek(key)
        if there:
            assert ev.last_ancestors == want.last_ancestors
            assert (ev.round, ev.round_received) == (want.round,
                                                     want.round_received)
            for cell, plain_cell in zip(cells, want.first_descendants):
                assert plain_cell in (cell, (MAX_INT32, ""))
    # a chain's tail stays pinned in a small cache long after its rows went
    assert released > 0 or cache > 200


def anchor_of(stream, upto):
    """(block, frame) of the newest block after `upto` events and
    consensus, as a joiner is sent them."""
    donor = Hashgraph(stream.peers, InmemStore(stream.peers, 50000))
    for i, signed in enumerate(stream.signed[:upto]):
        donor.insert_event(stream.copy(signed), True)
        if i % 100 == 99:
            donor.run_consensus()
    block = donor.store.get_block(donor.store.last_block_index())
    return block, donor.get_frame(block.round_received())


@pytest.mark.parametrize("n,kind", [(4, "honest"), (16, "withheld")])
def test_reset_fills_a_new_table(n, kind):
    """`reset` inserts the frame's events into a new table (rows from 0,
    chains that begin where the frame does); what the stream brings after
    it is refused or taken as the plain insert refuses or takes it, with
    the same hashes to the listener and the same cells."""
    stream = make_stream(n, kind)
    upto = len(stream.signed) * 2 // 3
    block, frame = anchor_of(stream, upto)
    peers = stream.peers
    new = Hashgraph(peers, InmemStore(peers, 50000))
    old = PlainInsert(peers, PlainInmemStore(peers, 50000))
    for signed in stream.signed[:200]:  # a table to be replaced
        new.insert_event(stream.copy(signed), True)
        old.insert_event(stream.copy(signed), True)
    before = new._coords
    for hg in (new, old):
        hg.reset(Block.from_json(block.to_json()),
                 Frame.from_json(frame.to_json()))
    table = new._coords
    assert table is not before and table.base == 0
    assert table.top == len(frame.events) == new.topological_index
    firsts = {}
    for ev in frame.events:
        firsts.setdefault(new.peer_position(ev.creator()), ev.index())
    for c, first in firsts.items():
        assert table._first[c] == first - 1
    new_fed, old_fed = tracked(new), tracked(old)
    taken = 0
    for signed in stream.signed:
        got = outcome(new, stream.copy(signed), True)
        assert got == outcome(old, stream.copy(signed), True)
        taken += got is None
    assert new_fed == old_fed and taken > 20
    held = [new.store.get_event(k) for k in new.store.event_cache.keys()]
    assert len(held) == len(frame.events) + taken
    for ev in held:
        want = old.store.get_event(ev.hex())
        assert ev.first_descendants == want.first_descendants
        assert ev.last_ancestors == want.last_ancestors
    assert np.array_equal(table.frontier, frontier_of(new, held))


@pytest.mark.parametrize("n,kind", [(4, "withheld"), (16, "honest")])
def test_section_ships_and_adopts_the_tables_rows(n, kind):
    """Fast-sync: the donor's section carries each event's cells as the
    table has them (the persisted form, unchanged on the wire), and the
    joiner's `apply_section` adopts them as rows: same cells, a frontier
    that is what the cells say, and the same strongly-see answers for the
    events that follow."""
    from babble_tpu.hashgraph.section import Section

    stream = make_stream(n, kind)
    peers, upto = stream.peers, len(stream.signed) * 2 // 3
    donor = Hashgraph(peers, InmemStore(peers, 50000))
    for i, signed in enumerate(stream.signed[:upto]):
        donor.insert_event(stream.copy(signed), True)
        if i % 100 == 99:
            donor.run_consensus()
    block = donor.store.get_block(donor.store.last_block_index() - 2)
    frame = donor.get_frame(block.round_received())
    section = donor.get_section(frame.round)
    shipped = Section.from_json(json.loads(json.dumps(section.to_json())))
    assert len(shipped.events) > 3 * n
    for ev, theirs in zip(shipped.events, section.events):
        assert ev.coordinates is None
        assert ev.first_descendants == theirs.first_descendants

    joiner = Hashgraph(peers, InmemStore(peers, 50000))
    joiner.reset(Block.from_json(block.to_json()),
                 Frame.from_json(frame.to_json()))
    joiner.apply_section(shipped)
    table = joiner._coords
    assert table.top == len(frame.events) + len(shipped.events)
    for ev, theirs in zip(shipped.events, section.events):
        assert table.slot_of(ev) >= 0 and ev.coordinates is table
        assert ev.first_descendants == theirs.first_descendants
    la, fd = joiner.coordinate_rows(shipped.events)
    theirs = donor.coordinate_rows(section.events)
    assert np.array_equal(la, theirs[0]) and np.array_equal(fd, theirs[1])
    held = [joiner.store.get_event(k) for k in joiner.store.event_cache.keys()]
    assert np.array_equal(table.frontier, frontier_of(joiner, held))
    # what follows: both take it, and see the section's events alike
    fed, their_fed = tracked(joiner), tracked(donor)
    later = []
    for signed in stream.signed[upto:]:
        assert outcome(joiner, stream.copy(signed), True) is None
        assert outcome(donor, stream.copy(signed), True) is None
        later.append(signed.hex())
    section_hashes = {ev.hex() for ev in shipped.events}
    for (key, cells), (their_key, their_cells) in zip(fed, their_fed):
        mine = [c for c in cells if c[0] in section_hashes]
        assert key == their_key
        assert mine == [c for c in their_cells if c[0] in section_hashes]
    rng = random.Random(5)
    for _ in range(300):
        x, y = rng.choice(later), rng.choice(shipped.events).hex()
        assert joiner.strongly_see(x, y) == donor.strongly_see(x, y)


# ---------------------------------------------------------------------------
# (b) the digest is the handed body's, whatever was cached
# ---------------------------------------------------------------------------


def test_body_altered_after_hex_fails_verify_and_insert(stream8):
    hg = Hashgraph(stream8.peers, InmemStore(stream8.peers, 1000))
    for signed in stream8.signed[:8]:
        hg.insert_event(stream8.copy(signed), True)
    ev = stream8.copy(stream8.signed[8])
    honest_hex = ev.hex()  # caches digest and hex of the honest body
    assert ev.verify()
    ev.body.transactions = [b"altered after hex()"]
    assert not ev.verify()
    # the cache now answers for the body as it stands, not the honest one
    assert ev.hex() != honest_hex
    assert ev.hex() == "0x" + ev.body.hash().hex().upper()
    ev2 = stream8.copy(stream8.signed[8])
    ev2.hex()
    ev2.body.transactions = [b"altered after hex()"]
    with pytest.raises(ValueError, match="Invalid Event signature"):
        hg.insert_event(ev2, True)
    assert hg.topological_index == 8
    hg.insert_event(stream8.copy(stream8.signed[8]), True)


def test_verify_leaves_the_digest_it_checked(stream8, monkeypatch):
    calls = []
    dumps = event_mod.canonical_dumps
    monkeypatch.setattr(event_mod, "canonical_dumps",
                        lambda obj: calls.append(1) or dumps(obj))
    ev = stream8.copy(stream8.signed[0])
    assert ev.verify()
    assert len(calls) == 1
    assert ev.hex() == stream8.signed[0].hex() and ev.hash()
    assert len(calls) == 1
    # and again from the body, not from the cache
    assert ev.verify()
    assert len(calls) == 2


def test_forged_event_refused_after_the_key_was_parsed(stream8):
    hg = Hashgraph(stream8.peers, InmemStore(stream8.peers, 1000))
    rows = list(range(40))
    for i in rows:
        hg.insert_event(stream8.copy(stream8.signed[i]), True)
    nxt = stream8.signed[40]
    assert nxt.body.creator in hg._validator_keys  # its key is parsed
    # a body of the same creator under another body's signature
    forged = Event(transactions=[b"forged"], parents=list(nxt.body.parents),
                   creator=nxt.body.creator, index=nxt.body.index)
    forged.signature = nxt.signature
    with pytest.raises(ValueError, match="Invalid Event signature"):
        hg.insert_event(forged, True)
    # signed by another validator's key under this creator's name
    other = next(e for e in stream8.signed[:40]
                 if e.body.creator != nxt.body.creator)
    forged2 = stream8.copy(nxt)
    forged2.signature = other.signature
    with pytest.raises(ValueError, match="Invalid Event signature"):
        hg.insert_event(forged2, True)
    assert hg.topological_index == 40
    hg.insert_event(stream8.copy(nxt), True)
    assert hg.topological_index == 41


# ---------------------------------------------------------------------------
# (c) the key table holds validators only
# ---------------------------------------------------------------------------


def test_key_table_holds_validators_only(stream8):
    peers = stream8.peers
    hg = Hashgraph(peers, InmemStore(peers, 2000))
    plain = PlainInsert(peers, PlainInmemStore(peers, 2000))
    outsiders = [crypto.generate_key() for _ in range(5)]
    refused = 0
    for i, signed in enumerate(stream8.signed[:400]):
        if i % 20 == 10:
            key = outsiders[(i // 20) % len(outsiders)]
            ev = Event(transactions=[b"outsider"],
                       parents=list(signed.body.parents),
                       creator=crypto.pub_key_bytes(key), index=0)
            ev.sign(key)
            assert ev.verify()  # a good signature, by nobody in the set
            got = outcome(hg, ev, True)
            want = outcome(plain, replay.Stream.copy(ev), True)
            assert got == want and got[0] == "StoreErr"
            assert "ParticipantEvents" in got[1]
            refused += 1
        assert outcome(hg, stream8.copy(signed), True) is None
        assert outcome(plain, stream8.copy(signed), True) is None
    assert refused == 20
    assert len(hg._validator_keys) == len(peers) == 8
    assert set(hg._validator_keys) == {e.body.creator for e in stream8.signed[:8]}
    assert hg.topological_index == 400
    # a malformed key is refused as it was: the parser's own error
    bad = stream8.copy(stream8.signed[400])
    bad.body.creator = b"\x04" + b"\x01" * 64
    assert outcome(hg, bad, True) == outcome(plain, stream8.copy(bad), True)
    assert len(hg._validator_keys) == 8


# ---------------------------------------------------------------------------
# (d) a store that persists: the table is the truth, the rows follow
# ---------------------------------------------------------------------------


def on_disk(cls, peers, cache, path):
    store = cls(peers, cache, str(path))
    store.db.execute("PRAGMA synchronous=OFF")  # the test needs no fsync
    return store


def db_rows(store):
    return store.db.execute(
        "SELECT hex, topo_index, creator, idx, data FROM events "
        "ORDER BY topo_index").fetchall()


def on_disk_cells(store, key):
    data = store.db.execute(
        "SELECT data FROM events WHERE hex = ?", (key,)).fetchone()[0]
    return [tuple(c) for c in json.loads(data)["Meta"]["FirstDescendants"]]


def test_sqlite_store_reads_back_through_the_table(stream8, tmp_path):
    """A cache of 60 under 900 events: most ancestors an insert writes to
    were evicted. No insert rewrites an ancestor's row (a row is written
    whole once, when its event is stored; its stamps go to a row of their
    own, and its cells once more when the table releases it); an evicted
    event read back answers through the table while the table holds its
    row, and from its row's final cells after."""
    peers = stream8.peers
    events = stream8.signed[:900]
    disk = on_disk(SQLiteStore, peers, 60, tmp_path / "new.db")
    mem = InmemStore(peers, 50000)
    hg, ref = Hashgraph(peers, disk), Hashgraph(peers, mem)
    disk_fed, fed = tracked(hg), tracked(ref)
    writes = []
    put = disk._db_put_event
    disk._db_put_event = lambda ev: writes.append(ev.hex()) or put(ev)
    for i, signed in enumerate(events):
        for g in (hg, ref):
            g.insert_event(stream8.copy(signed), True)
        if i % 50 == 49:
            hg.run_consensus()
            ref.run_consensus()
    assert disk_fed == fed
    assert bodies(hg) == bodies(ref) and len(bodies(hg)) > 3
    # one whole row an insert, in insertion order, and never another
    assert writes == [e.hex() for e in events]
    assert [r[0] for r in db_rows(disk)] == [e.hex() for e in events]
    table = hg._coords
    assert 0 < table.base and len(disk.inmem.event_cache) <= 60
    # evict everything, then read each event back from the database
    disk.inmem.event_cache = LRU(60)
    live = frozen = 0
    for signed in events:
        back = disk.get_event(signed.hex())
        want = mem.get_event(signed.hex())
        assert back is not want and back.coordinates is table
        assert back.last_ancestors == want.last_ancestors
        if table.slot_of(back) >= 0:
            live += 1
            assert back.first_descendants == want.first_descendants
        else:
            # released: the row holds the cells it had then, and a cell a
            # later insert would have written is the only difference
            frozen += 1
            assert back.first_descendants == on_disk_cells(disk, signed.hex())
            for cell, full in zip(back.first_descendants,
                                  want.first_descendants):
                assert cell in (full, (MAX_INT32, ""))
            assert sum(c[0] != MAX_INT32 for c in back.first_descendants) >= 6
    assert live >= 60 and frozen == table.base
    disk.close()


def test_a_stored_event_written_again_keeps_its_row(stream8, tmp_path):
    """`set_event` of an event the store has registered (a stamp written
    back, by the object it handed out or by a copy read from disk):
    InmemStore keeps the object it has, SQLiteStore refreshes the cache and
    the row's stamps under the row's topological index, leaves the row as
    it was, and neither registers it again."""
    peers = stream8.peers
    mem = InmemStore(peers, 100)
    hg = Hashgraph(peers, mem)
    for signed in stream8.signed[:20]:
        hg.insert_event(stream8.copy(signed), True)
    known = mem.known_events()
    oldest = mem.event_cache.peek(mem.event_cache.keys()[0])[0]
    oldest.set_round(7)
    mem.set_event(oldest)
    assert mem.known_events() == known
    assert mem.get_event(oldest.hex()) is oldest and oldest.round == 7

    disk = on_disk(SQLiteStore, peers, 100, tmp_path / "s.db")
    hd = Hashgraph(peers, disk)
    for signed in stream8.signed[:20]:
        hd.insert_event(stream8.copy(signed), True)
    h = stream8.signed[3].hex()
    rows = db_rows(disk)
    topo = dict((r[0], r[1]) for r in rows)[h]
    disk.inmem.event_cache = LRU(100)
    ev = disk.get_event(h)  # a copy read from disk
    ev.set_round(7)
    disk.set_event(ev)
    assert disk.inmem.event_cache.peek(h)[0] is ev
    disk.inmem.event_cache = LRU(100)
    back = disk.get_event(h)
    assert back.round == 7
    assert disk.db.execute("SELECT round FROM stamps WHERE topo_index = ?",
                           (topo,)).fetchone() == (7,)
    # the row carries no cells: the table answers while it holds them
    data = dict((r[0], r[4]) for r in rows)[h]
    assert json.loads(data)["Meta"]["FirstDescendants"] is None
    assert back.coordinates is hd._coords
    assert back.first_descendants == hd._coords.cells(3)
    assert db_rows(disk) == rows
    assert disk.known_events() == known
    disk.close()


def test_sqlite_event_from_before_a_reset_keeps_its_own_cells(tmp_path):
    """`SQLiteStore.reset` keeps the old events' rows and `Hashgraph.reset`
    numbers its inserts from 0 again in a new table: an event from before
    the reset, read back after it, has a topological index that is now
    another event's slot. It answers with the cells it had when the reset
    came (written to its row then), not with that slot's; an event of the
    frame, inserted again, answers through the new table."""
    stream = make_stream(4, "honest")
    peers = stream.peers
    block, frame = anchor_of(stream, 800)
    disk = on_disk(SQLiteStore, peers, 50000, tmp_path / "r.db")
    hg = Hashgraph(peers, disk)
    ref = Hashgraph(peers, InmemStore(peers, 50000))
    for signed in stream.signed[:600]:
        hg.insert_event(stream.copy(signed), True)
        ref.insert_event(stream.copy(signed), True)
    old_table = hg._coords
    final = {signed.hex(): ref.store.get_event(signed.hex()).first_descendants
             for signed in stream.signed[:600]}
    hg.reset(Block.from_json(block.to_json()), Frame.from_json(frame.to_json()))
    for signed in stream.signed[600:]:
        outcome(hg, stream.copy(signed), True)
    table = hg._coords
    assert table is not old_table and table.top > len(frame.events) + 20
    again = {ev.hex() for ev in frame.events}
    strangers = lookalikes = 0
    for signed in stream.signed[:600]:
        back = disk.get_event(signed.hex())
        if signed.hex() in again:
            assert table.slot_of(back) >= 0
            continue
        assert back.coordinates is table
        lookalikes += back.topological_index < table.top
        assert table.slot_of(back) == -1
        assert back.first_descendants == final[signed.hex()]
        assert table.rows([back]).tolist() == [
            [c[0] for c in final[signed.hex()]]]
        strangers += 1
    assert strangers > 400 and lookalikes > 200
    disk.close()


def test_sqlite_event_stored_again_after_a_reset_reads_back_renumbered(tmp_path):
    """The frame a reset inserts can hold events whose rows are on disk
    already: their rows stay as written, and their stamps row carries the
    topological index the new table gave them, so that each one, evicted
    and read back, answers through the new table with the live stamps."""
    stream = make_stream(4, "honest")
    peers = stream.peers
    block, frame = anchor_of(stream, 600)
    disk = on_disk(SQLiteStore, peers, 50000, tmp_path / "r.db")
    hg = Hashgraph(peers, disk)
    for signed in stream.signed[:600]:
        hg.insert_event(stream.copy(signed), True)
    rows = db_rows(disk)
    hg.reset(Block.from_json(block.to_json()), Frame.from_json(frame.to_json()))
    # every frame event was stored before: no row was added or moved (the
    # release patch wrote the old table's cells into them)
    assert [r[:4] for r in db_rows(disk)] == [r[:4] for r in rows]
    live = {ev.hex(): disk.get_event(ev.hex()) for ev in frame.events}
    on_row = {r[0]: json.loads(r[4])["Meta"]["Topo"] for r in rows}
    disk.inmem.event_cache = LRU(50000)
    table = hg._coords
    renumbered = 0
    for key, ev in live.items():
        back = disk.get_event(key)
        assert back is not ev and table.slot_of(back) >= 0
        renumbered += back.topological_index != on_row[key]
        assert back.first_descendants == ev.first_descendants
        assert ((back.round, back.lamport_timestamp, back.round_received)
                == (ev.round, ev.lamport_timestamp, ev.round_received))
    assert len(live) > 4 and renumbered == len(live)
    disk.close()


def test_lru_fetch_refreshes_like_get():
    a, b = LRU(3), LRU(3)
    for k in "xyz":
        a.add(k, k.upper())
        b.add(k, k.upper())
    assert a.fetch("x") == b.get("x")[0] == "X"
    assert a.keys() == b.keys() == ["y", "z", "x"]
    with pytest.raises(KeyError):
        a.fetch("missing")
    assert a.keys() == ["y", "z", "x"]


# ---------------------------------------------------------------------------
# (e) once: marshal per event, parse per validator, and the total that says so
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_fixture", ["stream8", "stream64"])
def test_marshal_once_an_event_and_parse_once_a_validator(request, n_fixture,
                                                          monkeypatch):
    stream = request.getfixturevalue(n_fixture)
    n = len(stream.peers)
    events = stream.signed[:600]
    handed = [stream.copy(ev) for ev in events]
    marshals, parses = [], []
    dumps, parse = event_mod.canonical_dumps, crypto.pub_key_from_bytes
    monkeypatch.setattr(event_mod, "canonical_dumps",
                        lambda obj: marshals.append(1) or dumps(obj))
    monkeypatch.setattr(crypto, "pub_key_from_bytes",
                        lambda raw: parses.append(raw) or parse(raw))
    hg = Hashgraph(stream.peers, InmemStore(stream.peers, 5000))
    for ev in handed:
        hg.insert_event(ev, True)
    assert len(marshals) == len(handed)
    assert len(parses) == n and len(set(parses)) == n
    # every event still carries its own hash, from that one marshalling
    assert [ev.hex() for ev in handed] == [ev.hex() for ev in events]
    assert len(marshals) == len(handed)

    # the counts reach the tracer once a consensus call, not once an event
    totals = hg.obs.tracer.totals()
    assert "insert.key_hit" not in totals and "insert" not in totals
    assert hg._key_hits == len(handed) - n
    hg.process_decided_rounds()
    assert hg.obs.tracer.totals()["insert"][0] == len(handed)
    assert hg.obs.tracer.totals()["insert.key_hit"] == (len(handed) - n, 0.0)
    assert hg._key_hits == 0
    hg.process_decided_rounds()
    assert hg.obs.tracer.totals()["insert.key_hit"] == (len(handed) - n, 0.0)


def test_refused_inserts_count_no_key_hit(stream8):
    hg = Hashgraph(stream8.peers, InmemStore(stream8.peers, 1000))
    for signed in stream8.signed[:8]:
        hg.insert_event(stream8.copy(signed), True)
    assert hg._key_hits == 0  # eight first events: eight parses
    # a replayed event: its key is found, its self-parent check refuses it
    with pytest.raises(ValueError, match="Self-parent not last known"):
        hg.insert_event(stream8.copy(stream8.signed[0]), True)
    assert hg._key_hits == 1


# ---------------------------------------------------------------------------
# (f) re-created events find no stale digest, key or cell
# ---------------------------------------------------------------------------


def test_bootstrap_from_disk_inserts_again(stream8, tmp_path):
    """A restart rebuilds the table: `bootstrap` replays the inserts, and
    whatever cells the rows on disk carried are not read."""
    peers = stream8.peers
    path = str(tmp_path / "boot.db")
    first = Hashgraph(peers, on_disk(SQLiteStore, peers, 200, path))
    for i, signed in enumerate(stream8.signed[:400]):
        first.insert_event(stream8.copy(signed), True)
        if i % 50 == 49:
            first.run_consensus()
    first.run_consensus()
    last_block = first.store.last_block_index()
    assert last_block >= 2
    # whatever the rows say of cells, the restart does not read it
    with first.store.db:
        for key, _, _, _, data in db_rows(first.store):
            d = json.loads(data)
            d["Meta"]["FirstDescendants"] = None
            first.store.db.execute(
                "UPDATE events SET data = ? WHERE hex = ?",
                (json.dumps(d), key))
    first.store.close()

    again = Hashgraph(peers, SQLiteStore.load_or_create(peers, 200, path))
    assert again.store.need_bootstrap()
    again.store.db.execute("PRAGMA synchronous=OFF")
    again.bootstrap()
    assert again.topological_index == 400
    assert len(again._validator_keys) == 8
    assert again.store.last_block_index() == last_block
    mem = Hashgraph(peers, InmemStore(peers, 5000))
    for i, signed in enumerate(stream8.signed[:400]):
        mem.insert_event(stream8.copy(signed), True)
        if i % 50 == 49:
            mem.run_consensus()
    mem.run_consensus()
    assert bodies(again) == bodies(mem)
    table = again._coords
    held = 0
    for signed in stream8.signed[:400]:
        back = again.store.get_event(signed.hex())
        want = mem.store.get_event(signed.hex())
        if table.slot_of(back) >= 0:
            held += 1
            assert back.first_descendants == want.first_descendants
        else:
            for cell, full in zip(back.first_descendants,
                                  want.first_descendants):
                assert cell in (full, (MAX_INT32, ""))
    assert held >= 200
    again.store.close()
