"""`Hashgraph.insert_event` does each thing once (a validator's key parsed
once, a body marshalled and hashed once, one look-up and one recency refresh
per step of the first-descendant walk, parents and root fetched once), and
leaves exactly what the insert it replaces left.

The yardstick is kept here: `PlainInsert`, `PlainInmemStore` and
`PlainSQLiteStore` are the insert and the stores' event accessors as they
stood before (every check re-fetching its parents, the walk going
`get_event` / mutate / `set_event`), so that a later change to either is
still compared with the plain loop and not with itself.
"""

import json

import pytest

from babble_tpu import crypto
from babble_tpu.common import LRU, StoreErr, StoreErrType
from babble_tpu.hashgraph import Event, Hashgraph, InmemStore, SQLiteStore
from babble_tpu.hashgraph import event as event_mod
from babble_tpu.hashgraph.hashgraph import MAX_INT32
from benchmark.entries import replay


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
                "INSERT OR REPLACE INTO events VALUES (?, ?, ?, ?, ?)",
                (event.hex(), topo, event.creator(), event.index(),
                 json.dumps(event.to_store_json())),
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


@pytest.fixture(scope="module")
def stream64():
    return replay.Stream(64, 3000, 5, 1.1, 1)


@pytest.fixture(scope="module")
def stream8():
    return replay.Stream(8, 1200, 6, 1.1, 1)


def tracked(hg):
    """Record what the insert listener is handed and what the event cache
    evicts. The plain walk hands (ancestor, column, value) triples; the
    insert hands the ancestors alone, column and value being the event's
    own creator position and index: spelled out here with the event's own,
    so that equality holds the ancestors, their order and both to the plain
    triples."""
    fed, evicted = [], []
    if isinstance(hg, PlainInsert):
        def listener(ev, writes):
            fed.append((ev.hex(), list(writes)))
    else:
        def listener(ev, cells):
            assert all(type(ah) is str for ah in cells)
            pos, index = hg.peer_position(ev.creator()), ev.index()
            fed.append((ev.hex(), [(ah, pos, index) for ah in cells]))
    hg.insert_listener = listener
    cache = getattr(hg.store, "inmem", hg.store).event_cache
    cache.on_evict = lambda key, _ev: evicted.append(key)
    return fed, evicted


def outcome(hg, ev, set_wire_info):
    try:
        hg.insert_event(ev, set_wire_info)
    except (ValueError, StoreErr) as e:
        return type(e).__name__, str(e)
    return None


def stored_state(store):
    """Every cached event, oldest first, with all insert leaves on it."""
    out = []
    for key in store.event_cache.keys():
        ev, _ = store.event_cache.peek(key)
        b = ev.body
        out.append((key, ev.topological_index, ev.first_descendants,
                    ev.last_ancestors,
                    (b.self_parent_index, b.other_parent_creator_id,
                     b.other_parent_index, b.creator_id)))
    return out


# ---------------------------------------------------------------------------
# (a) the insert against the plain one: deltas, coordinates, cache order,
# evictions
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("set_wire_info", [True, False], ids=["wire", "nowire"])
@pytest.mark.parametrize("cache", [150, 1000, 1500, 50000])
def test_insert_leaves_what_the_plain_insert_leaves_v64(stream64, cache,
                                                        set_wire_info):
    """64 validators, a cache that evicts by recency alone (`pin_live` off).
    At 150 and 1,000 entries chains lose their heads, so parents' fetches
    miss and most or some of the stream is refused, which both inserts must
    take the same way, leaving the cache's order alone; at 1,500 every
    event is inserted over a cache that evicts half of them, so the walk
    runs into evicted ancestors; at 50,000 nothing is evicted."""
    peers = stream64.peers
    new = Hashgraph(peers, InmemStore(peers, cache, pin_live=False))
    old = PlainInsert(peers, PlainInmemStore(peers, cache, pin_live=False))
    new_fed, new_evicted = tracked(new)
    old_fed, old_evicted = tracked(old)
    outcomes = []
    for i, signed in enumerate(stream64.signed):
        a, b = stream64.copy(signed), stream64.copy(signed)
        got, want = outcome(new, a, set_wire_info), outcome(old, b, set_wire_info)
        assert got == want, f"event {i}"
        outcomes.append(got)
        assert new_fed == old_fed, f"fd_writes differ at event {i}"
        new_fed.clear()
        old_fed.clear()
        assert new.store.event_cache.keys() == old.store.event_cache.keys(), (
            f"cache order differs after event {i}")
    assert new_evicted == old_evicted
    assert stored_state(new.store) == stored_state(old.store)
    assert new.undetermined_events == old.undetermined_events
    assert new.topological_index == old.topological_index
    inserted = outcomes.count(None)
    refusals = {o[1].split(",")[0] for o in outcomes if o}
    assert len(new_evicted) == max(inserted - cache, 0)
    if cache == 150 or (cache == 1000 and set_wire_info):
        assert {150: 200, 1000: 2000}[cache] < inserted < len(stream64.signed)
        assert refusals >= {"Self-parent not last known event by creator",
                            "Other-parent not known"}
        # a self-parent gone from the store stops the wire info, not the
        # coordinates
        assert ("EventCache" in refusals) == set_wire_info
    else:
        assert inserted == len(stream64.signed)


def test_insert_leaves_what_the_plain_insert_leaves_pinned(stream8):
    """The stores as a node builds them (`pin_live` on: only events that
    consensus has received and that left their chain's tail are evicted),
    consensus run every 40 events on both."""
    peers = stream8.peers
    new = Hashgraph(peers, InmemStore(peers, 120))
    old = PlainInsert(peers, PlainInmemStore(peers, 120))
    new_fed, new_evicted = tracked(new)
    old_fed, old_evicted = tracked(old)
    for i, signed in enumerate(stream8.signed):
        a, b = stream8.copy(signed), stream8.copy(signed)
        assert outcome(new, a, True) is None
        assert outcome(old, b, True) is None
        if i % 40 == 39:
            new.run_consensus()
            old.run_consensus()
            assert new.store.event_cache.keys() == old.store.event_cache.keys()
    assert new_fed == old_fed
    assert new_evicted == old_evicted and len(new_evicted) > 500
    assert new.store.event_cache.keys() == old.store.event_cache.keys()
    assert stored_state(new.store) == stored_state(old.store)
    assert new.store.last_block_index() == old.store.last_block_index() > 3


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
# (d) a store that persists writes the walk's cells through
# ---------------------------------------------------------------------------


def on_disk(cls, peers, cache, path):
    store = cls(peers, cache, str(path))
    store.db.execute("PRAGMA synchronous=OFF")  # the test needs no fsync
    return store


def db_rows(store):
    return store.db.execute(
        "SELECT hex, topo_index, creator, idx, data FROM events "
        "ORDER BY topo_index").fetchall()


def test_sqlite_store_persists_the_walks_cells(stream8, tmp_path):
    peers = stream8.peers
    events = stream8.signed[:500]
    # a cache of 60: most ancestors the walk writes to were evicted and are
    # read back from disk, mutated as a copy and written through
    disk = on_disk(SQLiteStore, peers, 60, tmp_path / "new.db")
    plain_disk = on_disk(PlainSQLiteStore, peers, 60, tmp_path / "plain.db")
    mem = InmemStore(peers, 50000)
    hg, plain, ref = (Hashgraph(peers, disk), PlainInsert(peers, plain_disk),
                      Hashgraph(peers, mem))
    fed, _ = tracked(ref)
    disk_fed, _ = tracked(hg)
    plain_fed, _ = tracked(plain)
    for signed in events:
        for g in (hg, plain, ref):
            g.insert_event(stream8.copy(signed), True)
    assert disk_fed == fed == plain_fed
    # what is on disk is what the plain write-back put there, row for row
    assert db_rows(disk) == db_rows(plain_disk)
    assert (disk.inmem.event_cache.keys()
            == plain_disk.inmem.event_cache.keys())
    # evict everything, then read each event back from the database
    disk.inmem.event_cache = LRU(60)
    assert len(disk.inmem.event_cache) == 0
    written = 0
    for signed in events:
        back = disk.get_event(signed.hex())
        want = mem.get_event(signed.hex())
        assert back is not want
        assert back.first_descendants == want.first_descendants
        assert back.last_ancestors == want.last_ancestors
        written += sum(1 for c in back.first_descendants if c[0] != MAX_INT32)
    assert written > 8 * len(events) // 2
    disk.close()
    plain_disk.close()


def test_update_event_is_the_stores_own(stream8, tmp_path):
    """InmemStore: nothing, not even a recency refresh. SQLiteStore: cache
    and row."""
    peers = stream8.peers
    mem = InmemStore(peers, 100)
    hg = Hashgraph(peers, mem)
    for signed in stream8.signed[:20]:
        hg.insert_event(stream8.copy(signed), True)
    order = mem.event_cache.keys()
    oldest = mem.event_cache.peek(order[0])[0]
    oldest.set_round(7)
    mem.update_event(oldest)
    assert mem.event_cache.keys() == order
    assert mem.get_event(order[0]).round == 7

    disk = on_disk(SQLiteStore, peers, 100, tmp_path / "s.db")
    hd = Hashgraph(peers, disk)
    for signed in stream8.signed[:20]:
        hd.insert_event(stream8.copy(signed), True)
    h = stream8.signed[3].hex()
    rows = len(db_rows(disk))
    topo = dict((r[0], r[1]) for r in db_rows(disk))[h]
    ev = disk.get_event(h)
    ev.set_round(7)
    disk.update_event(ev)
    disk.inmem.event_cache = LRU(100)
    assert disk.get_event(h).round == 7
    assert len(db_rows(disk)) == rows
    assert dict((r[0], r[1]) for r in db_rows(disk))[h] == topo
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

    # the count reaches the tracer once a consensus call, not once an event
    totals = hg.obs.tracer.totals()
    assert "insert.key_hit" not in totals
    assert totals["insert"][0] == len(handed)
    assert hg._key_hits == len(handed) - n
    hg.process_decided_rounds()
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
# (f) re-created events find no stale digest or key
# ---------------------------------------------------------------------------


def test_bootstrap_from_disk_inserts_again(stream8, tmp_path):
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
    first.store.close()

    again = Hashgraph(peers, SQLiteStore.load_or_create(peers, 200, path))
    assert again.store.need_bootstrap()
    again.store.db.execute("PRAGMA synchronous=OFF")
    again.bootstrap()
    assert again.topological_index == 400
    assert len(again._validator_keys) == 8
    assert again.store.last_block_index() == last_block
    mem = Hashgraph(peers, InmemStore(peers, 5000))
    for signed in stream8.signed[:400]:
        mem.insert_event(stream8.copy(signed), True)
    for signed in stream8.signed[:400]:
        assert (again.store.get_event(signed.hex()).first_descendants
                == mem.store.get_event(signed.hex()).first_descendants)
    again.store.close()
