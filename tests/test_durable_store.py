"""The durable deployment (`babble run --store`) at small sizes, JAX on the
CPU: a seeded gossip stream handed in 100-event syncs to an observer
`Core(consensus_backend="tpu")` on a `SQLiteStore` in a directory on disk.

What is held (the letters are the issue's, PR 37):

(a) blocks and every event's round, lamport timestamp and round received
    equal an in-memory Core's and the plain reference's over the file
    (`benchmark/reference/durable.py`), and the file carries them in its
    `stamps` table, joined to the rows on `topo_index`;
(b) at every block hand-over a second, read-only connection reads the
    block, its frame and every event it orders;
(c) a validator stopped at a sync boundary (the connection dropped, no
    flush) and started again from the file holds the same known events and
    re-derives the same blocks, byte for byte;
(d) the connection dropped inside a sync, in the inserts or at a block
    hand-over: whole earlier syncs are there, no block without its events,
    and the remaining events handed over again end in the same blocks;
(e) the `store.*` totals are there and non-zero, and a run of k syncs that
    commit b blocks makes at most k + b + 1 transactions;
(f) a Core on `InmemStore` records no `store.*` total;
(g) an event's row is written once (PR 38): k inserted events are k rows
    written whole, every later `set_event` is a stamps row (`store.stamp`),
    and an event read back from disk after its eviction carries the live
    object's stamps and last ancestors, and its first descendants through
    the table, then from its row once released;
(h) the row keeps its last ancestors as indices, one a chain;
(i) the events table's keys are integers: a row under its `topo_index`,
    found through `hkey` and `(chain, idx)`, no index on `hex` or `creator`;
    a shared hash prefix never reads a wrong row; a file written with the
    text keys is rebuilt on open and reads back whole; `store.pages` is
    the log's growth, and a 500-event sync at 64 validators writes at most
    70% of the pages the text keys write.
"""

import dataclasses
import json
import os
import sqlite3
from types import SimpleNamespace

import numpy as np
import pytest

from babble_tpu.common import LRU, StoreErr, StoreErrType
from babble_tpu.hashgraph import Block, Frame, Hashgraph, InmemStore, SQLiteStore, sqlite_store
from babble_tpu.hashgraph.coordinates import MAX_INT32
from babble_tpu.node import Core
from benchmark import traffic as gen
from benchmark.entries import replay
from benchmark.reference import durable

SYNC = 100
CACHE = 50000
CASES = {"v16": (16, 3000), "v64": (64, 4000)}  # validators, events
TOPOLOGY_SEED, SEED, ZIPF_A = 1000000007, 7, 1.1
STORE_TOTALS = ("store.set_event", "store.stamp", "store.set_round",
                "store.set_block_frame", "store.flush", "store.bytes")
ROW_BYTES = 1500  # an event's row at 64 validators, at most
COUNTS_ONLY = ("store.stamp", "store.bytes")  # totals without seconds


class Handover(replay.CommitStamps):
    """The application's end of `commit_ch`: every block's body, and at the
    hand-over what a reader on a connection of its own finds missing."""

    def __init__(self, stream, path=None):
        super().__init__()
        self.stream, self.path = stream, path
        self.bodies = []
        self.unreadable = []  # (block index, what was missing)
        # set where a test stops the validator at a block's hand-over
        self.store = self.stop_at = None

    def put(self, block) -> None:
        super().put(block)
        self.bodies.append(block.body.marshal())
        if self.path is not None:
            self.unreadable += [(block.index(), what)
                                for what in missing_on_disk(self.path, self.stream, block)]
        if self.stop_at == block.index():
            self.store.db.close()  # the process dies here: no flush
            raise Stopped()


class Stopped(BaseException):
    """The process dies: no `except Exception` of the ladder may hold it."""


def missing_on_disk(path, stream, block) -> list:
    """What of `block`, its frame and the events it orders a read-only
    connection does not find in the file at `path`."""
    missing = []
    db = durable.connect(path)
    try:
        row = db.execute("SELECT data FROM blocks WHERE idx = ?",
                         (block.index(),)).fetchone()
        if row is None or json.loads(row[0])["Body"] != block.body.to_canonical():
            missing.append("block")
        if db.execute("SELECT 1 FROM frames WHERE idx = ?",
                      (block.round_received(),)).fetchone() is None:
            missing.append("frame")
        for tx in block.transactions():
            key = stream.signed[gen.payload_event(tx)].hex()
            if db.execute("SELECT 1 FROM events WHERE hex = ?",
                          (key,)).fetchone() is None:
                missing.append(key)
    finally:
        db.close()
    return missing


def new_core(stream, store, commit):
    return Core(0, stream.key, stream.peers, store, commit_ch=commit,
                consensus_backend="tpu")


def feed(core, stream, lo, hi) -> int:
    """Hand over events [lo, hi) in SYNC-event syncs; the syncs made."""
    syncs = 0
    for a in range(lo, hi, SYNC):
        for signed in stream.signed[a:min(a + SYNC, hi)]:
            core.insert_event(stream.copy(signed), True)
        core.run_consensus()
        syncs += 1
    return syncs


def stamp(v) -> int:
    return -1 if v is None else int(v)


def stamps_of(core, stream, upto) -> list:
    out = []
    for signed in stream.signed[:upto]:
        ev = core.hg.store.get_event(signed.hex())
        out.append((stamp(ev.round), stamp(ev.lamport_timestamp),
                    stamp(ev.round_received)))
    return out


def stamps_on_disk(path) -> np.ndarray:
    """(E, 3) round, lamport, round received of the file's events in
    `topo_index` order, from the `stamps` table (-1 where unset)."""
    db = durable.connect(path)
    try:
        rows = db.execute(
            "SELECT s.round, s.lamport, s.round_received FROM events e "
            "LEFT JOIN stamps s ON s.topo_index = e.topo_index "
            "ORDER BY e.topo_index").fetchall()
    finally:
        db.close()
    return np.array([[stamp(v) for v in row] for row in rows], np.int64)


_STREAMS, _IN_MEMORY = {}, {}


def stream_of(case):
    if case not in _STREAMS:
        n, events = CASES[case]
        _STREAMS[case] = replay.Stream(n, events, SEED, ZIPF_A, 1, TOPOLOGY_SEED)
    return _STREAMS[case]


def in_memory(case):
    """The whole stream through a Core on `InmemStore`: (core, blocks)."""
    if case not in _IN_MEMORY:
        stream = stream_of(case)
        blocks = Handover(stream)
        core = new_core(stream, InmemStore(stream.peers, CACHE), blocks)
        feed(core, stream, 0, CASES[case][1])
        core.flush_device_dispatch()
        _IN_MEMORY[case] = core, blocks
    return _IN_MEMORY[case]


@pytest.fixture(scope="module", params=list(CASES))
def ran(request, tmp_path_factory):
    """One whole run on disk and one in memory, per case."""
    case = request.param
    stream = stream_of(case)
    events = CASES[case][1]
    path = str(tmp_path_factory.mktemp(case) / "store" / "babble.db")
    disk_blocks = Handover(stream, path)
    disk = new_core(stream, SQLiteStore(stream.peers, CACHE, path), disk_blocks)
    syncs = feed(disk, stream, 0, events)
    disk.flush_device_dispatch()
    mem, mem_blocks = in_memory(case)
    assert disk.ladder_rung() == mem.ladder_rung() == "live"
    return SimpleNamespace(case=case, stream=stream, events=events, path=path,
                           syncs=syncs, disk=disk, disk_blocks=disk_blocks,
                           mem=mem, mem_blocks=mem_blocks)


def test_orders_as_in_memory_and_as_the_reference(ran):
    """(a)"""
    assert ran.disk_blocks.bodies and ran.disk_blocks.bodies == ran.mem_blocks.bodies
    got = stamps_of(ran.disk, ran.stream, ran.events)
    assert got == stamps_of(ran.mem, ran.stream, ran.events)
    stored = durable.read(ran.path)
    assert stored.hexes == [ev.hex() for ev in ran.stream.signed]
    assert stored.topo == list(range(ran.events))
    stored = dataclasses.replace(stored, stamps=stamps_on_disk(ran.path))
    assert stored.stamps.tolist() == [list(s) for s in got]
    want = durable.order_stored(stored)
    observed = (stored.stamps, [(b.index(), b.round_received(), b.transactions())
                                for _, b in ran.disk_blocks.blocks])
    assert replay.mismatches(observed, want) == {
        "events_mismatched": 0, "blocks_mismatched": 0}
    assert stored.blocks == observed[1]


def test_a_block_is_on_disk_before_it_is_delivered(ran):
    """(b)"""
    assert ran.disk_blocks.blocks
    assert ran.disk_blocks.unreadable == []


def test_store_totals_and_transactions(ran):
    """(e)"""
    totals = ran.disk.hg.obs.tracer.totals()
    for name in STORE_TOTALS:
        count, seconds = totals[name]
        assert count > 0, name
        assert (seconds > 0) == (name not in COUNTS_ONLY), name
    blocks = len(ran.disk_blocks.blocks)
    assert totals["store.flush"][0] <= ran.syncs + blocks + 1
    assert totals["store.set_event"][0] >= ran.events
    assert totals["store.set_block_frame"][0] >= 2 * blocks
    # rows of tens of bytes a validator: an index a chain in the event's
    # row, the round rows' entries
    assert totals["store.bytes"][0] > ran.events * 20 * CASES[ran.case][0]
    # between two flushes nothing is handed over: the sums wait in the store
    ran.disk.hg.store.set_round(0, ran.disk.hg.store.get_round(0))
    assert ran.disk.hg.obs.tracer.totals()["store.set_round"] == totals["store.set_round"]
    ran.disk.hg.store.flush()
    assert (ran.disk.hg.obs.tracer.totals()["store.set_round"][0]
            == totals["store.set_round"][0] + 1)


def test_a_row_is_written_once(ran):
    """(g) k inserted events, k rows written whole under `topo_index` 0 to
    k - 1: a REPLACE of a row would store it again past the k-th, and
    nothing else did; every other `set_event` wrote a stamps row, and
    `store.stamp` counts them."""
    db = durable.connect(ran.path)
    try:
        top, rows = db.execute("SELECT MAX(topo_index), COUNT(*) FROM events").fetchone()
        assert top + 1 == rows == ran.events
        stamp_rows = db.execute("SELECT COUNT(*) FROM stamps").fetchone()[0]
    finally:
        db.close()
    totals = ran.disk.hg.obs.tracer.totals()
    rows, stamps = totals["store.set_event"][0], totals["store.stamp"][0]
    assert rows - stamps == ran.events
    # a round stamp and a reception stamp for most events, one row each
    assert 0 < stamp_rows <= ran.events < stamps <= 3 * ran.events


def test_an_in_memory_core_records_no_store_total(ran):
    """(f)"""
    assert [k for k in ran.mem.hg.obs.tracer.totals() if k.startswith("store.")] == []


def test_the_file_is_opened_durable(ran):
    db = ran.disk.hg.store.db
    assert db.execute("PRAGMA synchronous").fetchone()[0] == 2  # FULL
    assert db.execute("PRAGMA journal_mode").fetchone()[0] == "wal"
    assert not db.in_transaction  # the barrier flushed


@pytest.fixture(scope="module")
def small_cache(tmp_path_factory):
    """The v16 stream on a store whose cache holds 100 events: the newest
    hundred and the undetermined stay, the rest is read back from disk."""
    stream = stream_of("v16")
    store = SQLiteStore(stream.peers, 100,
                        str(tmp_path_factory.mktemp("small") / "small.db"))
    core = new_core(stream, store, None)
    syncs = feed(core, stream, 0, CASES["v16"][1])
    core.flush_device_dispatch()
    yield SimpleNamespace(stream=stream, store=store, core=core, syncs=syncs)
    store.close()


def test_release_patch_is_counted(small_cache):
    """The rows the coordinate table lets go are patched in the sync's own
    transaction, and `store.release_patch` counts them."""
    core = small_cache.core
    totals = core.hg.obs.tracer.totals()
    rows, seconds = totals["store.release_patch"]
    assert rows == core.hg._coords.base > 0 and seconds > 0
    # nobody is handed the blocks: a sync is one transaction, the patch in
    # it (the barrier after the last found nothing open)
    assert core.get_last_block_index() > 0
    assert totals["store.flush"][0] == small_cache.syncs


def test_an_evicted_event_reads_back_whole(small_cache):
    """(g) Every event, evicted and read back from disk, carries the stamps
    and last ancestors of the in-memory Core's object, and answers its
    first descendants through the table while the table holds its row and
    from the row's own cells after the release."""
    store, stream = small_cache.store, small_cache.stream
    mem = in_memory("v16")[0].hg.store
    table = small_cache.core.hg._coords
    store.inmem.event_cache = LRU(100)
    live = released = received = 0
    for signed in stream.signed:
        back = store.get_event(signed.hex())
        want = mem.get_event(signed.hex())
        assert back is not want
        assert ((back.round, back.lamport_timestamp, back.round_received)
                == (want.round, want.lamport_timestamp, want.round_received))
        assert back.topological_index == want.topological_index
        assert back.last_ancestors == want.last_ancestors
        received += back.round_received is not None
        if table.slot_of(back) >= 0:
            live += 1
            assert back.first_descendants == want.first_descendants
        else:
            released += 1
            data = store.db.execute("SELECT data FROM events WHERE hex = ?",
                                    (signed.hex(),)).fetchone()[0]
            cells = json.loads(data)["Meta"]["FirstDescendants"]
            assert back.first_descendants == [tuple(c) for c in cells]
            for cell, full in zip(back.first_descendants, want.first_descendants):
                assert cell in (full, (MAX_INT32, ""))
    assert live > 0 and released == table.base > 0 and received > 0


def restart(stream, path):
    """A new process on the directory: the store, a Core, the bootstrap."""
    blocks = Handover(stream, path)
    store = SQLiteStore.load_or_create(stream.peers, CACHE, path)
    assert store.need_bootstrap()
    core = new_core(stream, store, blocks)
    core.bootstrap()
    return core, blocks


def test_restart_at_a_sync_boundary(tmp_path):
    """(c): stopped when `run_consensus` returned, before any barrier."""
    stream, events = stream_of("v16"), 2000
    path = str(tmp_path / "babble.db")
    blocks = Handover(stream, path)
    core = new_core(stream, SQLiteStore(stream.peers, CACHE, path), blocks)
    feed(core, stream, 0, events)
    known = core.known_events()
    assert blocks.bodies
    core.hg.store.db.close()  # the process dies: nothing flushes after it

    again, found = restart(stream, path)
    assert again.known_events() == known
    assert [e.hex() for e in again.hg.store.db_topological_events()] == [
        ev.hex() for ev in stream.signed[:events]]
    # everything it had committed, byte for byte (and what its in-flight
    # dispatches had not brought back yet)
    assert found.bodies[:len(blocks.bodies)] == blocks.bodies
    assert found.unreadable == []
    again.hg.store.close()


@pytest.mark.parametrize("where", ["inserts", "block"])
def test_restart_inside_a_sync(tmp_path, where):
    """(d)"""
    stream, events = stream_of("v16"), CASES["v16"][1]
    path = str(tmp_path / "babble.db")
    whole = 1500  # events in whole syncs before the one that is cut
    blocks = Handover(stream, path)
    store = SQLiteStore(stream.peers, CACHE, path)
    core = new_core(stream, store, blocks)
    feed(core, stream, 0, whole)
    if where == "inserts":
        for signed in stream.signed[whole:whole + SYNC // 2]:
            core.insert_event(stream.copy(signed), True)
        store.db.close()
    else:
        blocks.store, blocks.stop_at = store, len(blocks.bodies) + 1
        with pytest.raises(Stopped):
            feed(core, stream, whole, events)
    delivered = list(blocks.bodies)

    stored = durable.read(path)
    held = len(stored.hexes)
    assert held >= whole and held % SYNC == 0  # whole syncs, the earlier ones all
    assert stored.hexes == [ev.hex() for ev in stream.signed[:held]]
    if where == "block":
        # the block that was being handed over is there with its sync
        assert len(stored.blocks) == len(delivered) and held > whole
    last = max((gen.payload_event(tx) for _, _, txs in stored.blocks for tx in txs),
               default=-1)
    assert last < held  # no block without its events
    assert set(rr for _, rr, _ in stored.blocks) <= set(stored.frames)

    again, found = restart(stream, path)
    assert found.bodies[:len(stored.blocks)] == delivered[:len(stored.blocks)]
    feed(again, stream, held, events)
    again.flush_device_dispatch()
    assert again.ladder_rung() == "live"
    assert found.bodies == in_memory("v16")[1].bodies
    assert found.unreadable == []
    again.hg.store.close()


def test_a_row_keeps_its_last_ancestors_as_indices(ran):
    """(h) One index a chain and no hash: a row under ROW_BYTES at 64
    validators, where the `[index, hash]` pairs made it ~5.4 KB."""
    db = durable.connect(ran.path)
    try:
        rows = [data for data, in db.execute("SELECT data FROM events")]
    finally:
        db.close()
    assert len(rows) == ran.events
    for data in rows:
        cells = json.loads(data)["Meta"]["LastAncestors"]
        assert len(cells) == CASES[ran.case][0]
        assert all(type(cell) is int for cell in cells)
        assert len(data) < ROW_BYTES


class PairRows(SQLiteStore):
    """An event's row in the old form: every last ancestor a pair."""

    def _db_put_event(self, event):
        topo = self._topo_counter
        self._topo_counter += 1
        d = event.to_json()
        d["Meta"] = {"Topo": event.topological_index, "Round": None,
                     "Lamport": None, "RoundReceived": None,
                     "LastAncestors": event.last_ancestors,
                     "FirstDescendants": None}
        data = json.dumps(d)
        self.db.execute("INSERT INTO events VALUES (?, ?, ?, ?, ?, ?, ?)",
                        (topo, event.hex(), event.creator(), event.index(), data,
                         sqlite_store._hkey(event.hex()),
                         self.participants().by_pub_key[event.creator()].id))
        return len(data)


def insert(hg, stream, lo, hi) -> None:
    """Events [lo, hi) into a host-engine graph, consensus every SYNC; an
    event the graph refuses (one it has, one below its frame) is passed."""
    for i, signed in enumerate(stream.signed[lo:hi], lo):
        try:
            hg.insert_event(stream.copy(signed), True)
        except (ValueError, StoreErr):
            pass
        if i % SYNC == SYNC - 1:
            hg.run_consensus()


def cells_on_disk(store) -> list:
    return [json.loads(data)["Meta"]["LastAncestors"]
            for data, in store.db.execute("SELECT data FROM events ORDER BY topo_index")]


def read_back(store, want) -> int:
    """Empty the cache, read every event of `want` ({hash: last ancestors})
    back from disk and hold its last ancestors; returns `store.read_back`'s
    count over the reads."""
    store.flush()
    before = store.tracer.totals().get("store.read_back", (0, 0.0))[0]
    store.inmem.event_cache = LRU(store.cache_size())
    for key, ancestors in want.items():
        assert store.get_event(key).last_ancestors == ancestors
    store.flush()
    return store.tracer.totals()["store.read_back"][0] - before


def live_ancestors(store) -> dict:
    return {key: list(store.get_event(key).last_ancestors)
            for key in store.inmem.event_cache.keys()}


def anchor(stream, upto, back):
    """A donor's graph over the first `upto` events: (donor, block, frame)
    of the block `back` before its newest."""
    donor = Hashgraph(stream.peers, InmemStore(stream.peers, CACHE))
    insert(donor, stream, 0, upto)
    block = donor.store.get_block(donor.store.last_block_index() - back)
    return donor, block, donor.get_frame(block.round_received())


def old_form(stream, path):
    """A file in the old form: every cell a pair, read back as written."""
    store = PairRows(stream.peers, CACHE, path)
    insert(Hashgraph(stream.peers, store), stream, 0, 1000)
    assert all(type(c) is list for cells in cells_on_disk(store) for c in cells)
    return store, live_ancestors(store)


def after_a_reset(stream, path):
    """Rows from before a reset read their hashes back from the table (the
    participant index starts again at the frame); rows written after it
    carry a pair where a cell lies at or below its chain's root (the
    frame's events found their self-parents' rows on disk), an index
    elsewhere."""
    store = SQLiteStore(stream.peers, CACHE, path)
    hg = Hashgraph(stream.peers, store)
    insert(hg, stream, 0, 1000)
    before = live_ancestors(store)
    _, block, frame = anchor(stream, 1600, 0)
    hg.reset(Block.from_json(block.to_json()), Frame.from_json(frame.to_json()))
    insert(hg, stream, 1000, 2000)
    after = [c for cells in cells_on_disk(store)[1000:] for c in cells]
    assert sum(type(c) is list for c in after) > 0
    assert sum(type(c) is int for c in after) > len(after) // 2
    # a row holds what it was written with: the frame's events stored
    # before the reset keep their first last ancestors on disk
    return store, {**live_ancestors(store), **before}


def after_a_section(stream, path):
    """A joiner's empty file after a fast-sync section, whose donor names
    last ancestors below the frame that the file never holds: those cells
    stay pairs (as indices they would be KEY_NOT_FOUND)."""
    from babble_tpu.hashgraph.section import Section

    donor, block, frame = anchor(stream, 1600, 2)
    shipped = Section.from_json(json.loads(json.dumps(
        donor.get_section(frame.round).to_json())))
    store = SQLiteStore(stream.peers, CACHE, path)
    hg = Hashgraph(stream.peers, store)
    hg.reset(Block.from_json(block.to_json()), Frame.from_json(frame.to_json()))
    hg.apply_section(shipped)
    insert(hg, stream, 1600, 2000)
    held = set(store.db.execute("SELECT creator, idx FROM events"))
    creators = stream.peers.to_pub_key_slice()
    assert any(type(cell) is list and (creators[c], cell[0]) not in held
               for cells in cells_on_disk(store) for c, cell in enumerate(cells))
    return store, live_ancestors(store)


def after_a_restart(stream, path):
    """A slim file, started again, re-derives the blocks it had committed
    and reads back as the live objects of the process that wrote it."""
    blocks = Handover(stream, path)
    core = new_core(stream, SQLiteStore(stream.peers, CACHE, path), blocks)
    feed(core, stream, 0, 1000)
    live = live_ancestors(core.hg.store)
    core.hg.store.db.close()  # the process dies: nothing flushes after it
    again, found = restart(stream, path)
    assert blocks.bodies and found.bodies[:len(blocks.bodies)] == blocks.bodies
    return again.hg.store, live


@pytest.mark.parametrize("written", [old_form, after_a_reset, after_a_section,
                                     after_a_restart],
                         ids=["pairs", "reset", "section", "restart"])
def test_an_evicted_event_reads_back_its_last_ancestors(tmp_path, written):
    """(h) Every event of the file, its cache emptied, reads back with the
    last ancestors it was written with, and `store.read_back` counts each."""
    store, want = written(stream_of("v16"), str(tmp_path / "babble.db"))
    assert len(want) > 500
    assert read_back(store, want) == len(want)
    store.close()


def test_an_index_no_row_holds_is_not_found(tmp_path):
    """(h) A read-back guesses no hash: a cell whose index neither the
    participant index nor the table holds is KEY_NOT_FOUND."""
    stream = stream_of("v16")
    store = SQLiteStore(stream.peers, CACHE, str(tmp_path / "babble.db"))
    insert(Hashgraph(stream.peers, store), stream, 0, 300)
    key = stream.signed[299].hex()
    store.db.execute("UPDATE events SET data = json_set(data, "
                     "'$.Meta.LastAncestors[0]', 1000000) WHERE hex = ?", (key,))
    store.inmem.event_cache = LRU(CACHE)
    with pytest.raises(StoreErr) as err:
        store.get_event(key)
    assert err.value.err_type == StoreErrType.KEY_NOT_FOUND
    store.close()


# (i) the short keys: every key the events table is searched by an integer

TEXT_KEYS = """CREATE TABLE IF NOT EXISTS events (
    hex TEXT PRIMARY KEY,
    topo_index INTEGER NOT NULL,
    creator TEXT NOT NULL,
    idx INTEGER NOT NULL,
    data TEXT NOT NULL
);
CREATE UNIQUE INDEX IF NOT EXISTS events_topo ON events(topo_index);
CREATE UNIQUE INDEX IF NOT EXISTS events_creator_idx ON events(creator, idx);
"""  # the events table as it was written before the short keys
FRAME_BYTES = 4096 + 24  # a WAL frame: a 4 KB page and its header


def event_indexes(db) -> dict:
    """{index name: its columns} of the events table."""
    return {name: [col for _, _, col in db.execute(f"PRAGMA index_info({name})")]
            for _, name, *_ in db.execute("PRAGMA index_list(events)")}


def test_the_event_table_is_keyed_by_integers(ran):
    """(i) A row under its `topo_index` (the rowid), searched through
    `hkey` and `(chain, idx)`; no index holds `hex` or `creator`."""
    db = durable.connect(ran.path)
    try:
        columns = {name: (kind, pk) for _, name, kind, _, _, pk
                   in db.execute("PRAGMA table_info(events)")}
        indexes = event_indexes(db)
    finally:
        db.close()
    assert columns["topo_index"] == ("INTEGER", 1)
    assert columns["hkey"][0] == columns["chain"][0] == "INTEGER"
    assert {"hex", "creator", "idx", "data"} <= set(columns)
    assert indexes == {"events_hkey": ["hkey"], "events_chain_idx": ["chain", "idx"]}


def shared_prefix(key: str) -> int:
    return 7


@pytest.mark.parametrize("hkey", [sqlite_store._hkey, shared_prefix],
                         ids=["hash", "shared"])
def test_an_event_is_found_by_its_hash_whatever_its_prefix(tmp_path, monkeypatch, hkey):
    """(i) `hkey` finds the rows that share a hash's prefix and `hex` picks
    the one: with every row under one `hkey`, every event, evicted, reads
    back as itself with its stamps, the stamps rows land on their own rows,
    and a hash no row holds is KEY_NOT_FOUND."""
    monkeypatch.setattr(sqlite_store, "_hkey", hkey)
    stream = stream_of("v16")
    store = SQLiteStore(stream.peers, CACHE, str(tmp_path / "babble.db"))
    insert(Hashgraph(stream.peers, store), stream, 0, 1000)
    want = {key: store.get_event(key) for key in store.inmem.event_cache.keys()}
    assert {k for k, in store.db.execute("SELECT DISTINCT hkey FROM events")} == (
        {7} if hkey is shared_prefix else {sqlite_store._hkey(k) for k in want})
    store.flush()
    store.inmem.event_cache = LRU(CACHE)
    for key, live in want.items():
        back = store.get_event(key)
        assert back.hex() == key
        assert ((back.topological_index, back.round, back.lamport_timestamp,
                 back.round_received, back.last_ancestors)
                == (live.topological_index, live.round, live.lamport_timestamp,
                    live.round_received, live.last_ancestors))
    with pytest.raises(StoreErr):
        store.get_event("0x" + "00" * 32)
    store.close()


def to_text_keys(path) -> None:
    """The file's events table rewritten in the layout it had before the
    short keys, the same rows under the same `topo_index`."""
    db = sqlite3.connect(path)
    db.execute("BEGIN")
    db.execute("ALTER TABLE events RENAME TO keyed")
    for statement in TEXT_KEYS.split(";")[:-1]:
        db.execute(statement)
    db.execute("INSERT INTO events SELECT hex, topo_index, creator, idx, data FROM keyed")
    db.execute("DROP TABLE keyed")
    db.commit()
    db.close()


def test_a_file_with_text_keys_is_rebuilt_on_open(tmp_path):
    """(i) A file written before the short keys opens in the new layout:
    every event (its row, its stamps, its last ancestors), every block and
    frame reads back as written, and the restart re-derives the blocks."""
    stream, events = stream_of("v16"), 2000
    path = str(tmp_path / "babble.db")
    blocks = Handover(stream, path)
    core = new_core(stream, SQLiteStore(stream.peers, CACHE, path), blocks)
    feed(core, stream, 0, events)
    core.flush_device_dispatch()
    live = {key: core.hg.store.get_event(key) for key in core.hg.store.inmem.event_cache.keys()}
    core.hg.store.close()
    written, stamps = durable.read(path), stamps_on_disk(path)
    to_text_keys(path)
    db = durable.connect(path)
    assert "events_creator_idx" in event_indexes(db)
    db.close()

    store = SQLiteStore.load_or_create(stream.peers, CACHE, path)
    assert event_indexes(store.db) == {"events_hkey": ["hkey"],
                                       "events_chain_idx": ["chain", "idx"]}
    assert store.db.execute("SELECT name FROM sqlite_master WHERE tbl_name = 'keyed' "
                            "OR name = 'events_creator_idx'").fetchall() == []
    rebuilt = durable.read(path)
    for field in dataclasses.fields(durable.Stored):
        got, want = getattr(rebuilt, field.name), getattr(written, field.name)
        assert (np.array_equal(got, want) if isinstance(want, np.ndarray)
                else got == want), field.name
    assert (stamps_on_disk(path) == stamps).all()
    assert len(live) == events
    for key, want in live.items():
        back = store.get_event(key)
        assert ((back.topological_index, back.round, back.lamport_timestamp,
                 back.round_received, back.last_ancestors)
                == (want.topological_index, want.round, want.lamport_timestamp,
                    want.round_received, want.last_ancestors))
    for index in range(len(blocks.bodies)):
        assert store.get_block(index).body.marshal() == blocks.bodies[index]
    store.close()

    again, found = restart(stream, path)
    assert again.known_events() == core.known_events()
    assert found.bodies[:len(blocks.bodies)] == blocks.bodies
    assert found.unreadable == []
    again.hg.store.close()


class Recorder:
    """A store's connection that keeps the statements it is handed, with
    their arguments, and a `None` at each commit."""

    def __init__(self, db):
        self.db, self.log = db, []

    def __getattr__(self, name):
        return getattr(self.db, name)

    def execute(self, sql, args=()):
        self.log.append((sql, args, False))
        return self.db.execute(sql, args)

    def executemany(self, sql, rows):
        rows = list(rows)
        self.log.append((sql, rows, True))
        return self.db.executemany(sql, rows)

    def commit(self):
        self.log.append(None)
        self.db.commit()


# the store's statements on the events table as the text keys had them
TEXT_KEYED = {
    sqlite_store._PUT_STAMPS: (
        "INSERT OR REPLACE INTO stamps SELECT topo_index, ?, ?, ?, ? "
        "FROM events WHERE hex = ?", lambda a: a[:4] + a[5:]),
    "INSERT INTO events VALUES (?, ?, ?, ?, ?, ?, ?)": (
        "INSERT OR REPLACE INTO events VALUES (?, ?, ?, ?, ?)",
        lambda a: (a[1], a[0]) + a[2:5]),
}


def log_frames(path) -> int:
    return (os.path.getsize(path + "-wal") - 32) // FRAME_BYTES


def text_keyed_frames(log, path) -> list:
    """The frames each commit of `log` appends to a file of the text keys,
    the log's statements on the events table put as they were then."""
    schema = sqlite_store._SCHEMA
    db = sqlite3.connect(path)
    db.execute("PRAGMA journal_mode=WAL")
    db.execute(f"PRAGMA cache_size=-{sqlite_store.PAGE_CACHE_KIB}")
    db.execute("PRAGMA wal_autocheckpoint=0")
    db.executescript(TEXT_KEYS + schema[schema.index("CREATE TABLE IF NOT EXISTS stamps"):])
    frames, out = log_frames(path), []
    for entry in log:
        if entry is None:
            db.commit()
            out.append(log_frames(path) - frames)
            frames += out[-1]
            continue
        sql, args, many = entry
        if sql.startswith(("SELECT", "WITH")):
            continue  # a read writes nothing
        if " events " in sql:
            sql, put = TEXT_KEYED[sql]
            args = [put(a) for a in args] if many else put(args)
        (db.executemany if many else db.execute)(sql, args)
    db.close()
    return out


def test_store_pages_counts_the_log_and_short_keys_write_fewer(tmp_path):
    """(i) `store.pages` is what each flush appended to the log (its growth
    with the checkpoint off), and at 64 validators a 500-event sync writes at
    most 70% of the pages that the same statements write under text keys."""
    stream, sync = replay.Stream(64, 5000, SEED, ZIPF_A, 1, TOPOLOGY_SEED), 500
    path = str(tmp_path / "babble.db")
    store = SQLiteStore(stream.peers, CACHE, path)
    store.db.execute("PRAGMA wal_autocheckpoint=0")
    store.db = Recorder(store.db)
    hg = Hashgraph(stream.peers, store)
    pages, grown = [], []
    for lo in range(0, len(stream.signed), sync):
        before, frames = hg.obs.tracer.totals().get("store.pages", (0, 0.0))[0], log_frames(path)
        for signed in stream.signed[lo:lo + sync]:
            hg.insert_event(stream.copy(signed), True)
        hg.run_consensus()
        store.flush()
        pages.append(hg.obs.tracer.totals()["store.pages"][0] - before)
        grown.append(log_frames(path) - frames)
    assert pages == grown and min(pages) > 0
    old = text_keyed_frames(store.db.log, str(tmp_path / "text.db"))
    assert pages[-1] <= 0.7 * old[-1]
    store.close()
