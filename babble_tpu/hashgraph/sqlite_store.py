"""Persistent store: InmemStore write-through + SQLite.

The TPU-native equivalent of the reference's BadgerStore
(reference: src/hashgraph/badger_store.go): every event / round / block /
frame / root is written through to disk, reads fall back cache-then-db, and
`db_topological_events` replays insertion order for Bootstrap
(reference: src/hashgraph/badger_store.go:403-444).

SQLite (stdlib) replaces BadgerDB; the reference's key scheme
(`topo_%09d`, `{participant}__event_%09d`, ... reference:
src/hashgraph/badger_store.go:121-147) becomes indexed relational tables,
which buys us ordered replay and participant-index lookups for free.

What is durable when. Writes gather in one open transaction, which `flush`
commits under `synchronous=FULL` (one fsync of the log): nothing is on disk
statement by statement, everything written between two flushes is there
together or not at all. The callers set the boundaries (`Store.flush`): a
sync is durable when `Core.sync` / `Core.run_consensus` return and before
the Core signs a self-event on top of it, and a block, its frame and every
event it orders are durable before the commit callback sees the block. A
process that dies between two flushes restarts (`load_or_create`, then
`Core.bootstrap`) with whole earlier syncs and never a block without its
events. `close` flushes. The log is a WAL, so that a reader on a connection
of its own (a tool, a second process) never finds the file locked, whatever
the writer has open; docs/store.md has the reading the choice rests on.

An event's row is written once, when the store first sees it. What changes
after that goes elsewhere: its round, lamport and reception stamps (and the
hashgraph's topological index, which a reset or a replay may renumber) to a
narrow `stamps` row keyed by the row's `topo_index`, its final first
descendants into the row's own field when the coordinate table lets them
go. A read-back joins the two.

The row keeps an event's last ancestors as chain c's index alone, not the
`[index, hash]` pair the object holds (~5 bytes a chain, not ~74): the hash
is the `hex` of chain c's row at that index (`events_chain_idx`), and a
read-back finds it there again. Where the table may not hold it, at or below
the root a reset left on that chain (a fast-sync section's donor names
events this file never saw), the cell stays a pair; a file written before
this form, whose every cell is a pair, reads back as it did.

Every key the `events` table is searched or ordered by is a small integer,
so that a sync's new rows touch few index pages: a row is stored under its
`topo_index` (the rowid: rows are appended in that order), found by its hash
through `hkey` (the hash's first 8 bytes; `hex` beside it settles a prefix
shared by two hashes) and by its creator's chain through `chain` (the
creator's `Peer.id`). No index holds `hex` or `creator`. A file written with
the text keys is rebuilt in this layout when it is opened.
"""

from __future__ import annotations

import json
import mmap
import os
import sqlite3
import struct
from typing import Dict, List, Tuple

from ..common import StoreErr, StoreErrType, is_store_err
from ..peers import Peer, Peers
from .block import Block
from .event import Event
from .frame import Frame
from .inmem_store import InmemStore
from .root import Root
from .round_info import RoundInfo
from .store import Store

_EVENTS = """CREATE TABLE IF NOT EXISTS events (
    topo_index INTEGER PRIMARY KEY,
    hex TEXT NOT NULL,
    creator TEXT NOT NULL,
    idx INTEGER NOT NULL,
    data TEXT NOT NULL,
    hkey INTEGER NOT NULL,
    chain INTEGER NOT NULL
)"""
_SCHEMA = _EVENTS + """;
CREATE INDEX IF NOT EXISTS events_hkey ON events(hkey);
CREATE UNIQUE INDEX IF NOT EXISTS events_chain_idx ON events(chain, idx);
CREATE TABLE IF NOT EXISTS stamps (
    topo_index INTEGER PRIMARY KEY,
    topo INTEGER,
    round INTEGER,
    lamport INTEGER,
    round_received INTEGER
);
CREATE TABLE IF NOT EXISTS rounds (
    idx INTEGER PRIMARY KEY,
    data TEXT NOT NULL
);
CREATE TABLE IF NOT EXISTS blocks (
    idx INTEGER PRIMARY KEY,
    data TEXT NOT NULL
);
CREATE TABLE IF NOT EXISTS frames (
    idx INTEGER PRIMARY KEY,
    data TEXT NOT NULL
);
CREATE TABLE IF NOT EXISTS roots (
    participant TEXT PRIMARY KEY,
    data TEXT NOT NULL
);
CREATE TABLE IF NOT EXISTS participants (
    pub_key_hex TEXT PRIMARY KEY,
    net_addr TEXT NOT NULL
);
"""

JOURNAL_MODE = "WAL"
# a sync's transaction is ~16 MB at 64 validators: a page cache that holds it
# (KiB) writes it once, at the commit, and a log that may grow to four of
# them (pages) is copied into the database every few syncs, not after each
# (PERF.md section 6, PR 37, has the readings on the chip's host)
PAGE_CACHE_KIB = 65536
WAL_CHECKPOINT_PAGES = 16384

# tracer totals (seconds; count), summed here and handed over at `flush`
SET_EVENT = "store.set_event"  # rows written, whole and stamps
STAMP = "store.stamp"  # no seconds; stamps rows written in place of a whole row
SET_ROUND = "store.set_round"  # rows written
SET_BLOCK_FRAME = "store.set_block_frame"  # rows written, blocks and frames
RELEASE_PATCH = "store.release_patch"  # rows patched
FLUSH = "store.flush"  # transactions committed
BYTES = "store.bytes"  # no seconds; bytes of row data handed to SQLite
READ_BACK = "store.read_back"  # events `get_event` read back from disk
PAGES = "store.pages"  # no seconds; log frames (pages) the commits appended
TOTALS = (SET_EVENT, STAMP, SET_ROUND, SET_BLOCK_FRAME, RELEASE_PATCH, FLUSH,
          BYTES, READ_BACK, PAGES)

# an event seen before: its stamps under the row's topo_index, none written
# where it has no row yet
_PUT_STAMPS = ("INSERT OR REPLACE INTO stamps "
               "SELECT topo_index, ?, ?, ?, ? FROM events "
               "WHERE hkey = ? AND hex = ?")
STAMP_BYTES = 4 * 8  # store.bytes of a stamps row: four integers bound
# the WAL index's header (the `-shm` file): `mxFrame`, the frames in the
# log, is a native u32 at byte 16 (sqlite.org/walformat.html)
_WAL_INDEX_HEADER, _MX_FRAME = 48, 16


def _hkey(key: str) -> int:
    """The key of `events_hkey`: the hash's first 8 bytes as a signed
    64-bit integer (0 for a key that is no hash, which no row holds)."""
    try:
        return int.from_bytes(bytes.fromhex(key[2:18]), "big", signed=True)
    except ValueError:
        return 0


def _chain(creator: str) -> int:
    """The key of `events_chain_idx`: the creator's `Peer.id`."""
    return Peer(pub_key_hex=creator).id


class SQLiteStore(Store):
    def __init__(self, participants: Peers, cache_size: int, path: str, existing_db: bool = False):
        self._path = path
        self.inmem = InmemStore(participants, cache_size, pin_live=False)
        self._need_bootstrap = existing_db

        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        # access is serialized by the node's core_lock, so sharing the
        # connection across the node's worker threads is safe
        self.db = sqlite3.connect(path, check_same_thread=False)
        journal = self.db.execute(f"PRAGMA journal_mode={JOURNAL_MODE}").fetchone()[0]
        self.db.execute("PRAGMA synchronous=FULL")
        self.db.execute(f"PRAGMA cache_size=-{PAGE_CACHE_KIB}")
        self.db.execute(f"PRAGMA wal_autocheckpoint={WAL_CHECKPOINT_PAGES}")
        self._rebuild_events()
        self.db.executescript(_SCHEMA)
        self._wal_index = self._map_wal_index() if journal == "wal" else None
        self._sums = {total: [0.0, 0] for total in TOTALS}  # [seconds, count]

        if existing_db:
            # participants come from the db, roots re-read from disk
            db_participants = self._db_participants()
            if len(db_participants):
                self.inmem = InmemStore(db_participants, cache_size, pin_live=False)
                for pk in db_participants.to_pub_key_slice():
                    try:
                        self.inmem.roots_by_participant[pk] = self._db_get_root(pk)
                    except StoreErr:
                        pass
                self.inmem._roots_by_self_parent = None
        else:
            for p in participants.to_peer_slice():
                self.db.execute(
                    "INSERT OR REPLACE INTO participants VALUES (?, ?)",
                    (p.pub_key_hex, p.net_addr),
                )
            for pk, root in self.inmem.roots_by_participant.items():
                self._db_set_root(pk, root)
            self.db.commit()

        self._topo_counter = self._db_max_topo() + 1
        self._set_chain_roots()

    # -- factory -----------------------------------------------------------

    @classmethod
    def load_or_create(cls, participants: Peers, cache_size: int, path: str) -> "SQLiteStore":
        if os.path.exists(path):
            return cls(participants, cache_size, path, existing_db=True)
        return cls(participants, cache_size, path, existing_db=False)

    def _rebuild_events(self) -> None:
        """A file written with the text keys (its `events` has no `hkey`):
        the table rebuilt in this layout in one transaction, every row
        under the `topo_index` it had (its stamps row still joins), and the
        old table dropped with its indexes."""
        columns = [row[1] for row in self.db.execute("PRAGMA table_info(events)")]
        if not columns or "hkey" in columns:
            return
        self.db.create_function("hkey", 1, _hkey, deterministic=True)
        self.db.create_function("chain", 1, _chain, deterministic=True)
        self.db.execute("BEGIN")
        self.db.execute("ALTER TABLE events RENAME TO text_keyed_events")
        self.db.execute(_EVENTS)
        self.db.execute(
            "INSERT INTO events SELECT topo_index, hex, creator, idx, data, "
            "hkey(hex), chain(creator) FROM text_keyed_events ORDER BY topo_index")
        self.db.execute("DROP TABLE text_keyed_events")
        self.db.commit()

    def _map_wal_index(self):
        """The WAL index's header mapped read-only, for `store.pages` (a map
        shares the pages SQLite writes through its own, where a read of the
        file may not see them yet): (file, map), both kept open while the
        connection is, since closing a descriptor of the file drops every
        lock the process holds on it, SQLite's too."""
        shm = open(self._path + "-shm", "rb", buffering=0)
        return shm, mmap.mmap(shm.fileno(), _WAL_INDEX_HEADER, access=mmap.ACCESS_READ)

    def _log_frames(self) -> int:
        """Frames in the log (`mxFrame`); 0 where the log is no WAL."""
        if self._wal_index is None:
            return 0
        return struct.unpack_from("=I", self._wal_index[1], _MX_FRAME)[0]

    # -- the flush boundary and the totals ---------------------------------

    def _now(self) -> float:
        """The owning Hashgraph's clock; nothing is timed without one."""
        tracer = self.tracer
        return tracer.clock.monotonic() if tracer is not None else 0.0

    def _note(self, total: str, since: float, count: int, nbytes: int = 0) -> None:
        got = self._sums[total]
        got[0] += self._now() - since
        got[1] += count
        self._sums[BYTES][1] += nbytes

    def flush(self) -> None:
        """Commit the open transaction, if one is open, and hand the sums
        since the last flush to the tracer."""
        if self.db.in_transaction:
            t = self._now()
            before = self._log_frames()
            self.db.commit()
            self._note(FLUSH, t, 1)
            # the frames this commit appended; a log that restarted at it
            # holds this commit's frames alone
            after = self._log_frames()
            self._sums[PAGES][1] += after - before if after >= before else after
        tracer = self.tracer
        if tracer is not None:
            for total, got in self._sums.items():
                if got[1]:
                    tracer.add(total, got[0], got[1])
                    got[:] = 0.0, 0

    # -- db helpers --------------------------------------------------------

    def _db_participants(self) -> Peers:
        rows = self.db.execute("SELECT pub_key_hex, net_addr FROM participants").fetchall()
        return Peers.from_slice([Peer(net_addr=a, pub_key_hex=pk) for pk, a in rows])

    def _db_max_topo(self) -> int:
        row = self.db.execute("SELECT MAX(topo_index) FROM events").fetchone()
        return row[0] if row and row[0] is not None else -1

    def _db_set_root(self, participant: str, root: Root) -> None:
        self.db.execute(
            "INSERT OR REPLACE INTO roots VALUES (?, ?)",
            (participant, json.dumps(root.to_canonical())),
        )

    def _db_get_root(self, participant: str) -> Root:
        row = self.db.execute(
            "SELECT data FROM roots WHERE participant = ?", (participant,)
        ).fetchone()
        if row is None:
            raise StoreErr("SQLite.Roots", StoreErrType.KEY_NOT_FOUND, participant)
        return Root.from_canonical(json.loads(row[0]))

    def db_topological_events(self) -> List[Event]:
        """All events in insertion order, for Bootstrap replay. Consensus
        metadata is deliberately stripped (from_json, not from_store_json):
        the replay recomputes coordinates/rounds through the full pipeline."""
        rows = self.db.execute(
            "SELECT data FROM events ORDER BY topo_index"
        ).fetchall()
        return [Event.from_json(json.loads(r[0])) for r in rows]

    # -- Store interface: write-through then read-through ------------------

    def cache_size(self) -> int:
        return self.inmem.cache_size()

    def participants(self) -> Peers:
        return self.inmem.participants()

    def roots_by_self_parent(self) -> Dict[str, Root]:
        return self.inmem.roots_by_self_parent()

    def get_event(self, key: str) -> Event:
        try:
            return self.inmem.get_event(key)
        except StoreErr:
            t = self._now()
            row = self.db.execute(
                "SELECT e.data, s.topo_index, s.topo, s.round, s.lamport, "
                "s.round_received FROM events e LEFT JOIN stamps s "
                "ON s.topo_index = e.topo_index WHERE e.hkey = ? AND e.hex = ?",
                (_hkey(key), key)).fetchone()
            if row is None:
                raise StoreErr("SQLite.Events", StoreErrType.KEY_NOT_FOUND, key)
            d = json.loads(row[0])
            # the row's cells are resolved here, not by from_store_json,
            # which takes every cell for a pair
            meta = d["Meta"]
            cells, meta["LastAncestors"] = meta["LastAncestors"], None
            event = Event.from_store_json(d)
            if cells is not None:
                event.last_ancestors = self._last_ancestors(cells)
            if row[1] is not None:  # its stamps row, written after the row
                (event.topological_index, event.round,
                 event.lamport_timestamp, event.round_received) = row[2:]
            # its cells are the table's while the table holds its row, and
            # the row's own after that
            event.coordinates = self.coordinates
            self._note(READ_BACK, t, 1)
            return event

    def _last_ancestors(self, cells: list) -> List[Tuple[int, str]]:
        """The `(index, hash)` pairs a row's last-ancestor cells stand for:
        a pair as written, -1 as `(-1, "")`, chain c's index as the hash its
        chain holds there, from the participant index (a root's included)
        and else from the table, all of a read-back's misses in one
        statement. A cell that neither holds is KEY_NOT_FOUND."""
        peers = self.inmem.participants().to_peer_slice()
        creators = [p.pub_key_hex for p in peers]
        out: list = []
        missing = []
        for c, cell in enumerate(cells):
            if isinstance(cell, list):
                out.append(tuple(cell))
            elif cell < 0:
                out.append((-1, ""))
            else:
                try:
                    out.append((cell, self.inmem.participant_event(creators[c], cell)))
                except StoreErr:
                    out.append(None)
                    missing.append(c)
        if missing:
            # the cells drive the join, so each is one search of
            # events_chain_idx (an IN over row values scans the table)
            pairs = ", ".join(["(?, ?)"] * len(missing))
            found = {(chain, index): key for chain, index, key in self.db.execute(
                f"WITH cell(chain, idx) AS (VALUES {pairs}) "
                "SELECT e.chain, e.idx, e.hex FROM cell CROSS JOIN events e "
                "ON e.chain = cell.chain AND e.idx = cell.idx",
                [v for c in missing for v in (peers[c].id, cells[c])])}
            for c in missing:
                key = found.get((peers[c].id, cells[c]))
                if key is None:
                    raise StoreErr("SQLite.Events", StoreErrType.KEY_NOT_FOUND,
                                   f"{creators[c]}:{cells[c]}")
                out[c] = (cells[c], key)
        return out

    def set_event(self, event: Event) -> None:
        t = self._now()
        key = event.hex()
        peer = self.inmem.participants().by_pub_key[event.creator()]
        last_known = self.inmem.participant_events_cache.known().get(peer.id, -1)
        if event.index() > last_known:
            # advances the creator's sequence: register in the
            # participant rolling index
            self.inmem.set_event(event)
        else:
            # an event already registered (possibly LRU-evicted meanwhile,
            # so the object may be a copy read from disk): put it in the
            # cache, which registers nothing again (that would hit a rolled
            # participant window), and write it through
            self.inmem.event_cache.add(key, event)
        if self.db.execute(_PUT_STAMPS, self._stamps(event) + (_hkey(key), key)).rowcount:
            self._sums[STAMP][1] += 1
            nbytes = STAMP_BYTES
        else:
            nbytes = self._db_put_event(event)
        self._note(SET_EVENT, t, 1, nbytes)

    @staticmethod
    def _stamps(event: Event) -> tuple:
        return (event.topological_index, event.round, event.lamport_timestamp,
                event.round_received)

    def keep_first_descendants(self, keys, cells_of) -> None:
        """Every one of them: the cached objects, and each event's row, so
        that an event read back after the graph released its cells still
        has them all. The one write of a row's cells (the graph's table is
        the truth while it holds them, and a restart rebuilds it:
        `Hashgraph.bootstrap`): the one field patched in place, the whole
        block in one statement."""
        t = self._now()
        self.inmem.keep_first_descendants(keys, cells_of)
        rows = nbytes = 0

        def patches():
            nonlocal rows, nbytes
            for k, key in enumerate(keys):
                if key:
                    cells = json.dumps(cells_of(k))
                    rows += 1
                    nbytes += len(cells)
                    yield cells, _hkey(key), key

        self.db.executemany(
            "UPDATE events SET data = "
            "json_set(data, '$.Meta.FirstDescendants', json(?)) "
            "WHERE hkey = ? AND hex = ?",
            patches(),
        )
        self._note(RELEASE_PATCH, t, rows, nbytes)

    def _db_put_event(self, event: Event) -> int:
        """The event's one row, under the next topological index and its two
        integer keys: body, signature, wire info, `Topo` and `LastAncestors`
        (an index a chain, a pair at or below the chain's root), and no
        first descendants (the graph's table holds them until the release
        patch writes them); its stamps row beside it if a stamp is set
        already (an event adopted from a fast-sync section). Returns the
        bytes written."""
        topo = self._topo_counter
        self._topo_counter += 1
        d = event.to_json()
        cells = event.last_ancestors
        if cells is not None:
            cells = [k if k > floor or k < 0 else [k, h]
                     for (k, h), floor in zip(cells, self._chain_roots)]
        d["Meta"] = {"Topo": event.topological_index, "Round": None,
                     "Lamport": None, "RoundReceived": None,
                     "LastAncestors": cells, "FirstDescendants": None}
        data = json.dumps(d)
        key, creator = event.hex(), event.creator()
        self.db.execute(
            "INSERT INTO events VALUES (?, ?, ?, ?, ?, ?, ?)",
            (topo, key, creator, event.index(), data, _hkey(key),
             self.inmem.participants().by_pub_key[creator].id),
        )
        stamps = self._stamps(event)
        if any(v is not None for v in stamps[1:]):
            self.db.execute("INSERT OR REPLACE INTO stamps VALUES (?, ?, ?, ?, ?)",
                            (topo,) + stamps)
            return len(data) + STAMP_BYTES
        return len(data)

    def participant_events(self, participant: str, skip: int) -> List[str]:
        try:
            return self.inmem.participant_events(participant, skip)
        except StoreErr:
            rows = self.db.execute(
                "SELECT hex FROM events WHERE chain = ? AND idx > ? ORDER BY idx",
                (_chain(participant), skip),
            ).fetchall()
            return [r[0] for r in rows]

    def participant_event(self, participant: str, index: int) -> str:
        try:
            return self.inmem.participant_event(participant, index)
        except StoreErr:
            row = self.db.execute(
                "SELECT hex FROM events WHERE chain = ? AND idx = ?",
                (_chain(participant), index),
            ).fetchone()
            if row is None:
                raise StoreErr("SQLite.Events", StoreErrType.KEY_NOT_FOUND, str(index))
            return row[0]

    def last_event_from(self, participant: str) -> Tuple[str, bool]:
        return self.inmem.last_event_from(participant)

    def last_consensus_event_from(self, participant: str) -> Tuple[str, bool]:
        return self.inmem.last_consensus_event_from(participant)

    def known_events(self) -> Dict[int, int]:
        return self.inmem.known_events()

    def consensus_events(self) -> List[str]:
        return self.inmem.consensus_events()

    def consensus_events_count(self) -> int:
        return self.inmem.consensus_events_count()

    def add_consensus_event(self, event: Event) -> None:
        self.inmem.add_consensus_event(event)

    def seed_last_consensus_event(self, participant: str, event_hex: str) -> None:
        self.inmem.seed_last_consensus_event(participant, event_hex)

    def get_round(self, r: int) -> RoundInfo:
        try:
            return self.inmem.get_round(r)
        except StoreErr:
            row = self.db.execute("SELECT data FROM rounds WHERE idx = ?", (r,)).fetchone()
            if row is None:
                raise StoreErr("SQLite.Rounds", StoreErrType.KEY_NOT_FOUND, str(r))
            return RoundInfo.from_json(json.loads(row[0]))

    def set_round(self, r: int, round_info: RoundInfo) -> None:
        t = self._now()
        self.inmem.set_round(r, round_info)
        data = json.dumps(round_info.to_json())
        self.db.execute("INSERT OR REPLACE INTO rounds VALUES (?, ?)", (r, data))
        self._note(SET_ROUND, t, 1, len(data))

    def last_round(self) -> int:
        return self.inmem.last_round()

    def round_witnesses(self, r: int) -> List[str]:
        try:
            return self.get_round(r).witnesses()
        except StoreErr:
            return []

    def round_events(self, r: int) -> int:
        try:
            return len(self.get_round(r).events)
        except StoreErr:
            return 0

    def get_root(self, participant: str) -> Root:
        try:
            return self.inmem.get_root(participant)
        except StoreErr:
            return self._db_get_root(participant)

    def get_block(self, index: int) -> Block:
        try:
            return self.inmem.get_block(index)
        except StoreErr:
            row = self.db.execute("SELECT data FROM blocks WHERE idx = ?", (index,)).fetchone()
            if row is None:
                raise StoreErr("SQLite.Blocks", StoreErrType.KEY_NOT_FOUND, str(index))
            return Block.from_json(json.loads(row[0]))

    def set_block(self, block: Block) -> None:
        t = self._now()
        self.inmem.set_block(block)
        data = json.dumps(block.to_json())
        self.db.execute(
            "INSERT OR REPLACE INTO blocks VALUES (?, ?)", (block.index(), data))
        self._note(SET_BLOCK_FRAME, t, 1, len(data))

    def last_block_index(self) -> int:
        return self.inmem.last_block_index()

    def get_frame(self, index: int) -> Frame:
        try:
            return self.inmem.get_frame(index)
        except StoreErr:
            row = self.db.execute("SELECT data FROM frames WHERE idx = ?", (index,)).fetchone()
            if row is None:
                raise StoreErr("SQLite.Frames", StoreErrType.KEY_NOT_FOUND, str(index))
            return Frame.from_json(json.loads(row[0]))

    def set_frame(self, frame: Frame) -> None:
        t = self._now()
        self.inmem.set_frame(frame)
        data = json.dumps(frame.to_json())
        self.db.execute(
            "INSERT OR REPLACE INTO frames VALUES (?, ?)", (frame.round, data))
        self._note(SET_BLOCK_FRAME, t, 1, len(data))

    def reset(self, roots: Dict[str, Root]) -> None:
        self.inmem.reset(roots)
        for pk, root in roots.items():
            self._db_set_root(pk, root)
        self._set_chain_roots()

    def _set_chain_roots(self) -> None:
        """Each chain's root index. Above it, every index an event names as
        a last ancestor is a row of this file: the chain's events there are
        the frame's and those stored after it. At or below it, maybe not."""
        roots = self.inmem.roots_by_participant
        self._chain_roots = [roots[pk].self_parent.index
                             for pk in self.inmem.participants().to_pub_key_slice()]

    def close(self) -> None:
        """Flush, then close; closing a closed store is nothing, as with
        sqlite3's own `close`."""
        try:
            self.flush()
        except sqlite3.ProgrammingError:
            pass  # the connection is closed already
        else:
            self.db.close()
        if self._wal_index is not None:  # after the connection: see _map_wal_index
            for handle in reversed(self._wal_index):
                handle.close()
            self._wal_index = None

    def need_bootstrap(self) -> bool:
        return self._need_bootstrap

    def store_path(self) -> str:
        return self._path
