"""Entry `replay_durable`: entry `replay` with the observer Core on the
persistent store (`babble run --store`: `SQLiteStore` in a directory on
disk), held to the durability the configuration states.

Everything of `replay` is reused by import and runs unchanged: the signed
stream, the lead-in, the window that ends on a commit, the flush, the
forged event and the comparison with the plain reference over the
generator's DAG. Two names of that module are exchanged for the length of
the run, in a process that runs one cell: its `Stream`, for one whose Core
stands on a `SQLiteStore` in a fresh directory under the checkout's
`.bench_out/` (made in set-up, removed when the run ends; `BENCH_KEEP_STORE`
set keeps it, for a restart by hand), and its `CommitStamps`, for one that
is the application's end of the guarantee: when a block is handed over it
stamps it, as `replay` does, and then reads the file through a read-only
connection of its own.

`compared` gains, each with limit 0:

- `blocks_delivered_before_durable`: blocks at whose hand-over that reader
  did not find the block's row, its frame's row, or an event row with a
  `topo_index` as high as the last event the block orders (events are
  handed over in stream order, so an event's row number is its
  `topo_index`); a locked file counts;
- `store_events_missing`, `store_blocks_mismatched`: after the window and
  the flush, with the clock stopped, the file as the plain reference
  `benchmark/reference/durable.py` reads it (sqlite3 and json, nothing of
  the program) holds exactly the events handed over, in hand-over order,
  and exactly the blocks handed to `commit_ch`, which are the blocks that
  reference orders from the events on disk;
- `store_sync_below_full`: 1 if the store's connection runs below
  `synchronous=FULL`.

Counters: `store_bytes_on_disk` (database and log), `store_rows` (events,
rounds, blocks, frames). A program whose store has no flush boundary cannot
state the guarantees: the entry says so and exits 4 before anything runs.
"""

from __future__ import annotations

import os
import shutil
import sqlite3
import sys
from typing import List

from benchmark import traffic as gen
from benchmark.entries import replay
from benchmark.reference import durable

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
FULL = 2  # PRAGMA synchronous


class DurableStream(replay.Stream):
    """`Stream` whose Core stands on a `SQLiteStore` at `path`."""

    def __init__(self, path: str, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.path = path
        self.built = None  # the Core `core` made

    def core(self, backend: str, cache_size: int, commit_ch=None, **knobs):
        from babble_tpu.hashgraph import SQLiteStore
        from babble_tpu.node import Core

        self.built = Core(0, self.key, self.peers,
                          SQLiteStore(self.peers, cache_size, self.path),
                          commit_ch=commit_ch, consensus_backend=backend, **knobs)
        return self.built


class DurableStamps(replay.CommitStamps):
    """`CommitStamps`, and after each stamp the application's own look at
    the file: is what it was just handed on disk?"""

    def __init__(self, path: str) -> None:
        super().__init__()
        self.path = path
        self.not_durable = 0

    def put(self, block) -> None:
        super().put(block)
        last = max(gen.payload_event(tx) for tx in block.transactions())
        try:
            db = durable.connect(self.path)
            try:
                top = db.execute("SELECT MAX(topo_index) FROM events").fetchone()[0]
                found = (
                    db.execute("SELECT 1 FROM blocks WHERE idx = ?",
                               (block.index(),)).fetchone() is not None
                    and db.execute("SELECT 1 FROM frames WHERE idx = ?",
                                   (block.round_received(),)).fetchone() is not None
                    and top is not None and top >= last)
            finally:
                db.close()
        except sqlite3.Error as e:  # a locked file is a block not readable
            print(f"[bench] block {block.index()}: {e}", file=sys.stderr)
            found = False
        self.not_durable += not found


def disk_bytes(path: str) -> int:
    return sum(os.path.getsize(path + tail) for tail in ("", "-wal")
               if os.path.exists(path + tail))


def off_by(got: list, want: list) -> int:
    """Places at which two lists differ, a missing or extra one counted."""
    return sum(a != b for a, b in zip(got, want)) + abs(len(got) - len(want))


def store_diff(stored, handed: list, delivered: list) -> tuple:
    """(`store_events_missing`, `store_blocks_mismatched`): the file as the
    reference read it against the hashes of the events handed over, in
    hand-over order, and against the blocks handed to `commit_ch` (index,
    round received, transactions), which also have to be the blocks the
    reference orders from the events on disk."""
    events_off = (off_by(stored.hexes, handed)
                  + off_by(stored.topo, list(range(len(handed)))))
    ordered = durable.order_stored(stored)
    blocks_off = (
        off_by(stored.blocks, delivered)
        + replay.mismatches((stored.stamps, stored.blocks), ordered)
        ["blocks_mismatched"])
    return events_off, blocks_off


def run(ctx) -> dict:
    from babble_tpu.hashgraph import Store

    if not hasattr(Store, "flush"):
        print("[bench] this program's store has no flush boundary "
              "(Store.flush): it cannot state the configuration's "
              "guarantees", file=sys.stderr)
        raise SystemExit(4)
    store_dir = os.path.join(ROOT, ".bench_out", f"store-{os.getpid()}")
    shutil.rmtree(store_dir, ignore_errors=True)
    os.makedirs(store_dir)
    path = os.path.join(store_dir, "babble.db")
    streams: List[DurableStream] = []
    stamps: List[DurableStamps] = []

    def stream(*args, **kwargs):
        streams.append(DurableStream(path, *args, **kwargs))
        return streams[-1]

    def commit_stamps():
        stamps.append(DurableStamps(path))
        return stamps[-1]

    kept = replay.Stream, replay.CommitStamps
    replay.Stream, replay.CommitStamps = stream, commit_stamps
    try:
        result = replay.run(ctx)
        store = streams[0].built.hg.store
        counters = result["counters"]
        handed = counters["events_lead_in"] + counters["events_inserted"]
        with ctx.rec.span("check.store"):
            below_full = int(
                store.db.execute("PRAGMA synchronous").fetchone()[0] < FULL)
            stored = durable.read(path)
            events_off, blocks_off = store_diff(
                stored, [ev.hex() for ev in streams[0].signed[:handed]],
                [(b.index(), b.round_received(), b.transactions())
                 for _, b in stamps[0].blocks])
        result["compared"] += [
            ("blocks_delivered_before_durable", stamps[0].not_durable, 0),
            ("store_events_missing", events_off, 0),
            ("store_blocks_mismatched", blocks_off, 0),
            ("store_sync_below_full", below_full, 0),
        ]
        counters.update({
            "store_bytes_on_disk": disk_bytes(path),
            "store_rows": len(stored.hexes) + stored.rounds
            + len(stored.blocks) + len(stored.frames),
        })
        store.close()
    finally:
        replay.Stream, replay.CommitStamps = kept
        if os.environ.get("BENCH_KEEP_STORE"):
            print(f"[bench] store kept at {store_dir}", file=sys.stderr)
        else:
            shutil.rmtree(store_dir, ignore_errors=True)
    return result
