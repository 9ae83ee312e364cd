"""Controls for the comparisons the entry `replay_durable` adds: each has to
read 0 on the program as it is and more than 0 on a program that breaks the
guarantee it stands for, at a size a test run can hold (16 validators, JAX
on the CPU, the host engine: the comparisons do not care which engine ran).

- `blocks_delivered_before_durable`: a store whose flush does nothing, the
  nearest weaker program (it hands a block over and flushes later), under
  the log the store uses and under the rollback journal, where the reader
  finds the file locked: both count.
- `store_events_missing`, `store_blocks_mismatched`: a file that lost an
  event row, a file that lost its newest block row, and a file whose newest
  block orders its transactions otherwise.
"""

import json
import os
import shutil
import sqlite3
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from babble_tpu.hashgraph import SQLiteStore, sqlite_store  # noqa: E402
from babble_tpu.node import Core  # noqa: E402
from benchmark import traffic as gen  # noqa: E402
from benchmark.entries import replay, replay_durable  # noqa: E402
from benchmark.reference import durable  # noqa: E402

N, EVENTS, SYNC = 16, 2000, 100


class LateFlush(SQLiteStore):
    """The control: nothing is committed until the store is closed."""

    def flush(self) -> None:
        pass

    def close(self) -> None:
        SQLiteStore.flush(self)
        SQLiteStore.close(self)


def drive(store_class, path):
    stream = replay.Stream(N, EVENTS, 7, 1.1, 1, 1000000007)
    stamps = replay_durable.DurableStamps(path)
    core = Core(0, stream.key, stream.peers,
                store_class(stream.peers, 50000, path), commit_ch=stamps)
    for lo, hi in gen.syncs(EVENTS, SYNC):
        for ev in stream.handed[lo:hi]:
            core.insert_event(ev, True)
        core.run_consensus()
    core.hg.store.close()
    delivered = [(b.index(), b.round_received(), b.transactions())
                 for _, b in stamps.blocks]
    return stream, stamps, delivered


@pytest.fixture(scope="module")
def honest(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("honest") / "babble.db")
    return (path,) + drive(SQLiteStore, path)


def test_the_program_reads_zero(honest):
    path, stream, stamps, delivered = honest
    assert len(delivered) >= 3 and stamps.not_durable == 0
    diff = replay_durable.store_diff(
        durable.read(path), [ev.hex() for ev in stream.signed], delivered)
    assert diff == (0, 0)


@pytest.mark.parametrize("journal", ["WAL", "DELETE"])
def test_a_late_flush_is_not_correct(tmp_path, monkeypatch, journal):
    monkeypatch.setattr(sqlite_store, "JOURNAL_MODE", journal)
    _, stamps, delivered = drive(LateFlush, str(tmp_path / "babble.db"))
    assert stamps.not_durable == len(delivered) >= 3


def damaged(path, tmp_path, statement, *args):
    copy = str(tmp_path / "damaged.db")
    for tail in ("", "-wal"):
        if os.path.exists(path + tail):
            shutil.copy(path + tail, copy + tail)
    db = sqlite3.connect(copy)
    with db:
        db.execute(statement, args)
    db.close()
    return durable.read(copy)


def test_a_lost_event_is_not_correct(honest, tmp_path):
    path, stream, _, delivered = honest
    stored = damaged(path, tmp_path, "DELETE FROM events WHERE topo_index = ?", 900)
    events_off, blocks_off = replay_durable.store_diff(
        stored, [ev.hex() for ev in stream.signed], delivered)
    assert events_off > 0 and blocks_off > 0  # the blocks order an event it lacks


def test_a_lost_block_is_not_correct(honest, tmp_path):
    path, stream, _, delivered = honest
    stored = damaged(path, tmp_path, "DELETE FROM blocks WHERE idx = ?",
                     delivered[-1][0])
    # missed twice: against the hand-over and against the reference's order
    assert replay_durable.store_diff(
        stored, [ev.hex() for ev in stream.signed], delivered) == (0, 2)


def test_a_reordered_block_is_not_correct(honest, tmp_path):
    path, stream, _, delivered = honest
    index = delivered[-1][0]
    db = durable.connect(path)
    doc = json.loads(db.execute("SELECT data FROM blocks WHERE idx = ?",
                                (index,)).fetchone()[0])
    db.close()
    doc["Body"]["Transactions"].reverse()
    stored = damaged(path, tmp_path, "UPDATE blocks SET data = ? WHERE idx = ?",
                     json.dumps(doc), index)
    events_off, blocks_off = replay_durable.store_diff(
        stored, [ev.hex() for ev in stream.signed], delivered)
    assert events_off == 0 and blocks_off >= 2  # against the hand-over and the reference
