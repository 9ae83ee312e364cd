"""`Core.bootstrap` with the stored events' signatures checked in a pool of
spawned workers (`babble_tpu/crypto/batch_verify.py`), at small sizes: a
seeded 8-validator stream handed in 100-event syncs to an observer
`Core(consensus_backend="cpu")` on a `SQLiteStore`, crashed inside a sync,
and each copy of the directory started again. The pool's threshold, chunk
and size are set low so that it engages on a store of 1,000 events, in
chunks of 32, with two workers.

What is held:

(a) the pooled bootstrap leaves the same stamps, blocks, known events and
    `bootstrap_report` as the one-by-one checks on the same directory, and
    its `bootstrap.verify` total counts every stored event;
(b) a row whose signature was replaced in the file is refused with
    `Invalid Event signature` at the event the one-by-one checks refuse,
    with every event before it inserted and none after it;
(c) a signature text that does not decode raises what the one-by-one
    checks raise, in the same place;
(d) a worker killed during the re-insert leaves no event unchecked: the
    result equals the one-by-one one, and a forged row after the kill is
    still refused;
(e) no worker is alive after the call returns or raises;
(f) below the threshold no pool is started.
"""

import json
import multiprocessing
import os
import shutil
import sqlite3
import time
from types import SimpleNamespace

import numpy as np
import pytest

from babble_tpu.crypto import batch_verify
from babble_tpu.hashgraph import SQLiteStore
from babble_tpu.node import Core
from benchmark.entries import replay

N, EVENTS, STORED, SYNC = 8, 1100, 1000, 100
CACHE = 50000
FORGED = 700  # the topological index of the row a case forges
# the row forged where the pool is broken before its first verdict: in a
# chunk in flight at the kill, whose future fails
FORGED_IN_FLIGHT = 40


def feed(core, stream, lo, hi) -> None:
    for a in range(lo, hi, SYNC):
        for signed in stream.signed[a:min(a + SYNC, hi)]:
            core.insert_event(stream.copy(signed), True)
        core.run_consensus()


@pytest.fixture(scope="module")
def crashed(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("verify")
    stream = replay.Stream(N, EVENTS, 5, 1.1, 1, 1000000007)
    path = str(tmp / "babble.db")
    store = SQLiteStore(stream.peers, CACHE, path)
    core = Core(0, stream.key, stream.peers, store, consensus_backend="cpu")
    feed(core, stream, 0, STORED)
    for signed in stream.signed[STORED:STORED + SYNC // 2]:
        core.insert_event(stream.copy(signed), True)
    store.db.close()  # the process dies inside the sync: nothing flushes
    store.close()  # and its other handles on the file go with it
    serial = boot(stream, copy_of(path, tmp, "serial"))
    yield SimpleNamespace(stream=stream, path=path, tmp=tmp, serial=serial)
    serial.core.hg.store.close()


def copy_of(path, tmp, name, forge=None, row=FORGED):
    """The crashed directory copied to `name`; `forge` gives `row`'s new
    signature text."""
    to = tmp / name
    to.mkdir()
    for tail in ("", "-wal"):
        if os.path.exists(path + tail):
            shutil.copy(path + tail, str(to / "babble.db") + tail)
    out = str(to / "babble.db")
    if forge is not None:
        db = sqlite3.connect(out)
        (data,) = db.execute("SELECT data FROM events WHERE topo_index = ?",
                             (row,)).fetchone()
        data = json.loads(data)
        data["Signature"] = forge
        db.execute("UPDATE events SET data = ? WHERE topo_index = ?",
                   (json.dumps(data), row))
        db.commit()
        db.close()
    return out


def boot(stream, path):
    """A new process on the directory: the store, a Core, the bootstrap;
    what it left, or what it raised."""
    blocks = replay.CommitStamps()
    store = SQLiteStore.load_or_create(stream.peers, CACHE, path)
    assert store.need_bootstrap()
    core = Core(0, stream.key, stream.peers, store, commit_ch=blocks,
                consensus_backend="cpu")
    error = None
    try:
        core.bootstrap()
    except ValueError as err:
        error = err
    return SimpleNamespace(core=core, blocks=blocks, error=error,
                           children=multiprocessing.active_children())


def state(ran, stream):
    """What a bootstrap left: stamps, block bodies, known events, the
    events inserted, and the report but for its seconds."""
    hg = ran.core.hg
    stamps = []
    for signed in stream.signed[:hg.topological_index]:
        ev = ran.core.get_event(signed.hex())
        stamps.append([-1 if v is None else int(v)
                       for v in (ev.round, ev.lamport_timestamp, ev.round_received)])
    report = dict(ran.core.bootstrap_report or {})
    report.pop("seconds", None)
    return dict(stamps=np.array(stamps, np.int64).tolist(),
                blocks=[b.body.marshal() for _, b in ran.blocks.blocks],
                known=ran.core.known_events(), inserted=hg.topological_index,
                undetermined=list(hg.undetermined_events), report=report)


@pytest.fixture
def pooled(monkeypatch):
    monkeypatch.setattr(batch_verify, "MIN_EVENTS", 64)
    monkeypatch.setattr(batch_verify, "CHUNK", 32)
    monkeypatch.setattr(batch_verify, "MAX_WORKERS", 2)


def kill_a_worker(monkeypatch, at_chunk):
    """A worker is killed before the parent waits for chunk `at_chunk`'s
    verdicts: the chunks in flight and every later one find the pool
    broken."""
    verdicts = batch_verify.PoolVerifier._verdicts
    calls = []

    def killing(self, n, sent):
        calls.append(n)
        if len(calls) == at_chunk + 1:
            workers = multiprocessing.active_children()
            assert workers
            workers[0].kill()
            workers[0].join(10)
            assert not workers[0].is_alive()
        return verdicts(self, n, sent)

    monkeypatch.setattr(batch_verify.PoolVerifier, "_verdicts", killing)


@pytest.mark.parametrize("case", ["workers", "worker_killed"])
def test_the_pooled_bootstrap_equals_the_serial_one(crashed, pooled, monkeypatch, case):
    """(a), (d), (e)"""
    if case == "worker_killed":
        kill_a_worker(monkeypatch, at_chunk=1)
    ran = boot(crashed.stream, copy_of(crashed.path, crashed.tmp, case))
    try:
        assert ran.error is None and crashed.serial.error is None
        assert ran.children == []
        want = state(crashed.serial, crashed.stream)
        assert want["inserted"] == STORED and want["report"]["events"] == STORED
        assert len(want["blocks"]) > 0
        assert state(ran, crashed.stream) == want
        totals = ran.core.hg.obs.tracer.totals()
        verified = totals["bootstrap.verify"][0]
        assert totals["bootstrap.events"][0] == STORED
        # an insert that checks a signature itself looks its key up, and
        # finds it parsed but for each validator's first: every event is
        # checked once, by the pool or by the parent
        key_hits = totals.get("insert.key_hit", (0, 0.0))[0]
        if case == "workers":
            assert verified == STORED and key_hits == 0
        else:
            assert 0 < verified < STORED
            assert STORED - N <= verified + key_hits <= STORED
        assert "bootstrap.verify" not in crashed.serial.core.hg.obs.tracer.totals()
    finally:
        ran.core.hg.store.close()


@pytest.mark.parametrize("case", ["forged", "undecodable", "forged_worker_killed"])
def test_a_bad_row_is_refused_where_the_serial_bootstrap_refuses_it(
        crashed, pooled, monkeypatch, case):
    """(b), (c), (d), (e)"""
    stream, tmp = crashed.stream, crashed.tmp
    row = FORGED_IN_FLIGHT if case == "forged_worker_killed" else FORGED
    forge = ("not a signature" if case == "undecodable"
             else stream.signed[row + 1].signature)
    with monkeypatch.context() as serial_only:
        serial_only.setattr(batch_verify, "MIN_EVENTS", 10**9)
        want = boot(stream, copy_of(crashed.path, tmp, case + "_serial", forge, row))
    if case == "forged_worker_killed":
        kill_a_worker(monkeypatch, at_chunk=0)
    ran = boot(stream, copy_of(crashed.path, tmp, case, forge, row))
    try:
        assert want.error is not None and ran.error is not None
        assert type(ran.error) is type(want.error)
        assert str(ran.error) == str(want.error)
        if case != "undecodable":
            assert str(ran.error) == "Invalid Event signature"
        assert ran.children == [] and want.children == []
        assert want.core.hg.topological_index == row
        assert state(ran, stream) == state(want, stream)
        # nothing after the forged row is in the graph
        held = set(ran.core.hg.undetermined_events)
        assert stream.signed[row - 1].hex() in held
        assert not held & {s.hex() for s in stream.signed[row:STORED]}
    finally:
        for r in (want, ran):
            r.core.hg.store.close()


def test_no_pool_below_the_threshold(crashed, monkeypatch):
    """(f)"""
    monkeypatch.setattr(batch_verify, "MIN_EVENTS", STORED + 1)

    def refused(*args, **kwargs):
        raise AssertionError("a pool was started below the threshold")

    monkeypatch.setattr(batch_verify, "ProcessPoolExecutor", refused)
    ran = boot(crashed.stream, copy_of(crashed.path, crashed.tmp, "below"))
    try:
        assert ran.error is None and ran.children == []
        assert "bootstrap.verify" not in ran.core.hg.obs.tracer.totals()
        assert state(ran, crashed.stream) == state(crashed.serial, crashed.stream)
    finally:
        ran.core.hg.store.close()


def test_the_pool_checks_what_the_serial_check_checks(pooled):
    """A worker's verdicts are `Event.verify`'s: a good signature, one over
    another body, and a key that does not parse, which is left to the
    one-by-one check (None)."""
    stream = replay.Stream(4, 64, 3, 1.1, 1, 1000000007)
    validators = {p.pub_key_hex for p in stream.peers.to_peer_slice()}
    events = [stream.copy(s) for s in stream.signed]
    events[5].signature = events[6].signature
    jobs = []
    for ev in events:
        r, s = batch_verify.crypto.decode_signature(ev.signature)
        jobs.append((ev.body.creator, ev.fresh_hash(), r, s))
    jobs.append((b"\x04" + b"\x01" * 64, jobs[0][1], jobs[0][2], jobs[0][3]))
    got = batch_verify._check(jobs)
    assert got[:-1] == [ev.verify() for ev in events]
    assert got[5] is False and got.count(True) == len(events) - 1
    assert got[-1] is (None if batch_verify.crypto.HAVE_REAL_CRYPTO else False)
    pool = batch_verify.PoolVerifier.start(len(events) * 100, time.monotonic)
    assert pool is not None
    try:
        verdicts = [v for _, v in pool.checked(events, validators)]
    finally:
        pool.close()
    assert verdicts == [ev.verify() for ev in events]
    assert pool.verified == len(events)
    assert multiprocessing.active_children() == []
