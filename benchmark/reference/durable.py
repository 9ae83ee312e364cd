"""Plain reference of the durable deployment: what a database file holds,
read with `sqlite3` and `json` alone, and ordered by the plain reference of
the ordering semantics (`benchmark/reference/hashgraph.py`).

It imports nothing of the program. Of the file it reads the program's
tables as rows of text: `participants` (the validator set), `events` in
`topo_index` order (the JSON body: creator, index, parents, transactions,
signature; and the stamps the program stored beside it), `blocks` and
`frames`. From the events it rebuilds the DAG by hash (a parent is the row
whose `hex` it names; a parent outside the file is a root), and hands the
DAG to `hashgraph.order`: the blocks a restart must find again are a fact
of the events on disk.

`order` is `hashgraph.order` itself: an entry that names this reference
orders the generator's DAG with it as every other replay cell does, and
the file's DAG beside it (`order_stored`).
"""

from __future__ import annotations

import base64
import json
import sqlite3
from dataclasses import dataclass
from typing import List, Tuple

import numpy as np

from benchmark.reference.hashgraph import Ordering, order

__all__ = ["Ordering", "Stored", "order", "order_stored", "read"]


@dataclass
class Stored:
    """What the file holds, events in the order the program was handed them."""

    n: int
    hexes: List[str]  # the events' hashes
    creator: np.ndarray  # (E,) position in the sorted validator set
    index: np.ndarray  # (E,)
    self_parent: np.ndarray  # (E,) row, -1 for a root
    other_parent: np.ndarray  # (E,) row, -1 for none
    transactions: List[List[bytes]]
    signatures: List[str]
    stamps: np.ndarray  # (E, 3) round, lamport, round received; -1 unset
    blocks: List[Tuple[int, int, List[bytes]]]  # index, round received, txs
    frames: List[int]  # rounds whose frame is on disk
    rounds: int  # rows of the rounds table
    topo: List[int]  # the rows' topo_index, as stored


def connect(path: str) -> sqlite3.Connection:
    """A connection of the reader's own, read-only."""
    return sqlite3.connect(f"file:{path}?mode=ro", uri=True)


def _stamp(v) -> int:
    return -1 if v is None else int(v)


def read(path: str) -> Stored:
    db = connect(path)
    try:
        validators = sorted(
            r[0] for r in db.execute("SELECT pub_key_hex FROM participants"))
        rows = db.execute(
            "SELECT hex, topo_index, creator, idx, data FROM events "
            "ORDER BY topo_index").fetchall()
        blocks = db.execute("SELECT idx, data FROM blocks ORDER BY idx").fetchall()
        frames = [r[0] for r in db.execute("SELECT idx FROM frames ORDER BY idx")]
        rounds = db.execute("SELECT COUNT(*) FROM rounds").fetchone()[0]
    finally:
        db.close()
    position = {pk: i for i, pk in enumerate(validators)}
    row_of = {r[0]: k for k, r in enumerate(rows)}
    e = len(rows)
    creator = np.zeros(e, dtype=np.int32)
    index = np.zeros(e, dtype=np.int32)
    self_parent = np.full(e, -1, dtype=np.int32)
    other_parent = np.full(e, -1, dtype=np.int32)
    stamps = np.full((e, 3), -1, dtype=np.int64)
    txs: List[List[bytes]] = []
    signatures: List[str] = []
    for k, (_, _, pub, idx, data) in enumerate(rows):
        doc = json.loads(data)
        body = doc["Body"]
        creator[k] = position[pub]
        index[k] = idx
        sp, op = body["Parents"]
        self_parent[k] = row_of.get(sp, -1)
        other_parent[k] = row_of.get(op, -1)
        txs.append([base64.b64decode(t) for t in body["Transactions"]])
        signatures.append(doc["Signature"])
        meta = doc.get("Meta") or {}
        stamps[k] = (_stamp(meta.get("Round")), _stamp(meta.get("Lamport")),
                     _stamp(meta.get("RoundReceived")))
    got_blocks = []
    for idx, data in blocks:
        body = json.loads(data)["Body"]
        got_blocks.append((int(idx), int(body["RoundReceived"]),
                           [base64.b64decode(t) for t in body["Transactions"]]))
    return Stored(len(validators), [r[0] for r in rows], creator, index,
                  self_parent, other_parent, txs, signatures, stamps,
                  got_blocks, frames, int(rounds), [int(r[1]) for r in rows])


def order_stored(stored: Stored) -> Ordering:
    """The plain reference's ordering of the events on disk: the
    signature's r breaks ties, the hash's middle byte is the coin."""
    sig_r = [int(s.split("|")[0], 36) for s in stored.signatures]
    coin = [bytes.fromhex(h[2:])[16] != 0 for h in stored.hexes]
    return order(stored.n, stored.creator, stored.index, stored.self_parent,
                 stored.other_parent, sig_r, coin, stored.transactions)
