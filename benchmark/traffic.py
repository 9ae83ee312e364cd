"""The one traffic generator: a seeded gossip DAG, and its replay as syncs.

A traffic mix is a data file under `benchmark/traffic/` (events per sync,
transactions per event, loop discipline); a configuration's file gives the
deployment (validators, fan-out skew, stream length). This module turns the
two and `--seed` into the arrays every entry and the plain reference read.
A configuration that states a `topology_seed` draws its DAG from that, so
that every `--seed` is handed the same rounds and blocks (`relabel`: the
validators in another order, and an entry's keys, hashes and signatures
from `--seed`); without one the DAG itself is drawn from `--seed`.
It imports nothing of the program: an entry materialises the arrays as the
program's signed events.

`gossip_dag` is a copy of the topology part of
`babble_tpu/tpu/grid.py synthetic_grid` (same draws from the same
generator, honest validators only), without the coordinate tables that
function builds for the kernels: the program may change, the yardstick may
not.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Iterator, Tuple

import numpy as np


@dataclass(frozen=True)
class Dag:
    """Events in topological (creation) order; a parent is an earlier row,
    -1 where the validator has none yet."""

    n: int
    creator: np.ndarray  # (E,) int32 validator position
    index: np.ndarray  # (E,) int32 sequence number on the creator's chain
    self_parent: np.ndarray  # (E,) int32 row
    other_parent: np.ndarray  # (E,) int32 row

    @property
    def e(self) -> int:
        return int(self.creator.shape[0])


def gossip_dag(n: int, events: int, seed: int, zipf_a: float) -> Dag:
    """Each new event is one sync: a uniformly drawn creator extends its
    chain with an other-parent that is the head of a partner drawn with
    weight 1/rank**zipf_a (uniform when zipf_a is 0). The first n events
    are the validators' first events, with no other-parent."""
    if n < 2 or events < n:
        raise ValueError(f"gossip_dag needs n >= 2 and events >= n, got {n}, {events}")
    rng = np.random.default_rng(seed)
    if zipf_a > 0:
        weights = 1.0 / np.arange(1, n + 1) ** zipf_a
        weights /= weights.sum()
    else:
        weights = np.full(n, 1.0 / n)
    creator = np.zeros(events, dtype=np.int32)
    index = np.zeros(events, dtype=np.int32)
    self_parent = np.full(events, -1, dtype=np.int32)
    other_parent = np.full(events, -1, dtype=np.int32)
    head = np.full(n, -1, dtype=np.int64)
    next_index = np.zeros(n, dtype=np.int64)
    for i in range(events):
        if i < n:
            c, op_row = i, -1
        else:
            c = int(rng.integers(n))
            partner = int(rng.choice(n, p=weights))
            while partner == c or head[partner] < 0:
                partner = int(rng.choice(n, p=weights))
            op_row = int(head[partner])
        creator[i] = c
        index[i] = next_index[c]
        self_parent[i] = head[c]
        other_parent[i] = op_row
        head[c] = i
        next_index[c] += 1
    return Dag(n, creator, index, self_parent, other_parent)


def relabel(dag: Dag, seed: int) -> Dag:
    """The same DAG with the validators' positions permuted from `seed`:
    the same work, met in another order."""
    perm = np.random.default_rng(seed).permutation(dag.n).astype(np.int32)
    return Dag(dag.n, perm[dag.creator], dag.index, dag.self_parent,
               dag.other_parent)


def syncs(total: int, sync_events: int) -> Iterator[Tuple[int, int]]:
    """[lo, hi) row ranges handed over one after the other."""
    if sync_events < 1:
        raise ValueError(f"sync_events must be positive, got {sync_events}")
    for lo in range(0, total, sync_events):
        yield lo, min(lo + sync_events, total)


def payload(i: int, tx_per_event: int) -> list:
    """The transactions event i carries."""
    if tx_per_event == 1:
        return [f"tx{i}".encode()]
    return [f"tx{i}.{k}".encode() for k in range(tx_per_event)]


def payload_event(tx: bytes) -> int:
    """The event that carried transaction `tx`: `payload`'s inverse."""
    return int(tx[2:].split(b".")[0])
