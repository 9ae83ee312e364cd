"""The withheld-chain gossip DAG: the traffic generator's DAG with an
adversarial minority, in the order a validator RECEIVES it.

`withheld_dag` is a copy of the `byzantine_frac` branch of
`babble_tpu/tpu/grid.py synthetic_grid` (the same lifecycle, topology only;
it imports nothing of the program): the first `byzantine` validators
withhold now and then. An episode starts at one of a validator's own events
with probability `start_p` while fewer than `max_hidden` validators are
hidden; from then on nobody is given its head (partners see the last event
it showed) while its own events still take the others' current heads as
other-parents; after `span` own events, drawn per episode, the next draw of
that validator becomes an honest validator's event whose other-parent is the
hidden head, which reveals the whole chain at once.

The rows are in arrival order: an event made while its creator withholds is
placed immediately before the honest event that reveals its chain, because
that is when an honest validator is first told of it. A parent is always an
earlier row. Events still hidden when the stream ends are not in it, and the
stream is cut at exactly `events` rows.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Tuple

import numpy as np

from benchmark.traffic import Dag


@dataclass(frozen=True)
class Withheld:
    dag: Dag  # rows in arrival order
    late: np.ndarray  # (E,) bool: made while its creator withheld
    reveal_rows: np.ndarray  # rows of the honest events that revealed a chain
    created: np.ndarray  # (E,) int64: the row's place in creation order


def parse_span(span) -> Tuple[int, int]:
    """"24-96" (or a single number): own events an episode lasts, drawn
    uniformly from the closed range."""
    lo, _, hi = str(span).partition("-")
    lo, hi = int(lo), int(hi or lo)
    if not 1 <= lo <= hi:
        raise ValueError(f"withhold span {span!r}")
    return lo, hi


def withheld_dag(n: int, events: int, seed: int, zipf_a: float,
                 byzantine: int, span, start_p: float,
                 max_hidden: int) -> Withheld:
    if n < 2 or events < n or not 0 <= byzantine < n:
        raise ValueError(f"withheld_dag: n {n}, events {events}, "
                         f"byzantine {byzantine}")
    span_lo, span_hi = parse_span(span)
    rng = np.random.default_rng(seed)
    if zipf_a > 0:
        weights = 1.0 / np.arange(1, n + 1) ** zipf_a
        weights /= weights.sum()
    else:
        weights = np.full(n, 1.0 / n)

    # per created event; the arrival order below indexes these
    creator, index, self_parent, other_parent, hidden = [], [], [], [], []
    head = np.full(n, -1, dtype=np.int64)  # creation numbers
    visible_head = np.full(n, -1, dtype=np.int64)
    next_index = np.zeros(n, dtype=np.int64)
    withholding = np.zeros(n, dtype=bool)
    hidden_since = np.zeros(n, dtype=np.int64)
    episode_span = np.zeros(n, dtype=np.int64)
    chain = [[] for _ in range(n)]  # creation numbers not yet delivered
    arrival = []  # creation numbers in arrival order
    reveals = []  # positions in `arrival` of the revealing events

    i = 0
    while len(arrival) < events:
        forced_op = None
        if i < n:
            c, op = i, -1
        else:
            c = int(rng.integers(n))
            if c < byzantine:
                if (not withholding[c]
                        and int(withholding.sum()) < max(max_hidden, 1)
                        and rng.random() < start_p):
                    withholding[c] = True
                    hidden_since[c] = next_index[c]
                    episode_span[c] = int(rng.integers(span_lo, span_hi + 1))
                elif (withholding[c]
                      and next_index[c] - hidden_since[c] >= episode_span[c]):
                    # an honest event takes the hidden head: the reveal
                    withholding[c] = False
                    visible_head[c] = head[c]
                    forced_op = int(head[c])
                    revealed = c
                    c = byzantine + int(rng.integers(n - byzantine))
            if forced_op is not None:
                op = forced_op
            else:
                partner = int(rng.choice(n, p=weights))
                while partner == c or visible_head[partner] < 0:
                    partner = int(rng.choice(n, p=weights))
                op = int(visible_head[partner])
        creator.append(c)
        index.append(int(next_index[c]))
        self_parent.append(int(head[c]))
        other_parent.append(op)
        hidden.append(bool(withholding[c]))
        head[c] = i
        next_index[c] += 1
        if withholding[c]:
            chain[c].append(i)
        else:
            visible_head[c] = i
            if forced_op is not None:
                arrival.extend(chain[revealed])
                chain[revealed] = []
                reveals.append(len(arrival))
            arrival.append(i)
        i += 1

    order = np.asarray(arrival[:events], dtype=np.int64)
    row_of = np.full(i, -1, dtype=np.int64)
    row_of[order] = np.arange(len(order))

    def rows(parents) -> np.ndarray:
        p = np.asarray(parents, dtype=np.int64)[order]
        return np.where(p >= 0, row_of[np.maximum(p, 0)], -1).astype(np.int32)

    dag = Dag(n, np.asarray(creator, dtype=np.int32)[order],
              np.asarray(index, dtype=np.int32)[order],
              rows(self_parent), rows(other_parent))
    # a cut can fall inside a revealed chain, never between a row and its parents
    assert (dag.self_parent < np.arange(dag.e)).all()
    assert (dag.other_parent < np.arange(dag.e)).all()
    return Withheld(
        dag=dag,
        late=np.asarray(hidden, dtype=bool)[order],
        reveal_rows=np.asarray([r for r in reveals if r < events], dtype=np.int64),
        created=order,
    )


def from_config(cfg: dict, seed: int) -> Withheld:
    """The configuration's DAG drawn from `seed`."""
    return withheld_dag(
        int(cfg["validators"]), int(cfg["events"]), seed, float(cfg["zipf_a"]),
        int(cfg["byzantine"]), cfg["withhold_span"],
        float(cfg["withhold_start_p"]), int(cfg["max_hidden"]))


def relabel(w: Withheld, seed: int) -> Withheld:
    """The same DAG with the validators' positions permuted from `seed`
    (`benchmark/traffic.py relabel`): the adversarial third is then any
    third of the positions."""
    from benchmark.traffic import relabel as relabel_dag

    return Withheld(relabel_dag(w.dag, seed), w.late, w.reveal_rows, w.created)


def creation_order(w: Withheld) -> Tuple[Dag, np.ndarray]:
    """The same events in the order they were made, and for each arrival
    row its row there: what a stream with no withholding would have been
    handed."""
    by_creation = np.argsort(w.created, kind="stable")
    row_there = np.empty(w.dag.e, dtype=np.int64)
    row_there[by_creation] = np.arange(w.dag.e)

    def rows(parents) -> np.ndarray:
        p = parents[by_creation]
        return np.where(p >= 0, row_there[np.maximum(p, 0)], -1).astype(np.int32)

    d = w.dag
    return Dag(d.n, d.creator[by_creation], d.index[by_creation],
               rows(d.self_parent), rows(d.other_parent)), row_there
