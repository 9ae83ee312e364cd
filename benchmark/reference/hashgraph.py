"""Plain reference of the ordering semantics: Hashgraph consensus as Babble
runs it (SURVEY: DivideRounds, DecideFame, DecideRoundReceived, then one
block per received round), computed in one batch over a prefix of the
stream. It imports nothing of the program and reads nothing the program
made: its inputs are the DAG the traffic generator drew, each event's
signature r (the order's tie-break), hash middle bit (the coin) and
transactions.

Every quantity here is a fact of the DAG, so a batch over the prefix and
an engine that met the same prefix sync by sync must agree once the engine
has integrated everything it was given:

- x sees y: y is an ancestor of x. la[x, c] is the highest index of
  validator c's chain among x's ancestors, so x sees y iff
  la[x, creator(y)] >= index(y).
- x strongly sees y: the validators p that have an event z with x sees z
  and z sees y number at least the supermajority 2n/3 + 1. With
  fd[y, p] the lowest index on p's chain that sees y, that is
  count_p(la[x, p] >= fd[y, p]).
- round(x) = max round of its parents, plus one if x strongly sees a
  supermajority of that round's witnesses; a validator's first event has
  round 0. x is a witness if its round exceeds its self-parent's.
- lamport(x) = 1 + max lamport of its parents (0 for a first event).
- fame of a witness x of round i: witnesses of round i+1 vote whether they
  see x; a witness y of round j > i+1 takes the majority vote v of the
  round j-1 witnesses it strongly sees, t being the size of that majority.
  In a normal round (j-i not a multiple of n) t >= supermajority decides
  fame = v; in a coin round y votes v if t >= supermajority, else its coin
  bit.
- x is received in the first round i > round(x) whose famous witnesses all
  see x, provided every round from round(x)+1 to i has all fame decided.
- rounds are committed in order while their fame is decided; a round that
  received events makes one block: its events by (lamport, signature r),
  their transactions in that order.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List, Sequence, Tuple

import numpy as np

NONE = np.iinfo(np.int32).max


@dataclass
class Ordering:
    rounds: np.ndarray  # (E,) int32
    lamport: np.ndarray  # (E,) int32
    received: np.ndarray  # (E,) int32 round received, -1 while undetermined
    blocks: List[Tuple[int, List[bytes]]]  # (round received, transactions)


def last_ancestors(n: int, creator, index, self_parent, other_parent) -> np.ndarray:
    e = len(creator)
    la = np.full((e, n), -1, dtype=np.int32)
    for i in range(e):
        sp, op = self_parent[i], other_parent[i]
        if sp >= 0 and op >= 0:
            np.maximum(la[sp], la[op], out=la[i])
        elif sp >= 0:
            la[i] = la[sp]
        elif op >= 0:
            la[i] = la[op]
        la[i, creator[i]] = index[i]
    return la


def first_descendants(n: int, creator, index, la) -> np.ndarray:
    """fd[y, p]: lowest index on p's chain whose event sees y. la[., c] never
    falls along a chain, so it is one binary search per (p, c)."""
    e = len(creator)
    fd = np.full((e, n), NONE, dtype=np.int32)
    chains = [np.flatnonzero(creator == p) for p in range(n)]  # by index
    for p in range(n):
        la_p = la[chains[p]]
        for c in range(n):
            rows = chains[c]
            if not len(rows) or not len(la_p):
                continue
            first = np.searchsorted(la_p[:, c], index[rows], side="left")
            fd[rows, p] = np.where(first < len(la_p), first, NONE)
    return fd


def order(n: int, creator, index, self_parent, other_parent,
          sig_r: Sequence[int], coin: Sequence[bool],
          transactions: Sequence[List[bytes]],
          super_majority: int = None) -> Ordering:
    """The ordering of the given events. `super_majority` is the stated
    2n/3 + 1 unless a control asks for another."""
    e = len(creator)
    sm = 2 * n // 3 + 1 if super_majority is None else super_majority
    la = last_ancestors(n, creator, index, self_parent, other_parent)
    fd = first_descendants(n, creator, index, la)

    # rounds, witnesses, lamport: one pass in creation order
    rounds = np.full(e, -1, dtype=np.int32)
    lamport = np.full(e, -1, dtype=np.int32)
    witnesses: List[List[int]] = []  # round -> witness rows, creation order
    for i in range(e):
        sp, op = self_parent[i], other_parent[i]
        sp_round = rounds[sp] if sp >= 0 else -1
        if sp < 0 and op < 0:
            r = 0
        else:
            r = max(sp_round, rounds[op] if op >= 0 else -1)
            ws = witnesses[r]
            seen = (la[i][None, :] >= fd[ws]).sum(axis=1) >= sm
            if int(seen.sum()) >= sm:
                r += 1
        rounds[i] = r
        lamport[i] = 1 + max(
            lamport[sp] if sp >= 0 else -1, lamport[op] if op >= 0 else -1
        )
        if r > sp_round:
            while len(witnesses) <= r:
                witnesses.append([])
            witnesses[r].append(i)
    last_round = len(witnesses) - 1
    wit = [np.asarray(w, dtype=np.int64) for w in witnesses]

    def strongly_sees(ys, ws):  # (|ys|, |ws|) bool
        return (la[ys][:, None, :] >= fd[ws][None, :, :]).sum(axis=2) >= sm

    # fame, round by round
    coin = np.asarray(coin, dtype=bool)
    famous: List[np.ndarray] = []  # round -> int8 per witness: 1, 0, -1 undecided
    for i in range(last_round + 1):
        xs = wit[i]
        fame = np.full(len(xs), -1, dtype=np.int8)
        votes = None
        for j in range(i + 1, last_round + 1):
            ys = wit[j]
            if j == i + 1:
                votes = la[ys][:, creator[xs]] >= index[xs][None, :]
                continue
            ss = strongly_sees(ys, wit[j - 1]).astype(np.int32)
            yays = ss @ votes.astype(np.int32)  # (|ys|, |xs|)
            nays = ss.sum(axis=1)[:, None] - yays
            v = yays >= nays
            t = np.where(v, yays, nays)
            if (j - i) % n:
                decides = t >= sm
                for k in np.flatnonzero((fame < 0) & decides.any(axis=0)):
                    fame[k] = v[np.argmax(decides[:, k]), k]
                votes = v
            else:
                votes = np.where(t >= sm, v, coin[ys][:, None])
            if (fame >= 0).all():
                break
        famous.append(fame)
    decided = [bool((f >= 0).all()) for f in famous]

    # round received
    received = np.full(e, -1, dtype=np.int32)
    open_ = np.ones(e, dtype=bool)  # undetermined and not yet behind an undecided round
    for i in range(1, last_round + 1):
        cand = np.flatnonzero(open_ & (rounds < i))
        if not len(cand):
            continue
        if not decided[i]:
            open_[cand] = False
            continue
        fws = wit[i][famous[i] == 1]
        if not len(fws):
            continue
        floor = la[fws].min(axis=0)  # per creator: index every famous witness sees
        got = cand[index[cand] <= floor[creator[cand]]]
        received[got] = i
        open_[got] = False

    # blocks: rounds in order while decided
    blocks: List[Tuple[int, List[bytes]]] = []
    by_round: dict = {}
    for x in np.flatnonzero(received >= 0):
        by_round.setdefault(int(received[x]), []).append(int(x))
    for i in range(last_round + 1):
        if not decided[i]:
            break
        xs = by_round.get(i)
        if not xs:
            continue
        xs.sort(key=lambda x: (int(lamport[x]), sig_r[x]))
        blocks.append((i, [tx for x in xs for tx in transactions[x]]))
    # an event received in a round past the first undecided one is not
    # committed yet, but its reception stands: the engine stamps it too
    return Ordering(rounds, lamport, received, blocks)
