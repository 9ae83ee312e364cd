"""Bytes the live rung's device work needs for one sync, from the cell's
shapes alone: what the algorithm must move between HBM and the cores
whatever program implements it, not the arrays today's `step`,
`multi_step` and `_pack_results` are passed. Widths are SURVEY's: an
event's lastAncestors and firstDescendants are int32 vectors of length n;
a round's witness table is n such vectors.

No arithmetic bound is given: the work is integer compares and small
reductions, and the chip's published peaks are for bf16 and int8 matrix
units, so HBM bandwidth is the only published roof it can be held to.
"""

from __future__ import annotations

I32 = 4
# fame is voted on in the rounds that are still open behind the newest one;
# on these streams a round's fame is decided two to four rounds later
OPEN_ROUNDS = 4


def bytes_per_sync(n: int, events: int, rounds_advanced: float) -> float:
    """Least HBM traffic of one sync of `events` new events that moves the
    newest round forward by `rounds_advanced`.

    - append: per event, read both parents' lastAncestors rows (2 n int32),
      write its own lastAncestors and firstDescendants rows (2 n int32),
      and write the firstDescendants cells it is the first descendant for:
      every (ancestor, creator) cell is written once in the stream's life,
      so n int32 per event in the long run.
    - rounds: an event's round needs the firstDescendants table (n x n
      int32) of its parents' round: one table per round the sync's events
      start from, `rounds_advanced + 1`.
    - fame: each round voted in reads the voters' lastAncestors table and
      the previous round's firstDescendants table (2 n x n int32);
      the rounds voted in are the new ones and the OPEN_ROUNDS behind them.
    - received: each of those rounds reads its famous witnesses'
      lastAncestors table once (n x n int32), and each event still
      undetermined is compared by (creator, index), 2 int32, in each.
    - results: per new event round, lamport, witness flag and round
      received (4 int32) go back to the host, and per round voted in the
      witness rows, decided flags and fame (3 n int32).
    """
    row = n * I32
    table = n * row
    voted = rounds_advanced + OPEN_ROUNDS
    append = events * 5 * row
    rounds = (rounds_advanced + 1) * table
    fame = voted * 2 * table
    received = voted * table + events * OPEN_ROUNDS * 2 * I32
    results = events * 4 * I32 + voted * 3 * row
    return append + rounds + fame + received + results
