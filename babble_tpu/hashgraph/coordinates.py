"""The first descendants of every event the graph holds, in one int32 table.

An insert sets ~n first-descendant cells, one on each of ~n ancestors. Kept
as a list on every event that is ~n cold objects fetched from the store and
touched an insert; here the indices live in one table owned by the graph,
and an insert writes its cells as ranges.

- A slot per inserted event, in insertion order (`topological_index` is
  contiguous; slot = topological index - `base`), a column per validator.
  `fd[p, s]`: the index of validator p's first event that descends from the
  event in slot s, `MAX_INT32` for none; laid out validator-major, because
  one insert writes one column, `fd[p]`, and its cells are the slots of
  recent events. An event keeps its last ancestors as its own list.
- `chain[c, k]`: the slot of validator c's k-th held event (index
  `_first[c] + 1 + k`), and `frontier[p, c]`: the highest index of
  chain c whose cell p is set. A chain is linear (a self-parent must be the
  creator's last event), so along it the set cells of a column are a prefix:
  an insert by p whose last ancestors are k writes, for every chain c,
  exactly the indices (frontier[p, c], k[c]], and nothing is probed.
  `write` is a scalar loop over those ranges: a step a cell, no look-up
  in the store.
- The hash half of a cell is the chain's own: validator p's event i is in
  slot `chain[p, i - _first[p] - 1]`, and `hashes[slot]` is its hash.
  `cells` builds the `(index, hash)` list on demand, for the persisted form
  and the tests.

Bounded: the table keeps the newest `keep` rows (the store's cache size)
and every row from `floor()` on (the graph's oldest event still without a
round received: consensus reads those however old they grow, and the store
pins them), and releases older ones in blocks of at least an eighth of its
capacity, handing `on_release` their final cells first (a store keeps them
on what it still holds of those events). A range is clipped at the oldest
held row, as the walk stopped at an ancestor the store had evicted.
"""

from __future__ import annotations

from typing import Callable, List, Tuple

import numpy as np

MAX_INT32 = 2**31 - 1

_MIN_ROWS = 256
_MIN_CHAIN = 64


class CoordinateTable:
    def __init__(self, n: int, keep: int, floor: Callable[[], int],
                 on_release: Callable):
        self.n = n
        self.keep = max(int(keep), 1)
        self.floor = floor
        self.on_release = on_release
        self.fd = np.full((n, _MIN_ROWS), MAX_INT32, np.int32)
        self.base = 0  # topological index of slot 0
        self.top = 0  # slots in use
        self.hashes: List[str] = []  # slot -> hash ("" for a hole)
        self.chain = np.zeros((n, _MIN_CHAIN), np.int32)
        # per chain: the index just below its oldest held event, the index
        # of its newest, how many it holds
        self._first = [-1] * n
        self._last = [-1] * n
        self._held = [0] * n
        # [column p, chain c]; never below chain c's `_first`, so that a
        # range never reaches under the oldest held row
        self.frontier = np.full((n, n), -1, np.int32)

    def _set_chain_first(self, c: int, first: int) -> None:
        self._first[c] = first
        column = self.frontier[:, c]
        np.maximum(column, first, out=column)

    # -- rows ----------------------------------------------------------------

    def slot_of(self, event) -> int:
        """The slot that holds `event`'s row, -1 if none does: released, or
        an event this table was never given. `begin` and `adopt` name the
        table on the event and a store names it on the copies it reads
        back, so the slot's hash has to be the event's own: a copy from
        before a `reset`, whose topological index now is another event's,
        or one that points at a hole, has no row here."""
        if event.coordinates is not self:
            return -1
        s = event.topological_index - self.base
        if 0 <= s < self.top and self.hashes[s] == event.hex():
            return s
        return -1

    def _new_slot(self, topo: int, key: str) -> int:
        s = topo - self.base
        if s >= self.fd.shape[1]:
            self._make_room(s)
            s = topo - self.base
        # a topological index burnt by a refused event is a hole
        if s > self.top:
            self.hashes.extend([""] * (s - self.top))
        self.hashes.append(key)
        self.top = s + 1
        return s

    def begin(self, event, pos: int) -> int:
        """Give `event` (`pos` its creator's position) its slot, its own
        first-descendant cell set."""
        s = self._new_slot(event.topological_index, event.hex())
        self.fd[pos, s] = event.body.index
        event.coordinates = self
        return s

    def adopt(self, event, pos: int) -> None:
        """A slot for an event that comes with its coordinates (a fast-sync
        section's: the donor's row, `Hashgraph.apply_section`)."""
        s = self._new_slot(event.topological_index, event.hex())
        cells = np.array([c[0] for c in event.first_descendants], np.int32)
        self.fd[:, s] = cells
        index = event.body.index
        self._register(pos, index, s)
        column = self.frontier[:, pos]
        np.maximum(column, index, out=column, where=cells != MAX_INT32)
        event.coordinates = self
        event.first_descendants = None

    def _register(self, c: int, index: int, s: int) -> None:
        """Chain c's event `index` is in slot s. An index that does not
        follow the chain's last (a creator numbering its events anew: the
        store overwrites its window there) is left out of the map: its row
        is read like any other, and no range reaches it."""
        held = self._held[c]
        if held == 0:
            self._set_chain_first(c, index - 1)
        elif index != self._last[c] + 1:
            return
        if held == self.chain.shape[1]:
            chain = np.zeros((self.n, 2 * held), np.int32)
            chain[:, :held] = self.chain
            self.chain = chain
        self.chain[c, held] = s
        self._held[c] = held + 1
        self._last[c] = index

    # -- the insert's write --------------------------------------------------

    def write(self, event, pos: int, s: int) -> List[int]:
        """Mark `event` (slot s, creator position `pos`) as first descendant
        down its last ancestors' chains: for every chain the indices past
        the frontier up to the last ancestor. Returns the slots written,
        chain by chain and top down (the event's own left out)."""
        index = event.body.index
        self._register(pos, index, s)
        front = self.frontier[pos]
        f = front.tolist()
        first, last = self._first, self._last
        slot_at, column = self.chain.item, self.fd[pos]
        out: List[int] = []
        for c, (kc, _) in enumerate(event.last_ancestors):
            lo = f[c]
            if kc <= lo:
                continue
            # a last ancestor is an event the chain holds or one below it;
            # a list that says otherwise (a section's, unchecked) is cut
            # to that
            if kc > last[c]:
                kc = last[c]
            f[c] = kc
            if c == pos:
                continue  # its own cell is set (begin)
            off = first[c] + 1
            while kc > lo:
                at = slot_at(c, kc - off)
                column[at] = index
                out.append(at)
                kc -= 1
        front[:] = f
        return out

    # -- reads ---------------------------------------------------------------

    def rows(self, events) -> np.ndarray:
        """The first descendants of `events` as a (len, n) int32 array: row
        slices for the events the table holds, an event's own list for the
        others."""
        slots = [self.slot_of(ev) for ev in events]
        fd = np.ascontiguousarray(self.fd[:, slots].T)
        for k, s in enumerate(slots):
            if s < 0:
                fd[k] = [c[0] for c in events[k].first_descendants]
        return fd

    def _hash_at(self, p: int, index: int) -> str:
        k = index - self._first[p] - 1
        if 0 <= k < self._held[p]:
            return self.hashes[self.chain[p, k]]
        return ""

    def cells(self, s: int) -> List[Tuple[int, str]]:
        """Slot s's first descendants as the `(index, hash)` list."""
        return [
            (MAX_INT32, "") if v == MAX_INT32 else (v, self._hash_at(p, v))
            for p, v in enumerate(self.fd[:, s].tolist())
        ]

    # -- room ----------------------------------------------------------------

    def _make_room(self, s: int) -> None:
        """Slot s is past the table's end: release the rows older than the
        newest `keep` and than `floor()` if they are an eighth of the
        capacity or more, else double the capacity."""
        cap = self.fd.shape[1]
        drop = min(self.top - self.keep, self.floor() - self.base)
        if drop >= cap // 8:
            self.release(drop)
            s -= drop
        if s < cap:
            return
        while s >= cap:
            cap *= 2
        fd = np.full((self.n, cap), MAX_INT32, np.int32)
        fd[:, : self.top] = self.fd[:, : self.top]
        self.fd = fd

    def release(self, drop: int) -> None:
        """Let the oldest `drop` rows go, `on_release` handed their final
        cells first."""
        self.on_release(self.hashes[:drop], self.cells)
        top = self.top
        fd = self.fd
        fd[:, : top - drop] = fd[:, drop:top]
        fd[:, top - drop : top] = MAX_INT32
        del self.hashes[:drop]
        self.base += drop
        self.top = top - drop
        chain = self.chain
        for c, held in enumerate(self._held):
            if not held:
                continue
            gone = int(np.searchsorted(chain[c, :held], drop))
            chain[c, : held - gone] = chain[c, gone:held]
            chain[c, : held - gone] -= drop
            self._held[c] = held - gone
            self._set_chain_first(c, self._first[c] + gone)
