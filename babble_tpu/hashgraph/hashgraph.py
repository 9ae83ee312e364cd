"""Hashgraph consensus core — scalar (CPU) engine.

Implements gossip-about-gossip virtual voting (reference:
src/hashgraph/hashgraph.go): a DAG of events plus five consensus passes
(DivideRounds, DecideFame, DecideRoundReceived, ProcessDecidedRounds,
ProcessSigPool) projecting a total order of transactions onto a blockchain.

This scalar engine is the semantic oracle: the TPU engine
(babble_tpu.engine.tpu) must produce identical rounds / fame / consensus
order on every DAG, enforced by differential tests.

Design deltas from the reference (deliberate, TPU-first):
- dense coordinates: last ancestors / first descendants are indexed by peer
  *position* in the sorted validator set (the reference uses ordered
  (participantId, coords) pairs, reference: src/hashgraph/event.go:62-99);
  position indexing is what the device grids use, so both engines share it.
  The first descendants of every held event are the rows of one int32 table
  (coordinates.CoordinateTable) and an insert writes its cells as ranges;
  an event keeps its last ancestors as a list.
- deterministic iteration everywhere (Python dicts are insertion-ordered;
  the reference relies on order-independence of random Go map iteration).
- memoization in plain dicts cleared on Reset (the reference uses bounded
  LRUs, reference: src/hashgraph/hashgraph.go:36-40); recursions are
  unrolled into explicit stacks so deep self-parent chains cannot overflow.
"""

from __future__ import annotations

import math
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from .. import crypto
from ..common import StoreErr, StoreErrType, is_store_err
from ..peers import Peers
from .block import Block, BlockSignature, new_block_from_frame
from .coordinates import MAX_INT32, CoordinateTable
from .event import Event, WireEvent, root_self_parent
from .frame import Frame
from .root import Root, RootEvent
from .round_info import PendingRound, RoundInfo
from .section import FrozenRef, Section
from .store import Store

MIN_INT32 = -(2**31)


class BlockDivergenceError(Exception):
    """SAFETY tripwire: a block body at an already-occupied index differs
    from the stored body. A BFT engine must never replace or divergently
    re-derive a committed body — raising here stops the node from
    compounding a fork instead of silently overwriting chain history."""


def middle_bit(ehex: str) -> bool:
    """Coin-round bit: middle byte of the event hash (reference:
    src/hashgraph/hashgraph.go:1526-1535)."""
    raw = bytes.fromhex(ehex[2:])
    if len(raw) > 0 and raw[len(raw) // 2] == 0:
        return False
    return True


class Hashgraph:
    def __init__(
        self,
        participants: Peers,
        store: Store,
        commit_callback: Optional[Callable[[Block], None]] = None,
        logger=None,
        obs=None,
    ):
        import logging

        from ..obs import Observability

        n = len(participants)
        self.participants = participants
        self.store = store
        self.commit_callback = commit_callback
        self.super_majority = 2 * n // 3 + 1
        self.trust_count = math.ceil(n / 3)
        self.logger = logger or logging.getLogger("babble.hashgraph")
        # always present so the device engines can instrument without
        # nil-guards; a Node passes its own bundle (sharing the injected
        # clock), direct construction gets a private system-clock one
        self.obs = obs if obs is not None else Observability()
        store.tracer = self.obs.tracer
        self._pass_hist = self.obs.histogram(
            "babble_consensus_pass_duration_seconds",
            "Wall time of each consensus pipeline pass",
            labels=("phase",),
        )

        self.undetermined_events: List[str] = []
        self.pending_rounds: List[PendingRound] = []
        self.last_consensus_round: Optional[int] = None
        self.first_consensus_round: Optional[int] = None
        self.anchor_block: Optional[int] = None
        # surfaced as the `round_events` stat; the reference declares this
        # counter but never assigns it (src/hashgraph/hashgraph.go:27 is its
        # only non-test mention), so staying 0 is bit-faithful parity
        self.last_committed_round_events = 0
        self.sig_pool: List[BlockSignature] = []
        # arrival inbox above; per-block-index backlog for signatures whose
        # block is not here yet (see process_sig_pool's pool discipline)
        self._sig_backlog: Dict[int, List[BlockSignature]] = {}
        # backlog indices whose signatures failed verification against a
        # still-empty state_hash: re-tried only once our commit fills it
        self._sig_wait_commit: set = set()
        self.consensus_transactions = 0
        # diagnostics: how often fame voting reached a coin round, and how
        # often the coin (event-hash middle bit) actually decided a vote —
        # lets tests prove the adversarial branch was exercised
        self.coin_rounds = 0
        self.coin_flips = 0
        # fork evidence observed locally: divergent re-derivations caught
        # by check_block_immutable. Exported in the cluster HealthDigest
        # (ISSUE 20) so any peer can see a neighbour that tripped the
        # safety invariant even after it stopped committing.
        self.fork_evidence = 0
        # deepest fame decision (j - round_index at the deciding vote):
        # 2 = every witness decided on the first ballot; >= 3 proves
        # contested fame (split votes forced extra voting rounds)
        self.max_fame_depth = 0
        self.pending_loaded_events = 0
        self.topological_index = 0
        # the frame a reset() was applied from, pinned beyond the store's
        # LRU so the anchor it backs stays servable (see reset/get_frame)
        self._reset_frame: Optional[Frame] = None

        # peer-position lookups shared with the device grids
        self._pos_by_pubkey: Dict[str, int] = {
            p.pub_key_hex: i for i, p in enumerate(participants.to_peer_slice())
        }
        self._pos_by_id: Dict[int, int] = {
            p.id: i for i, p in enumerate(participants.to_peer_slice())
        }
        # each validator's public key as the signature check takes it,
        # parsed on its first event and kept under the creator's exact
        # bytes (see _creator_key); the inserts that found theirs there
        # since process_decided_rounds last handed the count to the tracer
        # (total `insert.key_hit`)
        self._validator_keys: Dict[bytes, object] = {}
        self._key_hits = 0
        # the inserts since hand_over_inserts last gave them to the tracer
        # (totals `insert` and its parts): their count, how many of them a
        # listener was handed, and the seconds of each part
        self._inserts = 0
        self._listened = 0
        self._insert_s = 0.0
        self._verify_s = 0.0
        self._lookup_s = 0.0
        self._coords_s = 0.0
        self._fd_s = 0.0
        self._listener_s = 0.0
        # the first descendants of every held event
        self._coords = self._new_coordinates()

        # memo caches (unbounded dicts; cleared on Reset). An event's own
        # stamp fills them on a miss (see _memoized)
        self._round_cache: Dict[str, int] = {}
        self._timestamp_cache: Dict[str, int] = {}
        # round() misses answered from a stamp, rounds derived by
        # strongly-see walks, and decided rounds queued again for a late
        # witness, since process_decided_rounds last handed them to the
        # tracer (totals `round.stamp`, `round.derive`, `fame.reopen`)
        self._stamp_reads = 0
        self._derivations = 0
        self._reopens = 0

        # identities of events below a fast-sync section cut, referenced as
        # other-parents by section events (see section.py); reset_floor is
        # the anchor round of the last applied section — rounds at or below
        # it are undecidable here and skipped in the round-received scan
        self.frozen_refs: Dict[str, FrozenRef] = {}
        # (index, frame_hash, sig-set) -> valid-signature count; see
        # _block_proof_count
        self._proof_count_cache: Dict[tuple, int] = {}
        self.reset_floor: Optional[int] = None
        # index of the block this hashgraph was last reset() from (-1 if
        # never reset): the anchor-serving walk cannot build frames below it
        self._reset_anchor_index: int = -1
        # optional hook: called as (event, cells) after every insert, `cells`
        # the hashes of the ancestors whose first-descendant cell the insert
        # wrote, chain by chain and top down (column and value are the
        # event's own creator position and index) — the incremental device
        # engine's delta feed (babble_tpu/tpu/live.py)
        self.insert_listener = None

    def _new_coordinates(self) -> CoordinateTable:
        table = CoordinateTable(
            len(self.participants), self.store.cache_size(),
            self._oldest_undetermined, self.store.keep_first_descendants,
        )
        self.store.coordinates = table
        return table

    def _oldest_undetermined(self) -> int:
        """The topological index below which every event has its round
        received (`undetermined_events` is in insertion order): the table
        releases no row from there on."""
        if not self.undetermined_events:
            return self.topological_index
        try:
            oldest = self.store.get_event(self.undetermined_events[0])
        except StoreErr:
            return 0
        return oldest.topological_index

    # ------------------------------------------------------------------
    # positions
    # ------------------------------------------------------------------

    def peer_position(self, pub_key_hex: str) -> int:
        return self._pos_by_pubkey[pub_key_hex]

    # ------------------------------------------------------------------
    # DAG predicates (reference: src/hashgraph/hashgraph.go:80-395)
    # ------------------------------------------------------------------

    def ancestor(self, x: str, y: str) -> bool:
        """True if y is an ancestor of x (O(1) via last-ancestor coordinates)."""
        if x == y:
            return True
        ex = self.store.get_event(x)
        ey = self.store.get_event(y)
        pos = self._pos_by_pubkey[ey.creator()]
        last_known_index = ex.last_ancestors[pos][0]
        return last_known_index >= ey.index()

    def self_ancestor(self, x: str, y: str) -> bool:
        if x == y:
            return True
        ex = self.store.get_event(x)
        ey = self.store.get_event(y)
        return ex.creator() == ey.creator() and ex.index() >= ey.index()

    def see(self, x: str, y: str) -> bool:
        # forks are prevented at insertion, so seeing == ancestry
        return self.ancestor(x, y)

    def strongly_see(self, x: str, y: str) -> bool:
        """True if x sees y through events of a supermajority of validators:
        count positions where x's last ancestor is at or past y's first
        descendant (reference: src/hashgraph/hashgraph.go:172-191)."""
        ex = self.store.get_event(x)
        ey = self.store.get_event(y)
        table = self._coords
        sy = table.slot_of(ey)
        if sy >= 0:
            fd = table.fd[:, sy].tolist()
        else:
            fd = [cell[0] for cell in ey.first_descendants]
        c = sum(1 for la, f in zip(ex.last_ancestors, fd) if la[0] >= f)
        return c >= self.super_majority

    def coordinate_rows(self, events: List[Event]):
        """(last ancestors, first descendants) of `events` as two
        (len, n) int32 arrays: what a device attach or rebase uploads."""
        la = np.empty((len(events), len(self.participants)), np.int32)
        # a row at a time: one nested list of them all is twice as slow
        for k, ev in enumerate(events):
            la[k] = [c[0] for c in ev.last_ancestors]
        return la, self._coords.rows(events)

    # -- round ----------------------------------------------------------

    def round(self, x: str) -> int:
        cached = self._round_cache.get(x)
        if cached is not None:
            return cached
        return self._memoized(
            x, self._round_cache, self._stamped_round,
            self._round_deps, self._round_once,
        )

    def _memoized(self, x: str, memo: Dict[str, int], stamped, deps, once) -> int:
        """Iterative evaluation of a self/other-parent recursion (round,
        lamport timestamp) into its memo dict. An event's own validated
        stamp IS the memo: divide_rounds sets it from this very function,
        and every device write-back (tpu/live.py, tpu/engine.py, the mesh
        rung) stamps only what validate_round_writeback let through, so a
        miss on a stamped event reads the stamp and derives nothing. Only
        an event without a stamp (or gone from the store) is derived."""
        stack = [x]
        while stack:
            h = stack[-1]
            if h in memo:
                stack.pop()
                continue
            known = stamped(h)
            if known is None:
                missing = [d for d in deps(h) if d not in memo]
                if missing:
                    stack.extend(missing)
                    continue
                known = once(h)
            memo[h] = known
            stack.pop()
        return memo[x]

    def _stamped_round(self, x: str) -> Optional[int]:
        try:
            stamp = self.store.get_event(x).round
        except StoreErr:
            return None
        if stamp is not None:
            self._stamp_reads += 1
        return stamp

    def _stamped_lamport(self, x: str) -> Optional[int]:
        try:
            return self.store.get_event(x).lamport_timestamp
        except StoreErr:
            return None

    def _round_deps(self, x: str) -> List[str]:
        """Parent hashes whose rounds must be known before x's."""
        if x in self.store.roots_by_self_parent():
            return []
        ex = self.store.get_event(x)
        root = self.store.get_root(ex.creator())
        if ex.self_parent() == root.self_parent.hash:
            other = root.others.get(ex.hex())
            if ex.other_parent() == "" or (other is not None and other.hash == ex.other_parent()):
                return []
        deps = [ex.self_parent()]
        if ex.other_parent() != "":
            other = root.others.get(ex.hex())
            if not (other is not None and other.hash == ex.other_parent()):
                deps.append(ex.other_parent())
        return deps

    def _round_once(self, x: str) -> int:
        """Single-step round computation assuming parent rounds are cached
        (reference: src/hashgraph/hashgraph.go:205-278)."""
        roots_by_sp = self.store.roots_by_self_parent()
        if x in roots_by_sp:
            return roots_by_sp[x].self_parent.round

        ex = self.store.get_event(x)
        root = self.store.get_root(ex.creator())

        # event directly attached to the root
        if ex.self_parent() == root.self_parent.hash:
            other = root.others.get(ex.hex())
            if ex.other_parent() == "" or (other is not None and other.hash == ex.other_parent()):
                return root.next_round

        # whitepaper formula: parent round + increment
        parent_round = self._round_cache[ex.self_parent()]
        if ex.other_parent() != "":
            other = root.others.get(ex.hex())
            if other is not None and other.hash == ex.other_parent():
                op_round = root.next_round
            else:
                op_round = self._round_cache[ex.other_parent()]
            if op_round > parent_round:
                parent_round = op_round

        # from here on it is a derivation: one strongly-see walk for each
        # witness of the parent round
        self._derivations += 1
        c = 0
        for w in self.store.round_witnesses(parent_round):
            if self.strongly_see(x, w):
                c += 1
        if c >= self.super_majority:
            parent_round += 1
        return parent_round

    def witness(self, x: str) -> bool:
        """True if x is the first event of its creator in its round."""
        ex = self.store.get_event(x)
        return self.round(x) > self.round(ex.self_parent())

    def round_received(self, x: str) -> int:
        ex = self.store.get_event(x)
        return ex.round_received if ex.round_received is not None else -1

    # -- lamport ---------------------------------------------------------

    def lamport_timestamp(self, x: str) -> int:
        cached = self._timestamp_cache.get(x)
        if cached is not None:
            return cached
        return self._memoized(
            x, self._timestamp_cache, self._stamped_lamport,
            self._lamport_deps, self._lamport_once,
        )

    def _lamport_deps(self, x: str) -> List[str]:
        if x in self.store.roots_by_self_parent():
            return []
        ex = self.store.get_event(x)
        root = self.store.get_root(ex.creator())
        deps = []
        if ex.self_parent() != root.self_parent.hash:
            deps.append(ex.self_parent())
        if ex.other_parent() != "":
            try:
                self.store.get_event(ex.other_parent())
                deps.append(ex.other_parent())
            except StoreErr:
                pass
        return deps

    def _lamport_once(self, x: str) -> int:
        """reference: src/hashgraph/hashgraph.go:325-379."""
        roots_by_sp = self.store.roots_by_self_parent()
        if x in roots_by_sp:
            return roots_by_sp[x].self_parent.lamport_timestamp

        ex = self.store.get_event(x)
        root = self.store.get_root(ex.creator())

        if ex.self_parent() == root.self_parent.hash:
            plt = root.self_parent.lamport_timestamp
        else:
            plt = self._timestamp_cache[ex.self_parent()]

        if ex.other_parent() != "":
            op_lt = MIN_INT32
            if ex.other_parent() in self._timestamp_cache:
                op_lt = self._timestamp_cache[ex.other_parent()]
            else:
                other = root.others.get(x)
                if other is not None and other.hash == ex.other_parent():
                    op_lt = other.lamport_timestamp
            if op_lt > plt:
                plt = op_lt

        return plt + 1

    def round_diff(self, x: str, y: str) -> int:
        return self.round(x) - self.round(y)

    # ------------------------------------------------------------------
    # insertion (reference: src/hashgraph/hashgraph.go:398-544,714-761)
    # ------------------------------------------------------------------

    def _creator_key(self, event: Event):
        """The creator's public key, parsed. A validator's is parsed once
        and kept under the creator's exact bytes, so the table holds at most
        n keys; anyone else's is parsed for this call and kept nowhere (the
        self-parent check refuses the event after its signature)."""
        raw = event.body.creator
        pub = self._validator_keys.get(raw)
        if pub is None:
            pub = crypto.pub_key_from_bytes(raw)
            if event.creator() in self._pos_by_pubkey:
                self._validator_keys[raw] = pub
        return pub

    def _check_self_parent(self, event: Event) -> bool:
        """Raises unless the self-parent is the creator's last known event;
        returns whether that is its root's."""
        creator_last_known, is_root = self.store.last_event_from(event.creator())
        if event.self_parent() != creator_last_known:
            raise ValueError("Self-parent not last known event by creator")
        return is_root

    def _check_other_parent(self, event: Event, root: Root) -> None:
        """Raises unless the other-parent is in the store, a frozen ref, or
        the one `root` (the creator's) names for this event."""
        other_parent = event.other_parent()
        if other_parent == "":
            return
        try:
            self.store.get_event(other_parent)
            return
        except StoreErr:
            if other_parent in self.frozen_refs:
                return
            other = root.others.get(event.hex())
            if other is not None and other.hash == other_parent:
                return
            raise ValueError("Other-parent not known")

    def _stored_event(self, key: str) -> Optional[Event]:
        try:
            return self.store.get_event(key)
        except StoreErr:
            return None

    def _init_event_coordinates(
        self, event: Event, sp: Optional[Event], op: Optional[Event],
        pos: int, coords: Tuple[int, str],
    ) -> int:
        """`sp`, `op`: the parents as the store holds them (None: not
        there); `pos`, `coords`: the creator's position and the event's
        own (index, hash). Returns the event's slot in the table."""
        n = len(self.participants)

        if sp is None and op is None:
            event.last_ancestors = [(-1, "")] * n
        elif sp is None:
            event.last_ancestors = list(op.last_ancestors)
        elif op is None:
            event.last_ancestors = list(sp.last_ancestors)
        else:
            event.last_ancestors = [
                a if a[0] >= b[0] else b
                for a, b in zip(sp.last_ancestors, op.last_ancestors)
            ]

        event.last_ancestors[pos] = coords
        return self._coords.begin(event, pos)

    def _update_ancestor_first_descendant(
        self, event: Event, pos: int, slot: int,
    ) -> Optional[List[str]]:
        """Mark this event as first descendant down each last ancestor's
        self-parent chain (reference: src/hashgraph/hashgraph.go:510-544),
        as ranges of the table: for every chain the indices past the
        frontier up to the last ancestor (CoordinateTable.write). Returns the
        hashes of the ancestors whose cell was written, chain by chain and
        top down — the delta stream an incremental device engine replays;
        the cell's column and value are `pos` and the event's index for
        every one of them, so they are not repeated per cell. None where
        nobody listens. The table never asks the store: no ancestor is
        read, none has its recency refreshed, and a range stops at the
        oldest row the table holds."""
        table = self._coords
        slots = table.write(event, pos, slot)
        if self.insert_listener is None:
            return None
        return list(map(table.hashes.__getitem__, slots))

    def insert_event(self, event: Event, set_wire_info: bool) -> None:
        # per-event work is timed into totals only (`insert` and its parts
        # `.verify`, `.lookup`, `.coords`, `.fd`, `.listener`): a ring span
        # each would wrap the ring within one sync. The seconds are summed
        # here and reach the tracer once a consensus call
        # (hand_over_inserts), not through its lock three times an event
        now = self.obs.clock.monotonic
        t_insert = now()
        # every event's signature is checked, over a digest of the body as
        # handed in; only the parsing of a validator's key is not repeated
        if event.body.creator in self._validator_keys:
            self._key_hits += 1
        if not event.verify(self._creator_key(event)):
            raise ValueError("Invalid Event signature")
        t_verified = now()

        # the creator's root and the stored parents, fetched once for the
        # wire info and the coordinates. The store's recency order decides
        # where: a refused event leaves it alone, so the self-parent is read
        # after the other-parent's check (which keeps its own look-up), and
        # an accepted one leaves the self-parent older than the other-parent
        last_is_root = self._check_self_parent(event)
        creator = event.creator()
        root = self.store.get_root(creator)
        self._check_other_parent(event, root)
        sp = self._stored_event(event.self_parent())
        op = self._stored_event(event.other_parent())

        event.topological_index = self.topological_index
        self.topological_index += 1

        if set_wire_info:
            self._set_wire_info(event, sp, op, root, last_is_root)

        pos = self._pos_by_pubkey[creator]
        coords = (event.index(), event.hex())
        t_looked_up = now()
        slot = self._init_event_coordinates(event, sp, op, pos, coords)
        self.store.set_event(event)
        t_fd = now()
        cells = self._update_ancestor_first_descendant(event, pos, slot)
        t_fd_done = now()
        if self.insert_listener is not None:
            self.insert_listener(event, cells)
            self._listened += 1
            self._listener_s += now() - t_fd_done

        self.undetermined_events.append(coords[1])
        if event.is_loaded():
            self.pending_loaded_events += 1
        self.sig_pool.extend(event.block_signatures())
        # causal tracing (ISSUE 5): the traced txs this event carries are
        # now in the graph — the trace store looks them up by tx hash, so
        # no trace data touches the signed event bytes
        self.obs.traces.mark_event(event.transactions())
        self._inserts += 1
        self._verify_s += t_verified - t_insert
        self._lookup_s += t_looked_up - t_verified
        self._coords_s += t_fd - t_looked_up
        self._fd_s += t_fd_done - t_fd
        self._insert_s += now() - t_insert

    def hand_over_inserts(self) -> None:
        """Give the tracer the inserts summed since the last hand-over:
        totals `insert`, `insert.verify`, `insert.lookup`, `insert.coords`,
        `insert.fd`, each with the count of inserts, and `insert.listener`
        with the count of those a listener was handed. `Core.run_consensus`
        calls this before its entry checkpoint, so that a window of
        checkpoints holds whole calls and the inserts between them;
        process_decided_rounds does too, for a Hashgraph without a Core."""
        n = self._inserts
        if not n:
            return
        tracer = self.obs.tracer
        tracer.add("insert.verify", self._verify_s, n)
        tracer.add("insert.lookup", self._lookup_s, n)
        tracer.add("insert.coords", self._coords_s, n)
        tracer.add("insert.fd", self._fd_s, n)
        if self._listened:
            tracer.add("insert.listener", self._listener_s, self._listened)
        tracer.add("insert", self._insert_s, n)
        self._inserts = self._listened = 0
        self._insert_s = self._verify_s = self._lookup_s = 0.0
        self._coords_s = self._fd_s = self._listener_s = 0.0

    def _set_wire_info(
        self, event: Event, sp: Optional[Event], op: Optional[Event],
        root: Root, last_is_root: bool,
    ) -> None:
        """`sp`, `op`, `root`, `last_is_root`: what insert_event fetched
        (the stored parents, the creator's root, whether the self-parent is
        the root's). A parent that is needed here and is not in the store
        raises the store's own error."""
        other_parent_creator_id = -1
        other_parent_index = -1

        if last_is_root:
            self_parent_index = root.self_parent.index
        else:
            if sp is None:
                sp = self.store.get_event(event.self_parent())
            self_parent_index = sp.index()

        if event.other_parent() != "":
            other = root.others.get(event.hex())
            if other is not None and other.hash == event.other_parent():
                other_parent_creator_id = other.creator_id
                other_parent_index = other.index
            else:
                if op is None:
                    op = self.store.get_event(event.other_parent())
                other_parent_creator_id = self.participants.by_pub_key[
                    op.creator()
                ].id
                other_parent_index = op.index()

        event.set_wire_info(
            self_parent_index,
            other_parent_creator_id,
            other_parent_index,
            self.participants.by_pub_key[event.creator()].id,
        )

    # ------------------------------------------------------------------
    # roots (reference: src/hashgraph/hashgraph.go:546-640)
    # ------------------------------------------------------------------

    def _create_self_parent_root_event(self, ev: Event) -> RootEvent:
        sp = ev.self_parent()
        return RootEvent(
            hash=sp,
            creator_id=self.participants.by_pub_key[ev.creator()].id,
            index=ev.index() - 1,
            lamport_timestamp=self.lamport_timestamp(sp),
            round=self.round(sp),
        )

    def _create_other_parent_root_event(self, ev: Event) -> RootEvent:
        op = ev.other_parent()
        root = self.store.get_root(ev.creator())
        other = root.others.get(ev.hex())
        if other is not None and other.hash == op:
            return other
        try:
            other_parent = self.store.get_event(op)
        except StoreErr:
            ref = self.frozen_refs.get(op)
            if ref is None:
                raise
            return RootEvent(
                hash=op,
                creator_id=ref.creator_id,
                index=ref.index,
                lamport_timestamp=ref.lamport,
                round=ref.round,
            )
        return RootEvent(
            hash=op,
            creator_id=self.participants.by_pub_key[other_parent.creator()].id,
            index=other_parent.index(),
            lamport_timestamp=self.lamport_timestamp(op),
            round=self.round(op),
        )

    def _create_root(self, ev: Event) -> Root:
        root = Root(
            next_round=self.round(ev.hex()),
            self_parent=self._create_self_parent_root_event(ev),
            others={},
        )
        if ev.other_parent() != "":
            root.others[ev.hex()] = self._create_other_parent_root_event(ev)
        return root

    # ------------------------------------------------------------------
    # the five passes
    # ------------------------------------------------------------------

    def queue_round(self, round_number: int, round_info: RoundInfo,
                    late_witness: bool) -> bool:
        """Queue the round an event was just divided into: the round's
        first event queues it, and a witness that arrives after its round
        was decided and dequeued queues it AGAIN (`late_witness`: the
        event is a witness whose fame the round does not hold yet). The
        one place both engines register an event's round, DivideRounds and
        the device write-back alike. Returns True for a re-open.

        A late witness (a crashed peer's pre-crash tail event surfacing
        post-restart, a withheld chain revealed rounds later) would
        otherwise keep fame UNDEFINED forever: decide_fame and the device
        fame write-back only visit pending rounds, so witnesses_decided()
        flips false for good and every reception scan crossing this round
        stalls, while peers that held the event before deciding receive
        those events normally (the round-5 survivor-side reception
        divergence). Re-queued, fame resolves; process_decided_rounds
        drops a settled round again once it is decided, so no block is
        ever re-minted."""
        # lower bound prevents reprocessing the base layer after Reset
        if not round_info.queued and (
            self.last_consensus_round is None
            or round_number >= self.last_consensus_round
        ):
            self.pending_rounds.append(PendingRound(round_number, False))
            round_info.queued = True
            return False
        if (
            late_witness
            and round_info.queued
            # rounds at or below a fast-sync cut are the donor's to
            # decide: their votes are not derivable from the scrubbed
            # DAG, so re-queueing could never resolve
            and (self.reset_floor is None or round_number > self.reset_floor)
            and not any(p.index == round_number for p in self.pending_rounds)
        ):
            self.pending_rounds.append(PendingRound(round_number, False))
            self.obs.flightrec.record("fame.reopen", round=round_number)
            self._reopens += 1
            return True
        return False

    def divide_rounds(self) -> None:
        """Assign round + lamport timestamp, flag witnesses, queue pending
        rounds (reference: src/hashgraph/hashgraph.go:767-849)."""
        for hash_ in self.undetermined_events:
            ev = self.store.get_event(hash_)
            update_event = False

            if ev.round is None:
                round_number = self.round(hash_)
                ev.set_round(round_number)
                self.obs.traces.mark_round(ev.transactions())
                update_event = True

                try:
                    round_info = self.store.get_round(round_number)
                except StoreErr as e:
                    if not is_store_err(e, StoreErrType.KEY_NOT_FOUND):
                        raise
                    round_info = RoundInfo()

                is_witness = self.witness(hash_)

                self.queue_round(
                    round_number, round_info,
                    late_witness=is_witness and not round_info.is_decided(hash_),
                )

                round_info.add_event(hash_, is_witness)
                self.store.set_round(round_number, round_info)
                if is_witness:
                    self.obs.provenance.note_witness(
                        hash_, round_number, self.peer_position(ev.creator()),
                    )

            if ev.lamport_timestamp is None:
                ev.set_lamport_timestamp(self.lamport_timestamp(hash_))
                update_event = True

            if update_event:
                self.store.set_event(ev)
                if (
                    ev.round is not None
                    and ev.lamport_timestamp is not None
                    and ev.last_ancestors is not None
                ):
                    # decision provenance: the DivideRounds table cell —
                    # same value the device engines capture from their
                    # staged lastAncestors rows (obs/provenance.py)
                    self.obs.provenance.note_event(
                        hash_, ev.round, ev.lamport_timestamp,
                        ev.last_ancestors,
                    )

    def decide_fame(self) -> None:
        """Virtual voting on witness fame (reference:
        src/hashgraph/hashgraph.go:852-947)."""
        votes: Dict[Tuple[str, str], bool] = {}  # (y, x) => vote

        decided_rounds: Dict[int, int] = {}

        for pos, pr in enumerate(self.pending_rounds):
            round_index = pr.index
            round_info = self.store.get_round(round_index)
            for x in round_info.witnesses():
                if round_info.is_decided(x):
                    continue
                decided = False
                # decision provenance: coin rounds traversed (and coin
                # flips taken) while fame of x was open — part of the
                # "why" on the landed verdict (obs/provenance.py)
                x_coins = 0
                x_flips = 0
                for j in range(round_index + 1, self.store.last_round() + 1):
                    if decided:
                        break
                    for y in self.store.round_witnesses(j):
                        diff = j - round_index
                        if diff == 1:
                            votes[(y, x)] = self.see(y, x)
                        else:
                            # count votes among strongly-seen prev-round witnesses
                            ss_witnesses = [
                                w
                                for w in self.store.round_witnesses(j - 1)
                                if self.strongly_see(y, w)
                            ]
                            yays = sum(1 for w in ss_witnesses if votes.get((w, x), False))
                            nays = len(ss_witnesses) - yays
                            v = yays >= nays
                            t = yays if v else nays

                            if diff % len(self.participants) > 0:
                                # normal round: supermajority decides
                                if t >= self.super_majority:
                                    round_info.set_fame(x, v)
                                    votes[(y, x)] = v
                                    decided = True
                                    self.max_fame_depth = max(
                                        self.max_fame_depth, diff
                                    )
                                    # the landed verdict with its full
                                    # "why": deciding voter, tallies,
                                    # strongly-seen count, deciding step
                                    self.obs.provenance.note_fame(
                                        x, round_index, v, engine="cpu",
                                        voter=y, yays=yays, nays=nays,
                                        ss=len(ss_witnesses), step=diff,
                                        coins=x_coins, flips=x_flips,
                                    )
                                    break
                                votes[(y, x)] = v
                            else:
                                # coin round
                                self.coin_rounds += 1
                                x_coins += 1
                                if t >= self.super_majority:
                                    votes[(y, x)] = v
                                else:
                                    votes[(y, x)] = middle_bit(y)
                                    self.coin_flips += 1
                                    x_flips += 1

            self.store.set_round(round_index, round_info)
            if round_info.witnesses_decided():
                decided_rounds[round_index] = pos

        # recompute (not just promote): a late witness re-opening a round
        # must also UNSET a stale decided flag, or process_decided_rounds
        # could settle the round around an undefined fame
        for pr in self.pending_rounds:
            pr.decided = pr.index in decided_rounds

    def decide_round_received(self) -> None:
        """An event is received in the first round where all unique famous
        witnesses see it, provided all earlier rounds are fully decided
        (reference: src/hashgraph/hashgraph.go:951-1036)."""
        new_undetermined: List[str] = []

        for x in self.undetermined_events:
            received = False
            r = self.round(x)

            for i in range(r + 1, self.store.last_round() + 1):
                try:
                    tr = self.store.get_round(i)
                except StoreErr:
                    # rounds at or below a fast-sync cut are undecidable
                    # here; the donor already evaluated them as not
                    # receiving this event, so keep scanning upward
                    if self.reset_floor is not None and i <= self.reset_floor:
                        continue
                    # can happen after Reset/fast-sync
                    if (
                        self.last_consensus_round is not None
                        and r < self.last_consensus_round
                    ):
                        received = True
                        break
                    raise

                if not tr.witnesses_decided():
                    break

                fws = tr.famous_witnesses()
                s = [w for w in fws if self.see(w, x)]

                if len(s) == len(fws) and len(s) > 0:
                    received = True
                    ex = self.store.get_event(x)
                    ex.set_round_received(i)
                    self.obs.provenance.note_received(x, i)
                    self.obs.traces.mark_famous(ex.transactions())
                    self.store.set_event(ex)
                    tr.set_consensus_event(x)
                    self.store.set_round(i, tr)
                    break

            if not received:
                new_undetermined.append(x)

        self.undetermined_events = new_undetermined

    def process_decided_rounds(self) -> None:
        """Map decided rounds onto Frames and Blocks; commit through the
        callback (reference: src/hashgraph/hashgraph.go:1041-1122). Timed
        here, not by the caller, so that every engine rung that runs the
        host commit leaves the same span and histogram sample."""
        with self.obs.span(
            "consensus.process_decided_rounds",
            histogram=self._pass_hist.labels(phase="process_decided_rounds"),
        ):
            try:
                self._process_decided_rounds()
            finally:
                # once a call, not once an event: the host engine derives
                # a round for every event it divides
                tracer = self.obs.tracer
                if self._stamp_reads:
                    tracer.add("round.stamp", 0.0, count=self._stamp_reads)
                    self._stamp_reads = 0
                if self._derivations:
                    tracer.add("round.derive", 0.0, count=self._derivations)
                    self._derivations = 0
                if self._reopens:
                    tracer.add("fame.reopen", 0.0, count=self._reopens)
                    self._reopens = 0
                if self._key_hits:
                    tracer.add("insert.key_hit", 0.0, count=self._key_hits)
                    self._key_hits = 0
                self.hand_over_inserts()

    def _process_decided_rounds(self) -> None:
        """The commit loop of process_decided_rounds.

        Processing order is SORTED round order, not queue order, and any
        round at or below last_consensus_round is dropped as settled —
        both deliberate strengthenings of the reference (which processes
        its FIFO queue and skips only `index == LastConsensusRound`,
        hashgraph.go:1049-1063). The reference can rely on queue order
        because its joiners re-derive everything from live sync; this
        rebuild's section replay (apply_section) re-queues scrubbed rounds
        in section TOPOLOGICAL order, where a round-13 event can precede a
        round-12 event. Processing 13 first advances last_consensus_round
        past 12, after which an equality skip no longer recognizes the
        settled anchor round — it was re-minted as a duplicate block at
        the next free index, shifting the joiner's whole chain one block
        against the cluster (the round-5 in-suite byte-divergence). A
        round <= last_consensus_round is materialized by construction
        (blocks mint in this loop in ascending round order; reset/section
        replay settle the anchor), so the floor skip can never drop an
        unmaterialized round."""
        pending = sorted(self.pending_rounds, key=lambda p: p.index)
        pos = 0
        try:
            while pos < len(pending):
                pr = pending[pos]
                # rounds at or below a fast-sync cut were settled by the
                # donor; their fame is not re-derivable from the scrubbed
                # DAG, so they may never read as decided here — drop them
                # unconditionally (the original floor-skip behavior)
                donor_settled = (
                    self.reset_floor is not None
                    and pr.index <= self.reset_floor
                )
                if (
                    self.last_consensus_round is not None
                    and pr.index <= self.last_consensus_round
                    and (pr.decided or donor_settled)
                ):
                    # settled round back in the queue (re-queued for a late
                    # witness, or section replay): fame is whole again (or
                    # donor authority), drop it without re-minting a block
                    pos += 1
                    continue
                # never process a decided round before all previous rounds
                # are whole — including a settled round re-opened by a late
                # witness: later frames must not freeze while an earlier
                # round's famous set is still in question
                if not pr.decided:
                    break

                frame = self.get_frame(pr.index)

                if frame.events:
                    # the span's end is the commit instant
                    with self.obs.span("commit.block", round=pr.index) as sp:
                        txs = 0
                        for e in frame.events:
                            self.store.add_consensus_event(e)
                            txs += len(e.transactions())
                            if e.is_loaded():
                                self.pending_loaded_events -= 1
                        self.consensus_transactions += txs

                        last_block_index = self.store.last_block_index()
                        block = new_block_from_frame(last_block_index + 1, frame)
                        self.check_block_immutable(block)
                        self.store.set_block(block)
                        if self.commit_callback is not None:
                            # on disk before the application sees it: the
                            # block, its frame and every event it orders
                            self.store.flush()
                            self.commit_callback(block)
                        sp.attrs["index"] = block.index()
                        sp.attrs["txs"] = txs

                pos += 1
                self._set_last_consensus_round(pr.index)
                # the round's tables are committed history from here on
                self.obs.provenance.settle_round(pr.index)
        finally:
            self.pending_rounds = pending[pos:]

    def get_frame(self, round_received: int) -> Frame:
        """reference: src/hashgraph/hashgraph.go:1125-1231."""
        try:
            return self.store.get_frame(round_received)
        except StoreErr as e:
            if not is_store_err(e, StoreErrType.KEY_NOT_FOUND):
                raise
        rf = getattr(self, "_reset_frame", None)
        if rf is not None and rf.round == round_received:
            # the pinned post-reset frame (see reset()): evicted from the
            # store's LRU but still the only buildable copy of its round
            return rf

        with self.obs.span("commit.frame", round=round_received) as sp:
            return self._build_frame(round_received, sp.attrs)

    def _build_frame(self, round_received: int, note: dict) -> Frame:
        """Build and store the frame of a round the store does not hold;
        `note` takes what the `commit.frame` span says of it."""
        derived_before = self._derivations
        now = self.obs.clock.monotonic
        t_frame = now()
        round_info = self.store.get_round(round_received)
        events = [self.store.get_event(eh) for eh in round_info.consensus_events()]
        from .event import by_lamport_key

        events.sort(key=by_lamport_key)
        t_events = now()

        roots: Dict[str, Root] = {}
        created = 0
        for ev in events:
            p = ev.creator()
            if p not in roots:
                roots[p] = self._create_root(ev)
                created += 1

        # participants with no events in the frame: root from last consensus event
        for p in self.participants.to_pub_key_slice():
            if p not in roots:
                last_consensus, is_root = self.store.last_consensus_event_from(p)
                if is_root:
                    root = self.store.get_root(p)
                else:
                    root = self._create_root(self.store.get_event(last_consensus))
                    created += 1
                roots[p] = root

        # other-parents outside the frame must be reachable via Root.Others
        treated = set()
        for ev in events:
            treated.add(ev.hex())
            other_parent = ev.other_parent()
            if other_parent != "" and other_parent not in treated:
                if ev.self_parent() != roots[ev.creator()].self_parent.hash:
                    roots[ev.creator()].others[ev.hex()] = (
                        self._create_other_parent_root_event(ev)
                    )

        ordered_roots = [roots[p.pub_key_hex] for p in self.participants.to_peer_slice()]

        # the two parts of `commit.frame`, once a frame (the rest of the
        # span is set_frame): reading and sorting the round's events, and
        # the roots with the other-parent walk
        t_roots = now()
        tracer = self.obs.tracer
        tracer.add("commit.frame.events", t_events - t_frame, len(events))
        tracer.add("commit.frame.roots", t_roots - t_events, created)
        res = Frame(round=round_received, roots=ordered_roots, events=events)
        self.store.set_frame(res)
        note["events"] = len(events)
        note["roots_created"] = created
        note["rounds_derived"] = self._derivations - derived_before
        return res

    # ECDSA verifications per process_sig_pool pass. The pass runs under
    # core_lock on every sync; an unbounded pass (e.g. the burst of
    # backlogged signatures that all become verifiable the moment a
    # fast-forward rebuilds the store) stalls the lock past peers' RPC
    # timeouts and reads as a dead node (round-5 faulthandler capture:
    # every peer thread queued behind one process_sig_pool walk).
    SIG_POOL_VERIFY_BUDGET = 512

    # Bound on how far ABOVE our block height a backlogged signature may
    # claim to be before we refuse to hold it (ISSUE 1 satellite): without
    # a horizon, a lagging node accumulates one bucket per future block
    # its peers commit — unbounded memory held under core_lock forever if
    # the node never catches up incrementally (it fast-forwards instead,
    # and reset() clears pre-anchor buckets but future junk keyed by a
    # byzantine peer's fictitious indices would survive every pass). Sized
    # like a generous sync-limit horizon: signatures for blocks this far
    # ahead cannot attach before a fast-forward rebuilds state anyway, and
    # honest peers re-carry their signatures in events we re-receive then.
    SIG_BACKLOG_HORIZON = 1024
    # Hard cap on backlog buckets: even within the horizon, eviction keeps
    # a byzantine flood bounded. Farthest-future buckets go first: the
    # lowest indices are the next to attach (they advance the anchor),
    # while far-future signatures are re-carried by honest peers' events
    # after the fast-forward that reaching them requires — dropping those
    # loses nothing durable.
    SIG_BACKLOG_MAX_BUCKETS = 2048

    def pending_signatures(self) -> int:
        """Signatures waiting to attach: the arrival inbox plus the
        per-block backlog (observability + tests)."""
        return len(self.sig_pool) + sum(
            len(v) for v in self._sig_backlog.values()
        )

    def process_sig_pool(self) -> None:
        """Attach valid signatures to blocks; advance the anchor block once a
        block has >1/3 signatures (reference: src/hashgraph/hashgraph.go:1236-1300).
        Timed here, not by the caller, like process_decided_rounds."""
        with self.obs.span(
            "consensus.process_sig_pool",
            histogram=self._pass_hist.labels(phase="process_sig_pool"),
        ):
            self._process_sig_pool()

    def _process_sig_pool(self) -> None:
        """The pass of process_sig_pool.

        The pool discipline is deliberately tighter than the reference,
        which keeps every unprocessed signature in one flat list and
        re-walks it all — re-verifying the invalid ones — on every pass
        (hashgraph.go:1240-1297 marks only attached ones processed). Go
        clusters never feel that; this rebuild's lagging nodes do: a node
        2,000 blocks behind holds ~8,000 future-block signatures, and an
        O(pool) walk with store-miss exceptions under core_lock on EVERY
        sync is a round-5 cluster wedge (observed: joiner pinned at block
        23 while peers ran to 2,462). So arrivals land in an inbox
        (`sig_pool`), are routed once into a per-block-index backlog, and
        each pass touches ONLY indices at or below the store's block
        height — a far-future signature costs nothing until its block
        exists. Rules:
        - unknown validator: dropped (the validator set is static);
        - block index above our height: backlogged untouched;
        - block at or below our height but absent locally (pre-anchor gap
          after a fast-forward, or evicted): dropped — it can never attach;
        - invalid against a body whose state_hash is still empty:
          retained, and the bucket is then skipped at zero ECDSA cost
          until our commit fills the hash (the only event that can change
          the outcome; peers sign after their commit does). The skip is
          armed by a FAILED verify, never by the empty hash alone —
          stateless apps legitimately finalize at state_hash=b"" and
          their signatures must attach on the first pass;
        - invalid against a final (state-hashed) body: dropped — an
          immutable body can never re-validate the signature."""
        inbox, self.sig_pool = self.sig_pool, []
        for bs in inbox:
            if bs.validator_hex() not in self.participants.by_pub_key:
                self.logger.warning(
                    "Unknown validator for block signature: %s",
                    bs.validator_hex(),
                )
                continue
            self._sig_backlog.setdefault(bs.index, []).append(bs)
            # a new arrival re-opens a wait-committed bucket: the skip
            # below exists to avoid RE-verifying known failures, and must
            # not deny a first verification to a fresh signature — for a
            # stateless app (final state_hash=b"") one corrupt signature
            # would otherwise wedge the bucket and block valid ones from
            # ever attaching (code review r5)
            self._sig_wait_commit.discard(bs.index)

        last_block = self.store.last_block_index()
        # backlog bound (see SIG_BACKLOG_HORIZON/MAX_BUCKETS): drop buckets
        # past the horizon, then evict farthest-future buckets beyond the
        # hard cap. Runs after routing so a single pass bounds whatever the
        # inbox brought in.
        horizon = last_block + self.SIG_BACKLOG_HORIZON
        beyond = [i for i in self._sig_backlog if i > horizon]
        for idx in beyond:
            self._sig_backlog.pop(idx)
            self._sig_wait_commit.discard(idx)
        if beyond:
            self.obs.flightrec.record(
                "sig.pressure", kind="horizon", dropped=len(beyond),
                last_block=last_block,
            )
            self.logger.warning(
                "sig backlog: dropped %d bucket(s) beyond horizon "
                "(last_block=%d horizon=+%d max_index=%d)",
                len(beyond), last_block, self.SIG_BACKLOG_HORIZON,
                max(beyond),
            )
        if len(self._sig_backlog) > self.SIG_BACKLOG_MAX_BUCKETS:
            excess = sorted(self._sig_backlog, reverse=True)[
                : len(self._sig_backlog) - self.SIG_BACKLOG_MAX_BUCKETS
            ]
            for idx in excess:
                self._sig_backlog.pop(idx)
                self._sig_wait_commit.discard(idx)
            self.obs.flightrec.record(
                "sig.pressure", kind="cap", dropped=len(excess),
                last_block=last_block,
            )
            self.logger.warning(
                "sig backlog: evicted %d farthest-future bucket(s) over "
                "the %d-bucket cap", len(excess), self.SIG_BACKLOG_MAX_BUCKETS,
            )
        verified = 0
        for idx in sorted(i for i in self._sig_backlog if i <= last_block):
            if verified >= self.SIG_POOL_VERIFY_BUDGET:
                break
            try:
                block = self.store.get_block(idx)
            except StoreErr:
                self._sig_backlog.pop(idx)
                self._sig_wait_commit.discard(idx)
                continue
            if idx in self._sig_wait_commit and not block.state_hash():
                # this bucket already failed verification against the
                # still-empty body; the only event that can change the
                # outcome is our commit filling state_hash — skip at zero
                # ECDSA cost until then (code review r5: re-verifying
                # burned the whole budget on deterministic failures).
                # NOTE an empty state_hash is NOT itself proof of a
                # pending commit — stateless apps legitimately finalize
                # at b"" — which is why entry to this set requires an
                # actual failed verify, not the falsy hash alone.
                continue
            bucket = self._sig_backlog.pop(idx)
            self._sig_wait_commit.discard(idx)
            retained: List[BlockSignature] = []
            failed_on_empty = False
            truncated = False
            updated = False
            for pos, bs in enumerate(bucket):
                if verified >= self.SIG_POOL_VERIFY_BUDGET:
                    retained.extend(bucket[pos:])
                    truncated = True
                    break
                verified += 1
                if not block.verify(bs):
                    if not block.state_hash():
                        # may be OUR commit lagging (peers sign after
                        # theirs fills state_hash): retry after commit
                        retained.append(bs)
                        failed_on_empty = True
                    else:
                        self.logger.warning(
                            "Invalid block signature for block %d "
                            "(validator=%s rr=%d txs=%d)",
                            idx,
                            bs.validator_hex()[:12],
                            block.round_received(),
                            len(block.transactions()),
                        )
                    continue
                block.set_signature(bs)
                updated = True
            if updated:
                self.store.set_block(block)
                if len(block.signatures) > self.trust_count and (
                    self.anchor_block is None or block.index() > self.anchor_block
                ):
                    self.anchor_block = block.index()
            if retained:
                self._sig_backlog[idx] = retained
                # arm the skip only when EVERY retained signature actually
                # failed against the empty body — budget-truncated ones
                # were never verified, and for a stateless app (hash stays
                # b"" forever) the skip would deny them a first pass for
                # good (code review r5)
                if failed_on_empty and not truncated:
                    self._sig_wait_commit.add(idx)

    def run_consensus(self) -> None:
        """The full pipeline with per-pass timing into the obs layer
        (reference: src/node/core.go:335-377). Durations ride the
        injected clock, not perf_counter, so the per-pass histograms are
        byte-deterministic under the simulator's virtual time (where
        every pass reads as zero-cost, which is exactly the sim's model)."""
        for name, phase, pass_ in (
            ("DivideRounds", "divide_rounds", self.divide_rounds),
            ("DecideFame", "decide_fame", self.decide_fame),
            ("DecideRoundReceived", "decide_round_received",
             self.decide_round_received),
        ):
            with self.obs.span("consensus." + phase, histogram=self._pass_hist.labels(phase=phase)) as sp:  # obs-ok: phases are the literal tuple above
                pass_()
            self.logger.debug("%s() duration=%dns", name, int(sp.duration * 1e9))
        # the last two passes time themselves: the device rungs call them too
        self.process_decided_rounds()
        self.process_sig_pool()

    # ------------------------------------------------------------------
    # anchor / reset / bootstrap (reference: src/hashgraph/hashgraph.go:1302-1410)
    # ------------------------------------------------------------------

    def get_anchor_block_with_frame(
        self, max_index: Optional[int] = None
    ) -> Tuple[Block, Frame]:
        """The freshest servable anchor: a block with >1/3 accumulated
        signatures and a buildable frame, at or below `max_index`.

        `max_index` caps the anchor at the app's last-committed block: the
        commit channel is async (reference analog src/node/node.go:323-345),
        so the hashgraph's anchor_block can run up to a full channel ahead
        of the app — serving it would make the donor's get_snapshot fail
        ("snapshot N not found") and starve every joiner until the commit
        loop catches up. Capping here makes that starvation impossible by
        construction (VERDICT r4 #2). Signatures on locally stored blocks
        were verified before being attached (process_sig_pool), so the
        threshold check is a length test, not an ECDSA pass."""
        if self.anchor_block is None:
            raise ValueError("No Anchor Block")
        idx = self.anchor_block
        if max_index is not None and max_index < idx:
            idx = max_index
        # bounded walk (code review r5): blocks below our own reset anchor
        # have no rebuildable frames (reset cleared their rounds), and a
        # donor whose chain is healthy finds a signed anchor within a few
        # steps — so don't let a pathological store turn every joiner
        # request into an O(cache) scan under core_lock
        floor = max(self._reset_anchor_index, idx - 128)
        while idx >= floor:
            try:
                block = self.store.get_block(idx)
            except StoreErr:
                break
            if len(block.signatures) > self.trust_count:
                try:
                    frame = self.get_frame(block.round_received())
                except StoreErr:
                    idx -= 1
                    continue
                return block, frame
            idx -= 1
        raise ValueError(
            "No servable anchor"
            + (f" at or below block {max_index}" if max_index is not None else "")
        )

    def reset(self, block: Block, frame: Frame) -> None:
        self.obs.flightrec.record(
            "hashgraph.reset", block=block.index(),
            round=block.round_received(),
        )
        # any incremental device state is invalid after a reset
        eng = getattr(self, "_live_device_engine", None)
        if eng is not None:
            eng.detach()
            self._live_device_engine = None
        self.last_consensus_round = None
        self.first_consensus_round = None
        self.anchor_block = None

        self.undetermined_events = []
        self.pending_rounds = []
        self.pending_loaded_events = 0
        self.topological_index = 0

        self._round_cache.clear()
        self._timestamp_cache.clear()
        self.frozen_refs.clear()
        self.reset_floor = None
        # wait-commit flags describe pre-reset block bodies; the backlog
        # itself is kept (signatures may attach to replayed blocks) but
        # every bucket deserves a fresh verification pass against them
        self._sig_wait_commit.clear()

        participants = self.participants.to_peer_slice()
        root_map = {participants[pos].pub_key_hex: root for pos, root in enumerate(frame.roots)}
        self.store.reset(root_map)
        # what the store still keeps of the events so far (a persisting
        # store's rows) keeps their final cells; the inserts below fill a
        # new table
        self._coords.release(self._coords.top)
        self._coords = self._new_coordinates()
        self.store.set_block(block)
        # keep the received frame servable: it IS the frame at the anchor's
        # round_received, already validated against the block's signed
        # FrameHash. Without it, a fresh-synced node that becomes an anchor
        # holder cannot rebuild the frame (the round's consensus bookkeeping
        # predates the reset) and every FastForwardRequest it serves fails
        # with a missing-round error — observed livelocking a cluster whose
        # only Babbling node was a fresh joiner. Pinned on the hashgraph as
        # well: the store's frame cache is an evicting LRU, and a stalled
        # anchor must stay servable past cache_size newer rounds.
        self.store.set_frame(frame)
        self._reset_frame = frame
        self._reset_anchor_index = block.index()
        self._set_last_consensus_round(block.round_received())

        for ev in frame.events:
            self.insert_event(ev, False)

        # Seed the last-consensus-event baseline recoverable from the frame
        # itself: frame events are the events RECEIVED at the anchor round,
        # and round-received is monotone along each self-parent chain, so a
        # participant's highest-indexed frame event IS its last consensus
        # event as of the anchor. Without this, the next frame this node
        # builds constructs roots for participants quiet since the anchor
        # from the anchor ROOT (their first-received event) instead of
        # their last consensus event — a divergent FrameHash, hence a
        # byte-divergent block (the round-5 root cause of the mixed-backend
        # fast-sync divergence; the section path's consensus_baseline
        # refines this for participants quiet since BEFORE the anchor,
        # whose correct roots the frame's root_map already carries).
        last_per_creator: Dict[str, Event] = {}
        for ev in frame.events:
            cur = last_per_creator.get(ev.creator())
            if cur is None or ev.index() > cur.index():
                last_per_creator[ev.creator()] = ev
        for p, ev in last_per_creator.items():
            self.store.seed_last_consensus_event(p, ev.hex())

    # ------------------------------------------------------------------
    # fast-sync live section (beyond the reference — see section.py)
    # ------------------------------------------------------------------

    def get_section(self, anchor_round: int, anchor_block_index: int = -1) -> Section:
        """Donor side: everything decided or pending above the anchor cut.
        Caller must hold the node's core lock so the snapshot is consistent.
        `anchor_block_index` keys the accumulated-signature proof for the
        blocks above the anchor (verify_section on the joiner)."""
        last_consensus = (
            self.last_consensus_round
            if self.last_consensus_round is not None
            else anchor_round
        )

        # Per-column collection: every event above the joiner's post-reset
        # base head (its frame head, or the frame root's self-parent for
        # columns absent from the frame). This is exactly the diff a fresh
        # reset store would request, so self-parent chains stay intact.
        frame = self.get_frame(anchor_round)
        peer_slice = self.participants.to_peer_slice()
        base_idx: Dict[str, int] = {
            peer.pub_key_hex: frame.roots[i].self_parent.index
            for i, peer in enumerate(peer_slice)
        }
        for ev in frame.events:
            p = ev.creator()
            if ev.index() > base_idx[p]:
                base_idx[p] = ev.index()

        events: List[Event] = []
        seen = set()
        for p, base in base_idx.items():
            for h in self.store.participant_events(p, base):
                ev = self.store.get_event(h)
                if ev.round is None:
                    ev.set_round(self.round(h))
                if ev.lamport_timestamp is None:
                    ev.set_lamport_timestamp(self.lamport_timestamp(h))
                events.append(ev)
                seen.add(h)
        events.sort(key=lambda e: e.topological_index)

        # from anchor_round INCLUSIVE: the anchor round's RoundInfo carries
        # the witness set every post-reset round computation grounds on —
        # without it, a joiner whose section has no higher decided rounds
        # recreates round(anchor) empty on first use, every new event
        # computes round == anchor (strongly_see needs 2/3 of the TRUE
        # witness set to advance), and consensus freezes at the anchor
        # forever (round-5 capture: 3,999 of 4,000 backlogged events in
        # round 22, witness_state {22: (1, 0)})
        rounds: Dict[int, RoundInfo] = {}
        for r in range(anchor_round, self.store.last_round() + 1):
            try:
                rounds[r] = self.store.get_round(r)
            except StoreErr:
                continue

        # refs for other-parents below the cut (frame events of the anchor
        # round are shipped separately and are not "frozen")
        frame_hashes = {e.hex() for e in frame.events}
        frozen: List[FrozenRef] = []
        frozen_seen = set()
        for ev in events:
            op = ev.other_parent()
            if (
                op != ""
                and op not in seen
                and op not in frame_hashes
                and op not in frozen_seen
            ):
                try:
                    ope = self.store.get_event(op)
                except StoreErr:
                    # a donor that itself fast-synced may hold only a ref —
                    # forward it, or a joiner chaining off this donor cannot
                    # resolve the other-parent and is stuck retrying
                    ref = self.frozen_refs.get(op)
                    if ref is not None:
                        frozen_seen.add(op)
                        frozen.append(ref)
                    continue
                frozen_seen.add(op)
                frozen.append(
                    FrozenRef(
                        hash=op,
                        creator_id=self.participants.by_pub_key[ope.creator()].id,
                        index=ope.index(),
                        round=self.round(op),
                        lamport=self.lamport_timestamp(op),
                    )
                )

        frames = [
            self.get_frame(r) for r in range(anchor_round + 1, last_consensus + 1)
        ]
        # stored blocks (with accumulated validator signatures) for every
        # block the joiner will replay from these frames — its proof the
        # continuation is the network's chain, not this donor's invention
        proof_blocks: Dict[int, Block] = {}
        if anchor_block_index >= 0:
            for i in range(anchor_block_index + 1, self.store.last_block_index() + 1):
                try:
                    proof_blocks[i] = self.store.get_block(i)
                except StoreErr:
                    continue

        # Truncate to the provable prefix. The joiner refuses any replayed
        # block below its 2-round trust window without >1/3 valid
        # signatures (verify_section) — and blocks committed right before
        # a validator die-off may NEVER gather them (the signers are
        # gone). Shipping those frames would make every fast-forward from
        # this donor fail permanently. Instead, ship frames only up to one
        # round past the first unprovable block — inside the joiner's
        # trust window — and let the joiner recompute the rest from the
        # shipped events through its own consensus (same DAG, same
        # decisions; the section docstring's "truncation only delays the
        # joiner" promise, made real).
        if anchor_block_index >= 0:
            next_index = anchor_block_index + 1
            cut_round = None
            for f in frames:
                if not f.events:
                    continue
                valid = self._block_proof_count(
                    f, proof_blocks.get(next_index), next_index
                )
                if valid <= self.trust_count:
                    cut_round = f.round + 1
                    break
                next_index += 1
            if cut_round is not None:
                frames = [f for f in frames if f.round <= cut_round]
                # the joiner's apply_section scrubs all decided metadata
                # above its shipped-frame ceiling regardless (advisor r3:
                # donor-stamped rounds above the cut must not seed block
                # composition); don't ship what will be ignored
                rounds = {r: ri for r, ri in rounds.items() if r <= cut_round}
        base_meta = [
            FrozenRef(
                hash=ev.hex(),
                creator_id=self.participants.by_pub_key[ev.creator()].id,
                index=ev.index(),
                round=self.round(ev.hex()),
                lamport=self.lamport_timestamp(ev.hex()),
            )
            for ev in frame.events
        ]

        # last consensus event per participant AS OF the anchor round: walk
        # each chain down from the donor's current last-consensus-event until
        # round-received <= anchor. Frame roots for participants quiet since
        # the anchor are built from exactly this event (get_frame), so the
        # joiner must share it or its frame hashes diverge from the network.
        consensus_baseline: Dict[str, str] = {}
        for p in self.participants.to_pub_key_slice():
            h, is_root = self.store.last_consensus_event_from(p)
            while not is_root:
                try:
                    ev = self.store.get_event(h)
                except StoreErr:
                    h = ""
                    break
                if ev.round_received is not None and ev.round_received <= anchor_round:
                    break
                h = ev.self_parent()
            if not is_root and h:
                consensus_baseline[p] = h
        return Section(
            anchor_round=anchor_round,
            last_consensus_round=last_consensus,
            events=events,
            rounds=rounds,
            frames=frames,
            frozen_refs=frozen,
            base_meta=base_meta,
            proof_blocks=proof_blocks,
            consensus_baseline=consensus_baseline,
        )

    def verify_section(self, anchor_block: Block, section: Section) -> None:
        """Joiner side, BEFORE any state is mutated: check that the chain
        the section replays is the network's, not a single donor's
        fabrication.

        Every event must carry a valid creator signature. Every replayed
        block must be endorsed by >1/3 of the validator set (the
        check_block threshold): the donor ships its stored blocks as proof,
        whose signatures cover the full body (index, round-received, state
        hash, frame hash, txs) — so a proof block with enough valid
        signatures whose identity fields match the frame we will replay
        pins that frame to the network's chain.

        Residual trust window, stated honestly: the freshest two rounds are
        exempt from the proof requirement, because a block's signatures
        ride self-events of strictly later rounds and cannot have
        propagated yet. A donor therefore gets an optimistic window of at
        most two replayed rounds whose ordering is its word alone — the
        same post-anchor trust the reference extends when re-deciding from
        donor-gossiped data — and forging even that window requires a
        malicious *validator* (events are signature-checked, so frame
        contents must be real validator events). Everything deeper must be
        proven or the sync is rejected; a donor that truncates its section
        to stay inside the window only delays the joiner, which picks up
        the rest through ordinary gossip."""
        for ev in section.events:
            if not ev.verify(self._creator_key(ev)):
                raise ValueError("Invalid Event signature in fast-sync section")

        # frames must be the contiguous round range above the anchor (the
        # donor builds exactly that, get_section) — gaps would desynchronize
        # the frame->block index chain that pairs proofs with frames, and a
        # round "skipped" by the donor would keep donor-stamped metadata
        # below the scrub ceiling without any frame to pin it
        expected = section.anchor_round + 1
        for f in section.frames:
            if f.round != expected:
                raise ValueError(
                    "fast-sync section: frames not contiguous from the anchor"
                    f" (got round {f.round}, want {expected})"
                )
            expected += 1

        sig_lag_floor = (
            max(f.round for f in section.frames) - 2 if section.frames else -1
        )
        # replicate process_decided_rounds' index assignment: ascending
        # frames, empty frames produce no block
        next_index = anchor_block.index() + 1
        for frame in section.frames:
            if not frame.events:
                continue
            valid = self._block_proof_count(
                frame, section.proof_blocks.get(next_index), next_index
            )
            if valid <= self.trust_count and frame.round <= sig_lag_floor:
                raise ValueError(
                    f"fast-sync section: replayed block {next_index} "
                    f"(round {frame.round}) has {valid} valid signatures, "
                    f"need {self.trust_count + 1}"
                )
            next_index += 1

        self._verify_consensus_baseline(section)

    def _verify_consensus_baseline(self, section: Section) -> None:
        """The baseline hashes seed future frame-root construction
        (apply_section), so each must identify a shipped, signature-checked
        event of the claimed participant that was received at or below the
        anchor — a fabricated hash would fork every later frame the joiner
        builds."""
        known: Dict[str, Event] = {ev.hex(): ev for ev in section.events}
        for f in section.frames:
            for ev in f.events:
                known[ev.hex()] = ev
        base_hashes = {fr.hash for fr in section.base_meta}
        for p, h in section.consensus_baseline.items():
            ev = known.get(h)
            if ev is None:
                if h in base_hashes:
                    continue  # anchor-frame event, already pinned + checked
                raise ValueError(
                    "fast-sync section: consensus baseline references an "
                    "unknown event"
                )
            if ev.creator() != p:
                raise ValueError(
                    "fast-sync section: consensus baseline creator mismatch"
                )
            if ev.round_received is not None and ev.round_received > section.anchor_round:
                raise ValueError(
                    "fast-sync section: consensus baseline above the anchor"
                )

    def _section_trusted_ceiling(self, anchor_index: int, section: Section) -> int:
        """Highest round of donor-DECIDED state the joiner accepts from a
        section. Walk the shipped frames in round order (contiguity is
        enforced by verify_section), chaining block indices exactly like
        process_decided_rounds, and extend the proven prefix on every
        non-empty frame whose proof block carries >1/3 valid validator
        signatures. The ceiling is that proven prefix plus the two-round
        signature-lag window (a block's signatures ride strictly LATER
        self-events, so the freshest two rounds cannot have proofs yet) —
        anchored to the proven prefix, NOT to the donor-controlled frame
        list: fabricated frames (empty-round padding included) cannot lift
        it, because padding never extends `last_proven`."""
        frames = sorted(section.frames, key=lambda f: f.round)
        if not frames:
            return section.anchor_round
        last_proven = section.anchor_round  # the anchor block is check_block-verified
        next_index = anchor_index + 1
        for f in frames:
            if not f.events:
                continue  # empty rounds mint no block; covered transitively
                # by the index chain when a later frame proves
            valid = self._block_proof_count(
                f, section.proof_blocks.get(next_index), next_index
            )
            if valid <= self.trust_count:
                break
            last_proven = f.round
            next_index += 1
        return min(frames[-1].round, last_proven + 2)

    def apply_section(self, section: Section, anchor_index: int = -1) -> None:
        """Joiner side: replay the donor's decided state above the anchor.
        Must run right after reset(block, frame); run_consensus() afterwards
        rebuilds the donor's blocks byte-identically via the shipped frames
        and then continues live from the donor's frontier.
        `anchor_index` is the verified anchor block's index (proof-chain
        base for the scrub ceiling).

        SCRUB CEILING (round 4, advisor finding): donor authority over
        DECIDED consensus state extends exactly as far as the proof-checked
        frame prefix plus the signature-lag window
        (_section_trusted_ceiling) — the anchor round itself if no frame
        proves. Above that ceiling, frames, RoundInfo snapshots, and event
        round/lamport/round-received stamps are unproven donor metadata:
        process_decided_rounds rebuilds blocks from stored frames and
        RoundInfo consensus membership, so accepting a "decided" round
        above the provable prefix would commit a donor-fabricated block.
        Everything above the ceiling is therefore dropped here and
        RE-DECIDED by this node's own consensus passes over the
        (signature-checked) shipped events — divide_rounds recomputes
        rounds/lamports grounded in the pinned anchor metadata and
        re-queues the rounds, decide_fame re-votes, decide_round_received
        re-stamps. The residual trust surface is the two-round sig-lag
        window (verify_section) plus sub-consensus metadata of the proven
        prefix (witness sets, frozen-ref coordinates), which cannot mint
        blocks on its own."""
        cut = self._section_trusted_ceiling(anchor_index, section)
        # events/rounds/frames are this joiner's own deserialized copies
        # (core.prepare_fast_forward round-trips the section through the
        # wire codec before any of this runs), so stripping in place is safe
        events: List[Event] = section.events
        for ev in events:
            if ev.round_received is not None and ev.round_received > cut:
                ev.set_round_received(None)
            if ev.round is not None and ev.round > cut:
                ev.set_round(None)
                ev.set_lamport_timestamp(None)
        rounds = {r: ri for r, ri in section.rounds.items() if r <= cut}
        frames = [f for f in section.frames if f.round <= cut]

        # the frame base is settled by definition (anchored in the block);
        # it must never be re-received into a later round
        for h in self.undetermined_events:
            ev = self.store.get_event(h)
            ev.set_round_received(section.anchor_round)
            self.store.set_event(ev)
        self.undetermined_events = []
        self.reset_floor = section.anchor_round

        self.frozen_refs.update({fr.hash: fr for fr in section.frozen_refs})
        # frozen refs ground the round/lamport recursion for re-decided
        # events whose other-parents sit below the cut (the event bodies
        # never ship, so the recursion cannot reach past them)
        for fr in section.frozen_refs:
            self._round_cache.setdefault(fr.hash, fr.round)
            self._timestamp_cache.setdefault(fr.hash, fr.lamport)
        # adopt the donor's last-consensus-event baseline: the anchor round
        # itself is never replayed (it is settled by the frame), so without
        # this the joiner's frame roots for participants quiet since the
        # anchor would be built from a different event than the network's
        for p, h in section.consensus_baseline.items():
            self.store.seed_last_consensus_event(p, h)
        # pin the anchor frame events' consensus metadata so nothing here
        # recomputes it from the amnesiac base
        for fr in section.base_meta:
            self._round_cache[fr.hash] = fr.round
            self._timestamp_cache[fr.hash] = fr.lamport
            try:
                ev = self.store.get_event(fr.hash)
            except StoreErr:
                continue
            ev.set_round(fr.round)
            ev.set_lamport_timestamp(fr.lamport)
            self.store.set_event(ev)
        for f in frames:
            self.store.set_frame(f)
        for r in sorted(rounds):
            ri = rounds[r]
            ri.queued = True  # pending status is tracked below
            self.store.set_round(r, ri)

        # event signatures were checked by verify_section (fast_forward
        # always validates before applying); re-verifying here would double
        # the dominant ECDSA cost of catch-up
        for ev in events:
            self._check_self_parent(ev)
            self._check_other_parent(ev, self.store.get_root(ev.creator()))
            ev.topological_index = self.topological_index
            self.topological_index += 1
            # the donor's coordinate rows, as shipped
            self._coords.adopt(ev, self._pos_by_pubkey[ev.creator()])
            # a stamp left below the scrub ceiling is authoritative donor
            # metadata and the memo of round()/lamport_timestamp(): not
            # recomputed; scrubbed events (None) are re-decided instead
            self.store.set_event(ev)
            if ev.round_received is None:
                self.undetermined_events.append(ev.hex())
                if ev.is_loaded():
                    self.pending_loaded_events += 1
            elif ev.round_received > section.anchor_round and ev.is_loaded():
                # decremented again when its round is replayed into a block
                self.pending_loaded_events += 1
            self.sig_pool.extend(ev.block_signatures())

        self.pending_rounds = [
            PendingRound(r, rounds[r].witnesses_decided())
            for r in sorted(rounds)
        ]

    def bootstrap(self) -> None:
        """Replay a persistent store's topologically-ordered events through
        the full pipeline (reference: src/hashgraph/hashgraph.go:1375-1410)."""
        topo = getattr(self.store, "db_topological_events", None)
        if topo is None:
            return
        for e in topo():
            self.insert_event(e, True)
        self.run_consensus()

    # ------------------------------------------------------------------
    # wire (reference: src/hashgraph/hashgraph.go:1414-1479)
    # ------------------------------------------------------------------

    def read_wire_info(self, wevent: WireEvent) -> Event:
        self_parent = root_self_parent(wevent.body.creator_id)
        other_parent = ""

        creator = self.participants.by_id[wevent.body.creator_id]
        creator_bytes = bytes.fromhex(creator.pub_key_hex[2:])

        if wevent.body.self_parent_index >= 0:
            self_parent = self.store.participant_event(
                creator.pub_key_hex, wevent.body.self_parent_index
            )
        if wevent.body.other_parent_index >= 0:
            try:
                other_creator = self.participants.by_id[wevent.body.other_parent_creator_id]
                other_parent = self.store.participant_event(
                    other_creator.pub_key_hex, wevent.body.other_parent_index
                )
            except (StoreErr, KeyError):
                # check if other parent can be found in the creator's root
                root = self.store.get_root(creator.pub_key_hex)
                found = False
                for re_ in root.others.values():
                    if (
                        re_.creator_id == wevent.body.other_parent_creator_id
                        and re_.index == wevent.body.other_parent_index
                    ):
                        other_parent = re_.hash
                        found = True
                        break
                if not found:
                    raise ValueError("OtherParent not found")

        event = Event(
            transactions=wevent.body.transactions,
            block_signatures=wevent.block_signatures(creator_bytes),
            parents=[self_parent, other_parent],
            creator=creator_bytes,
            index=wevent.body.index,
        )
        event.signature = wevent.signature
        event.set_wire_info(
            wevent.body.self_parent_index,
            wevent.body.other_parent_creator_id,
            wevent.body.other_parent_index,
            wevent.body.creator_id,
        )
        return event

    def valid_signature_count(self, block: Block, limit: int = None) -> int:
        """Signatures that are both cryptographically valid AND from a
        member of the validator set — a signature from any other key proves
        nothing (process_sig_pool applies the same membership filter).
        `limit` stops the (ECDSA-verify-per-signature) count early once
        reached — threshold checks only need trust_count + 1, not all N."""
        count = 0
        for s in block.get_signatures():
            if s.validator_hex() in self.participants.by_pub_key and block.verify(s):
                count += 1
                if limit is not None and count >= limit:
                    return count
        return count

    def _block_proof_count(self, frame: Frame, proof: Optional[Block],
                           expected_index: int) -> int:
        """Valid-signature count of `proof` iff it matches the block this
        frame replays (identity triple: index, round_received, frame hash)
        — the ONE pairing rule shared by the donor's provable-prefix
        truncation (get_section) and the joiner's check (verify_section);
        the two must never diverge or donors ship sections their joiners
        deterministically reject. Capped at trust_count + 1 (the threshold
        both callers compare against)."""
        if (
            proof is None
            or proof.index() != expected_index
            or proof.round_received() != frame.round
            or proof.frame_hash() != frame.hash()
        ):
            return 0
        # memoized: verify_section and _section_trusted_ceiling walk the
        # same (frame, proof) pairs back to back within one fast_forward,
        # and ECDSA verification dominates catch-up cost. The key binds
        # the FULL signed body digest (signature validity depends on every
        # body field, not just the pairing identity — a forged proof
        # reusing a genuine block's signature set over an altered body
        # must not share a cache slot with the genuine one, ADVICE r4)
        # plus the signature set being counted. The digest is memoized on
        # the proof object because verify_section + _section_trusted_ceiling
        # hash the same proofs back to back — re-marshalling every
        # transaction twice per walk would put an O(tx bytes) serialization
        # back on the catch-up hot path. Donor-side proofs are LIVE store
        # blocks whose state_hash is replaced by commit(), so the memo is
        # keyed on the state_hash object's identity and self-invalidates
        # across that mutation (code review r5).
        memo = getattr(proof, "_body_digest", None)
        if memo is not None and memo[0] is proof.body.state_hash:
            digest = memo[1]
        else:
            digest = proof.body.hash()
            proof._body_digest = (proof.body.state_hash, digest)
        key = (
            digest,
            tuple(sorted(proof.signatures.items())),
        )
        cached = self._proof_count_cache.get(key)
        if cached is not None:
            return cached
        count = self.valid_signature_count(proof, limit=self.trust_count + 1)
        while len(self._proof_count_cache) >= 256:
            # FIFO eviction: dropping one cold entry keeps the back-to-back
            # verify_section / _section_trusted_ceiling walk hot (ADVICE r4)
            self._proof_count_cache.pop(next(iter(self._proof_count_cache)))
        self._proof_count_cache[key] = count
        return count

    def check_block(self, block: Block) -> None:
        """Valid iff strictly more than 1/3 of participants signed."""
        valid = self.valid_signature_count(block)
        if valid <= self.trust_count:
            raise ValueError(
                f"Not enough valid signatures: got {valid}, need {self.trust_count + 1}"
            )

    def check_block_immutable(self, block: Block) -> None:
        """SAFETY INVARIANT (VERDICT r4): a committed body at index i is
        never replaced or divergently re-derived. Legitimate rewrites of a
        stored block only ADD to it — the app fills state_hash after
        commit, signatures accumulate — so the consensus-derived body
        fields must match whatever is already stored at that index (e.g.
        a bootstrap replay re-minting the identical block passes).
        Raising makes a diverged node stop loudly instead of compounding
        a fork; the error carries both bodies for the post-mortem."""
        try:
            old = self.store.get_block(block.index())
        except StoreErr:
            return
        divergent = (
            old.round_received() != block.round_received()
            or old.frame_hash() != block.frame_hash()
            or old.transactions() != block.transactions()
        )
        if not divergent and old.state_hash() and block.state_hash():
            divergent = old.state_hash() != block.state_hash()
        if divergent:
            self.fork_evidence += 1
            msg = (
                f"block {block.index()} body divergence: stored "
                f"(round_received={old.round_received()}, "
                f"frame_hash={old.frame_hash().hex()[:16]}, "
                f"txs={len(old.transactions())}) vs re-derived "
                f"(round_received={block.round_received()}, "
                f"frame_hash={block.frame_hash().hex()[:16]}, "
                f"txs={len(block.transactions())})"
            )
            self.logger.error("SAFETY: %s", msg)
            raise BlockDivergenceError(msg)

    # ------------------------------------------------------------------

    def _set_last_consensus_round(self, i: int) -> None:
        self.last_consensus_round = i
        if self.first_consensus_round is None:
            self.first_consensus_round = i
        # "number of events in round before LastConsensusRound" — declared
        # but never maintained in the reference (hashgraph.go:27 is its
        # only non-getter mention, so its round_events stat is always 0);
        # here the stat is actually kept
        try:
            self.last_committed_round_events = len(
                self.store.get_round(i - 1).round_events()
            )
        except StoreErr:
            self.last_committed_round_events = 0
