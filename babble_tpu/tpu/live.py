"""Live-node incremental device consensus: the persistent append-batch
pipeline (babble_tpu/tpu/incremental.py) wired into a running Hashgraph.

Where run_consensus_device re-stages the full DAG every sync (O(E) host
work per call), this engine keeps the DAG on device and ships only the
events inserted since the last consensus call — the host work per sync is
O(batch), mirroring the reference's UndeterminedEvents discipline
(reference: src/hashgraph/hashgraph.go:36-40,767-780) with device-resident
state.

Wiring: the Hashgraph's insert path reports each inserted event plus the
first-descendant cells its insert wrote (hashgraph.insert_listener): the
hashes of the ancestors written, since a cell's column and value are the
event's own creator position and index. LiveDeviceEngine.serve drains that
queue into fixed-shape append batches, a batch's cells going to the three
update arrays in bulk (`_build_batch`, `incremental._pack_upd`),
advances the device state, and writes new rounds/fame/received back into
the store exactly like the one-shot engine. Passes 4-5 stay host-side, so
blocks remain byte-identical by construction.

Scope and fallback: base-state hashgraphs only (no resets — the dense
incremental state has no external-parent metadata). Any unsupported
condition (post-reset state, capacity overflow, fame-unroll exhaustion,
received-window staleness) raises GridUnsupported, and Core falls back to
the one-shot device path (which itself falls back to the CPU engine).
"""

from __future__ import annotations

import itertools
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..obs.devledger import ledger_call
from .grid import MAX_INT32, DagGrid, GridUnsupported, grid_from_hashgraph
from .incremental import (
    Batch,
    IncState,
    L_MAX,
    _dep_levels,
    _level_table,
    _pack_upd,
    init_state,
    multi_step,
    stack_batches,
    step,
)
from .packed import observe_table_bytes, packed_enabled


def derive_fd_updates(grid: DagGrid) -> List[List[int]]:
    """Reconstruct the per-event first-descendant write stream from a
    completed grid (per event, the rows whose cell it wrote): cell
    fd[row, c] == v was written by the insert of the event (creator c,
    index v). O(E*N)."""
    rows_by = np.full(
        (grid.n, int(grid.index.max(initial=0)) + 1), -1, dtype=np.int32
    )
    if grid.e:
        rows_by[grid.creator, grid.index] = np.arange(grid.e, dtype=np.int32)
    stream: List[List[int]] = [[] for _ in range(grid.e)]
    rows, cols = np.nonzero(grid.first_descendants != MAX_INT32)
    vals = grid.first_descendants[rows, cols]
    for row, c, v in zip(rows.tolist(), cols.tolist(), vals.tolist()):
        updater = int(rows_by[c, v])
        if updater != row:  # own-cell writes ride with the appended row
            stream[updater].append(row)
    return stream


# constructor defaults, module-level so tests can shrink the capacities
# to force rebases quickly.
# Which capacities follow the validator count n, and which are flat:
# - e_win, the received window, follows it: the value here is per 32
#   validators (LiveDeviceEngine.__init__), 32,768 rows at 128;
# - upd_cap, the first-descendant updates a batch stages, is flat. An
#   inserted event writes about n cells, so a 32-row batch carries ~2,000
#   at 64 validators (none over the cap) and ~4,000 at 128, where one
#   batch in a hundred passes it (worst counted: 12,697): _cut ends such a
#   batch early and counts it (tracer total `stage.cut`). On the chip at
#   128 a staging of 16,384 read the same rate as the cuts (PERF.md
#   section 6, PR 33: most second trains come from the level table's cuts
#   of revealed chains, not from the cap's), so the cap did not follow;
# - e_cap, the event axis, is flat: 65,536 rows hold two 500-event syncs,
#   the held chain heads and the ~21,000-25,000 undetermined rows of a
#   128-validator withheld stream between rebases (one rebase a window);
# - r_cap / r_win, batch_cap, queue_depth are flat.
# r_win (the live-stepping round window) widened 32 -> 64 DELIBERATELY in
# round 5: post-fast-sync recovery states exhibit round spans past 32
# that tripped the attach span guard into attach/demote/retry churn
# (docs/tpu.md "Round-5: attach-window guards" has the measured numbers).
# It is a named default — not a buried constant — so the choice stays
# visible and tests/benchmarks can narrow it explicitly.
ENGINE_DEFAULTS = dict(
    e_cap=1 << 16, r_cap=64, batch_cap=64, upd_cap=8192, e_win=8192,
    r_win=64,
    # async dispatch queue (ISSUE 6): at most queue_depth dispatches in
    # flight under the pipelined discipline. It is the cap, not the lag:
    # the engine keeps one in flight and goes deeper only while the oldest
    # fetch is really waited on (_note_wait). batch_deadline > 0
    # holds gossip-staged rows for that many Clock seconds (or until
    # batch_cap rows accumulate) before dispatching, so the device sees
    # fewer, larger trains. Node configs override both via
    # Config.dispatch_queue_depth / dispatch_batch_deadline.
    queue_depth=4, batch_deadline=0.0,
)


class LiveDeviceEngine:
    """Device-resident DAG state for one live Hashgraph.

    Capacities are finite (e_cap event rows, r_cap round slots) but the
    DAG is not: when either axis nears exhaustion the engine REBASES —
    it rebuilds its device state from the undecided frontier (events of
    recent rounds + still-undetermined events), with all rounds stored
    relative to a new ``round_base``. Decided history below the base is
    final and never consulted again (the same windowing argument as the
    reference's RollingIndex pruning, SURVEY §5), so a live node streams
    indefinitely through bounded device memory."""

    def __init__(self, hg, e_cap: int = None, r_cap: int = None,
                 batch_cap: int = None, upd_cap: int = None,
                 e_win: int = None, r_win: int = None,
                 queue_depth: int = None, batch_deadline: float = None):
        d = ENGINE_DEFAULTS
        self.hg = hg
        self.n = len(hg.participants.to_peer_slice())
        self.e_cap = d["e_cap"] if e_cap is None else e_cap
        self.r_cap = d["r_cap"] if r_cap is None else r_cap
        self.batch_cap = d["batch_cap"] if batch_cap is None else batch_cap
        self.upd_cap = d["upd_cap"] if upd_cap is None else upd_cap
        # the received window must hold every undetermined row, and those
        # grow with the validator count: ~100-135 rows per validator on the
        # 64-validator Zipf(1.1) stream of chip_smoke.py's replay64, the
        # pipelined fetch's lag included — a flat 8,192 rows latched
        # `stale` there and demoted the engine. So the default is per 32
        # validators (about twice that need).
        if e_win is None:
            e_win = d["e_win"] * -(-self.n // 32)
        self.e_win = min(e_win, self.e_cap)
        # single source of truth for the device round window: the span
        # guard in _install_state and every step() call must agree, or
        # clamped rounds slip past the guard (code review r5). The default
        # is the deliberate 64-wide window (see ENGINE_DEFAULTS).
        self.r_win = min(d["r_win"] if r_win is None else r_win, self.r_cap)
        # voting-table layout, resolved once at engine construction so
        # every step/multi_step dispatch compiles one consistent program
        # (tpu/packed.py; per-engine override via BABBLE_PACKED_VOTING)
        self.packed = packed_enabled(self.n)
        observe_table_bytes(hg.obs, self.n, self.r_win, self.packed)
        self.round_base = 0
        self.rebases = 0
        # latency accounting: device dispatches vs result fetches — host
        # launch work against the blocking wait for the results (on a
        # colocated chip that wait is the device compute itself, since the
        # launches return before it finishes). Durations go to the obs registry
        # histograms (babble_device_dispatch/fetch_seconds, shared with
        # the Node's /stats adapter); structural counts stay here because
        # the pipelining heuristic reads them per-engine.
        self.dispatches = 0
        self.consensus_calls = 0
        # serve calls and dispatches so far: the `dispatch`
        # attribute ties one dispatch's launch, fetch and integration,
        # which the pipelined discipline spreads over several calls
        self.calls = 0
        self.dispatch_seq = 0
        # the most events one call has staged so far: the event axis keeps
        # room for two more such syncs (_capacity_soft)
        self.largest_sync = 0
        # first-descendant cells staged so far (_build_batch; the cells of
        # pruned ancestors are dropped and not counted): advance hands the
        # tracer each dispatch's share as the total `stage.cells`
        self.cells_staged = 0
        # batches `_cut` ended because their first-descendant updates would
        # pass the staging (not those it ended for L_MAX): advance hands
        # the tracer each dispatch's share as the total `stage.cut`
        self.update_cuts = 0
        self._m_dispatch = hg.obs.histogram(
            "babble_device_dispatch_seconds",
            "Host-side device program launch time per advance",
        )
        self._m_fetch = hg.obs.histogram(
            "babble_device_fetch_seconds",
            "Blocking device result fetch (round-trip) time",
        )
        self._m_rebase = hg.obs.counter(
            "babble_device_rebases_total",
            "Live-engine grid rebases onto a committed frontier",
        )
        self._m_host_repair = hg.obs.counter(
            "babble_live_host_repaired_integrations_total",
            "Integrations whose fetched receptions the host rule refused, "
            "so that the host's reception pass ran in the device's place",
        )
        # pipelined-fetch discipline: flips on when the measured blocking
        # fetch is consistently expensive (ASYNC_FETCH_MIN_S). inflight is
        # a bounded FIFO of
        # (_AsyncFetch, snapshot, t_dispatch) tuples — fetch_lag dispatches
        # ride concurrently (one; up to queue_depth after waits read from
        # the obs clock: _note_wait), integrated oldest-first on
        # DETERMINISTIC conditions only (fetch_lag in flight, or no
        # dispatch this call) so same-seed sim runs never diverge on
        # thread timing.
        self.async_fetch = ENGINE_DEFAULTS.get("async_fetch") is True
        self.fetch_lag = 1
        self.queue_depth = (
            d["queue_depth"] if queue_depth is None else queue_depth
        )
        self.batch_deadline = (
            d["batch_deadline"] if batch_deadline is None else batch_deadline
        )
        self.inflight: List[tuple] = []
        self._pending_since: Optional[float] = None
        self._slow_fetches = 0
        self._m_qdepth = hg.obs.gauge(
            "babble_device_queue_depth",
            "Device dispatches currently in flight in the async queue",
        )
        self._m_overlap = hg.obs.histogram(
            "babble_device_overlap_utilization",
            "Fraction of each dispatch's in-flight time overlapped with "
            "gossip (1.0 = the fetch never blocked the serve path)",
            buckets=[i / 10 for i in range(11)],
        )
        self.layout = "packed" if self.packed else "wide"  # ledger cells
        self.state: IncState = init_state(self.n, self.e_cap, self.r_cap)
        # the state's `reopened` counts as of the last integration
        self.reopened_seen = np.zeros(self.r_cap, np.int32)
        self.row_of: Dict[str, int] = {}
        self.hashes: List[str] = []
        self.pending: List[tuple] = []  # (event, cells)
        self._bootstrap()
        hg.insert_listener = self._on_insert

    # -- hashgraph hooks ---------------------------------------------------

    def _on_insert(self, event, cells) -> None:
        """Called by Hashgraph.insert_event with the event and the hashes
        of the ancestors whose first-descendant cell its insert wrote, in
        walk order (column and value: the event's own creator position and
        index)."""
        if not self.pending:
            # batch-deadline anchor, on the injected Clock (sim-safe)
            self._pending_since = self.hg.obs.clock.monotonic()
        self.pending.append((event, cells))

    def detach(self) -> None:
        if getattr(self.hg, "insert_listener", None) is self._on_insert:
            self.hg.insert_listener = None
        self.inflight = []  # results of a dropped engine are never stamped

    # -- serving -----------------------------------------------------------

    def serve(self) -> None:
        """One consensus call: advance the persistent state by the events
        inserted since the last call, then write decisions back and run the
        host passes (mirrors engine.run_consensus_device's write-back,
        restricted to new/undetermined work). The first call, the one that
        attached the engine, writes back the bootstrap's rows as well.

        Two fetch disciplines (a slow fetch must not serialize gossip under
        the core lock):

        - synchronous (default): dispatch, fetch, integrate, all in this call.
          The blocking fetch waits for the device to finish the programs just
          launched, then copies one packed vector.
        - pipelined (self-activating): when the measured blocking fetch is
          expensive (threshold ASYNC_FETCH_MIN_S over 3 consecutive calls —
          on a colocated chip that means the device compute itself is slow
          against the gossip interval), the fetch moves OFF the consensus
          critical path: each call integrates the PREVIOUS call's dispatch
          (its results already resident host-side via a background reader
          thread) and launches a new dispatch whose compute and transfer
          overlap the next gossip interval. Only while that fetch is still
          waited on (the same threshold, 3 consecutive calls) does the engine
          keep one more dispatch in flight, up to ``queue_depth`` of them,
          and a queue that drains for lack of traffic starts again at one
          (_note_wait, _run_pipelined).
          Decisions lag one sync, and up to queue_depth where the device
          needs them — pure timing, not content:
          rounds, fame, and receptions are DAG facts, so block bodies stay
          byte-identical (pinned by the strict joiner differentials), they
          just seal that many calls later. The write-back validation gates run
          unchanged at integration time against a dispatch-time snapshot of
          the row mapping (rebases build fresh containers, so snapshots are
          O(1) references), and integration order is FIFO so parents' rounds
          always land before children's. Integration TRIGGERS are
          deterministic (queue occupancy, call sequence and waits read from
          the obs clock, as the flip is; never thread completion state) so
          same-seed sim runs stay byte-identical.
        """
        self.calls += 1
        if self.calls == 1:
            # the bootstrap replayed the whole pre-existing DAG on device;
            # its rows still need the host write-back — the attach call is
            # always synchronous so the node leaves it with a fully written
            # store
            new_rows = list(range(len(self.hashes)))
            new_rows.extend(self.advance())
            _run_sync(self.hg, self, new_rows)
        elif self.async_fetch:
            _run_pipelined(self.hg, self)
        else:
            _run_sync(self.hg, self, self.advance())

    def flush(self) -> None:
        """Blocking barrier: integrate every in-flight dispatch
        (Core.flush_device_dispatch, before asserting on store state)."""
        if not self.inflight:
            return
        last_round_rel = 0
        while self.inflight:
            last_round_rel = _integrate_oldest(self.hg, self)
        _manage_capacity(self, last_round_rel)
        self.hg.process_decided_rounds()
        self.hg.process_sig_pool()

    # -- construction ------------------------------------------------------

    def _bootstrap(self) -> None:
        """Build device state from the hashgraph's existing DAG.

        Small base-state DAGs replay through the append pipeline (the
        cheapest path and the one that exercises no store round lookups).
        Anything else — post-reset states, DAGs past the write-back
        window, rolled store windows — attaches FROM THE FRONTIER: the
        same store-driven assembly a rebase performs, keeping only events
        of rounds >= base plus undetermined ones. This is what lets a
        restarted node with a deep sqlite history, or a node returning
        from fast-sync, ride the live engine instead of being stuck on
        the one-shot path forever."""
        try:
            grid = grid_from_hashgraph(self.hg)
        except GridUnsupported:
            # rolled store window: full history is unreachable, but the
            # frontier assembly only touches recent rows
            self._attach_from_frontier()
            return
        base_state = not grid.e or (
            (grid.ext_sp_round == -1).all() and (grid.ext_op_round == -1).all()
        )
        if not base_state or grid.e > self.e_win:
            # deep or post-reset history: settle it through the
            # log-diameter cold path first (O(log depth) device passes vs
            # the store-driven replay's per-round work), so the frontier
            # attach below only carries the unsettled tail
            from .doubling import maybe_cold_replay

            maybe_cold_replay(self.hg, grid)
            # capacity for the kept rows is enforced by _install_state
            self._attach_from_frontier()
            return
        self.hashes = list(grid.hashes)
        self.row_of = {h: r for r, h in enumerate(self.hashes)}
        if grid.e == 0:
            return
        import dataclasses

        grid = dataclasses.replace(
            grid, fd_update_stream=derive_fd_updates(grid)
        )
        from .incremental import batches_from_grid

        for b in batches_from_grid(grid, self.batch_cap, self.upd_cap, self.e_cap):
            self.state = step(
                self.state, b, self.hg.super_majority, self.n,
                e_win=self.e_win, r_win=self.r_win, packed=self.packed,
            )

    def _attach_base_round(self):
        """(base, floor): floor = first fame-undecided round, base =
        floor - 1 — the rebase invariant: fame voting for round j only
        consults round j-1's witnesses, and an event no decided round
        received can only be received at or after the first undecided
        round — lowered to the round of the oldest chain head where the
        windows have room (_held_base)."""
        hg = self.hg
        undecided = [p.index for p in hg.pending_rounds if not p.decided]
        if undecided:
            floor = min(undecided)
        elif hg.last_consensus_round is not None:
            floor = hg.last_consensus_round + 1
        else:
            floor = 0
        return self._held_base(max(0, floor - 1), floor), floor

    def _held_base(self, base: int, floor: int) -> int:
        """`base`, or as far below it as the round of the oldest chain
        head, while the kept rows fit the received window and the rounds
        a quarter of the round window. A validator's next event is at or
        above its head's round, so what a silent or withholding validator
        shows next then lands inside the round window: a late witness
        re-opens a round the state still holds, instead of a round below
        the base, which the state can only answer by latching `stale`.
        Honest heads are within a round or two of the frontier and hold
        nothing back."""
        from ..common import StoreErr

        hg = self.hg

        def known_round(h: str):
            try:
                return hg.store.get_event(h).round
            except StoreErr:
                return None

        oldest = base
        for p in hg.participants.to_peer_slice():
            try:
                h, is_root = hg.store.last_event_from(p.pub_key_hex)
            except StoreErr:
                continue
            head_round = None if is_root else known_round(h)
            if head_round is not None:
                oldest = min(oldest, head_round)
        # inserted and not staged yet (the pipelined discipline rebases
        # before it stages the call's events): such a chain's first event
        # lands at or above the highest round among its parents
        unstaged = {ev.hex() for ev, _ in self.pending}
        for ev, _ in self.pending:
            parents = [h for h in (ev.self_parent(), ev.other_parent()) if h]
            if not any(h in unstaged for h in parents):
                rounds = [r for r in map(known_round, parents) if r is not None]
                if rounds:
                    oldest = min(oldest, max(rounds))
        # rows a base of r keeps: the undetermined events, and the events
        # of the decided rounds from r up (those of the two that are both
        # are counted twice: the bound errs on the small side)
        rows = len(hg.undetermined_events)
        room = self.e_win - 2 * self.batch_cap
        for r in range(floor - 1, oldest - 1, -1):
            try:
                rows += len(hg.store.get_round(r).events)
            except StoreErr:
                break
            if rows > room or floor - r > self.r_win // 4:
                break
            base = min(base, r)
        return max(base, self.round_base)

    def _attach_from_frontier(self) -> None:
        """Fresh attach from the undecided frontier: walk each validator's
        chain back from its head, keeping events of rounds >= base plus
        undetermined ones — O(kept), no full-history enumeration, valid on
        post-reset states (coordinates are reset-relative but internally
        consistent) and rolled store windows."""
        from ..common import StoreErr

        hg = self.hg
        base, floor = self._attach_base_round()

        undet = set(hg.undetermined_events)
        # stop the walk-back only below every undetermined event's round
        stop = base
        # det-ok: pure min-reduction over the set — order-independent
        for h in undet:
            try:
                ev = hg.store.get_event(h)
            except StoreErr as e:
                raise GridUnsupported(f"attach: undetermined event lost ({e})")
            if ev.round is not None:
                stop = min(stop, ev.round)

        kept_map = {}
        for p in hg.participants.to_peer_slice():
            try:
                h, is_root = hg.store.last_event_from(p.pub_key_hex)
            except StoreErr:
                continue
            if is_root:
                continue
            chain = []
            while h:
                try:
                    ev = hg.store.get_event(h)
                except StoreErr:
                    break  # below the store window: everything older is final
                if (
                    ev.round is not None and ev.round < stop
                    and h not in undet
                ):
                    break
                chain.append((h, ev))
                h = ev.self_parent()
            for h2, ev2 in reversed(chain):
                if (ev2.round is not None and ev2.round >= base) or h2 in undet:
                    kept_map[h2] = ev2

        # ROUND CLOSURE: an event without a host round must be computable
        # WITHIN the modeled window — both parents either carry known
        # rounds or are themselves kept. _install_state stages no external
        # round seeds (unlike grid_from_hashgraph, which seeds from roots
        # and frozen refs), so an unrounded event with an out-of-window
        # parent would be mis-derived as root-attached at the engine base
        # (observed: a fresh post-fast-sync attach stamping base-relative
        # rounds onto genesis events). Refuse and let the one-shot path —
        # which has full external seeding — run until rounds settle; the
        # attach succeeds on a later call.
        def _parent_ok(ph: str) -> bool:
            # membership only: a parent with a known round but OUTSIDE the
            # window is still unusable — the engine has no row to read the
            # round from and no external seed channel
            return ph == "" or ph in kept_map
        for h2, ev2 in kept_map.items():
            if ev2.round is None and not (
                _parent_ok(ev2.self_parent()) and _parent_ok(ev2.other_parent())
            ):
                raise GridUnsupported(
                    f"attach: unrounded event with out-of-window parent "
                    f"({h2[:18]}…)"
                )

        # topological order (coordinates reference earlier rows only)
        kept = sorted(kept_map.items(), key=lambda kv: kv[1].topological_index)
        self._install_state(base, floor, kept)

    # -- rebasing ----------------------------------------------------------

    def rebase(self) -> None:
        """Rebuild the device state from the undecided frontier.

        Kept rows: every event of an absolute round >= base, plus every
        event whose round-received is still undetermined, where
        base = (first fame-undecided round) - 1 — fame voting for round j
        only ever consults round j-1's witnesses, and an event that no
        decided round received can only be received at a round >= the
        first undecided one, so nothing below the base can influence any
        future decision. Rounds are stored base-relative on device;
        the write-back translates them back.

        Everything is assembled host-side from the store (coordinates are
        host-maintained and write-once) — one device upload, no replay.
        """
        from ..common import StoreErr

        if self.inflight:
            # invariant (docs/tpu.md backend ladder): a rebase replaces
            # the row containers in-flight snapshots alias — callers must
            # drain the dispatch queue first (_settle_capacity does)
            raise GridUnsupported("rebase with dispatches in flight")
        hg = self.hg
        base, floor = self._attach_base_round()
        if base <= self.round_base:
            raise GridUnsupported(
                f"rebase cannot advance the round base (stuck at {base})"
            )

        undet = set(hg.undetermined_events)
        kept: List[tuple] = []  # (hash, event)
        for h in self.hashes:
            try:
                ev = hg.store.get_event(h)
            except StoreErr:
                # below the store's window: the store evicts only events
                # that were received long ago (it pins the undetermined
                # ones and every chain's tail), so this row is decided
                # history, as in _attach_from_frontier. A state that has
                # grown past the store's cache since its last rebase (an
                # event axis of 65,536 rows over a cache of 50,000) meets
                # this on every rebase of the event axis
                continue
            if (ev.round is not None and ev.round >= base) or h in undet:
                kept.append((h, ev))
        self._install_state(base, floor, kept)
        self.rebases += 1
        self._m_rebase.inc()
        hg.obs.flightrec.record(
            "live.rebase", base=base, kept=len(kept), rebases=self.rebases,
        )

    def _install_state(self, base: int, floor: int, kept: List[tuple]) -> None:
        """Assemble IncState host-side from (hash, event) rows of rounds
        >= base plus undetermined ones, rounds stored base-relative — one
        device upload, no replay. Shared by rebase() and the fresh
        frontier attach."""
        import numpy as np

        from ..common import StoreErr
        from ..hashgraph.hashgraph import middle_bit
        from ..hashgraph.round_info import Trilean

        hg = self.hg
        n, e_cap, r_cap = self.n, self.e_cap, self.r_cap
        undet = set(hg.undetermined_events)

        min_undet_round = floor
        for h, ev in kept:
            if h in undet and ev.round is not None:
                min_undet_round = min(min_undet_round, ev.round)

        # host-frozen rounds: a round below the frontier whose fame the
        # host cannot decide (at or under a fast-sync cut a late witness is
        # the donor's to decide and is never re-queued; everywhere else
        # queue_round re-queues the round and the floor stays under it)
        # blocks receptions of older events behind it. The rebased state
        # cannot represent that block (the round is below the base), so
        # refuse and let the host engine carry this hashgraph.
        for r_abs in range(min_undet_round + 1, floor):
            try:
                if not hg.store.get_round(r_abs).witnesses_decided():
                    raise GridUnsupported(
                        f"rebase: round {r_abs} is host-frozen below the "
                        f"frontier"
                    )
            except StoreErr:
                continue
        # ROUND-SPAN GUARD: rounds are staged base-relative on a finite
        # round axis; a kept event whose known round falls outside it
        # would be CLAMPED, and every child computed from the clamped
        # value comes out a few rounds low — the write-back gate then
        # rejects the whole batch ("round write-back violates parent
        # bounds: 9783 vs parents<= 9785", round-5 strict-loop capture),
        # so the attach churns demote/retry forever while stamping
        # nothing. Refuse up front instead: the host keeps deciding fame,
        # the span shrinks, and a later attach fits.
        r_win = self.r_win
        max_known = max(
            (ev.round for _, ev in kept if ev.round is not None),
            default=base,
        )
        if max_known - base >= r_win - 2:  # margin for rounds formed mid-flight
            raise GridUnsupported(
                f"attach: round span {max_known - base} exceeds the device "
                f"round window {r_win}"
            )
        if len(kept) > e_cap - 4 * self.batch_cap:
            raise GridUnsupported(
                f"rebase keeps {len(kept)} rows; capacity {e_cap} too small"
            )
        if len(kept) > self.e_win - 2 * self.batch_cap:
            # undetermined rows must stay inside the received fetch window
            # (same constraint the bootstrap imposes on grid.e)
            raise GridUnsupported(
                f"rebase keeps {len(kept)} rows; write-back window "
                f"{self.e_win} too small"
            )

        la = np.full((e_cap, n), -1, np.int32)
        fd = np.full((e_cap, n), MAX_INT32, np.int32)
        creator = np.zeros(e_cap, np.int32)
        index = np.full(e_cap, MAX_INT32, np.int32)
        rounds = np.full(e_cap, -1, np.int32)
        lamport = np.full(e_cap, -1, np.int32)
        witness = np.zeros(e_cap, bool)
        received = np.full(e_cap, -1, np.int32)
        w_of_row = np.full(e_cap, -1, np.int32)
        wtable = np.full((r_cap, n), -1, np.int32)
        la_w = np.full((r_cap, n, n), -1, np.int32)
        fd_w = np.full((r_cap, n, n), MAX_INT32, np.int32)
        idx_w = np.full((r_cap, n), MAX_INT32, np.int32)
        coin_w = np.zeros((r_cap, n), bool)
        fame_decided = np.zeros((r_cap, n), bool)
        famous = np.zeros((r_cap, n), bool)
        rounds_decided = np.zeros(r_cap, bool)

        new_row_of: Dict[str, int] = {}
        new_hashes: List[str] = []
        last_abs = base
        # the kept events' coordinates: row slices of the graph's table
        la[: len(kept)], fd[: len(kept)] = hg.coordinate_rows(
            [ev for _, ev in kept]
        )
        for k, (h, ev) in enumerate(kept):
            new_row_of[h] = k
            new_hashes.append(h)
            creator[k] = hg.peer_position(ev.creator())
            index[k] = ev.index()
            if ev.round is not None:
                if ev.round >= base:
                    rounds[k] = ev.round - base
                    last_abs = max(last_abs, ev.round)
                # else: a still-undetermined event below the base — its
                # reception is pending at rounds >= floor but its round
                # cannot be represented base-relative; leave the sentinel
                # (-1). The write-back never re-stamps host-known rounds,
                # so the true round is preserved host-side.
            lamport[k] = (
                ev.lamport_timestamp if ev.lamport_timestamp is not None else -1
            )
            rr = ev.round_received
            received[k] = (rr - base) if (rr is not None and h not in undet) else -1

        # witness tables + fame state for the kept round window
        for r_abs in range(base, min(last_abs, base + r_cap - 1) + 1):
            sh = r_abs - base
            try:
                ri = hg.store.get_round(r_abs)
            except StoreErr:
                continue
            for h, re in ri.events.items():
                if not re.witness:
                    continue
                row = new_row_of.get(h)
                if row is None:
                    raise GridUnsupported(
                        f"rebase: witness of round {r_abs} not kept"
                    )
                c = int(creator[row])
                wtable[sh, c] = row
                la_w[sh, c] = la[row]
                fd_w[sh, c] = fd[row]
                idx_w[sh, c] = index[row]
                coin_w[sh, c] = middle_bit(h)
                w_of_row[row] = sh * n + c
                if re.famous != Trilean.UNDEFINED:
                    fame_decided[sh, c] = True
                    famous[sh, c] = re.famous == Trilean.TRUE
            rounds_decided[sh] = ri.witnesses_decided()

        import jax

        # NumPy scalars: jnp.int32(x) would be a device program each
        self.state = IncState(
            la=jax.device_put(la), fd=jax.device_put(fd),
            creator=jax.device_put(creator), index=jax.device_put(index),
            rounds=jax.device_put(rounds), lamport=jax.device_put(lamport),
            witness=jax.device_put(witness), received=jax.device_put(received),
            w_of_row=jax.device_put(w_of_row), wtable=jax.device_put(wtable),
            la_w=jax.device_put(la_w), fd_w=jax.device_put(fd_w),
            idx_w=jax.device_put(idx_w), coin_w=jax.device_put(coin_w),
            fame_decided=jax.device_put(fame_decided),
            famous=jax.device_put(famous),
            rounds_decided=jax.device_put(rounds_decided),
            reopened=jax.device_put(np.zeros(r_cap, np.int32)),
            last_round=jax.device_put(np.int32(last_abs - base)),
            count=jax.device_put(np.int32(len(kept))),
            stale=jax.device_put(np.bool_(False)),
            fame_lag=jax.device_put(np.bool_(False)),
        )
        self.row_of = new_row_of
        self.hashes = new_hashes
        self.round_base = base
        self.reopened_seen = np.zeros(r_cap, np.int32)

    # -- advancing ---------------------------------------------------------

    def advance(self) -> List[int]:
        """Append all events inserted since the last call; returns their
        device rows.

        Hybrid dispatch: a normal gossip sync stages 1-2 batches and goes
        through the straight-line ``step`` program (cheapest per small
        append); a catch-up burst (3+ batches) is stacked into
        ``multi_step`` trains — one device program per up to 16 batches —
        padded with no-op batches to two fixed shapes (K=4 for up to four
        batches, K=16 beyond) so the live path compiles at most three
        programs."""
        if not self.pending:
            return []
        obs = self.hg.obs
        with obs.span("device.dispatch", histogram=self._m_dispatch,
                      node=obs.node_id, dispatch=self.dispatch_seq + 1) as sp:
            with obs.span("live.stage",
                          ledger=("live", "stage", self.layout)) as stage:
                drained, self.pending = self.pending, []
                self.largest_sync = max(self.largest_sync, len(drained))
                stage.attrs["events"] = len(drained)
                stage.attrs["fd_updates"] = sum(len(w) for _, w in drained)
                new_rows: List[int] = []
                if len(self.hashes) + len(drained) > self.e_cap:
                    raise GridUnsupported("device event capacity exhausted")

                # greedy chunking: cap the batch size, the within-batch
                # dependency depth (a creator chaining deeply in one sync
                # would otherwise exceed the level table) and the batch's
                # first-descendant updates (a wide validator set, or a
                # withheld chain revealed at once, bursts past the staging)
                built: List[Batch] = []
                pos, staged_before = 0, self.cells_staged
                cuts_before = self.update_cuts
                while pos < len(drained):
                    chunk = drained[pos : pos + self.batch_cap]
                    chunk = self._cut(chunk)
                    pos += len(chunk)
                    batch, rows = self._build_batch(chunk)
                    built.append(batch)
                    new_rows.extend(rows)
                sp.attrs["batches"] = len(built)
                # trains: up to 16 batches, padded to K=4 or K=16, stacked.
                # One shape per dispatch: where a sync overflows one K=16
                # train (a revealed chain is cut every L_MAX events), its
                # last few batches ride a second K=16 train and not the
                # K=4 program, which a node with large syncs never
                # compiled
                trains = []
                if len(built) > 2:
                    k = 4 if len(built) <= 4 else 16
                    for i in range(0, len(built), k):
                        group = built[i : i + k]
                        group = group + [self._empty_batch()] * (k - len(group))
                        trains.append((k, stack_batches(group)))

            with obs.devledger.activate("live", layout=self.layout):
                for b in ([] if trains else built):
                    with obs.span("live.launch", program="step", k=1):
                        self.state = ledger_call(
                            "_step_full", step,
                            self.state, b, self.hg.super_majority, self.n,
                            e_win=self.e_win, r_win=self.r_win,
                            packed=self.packed,
                        )
                    self.dispatches += 1
                for k, stacked in trains:
                    with obs.span("live.launch", program="multi_step", k=k):
                        self.state = ledger_call(
                            "multi_step", multi_step,
                            self.state, stacked,
                            self.hg.super_majority, self.n, e_win=self.e_win,
                            r_win=self.r_win, packed=self.packed,
                        )
                    self.dispatches += 1
            # the cells the stage took in bulk (the pruned ones dropped) and
            # which program the dispatch launched, as count totals
            obs.tracer.add(
                "stage.cells", 0.0, self.cells_staged - staged_before,
            )
            obs.tracer.add("stage.cut", 0.0, self.update_cuts - cuts_before)
            if trains:
                obs.tracer.add("live.launch.train", 0.0, len(trains))
            else:
                obs.tracer.add("live.launch.step", 0.0, len(built))
        return new_rows

    def _empty_batch(self) -> Batch:
        """A no-op Batch (every scatter drops) for padding multi_step
        groups to their fixed stack shapes."""
        cached = getattr(self, "_empty_batch_cache", None)
        if cached is not None:
            return cached
        n, b_cap = self.n, self.batch_cap
        b = Batch(
            rows=np.full(b_cap, -1, dtype=np.int32),
            creator=np.zeros(b_cap, dtype=np.int32),
            index=np.full(b_cap, MAX_INT32, dtype=np.int32),
            sp_row=np.full(b_cap, -1, dtype=np.int32),
            op_row=np.full(b_cap, -1, dtype=np.int32),
            la_rows=np.full((b_cap, n), -1, dtype=np.int32),
            coin=np.zeros(b_cap, dtype=bool),
            fixed_round=np.full(b_cap, -1, dtype=np.int32),
            upd_row=np.full(self.upd_cap, self.e_cap, dtype=np.int32),
            upd_col=np.zeros(self.upd_cap, dtype=np.int32),
            upd_val=np.zeros(self.upd_cap, dtype=np.int32),
            levels=np.full((L_MAX, b_cap), -1, dtype=np.int32),
            sp_lamport=np.full(b_cap, -1, dtype=np.int32),
            op_lamport=np.full(b_cap, -1, dtype=np.int32),
        )
        self._empty_batch_cache = b
        return b

    def _cut(self, chunk):
        """Longest prefix of `chunk` whose within-chunk dependency depth
        stays under the level-table height and whose first-descendant
        updates fit the staging (`upd_cap`; the count here includes
        updates to pruned rows, which _build_batch drops: an upper
        bound). One event over the cap alone is left to _build_batch.
        `upd_cap` is flat while an event's cells grow with the validator
        count (ENGINE_DEFAULTS): from 128 validators on this is what
        serves the batches that pass it, and `update_cuts` counts them
        (cuts at the level table's height are not counted)."""
        depth: Dict[str, int] = {}
        updates = 0
        for k, (ev, cells) in enumerate(chunk):
            d = 0
            for parent in (ev.self_parent(), ev.other_parent()):
                if parent in depth:
                    d = max(d, depth[parent] + 1)
            updates += len(cells)
            if d >= L_MAX:
                return chunk[:k]
            if k and updates > self.upd_cap:
                self.update_cuts += 1
                return chunk[:k]
            depth[ev.hex()] = d
        return chunk

    def _build_batch(self, chunk) -> Tuple[Batch, List[int]]:
        """One append batch from `chunk` ((event, cells) pairs, as the
        listener was handed them): the Batch and the rows it appends. Built
        per batch: a field is a Python list through the event loop and one
        slice store after it, and the cells of all the chunk's events
        become the three update arrays in one `_pack_upd`."""
        n, b_cap = self.n, self.batch_cap
        b = len(chunk)
        row_of, position = self.row_of, self.hg.peer_position
        base_row = len(self.hashes)
        rows = list(range(base_row, base_row + b))
        creators, indexes, sp_rows, op_rows, coins = [], [], [], [], []
        la_flat: List[int] = []
        cell_hashes: List[str] = []
        counts: List[int] = []
        fixed_round = np.full(b_cap, -1, dtype=np.int32)
        sp_lamport = np.full(b_cap, -1, dtype=np.int32)
        op_lamport = np.full(b_cap, -1, dtype=np.int32)

        from ..hashgraph.hashgraph import middle_bit

        for k, (ev, cells) in enumerate(chunk):
            h = ev.hex()
            row_of[h] = base_row + k
            self.hashes.append(h)

            creators.append(position(ev.creator()))
            idx, sp_hash, op_hash = ev.index(), ev.self_parent(), ev.other_parent()
            indexes.append(idx)
            sp = row_of.get(sp_hash, -1)
            op = row_of.get(op_hash, -1)
            # a rebase dropped decided history, and an event may still name
            # it (a creator reviving after rounds of silence, a withheld
            # chain revealed late): the parent's round lies below the base,
            # which "no row" already says to the device, and its lamport
            # timestamp is the host's stamp
            if sp < 0 and idx != 0:
                sp_lamport[k] = self._pruned_lamport(sp_hash)
            if op < 0 and op_hash != "":
                op_lamport[k] = self._pruned_lamport(op_hash)
            if sp < 0 and op_hash == "":
                # directly root-attached: round forced to the base root's
                # next_round (reference: hashgraph.go:207-236); first
                # events WITH an other-parent compute theirs normally.
                # Roots here are the genesis base roots (round -1), which
                # can only occur before any rebase (base 0).
                if self.round_base > 0:
                    raise GridUnsupported("root attachment after rebase")
                fixed_round[k] = 0
            sp_rows.append(sp)
            op_rows.append(op)
            la_flat.extend([c[0] for c in ev.last_ancestors])
            coins.append(middle_bit(h))
            cell_hashes.extend(cells)
            counts.append(len(cells))

        brows = np.full(b_cap, -1, dtype=np.int32)
        brows[:b] = rows
        creator = np.zeros(b_cap, dtype=np.int32)
        creator[:b] = creators
        index = np.full(b_cap, MAX_INT32, dtype=np.int32)
        index[:b] = indexes
        sp_row = np.full(b_cap, -1, dtype=np.int32)
        sp_row[:b] = sp_rows
        op_row = np.full(b_cap, -1, dtype=np.int32)
        op_row[:b] = op_rows
        coin = np.zeros(b_cap, dtype=bool)
        coin[:b] = coins
        la_rows = np.full((b_cap, n), -1, dtype=np.int32)
        la_rows.reshape(-1)[: b * n] = la_flat
        # the cells' rows, resolved after the loop so that an ancestor of
        # the same batch has its row. No row: pruned by a rebase, its fd
        # row is final, and _pack_upd drops the update. (The cells come
        # from the hashgraph's own insert walk, so a hash is always a real
        # ancestor.)
        urow, ucol, uval, staged = _pack_upd(
            map(row_of.get, cell_hashes, itertools.repeat(-1)),
            len(cell_hashes), counts, creators, indexes,
            self.upd_cap, self.e_cap,
        )
        self.cells_staged += staged
        # within-batch levels over batch-local dependencies (a parent of an
        # earlier batch is below base_row: negative, outside the slice);
        # the caller (_cut) guarantees depth < L_MAX
        lvl = _dep_levels(
            [p - base_row for p in sp_rows], [p - base_row for p in op_rows],
        )
        return (
            Batch(
                rows=brows, creator=creator, index=index,
                sp_row=sp_row, op_row=op_row, la_rows=la_rows, coin=coin,
                fixed_round=fixed_round,
                upd_row=urow, upd_col=ucol, upd_val=uval,
                levels=_level_table(lvl, b_cap),
                sp_lamport=sp_lamport, op_lamport=op_lamport,
            ),
            rows,
        )

    def _pruned_lamport(self, parent: str) -> int:
        """The host's lamport stamp of a parent that is no row of the
        device state. A rebase runs with nothing in flight, so whatever it
        pruned the host has stamped; anything else is not a pruned row."""
        from ..common import StoreErr

        if self.round_base == 0:
            raise GridUnsupported("parent outside device state")
        try:
            lamport = self.hg.store.get_event(parent).lamport_timestamp
        except StoreErr:
            lamport = None
        if lamport is None:
            raise GridUnsupported("parent outside device state")
        return lamport


import functools

import jax
import jax.numpy as jnp


# kernel-contract: _pack_results
#   in: st:pytree lo:i32[0]
#   static: e_win r_cap n
#   rung: live
#   out: one flat i32[1] vector (single host transfer)
@functools.partial(jax.jit, static_argnames=("e_win", "r_cap", "n"))
def _pack_results(st: IncState, lo, e_win: int, r_cap: int, n: int):
    """Flatten everything the host write-back reads into ONE int32 vector
    (a single transfer instead of nine round trips)."""
    sl = lambda a: jax.lax.dynamic_slice(a, (lo,), (e_win,)).astype(jnp.int32)
    with jax.named_scope("live.pack"):
        return jnp.concatenate([
            sl(st.rounds), sl(st.lamport),
            sl(st.witness.astype(jnp.int32)), sl(st.received),
            st.wtable.reshape(-1),
            st.fame_decided.astype(jnp.int32).reshape(-1),
            st.famous.astype(jnp.int32).reshape(-1),
            jnp.stack([st.stale.astype(jnp.int32),
                       st.fame_lag.astype(jnp.int32), st.last_round]),
            st.reopened,
        ])


def _unpack_results(packed, e_win: int, r_cap: int, n: int):
    o = 0
    def take(sz, shape=None):
        nonlocal o
        part = packed[o : o + sz]
        o += sz
        return part if shape is None else part.reshape(shape)
    rounds_w = take(e_win)
    lamport_w = take(e_win)
    witness_w = take(e_win).astype(bool)
    received_w = take(e_win)
    wtable = take(r_cap * n, (r_cap, n))
    fame_decided = take(r_cap * n, (r_cap, n)).astype(bool)
    famous = take(r_cap * n, (r_cap, n)).astype(bool)
    flags = take(3)
    reopened = take(r_cap)
    return (rounds_w, lamport_w, witness_w, received_w, wtable,
            fame_decided, famous, bool(flags[0]), bool(flags[1]),
            int(flags[2]), reopened)


# blocking-fetch cost that flips an engine to the pipelined discipline
# (3 consecutive calls over the threshold); ENGINE_DEFAULTS["async_fetch"]
# forces True/False for tests. The wait is a sync's device time less what
# the host does between the launch and the fetch. 10 ms until PR 32, when
# a one-train sync at 64 validators read 7.5 ms because freeing the sync's
# 32,000 cell tuples ran behind the launch; without the tuples it reads
# the train itself, ~10 ms, and sat on the threshold. 12 ms keeps such a
# sync on the synchronous discipline it had; syncs of two trains, or of a
# narrower state's longer one, wait 13 ms and more and pipeline as before
# (PERF.md section 6, PR 32: every cell's waits over six runs). A fixed
# number of milliseconds stays the wrong rule for this trade of the wait
# against one call of latency (the pipelined discipline starts at a lag
# of one: _note_wait): ROADMAP.md queue A item 6. The same threshold and
# the same three in a row deepen the pipelined lag.
ASYNC_FETCH_MIN_S = 0.012


class _AsyncFetch:
    """Background device->host reader for one dispatch's packed results."""

    def __init__(self, device_array):
        import threading

        self.done = threading.Event()
        # unguarded-ok: Event handoff — _run's writes happen-before
        # done.set(), and result() reads only after done.wait()
        self.value = None
        # unguarded-ok: same Event handoff as value
        self.error: Optional[BaseException] = None
        threading.Thread(
            target=self._run, args=(device_array,), name="live-fetch",
            daemon=True,
        ).start()

    def _run(self, device_array) -> None:
        try:
            self.value = jax.device_get(device_array)
        except BaseException as e:  # noqa: BLE001 — surfaced in result()
            self.error = e
        finally:
            self.done.set()

    def result(self):
        self.done.wait()
        if self.error is not None:
            raise self.error
        return self.value


def _snapshot(eng: LiveDeviceEngine, new_rows: List[int]) -> dict:
    """Dispatch-time view the integration needs: row mapping references,
    the fetch window, the round base, and the insertion high-water mark
    that separates 'inserted after this dispatch' from 'lost by staging'.

    hashes/row_of are the LIVE objects — advance() appends to both in
    place — so `count` is the consistency fence: any row >= count was
    appended after this dispatch and must be ignored by readers of this
    snapshot (_covered enforces it). Rebases REPLACE both objects, so a
    snapshot taken before a rebase keeps the pre-rebase view intact
    (ADVICE r4)."""
    count = len(eng.hashes)
    return dict(
        dispatch=eng.dispatch_seq,
        call=eng.calls,
        new_rows=new_rows,
        hashes=eng.hashes,
        row_of=eng.row_of,
        count=count,
        lo=max(count - eng.e_win, 0),
        base=eng.round_base,
        topo_hi=eng.hg.topological_index,
    )


def _dispatch(eng: LiveDeviceEngine, new_rows: List[int]):
    """Launch the packed-results program for the current device state.
    Returns (device_array, snapshot); does NOT block on the transfer."""
    eng.dispatch_seq += 1
    obs = eng.hg.obs
    with obs.span("live.pack", dispatch=eng.dispatch_seq):
        snap = _snapshot(eng, new_rows)
        with obs.devledger.activate("live", layout=eng.layout):
            # a NumPy scalar: jnp.int32(lo) would be a device program of its own
            packed = ledger_call(
                "_pack_results", _pack_results,
                eng.state, np.int32(snap["lo"]), eng.e_win, eng.r_cap, eng.n,
            )
    return packed, snap


def _fetch(hg, eng: LiveDeviceEngine, snap: dict, wait, discipline: str):
    """Block on one dispatch's packed results (`wait()` returns them): one
    reading is the `device.fetch` span, the babble_device_fetch_seconds
    sample and the ledger cell live/fetch. Returns (packed, the span)."""
    with hg.obs.span(
        "device.fetch", histogram=eng._m_fetch,
        ledger=("live", "fetch", eng.layout), node=hg.obs.node_id,
        dispatch=snap["dispatch"], discipline=discipline,
        lag_calls=eng.calls - snap["call"],
    ) as sp:
        packed = wait()
    # the span's two attributes as counts a window can be read over: the
    # fetches that were pipelined, and the calls they lagged their dispatch
    tracer = hg.obs.tracer
    if discipline == "pipelined":
        tracer.add("fetch.pipelined", 0.0)
    tracer.add("fetch.lag", 0.0, sp.attrs["lag_calls"])
    eng.consensus_calls += 1
    return packed, sp


def _run_sync(hg, eng: LiveDeviceEngine, new_rows: List[int]) -> None:
    """Dispatch + blocking fetch + integrate, all under the caller's core
    lock (the original discipline)."""
    packed_dev, snap = _dispatch(eng, new_rows)
    packed, fetched = _fetch(
        hg, eng, snap, lambda: jax.device_get(packed_dev), "sync",
    )
    dt = fetched.duration

    last_round_rel = _integrate(hg, eng, packed, snap)
    hg.process_decided_rounds()
    hg.process_sig_pool()
    _manage_capacity(eng, last_round_rel)

    # self-activation of the pipelined discipline on consistently slow
    # fetches; ENGINE_DEFAULTS["async_fetch"] pins it
    forced = ENGINE_DEFAULTS.get("async_fetch")
    if forced is False:
        return
    if forced is True or _waited_thrice(eng, dt):
        eng.async_fetch = True
        eng._slow_fetches = 0  # the pipelined lag counts its own waits


def _waited_thrice(eng: LiveDeviceEngine, dt: float) -> bool:
    """The evidence both for the flip and for a deeper lag: the third
    consecutive fetch that blocked over ASYNC_FETCH_MIN_S."""
    eng._slow_fetches = eng._slow_fetches + 1 if dt > ASYNC_FETCH_MIN_S else 0
    return eng._slow_fetches >= 3


def _note_wait(hg, eng: LiveDeviceEngine, dt: float) -> None:
    """The pipelined lag finds its own depth: a fetch the queue's
    occupancy asked for (not a barrier's drain, which waits by design)
    that blocked on three consecutive calls means the device is not done
    with a sync's programs when the call `fetch_lag` later comes, so one
    more dispatch stays in flight, up to the configured queue_depth. `dt`
    is read from the obs clock, as the flip's is: under the sim's virtual
    clock it is 0 and the lag stays one."""
    if _waited_thrice(eng, dt) and eng.fetch_lag < eng.queue_depth:
        eng.fetch_lag += 1
        eng._slow_fetches = 0
        hg.obs.tracer.add("fetch.deepen", 0.0)


def _integrate_oldest(hg, eng: LiveDeviceEngine, paced: bool = False) -> int:
    """Pop + integrate the oldest in-flight dispatch (FIFO — parents'
    rounds land before children's). Blocks only if the background reader
    has not finished; the blocked fraction of the dispatch's in-flight
    wall time feeds the overlap-utilization histogram and, where the
    queue's occupancy asked for this integration (`paced`), the lag."""
    fetch, snap, t_disp = eng.inflight.pop(0)
    # normally already resident
    packed, fetched = _fetch(hg, eng, snap, fetch.result, "pipelined")
    dt = fetched.duration
    in_flight = max(fetched.start + dt - t_disp, 1e-9)
    eng._m_overlap.observe(max(0.0, min(1.0, 1.0 - dt / in_flight)))
    if paced:
        _note_wait(hg, eng, dt)
    hg.obs.flightrec.record(
        "live.integrate", blocked=dt, depth=len(eng.inflight),
        lag=eng.fetch_lag,
    )
    return _integrate(hg, eng, packed, snap)


def _settle_capacity(hg, eng: LiveDeviceEngine, last_round_rel: int) -> None:
    """Rebase barrier: a rebase must NEVER run with a dispatch in flight
    (it replaces the row containers the in-flight snapshots alias and
    reads store rounds the pending integrations have not written yet).
    On capacity pressure the queue therefore drains fully — blocking
    FIFO integration — before _manage_capacity may rebase."""
    if not _capacity_soft(eng, last_round_rel):
        return
    while eng.inflight:
        last_round_rel = _integrate_oldest(hg, eng)
    _manage_capacity(eng, last_round_rel)


def _run_pipelined(hg, eng: LiveDeviceEngine) -> None:
    """Multi-slot overlap: keep fetch_lag dispatches in flight (one, and
    up to queue_depth while the oldest fetch is really waited on:
    _note_wait), integrating the oldest when that many ride (steady
    state: integrate N-1, dispatch N) or when gossip staged nothing this
    call (so the queue drains when traffic quiets, and a drained queue
    starts again at a lag of one: what the waits said of the device's
    pace is stale by then, and nothing in flight is held back by it). The
    triggers are functions of queue occupancy, the call sequence and
    waits on the obs clock — never of whether a background fetch happens
    to have finished — so the integration schedule is deterministic under
    the sim's virtual clock.
    """
    clock = hg.obs.clock
    while len(eng.inflight) >= eng.fetch_lag:
        _settle_capacity(hg, eng, _integrate_oldest(hg, eng, paced=True))

    # cross-round dispatch batching: hold gossip-staged rows (all of
    # them — a partial drain would strand events no snapshot models)
    # until batch_cap rows accumulate or the Clock deadline passes
    hold = (
        eng.batch_deadline > 0.0
        and eng.pending
        and len(eng.pending) < eng.batch_cap
        and eng._pending_since is not None
        and clock.monotonic() - eng._pending_since < eng.batch_deadline
    )
    dispatched = False
    if not hold:
        new_rows = eng.advance()
        if new_rows:
            packed_dev, snap = _dispatch(eng, new_rows)
            eng.inflight.append(
                (_AsyncFetch(packed_dev), snap, clock.monotonic())
            )
            dispatched = True
            hg.obs.flightrec.record(
                "live.dispatch", rows=len(new_rows),
                depth=len(eng.inflight),
            )
    if not dispatched and eng.inflight:
        _settle_capacity(hg, eng, _integrate_oldest(hg, eng))
        if not eng.inflight:
            eng.fetch_lag, eng._slow_fetches = 1, 0
    eng._m_qdepth.set(float(len(eng.inflight)))

    hg.process_decided_rounds()
    hg.process_sig_pool()


def _integrate(hg, eng: LiveDeviceEngine, packed, snap: dict) -> int:
    """_write_back as the `live.integrate` span and ledger cell."""
    with hg.obs.span(
        "live.integrate", ledger=("live", "integrate", eng.layout),
        dispatch=snap["dispatch"], rows=len(snap["new_rows"]),
    ):
        return _write_back(hg, eng, packed, snap)


def _write_back(hg, eng: LiveDeviceEngine, packed, snap: dict) -> int:
    """One dispatch's results into the host hashgraph, behind the
    same validation gates as the one-shot engine. Returns the dispatch's
    last_round (base-relative) for capacity management.

    All row arithmetic uses the dispatch-time snapshot: under the
    pipelined discipline the engine may have appended further rows since,
    and those are simply not covered here (the next integration handles
    them)."""
    from ..common import StoreErr, StoreErrType, is_store_err
    from ..hashgraph import RoundInfo

    obs, now = hg.obs, hg.obs.clock.monotonic
    dispatch = snap["dispatch"]
    count, lo, base = snap["count"], snap["lo"], snap["base"]
    if base != eng.round_base:
        # rebases are ordered strictly between integrations; a mismatch
        # means the discipline was violated somewhere — refuse to stamp
        raise GridUnsupported(
            f"integration base {base} != engine base {eng.round_base}"
        )
    hashes = snap["hashes"]
    new_rows = snap["new_rows"]

    def at(row, arr):
        if row < lo:
            raise GridUnsupported("decision row below fetch window")
        return arr[row - lo]

    with obs.span("live.integrate.gate", dispatch=dispatch):
        (rounds_w, lamport_w, witness_w, received_w, wtable, fame_decided,
         famous, stale, fame_lag, last_round_rel, reopened) = _unpack_results(
            packed, eng.e_win, eng.r_cap, eng.n)
        rounds_w = rounds_w[: count - lo]
        lamport_w = lamport_w[: count - lo]
        witness_w = witness_w[: count - lo]
        received_w = received_w[: count - lo]
        if bool(stale) or bool(fame_lag):
            # Core drops the engine on the raise
            raise GridUnsupported(
                "device window/unroll exhausted; rebuilding via one-shot path"
            )

        # --- DivideRounds write-back for the new events -------------------
        # boundary gate: validate the whole batch before stamping (a wrong
        # round poisons the write-once host round function; see
        # engine.validate_round_writeback) — violations demote this engine
        from .engine import validate_round_writeback

        # host-known rounds are AUTHORITATIVE: never re-stamp them (a fresh
        # attach write-back covers every staged row, including rows below
        # the engine base whose device-side round is a sentinel)
        def _fresh_rows():
            for row in new_rows:
                if hg.store.get_event(hashes[row]).round is None:
                    yield row

        validate_round_writeback(
            hg,
            (
                (
                    hashes[row],
                    (int(at(row, rounds_w)) + base, int(at(row, lamport_w))),
                )
                for row in _fresh_rows()
            ),
        )
    round_infos: Dict[int, RoundInfo] = {}
    # decision provenance (obs/provenance.py): cells captured from the
    # fetched host buffers / host store only — no extra device syncs.
    # `prov_s`: the seconds of its dearest call, note_event (an n-long
    # list rebuilt a row), summed here and handed over once (total
    # `obs.provenance`, below)
    prov = obs.provenance
    prov_cells = 0
    prov_s = 0.0
    with obs.span("live.integrate.rounds", dispatch=dispatch):
        undetermined = set(hg.undetermined_events)
        for row in new_rows:
            h = hashes[row]
            ev = hg.store.get_event(h)
            if ev.round is None:
                rnum = int(at(row, rounds_w)) + base
                ev.set_round(rnum)
                ev.set_lamport_timestamp(int(at(row, lamport_w)))
                hg.store.set_event(ev)
            else:
                rnum = ev.round
            if h in undetermined:
                if ev.lamport_timestamp is not None and ev.last_ancestors is not None:
                    t_note = now()
                    prov_cells += prov.note_event(
                        h, rnum, ev.lamport_timestamp, ev.last_ancestors,
                    )
                    prov_s += now() - t_note
                if bool(at(row, witness_w)):
                    prov_cells += prov.note_witness(
                        h, rnum, hg.peer_position(ev.creator()),
                    )
                ri = round_infos.get(rnum)
                if ri is None:
                    try:
                        ri = hg.store.get_round(rnum)
                    except StoreErr as err:
                        if not is_store_err(err, StoreErrType.KEY_NOT_FOUND):
                            raise
                        ri = RoundInfo()
                    round_infos[rnum] = ri
                is_witness = bool(at(row, witness_w))
                hg.queue_round(
                    rnum, ri, late_witness=is_witness and not ri.is_decided(h))
                ri.add_event(h, is_witness)

    # --- DecideFame write-back (pending rounds only) ----------------------
    delegated = hg.reset_floor is not None
    with obs.span("live.integrate.fame", dispatch=dispatch):
        if delegated:
            # post-reset delegation, same reasoning as engine.py: fame and
            # reception decision TIMING must match the host call-for-call or
            # block composition skews between backends. Falls through to the
            # capacity management — the engine still windows (rebases) like
            # any other.
            for rnum, ri in round_infos.items():
                hg.store.set_round(rnum, ri)
            hg.decide_fame()
            hg.decide_round_received()
        for pr in ([] if delegated else hg.pending_rounds):
            ri = round_infos.get(pr.index)
            if ri is None:
                ri = hg.store.get_round(pr.index)
                round_infos[pr.index] = ri
            sh = pr.index - base
            if 0 <= sh < eng.r_cap:
                for c in range(eng.n):
                    wrow = int(wtable[sh, c])
                    if wrow < 0:
                        continue
                    if fame_decided[sh, c]:
                        ri.set_fame(hashes[wrow], bool(famous[sh, c]))
                        prov_cells += prov.note_fame(
                            hashes[wrow], pr.index, bool(famous[sh, c]),
                            engine="live",
                        )
            # recompute, not just promote (as decide_fame does): a late
            # witness in a round that is decided and still queued must unset
            # the flag, or process_decided_rounds could settle the round
            # around an undefined fame
            pr.decided = ri.witnesses_decided()

    # --- DecideRoundReceived write-back (undetermined only) ---------------
    from .engine import admissible_receptions, stamp_receptions

    def _covered(h):
        """Row for h in THIS dispatch, None if h postdates it (pipelined
        lag: the next integration covers it), or GridUnsupported if the
        staging genuinely lost it."""
        row = snap["row_of"].get(h)
        if row is not None:
            if row >= snap["count"]:
                # appended to the live row_of AFTER this dispatch (the
                # snapshot aliases the live dict); the packed results
                # don't model it yet — next integration covers it
                return None
            return row
        try:
            ev = hg.store.get_event(h)
        except StoreErr:
            ev = None
        if ev is not None and ev.topological_index >= snap["topo_hi"]:
            return None  # inserted after this dispatch
        # every undetermined event known at dispatch time must be modeled
        # (the attach keeps undetermined events regardless of round);
        # anything unmodeled means the staging walk silently lost one —
        # demote rather than silently never receiving it (that skews
        # block composition)
        raise GridUnsupported(f"undetermined event unmodeled ({h[:18]}…)")

    if not delegated:
        # one walk over the undetermined events: what it proposes is what
        # the gate checks and, admitted, what is stamped
        with hg.obs.span("live.admissible") as sp:
            proposed, left = [], []
            for h in hg.undetermined_events:
                row = _covered(h)
                rr = -1 if row is None else int(at(row, received_w))
                if rr >= 0:
                    proposed.append((h, rr + base))
                else:
                    left.append(h)
            sp.attrs["proposed"] = len(proposed)
            admissible = admissible_receptions(hg, round_infos, proposed)
        if admissible:
            with obs.span("live.integrate.receptions", dispatch=dispatch):
                prov_cells += stamp_receptions(hg, round_infos, proposed)
                hg.undetermined_events = left

                for rnum, ri in round_infos.items():
                    hg.store.set_round(rnum, ri)
        else:
            # the device "unblocked" a reception the host rule refuses
            # (frozen/missing rounds): persist the fame state and run the
            # HOST's reception pass this call — exact host timing, so
            # block composition cannot skew (engine.admissible_receptions)
            eng._m_host_repair.inc()
            with hg.obs.span("live.host_repair"):
                for rnum, ri in round_infos.items():
                    hg.store.set_round(rnum, ri)
                hg.decide_round_received()

    late = reopened - eng.reopened_seen
    if late.any():
        # the device re-opened decided rounds for witnesses that registered
        # late, and this write-back has served them in place: a marker
        eng.reopened_seen = reopened.copy()  # not a view of the fetch
        hg.obs.tracer.record(
            "live.late_witness", hg.obs.clock.monotonic(), 0.0,
            {"dispatch": snap["dispatch"],
             "round": int(np.flatnonzero(late)[0]) + base,
             "witnesses": int(late.sum())},
        )
    if prov_cells:
        prov.mark("prov.capture", engine="live", cells=prov_cells)
        obs.tracer.add("obs.provenance", prov_s, prov_cells)
    return last_round_rel


def _capacity_soft(eng: LiveDeviceEngine, last_round_rel: int) -> bool:
    """Soft capacity-pressure predicate: the round axis needs headroom
    for fame-decision lag (~8 rounds), the event axis for the next syncs'
    appends: two syncs of the largest size seen, since `advance` refuses a
    sync that does not fit and the rebase that makes room runs only after
    a sync (a margin of four batches let a 500-event sync at 32-row
    batches run into the end of the axis with no rebase ever tried).
    len(eng.hashes) is the LIVE count, so rows appended by still-queued
    dispatches are included (conservative)."""
    margin = max(4 * eng.batch_cap, 2 * eng.largest_sync)
    return (
        last_round_rel >= eng.r_cap - 8
        or len(eng.hashes) >= eng.e_cap - margin
    )


def _manage_capacity(eng: LiveDeviceEngine, last_round_rel: int) -> None:
    """Rebase BEFORE either device axis exhausts. A momentarily-stuck
    rebase (fame decisions lagging, so the base cannot advance yet) is
    tolerated while hard room remains — it is retried on every
    subsequent sync; only an exhausted axis escalates to the caller's
    fallback. Under the queued discipline last_round_rel is fetch_lag
    dispatches old (one, at most queue_depth); the soft margin (8 rounds)
    absorbs the lag, and the caller (_settle_capacity) guarantees the in-flight
    queue is empty before this may rebase."""
    hard = (
        last_round_rel >= eng.r_cap - 3
        or len(eng.hashes) >= eng.e_cap - eng.batch_cap
    )
    if _capacity_soft(eng, last_round_rel):
        try:
            with eng.hg.obs.span("live.rebase") as sp:
                eng.rebase()
                sp.attrs["base"] = eng.round_base
        except GridUnsupported:
            if hard:
                raise
