"""Per-node consensus façade: key ownership, head/seq tracking, tx and
signature pools, wire conversion (reference: src/node/core.go:17-453)."""

from __future__ import annotations

import logging
import queue
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple

from ..crypto import pub_key_bytes
from ..hashgraph import (
    Block,
    BlockSignature,
    Event,
    Frame,
    Hashgraph,
    Store,
    Trilean,
    WireEvent,
)
from ..peers import Peers


class _Backoff:
    """Retry slots for a failing rung: after a failure at call c the next
    try is at c + step, the step doubling with each failure up to `cap`
    and back to 1 once the rung serves."""

    def __init__(self, cap: int):
        self.cap, self.step, self.at = cap, 1, 0

    def fail(self, call: int) -> int:
        self.step = min(self.step * 2, self.cap)
        self.at = call + self.step
        return self.step


class _Rung(NamedTuple):
    """A device rung whose engine stays attached across calls. The engine
    has serve(), flush() and detach(); building it is attaching it."""

    name: str  # ladder_rung(), the logs and the ladder.* records
    factory: Callable[[Core], object]


def _live_engine(core: Core):
    from ..tpu.live import LiveDeviceEngine

    return LiveDeviceEngine(
        core.hg, queue_depth=core.dispatch_queue_depth,
        batch_deadline=core.dispatch_batch_deadline,
        batch_cap=core.dispatch_batch_rows,
    )


def _mesh_queue(core: Core):
    from ..tpu.dispatch import MeshDispatchQueue

    return MeshDispatchQueue(
        core.hg, core._get_mesh(), queue_depth=core.dispatch_queue_depth,
        batch_deadline=core.dispatch_batch_deadline,
        batch_rows=core.dispatch_batch_rows,
    )


_LIVE = _Rung("live", _live_engine)
_MESH_QUEUED = _Rung("mesh_queued", _mesh_queue)


class Core:
    def __init__(
        self,
        id_: int,
        key,
        participants: Peers,
        store: Store,
        commit_ch: Optional["queue.Queue[Block]"] = None,
        logger: Optional[logging.Logger] = None,
        consensus_backend: str = "cpu",
        mesh_devices: int = 0,
        dispatch_queue_depth: int = 4,
        dispatch_batch_deadline: float = 0.0,
        dispatch_batch_rows: int = 64,
        mesh_validator_shards: int = 1,
        packed_voting: str = "auto",
        obs=None,
    ):
        self.id = id_
        self.key = key
        self._pub_key: bytes = b""
        self._hex_id: str = ""
        self.logger = logger or logging.getLogger(f"babble.core.{id_}")
        self.hg = Hashgraph(
            participants,
            store,
            commit_callback=commit_ch.put if commit_ch is not None else None,
            logger=self.logger,
            obs=obs,
        )
        self.participants = participants
        self.head: str = ""
        self.seq: int = -1
        self.transaction_pool: List[bytes] = []
        # per pooled transaction, when the front door answered for it (the
        # ingress verdict; for a transaction that came another way, when it
        # was pooled): the next self-event hands the waits over as the
        # total `ingress.wait`
        self._pooled_at: List[float] = []
        self.block_signature_pool: List[BlockSignature] = []
        if consensus_backend not in ("cpu", "tpu"):
            raise ValueError(f"unknown consensus backend: {consensus_backend!r}")
        self.consensus_backend = consensus_backend
        self.mesh_devices = mesh_devices
        # async dispatch knobs (Config.dispatch_queue_depth /
        # dispatch_batch_deadline): bound the in-flight device dispatch
        # queue and the cross-round batching hold, for both the live
        # single-device engine and the queued-mesh rung. depth 0 disables
        # the queued-mesh rung (sync one-shot mesh calls only).
        self.dispatch_queue_depth = dispatch_queue_depth
        self.dispatch_batch_deadline = dispatch_batch_deadline
        # dispatch_batch_rows: delta-row threshold past which a queued
        # dispatch prefers the pointer-doubling cold path (round-batched
        # rung); mesh_validator_shards > 1 folds the device list into a
        # 2-D (validators, rounds) mesh so voting state is partitioned
        # over validators as well as rounds
        self.dispatch_batch_rows = max(1, int(dispatch_batch_rows))
        self.mesh_validator_shards = max(1, int(mesh_validator_shards))
        # voting-table layout knob (ISSUE 17): installed process-wide via
        # tpu.packed.set_packed_mode so every engine rung — one-shot,
        # doubling, sharded mesh, incremental live, queued dispatch —
        # resolves the same layout. Validated here (not just at the CLI)
        # because config files and embedding callers bypass argparse; the
        # lazy import keeps CPU-backend nodes free of the jax import.
        if str(packed_voting) not in ("0", "1", "auto"):
            raise ValueError(f"unknown packed_voting mode: {packed_voting!r}")
        self.packed_voting = str(packed_voting)
        # platform the device engines run on ({"platform", "kind",
        # "count"}; None for the host backend), surfaced in /stats and the
        # HealthDigest. Checked ONCE, here: a "tpu" node that lost the
        # chip to another process must fail at start, not run XLA:CPU
        # under the name "tpu" (tpu/runtime.py)
        self.device: Optional[Dict[str, object]] = None
        if consensus_backend == "tpu":
            from ..tpu.packed import set_packed_mode
            from ..tpu.runtime import (
                annotate_spans,
                enable_compile_cache,
                require_tpu,
            )

            set_packed_mode(self.packed_voting)
            enable_compile_cache()
            annotate_spans()
            self.device = require_tpu()
        self._mesh = None  # built lazily on the first mesh-backend run
        self.device_consensus_runs = 0
        self.device_consensus_fallbacks = 0
        # live-engine health: demotions (live -> one-shot falls) and
        # re-attaches are counted and surfaced in /stats; a demotion is
        # NOT sticky — the live engine is retried with bounded backoff
        # (the frontier attach can rebuild it from any settled state,
        # including post-fast-sync and deep-history restarts)
        self.live_demotions = 0
        self.live_reattaches = 0
        # first attaches of the live / queued-mesh rung that failed on
        # something other than GridUnsupported (a compile error, device
        # memory): the ladder rides the one-shot rung either way, so this
        # counter and one warning per error type are what make a rung
        # that cannot start visible
        self.device_attach_failures = 0
        self._attach_errors_logged: set = set()
        self._consensus_calls = 0
        # the engine of the device rung that stays attached across calls
        # (tpu/live.py LiveDeviceEngine, tpu/dispatch.py MeshDispatchQueue;
        # _device_rung says which), or None. Core alone attaches, serves,
        # flushes and drops it; the hashgraph does not know it
        self.device_engine = None
        self._rung_retry = _Backoff(64)
        # set when the hashgraph state stops being grid-expressible (e.g. a
        # rolled store window). NOT a one-way door (VERDICT r4 #3): the
        # one-shot path is retried with bounded exponential backoff — a
        # node whose window rolled can recover the device backend without
        # needing a fast-forward (which also clears it, by compacting the
        # state back into grid range). Heals are counted for /stats.
        self._device_down = False
        self._down_retry = _Backoff(256)
        self.device_heals = 0

    # -- identity ----------------------------------------------------------

    def pub_key(self) -> bytes:
        if not self._pub_key:
            self._pub_key = pub_key_bytes(self.key)
        return self._pub_key

    def hex_id(self) -> str:
        if not self._hex_id:
            self._hex_id = "0x" + self.pub_key().hex().upper()
        return self._hex_id

    # -- head / bootstrap --------------------------------------------------

    def set_head_and_seq(self) -> None:
        last, is_root = self.hg.store.last_event_from(self.hex_id())
        if is_root:
            root = self.hg.store.get_root(self.hex_id())
            self.head = root.self_parent.hash
            self.seq = root.self_parent.index
        else:
            last_event = self.get_event(last)
            self.head = last
            self.seq = last_event.index()

    def bootstrap(self) -> None:
        self.hg.bootstrap()
        self.hg.store.flush()

    # -- event insertion ---------------------------------------------------

    def sign_and_insert_self_event(self, event: Event) -> None:
        # a self-event vouches for its other-parent and everything under
        # it: that is durable before the signature exists
        self.hg.store.flush()
        event.sign(self.key)
        self.insert_event(event, True)

    def insert_event(self, event: Event, set_wire_info: bool) -> None:
        self.hg.insert_event(event, set_wire_info)
        if event.creator() == self.hex_id():
            self.head = event.hex()
            self.seq = event.index()

    def known_events(self) -> Dict[int, int]:
        return self.hg.store.known_events()

    # -- blocks ------------------------------------------------------------

    def sign_block(self, block: Block) -> BlockSignature:
        sig = block.sign(self.key)
        block.set_signature(sig)
        self.hg.store.set_block(block)
        return sig

    # -- sync --------------------------------------------------------------

    def over_sync_limit(self, known_events: Dict[int, int], sync_limit: int) -> bool:
        tot_unknown = 0
        for pid, li in self.known_events().items():
            other = known_events.get(pid, 0)
            if li > other:
                tot_unknown += li - other
        return tot_unknown > sync_limit

    def get_anchor_block_with_frame(
        self, max_index: Optional[int] = None
    ) -> Tuple[Block, Frame]:
        return self.hg.get_anchor_block_with_frame(max_index)

    def event_diff(self, known: Dict[int, int]) -> List[Event]:
        """Events we know about that the peer (whose view is `known`) does not,
        in topological order (reference: src/node/core.go:184-207)."""
        unknown: List[Event] = []
        for pid, ct in known.items():
            peer = self.participants.by_id.get(pid)
            if peer is None:
                continue
            for h in self.hg.store.participant_events(peer.pub_key_hex, ct):
                unknown.append(self.hg.store.get_event(h))
        unknown.sort(key=lambda e: e.topological_index)
        return unknown

    def sync(self, unknown_events: List[WireEvent]) -> None:
        """Insert a batch of wire events, then record the sync with a new
        self-event whose other-parent is the batch head
        (reference: src/node/core.go:209-238).

        Stale-head inserts are skipped PER EVENT, not allowed to abort the
        batch (deliberate deviation from the reference, whose per-peer Go
        channels rarely interleave): with several peers concurrently
        pushing overlapping diffs at one node, most batches contain some
        events the store already holds — and aborting the whole batch on
        the first one also skips run_consensus, so the node's DAG keeps
        growing while its pipeline never runs (round-5 joiner freeze:
        43,000 undetermined events, zero rounds decided, every batch dead
        on 'Self-parent not last known event'). A duplicate still counts
        as a valid batch head; an event whose predecessor is genuinely
        missing (diff computed against newer state) is dropped and will be
        resent once the predecessor lands. Forks (same self-parent, new
        body) are also dropped here without poisoning the batch —
        insert_event still rejects them; they simply never enter the
        store. A KEY_NOT_FOUND from resolving wire parents, by contrast,
        still aborts the batch DELIBERATELY: it means this store lost
        bodies the diff builds on, and the node-level missing-parent
        escape (node._gossip) needs to see that error to flip the node
        into CatchingUp and rebuild the store."""
        obs = self.hg.obs
        spent = [0.0, 0.0, 0]  # s in read_wire_info, s in insert_event, inserted
        with obs.span("core.sync", events=len(unknown_events)) as sp:
            try:
                other_head = self._insert_wire_events(unknown_events, spent)
            finally:
                # decode and insert alternate (a wire event names its
                # parents by creator and index, which the events before it
                # resolve), so their times are summed over the loop and
                # handed over once a call, laid end to end from the head
                # of the span they are children of
                decode_s, insert_s, inserted = spent
                obs.tracer.record("sync.decode", sp.start, decode_s)
                obs.tracer.record("sync.insert", sp.start + decode_s, insert_s)
                obs.tracer.add("sync.events", 0.0, inserted)
            self.add_self_event(other_head)
            self.hg.store.flush()

    def _insert_wire_events(
        self, unknown_events: List[WireEvent], spent: list
    ) -> str:
        """The insert loop of `sync`; returns the batch head and adds to
        `spent` the seconds in `read_wire_info`, the seconds in
        `insert_event` and the events inserted."""
        now = self.hg.obs.clock.monotonic
        other_head = ""
        for we in unknown_events:
            t0 = now()
            ev = self.hg.read_wire_info(we)
            t1 = now()
            spent[0] += t1 - t0
            try:
                try:
                    self.insert_event(ev, False)
                    spent[2] += 1
                finally:
                    spent[1] += now() - t1
            except ValueError as e:
                if "Self-parent not last known event" not in str(e):
                    raise
                try:
                    self.hg.store.get_event(ev.hex())
                except Exception:  # noqa: BLE001 — not here: gap or fork
                    # A skipped insert whose body is ABSENT from the store
                    # is either a diff computed against newer state (benign
                    # gap — the resend heals it) or a byzantine fork: a
                    # DIFFERENT body already occupies this creator+index
                    # slot. The creator's known high-water distinguishes
                    # them, and the fork case must be observable — this
                    # warning is the only trace a forking creator leaves on
                    # an honest node's logs (the event never enters the
                    # store).
                    peer = self.participants.by_pub_key.get(ev.creator())
                    slot_taken = (
                        peer is not None
                        and self.known_events().get(peer.id, -1) >= ev.index()
                    )
                    if slot_taken:
                        self.hg.obs.flightrec.record(
                            "fork.evidence",
                            creator=ev.creator()[:16], index=ev.index(),
                        )
                    log = self.logger.warning if slot_taken else self.logger.debug
                    log(
                        "sync: dropped insert absent from store "
                        "(creator=%s index=%d): %s",
                        ev.creator()[:16], ev.index(),
                        "byzantine fork evidence — a different body holds "
                        "this slot" if slot_taken
                        else "parent gap; awaiting resend",
                    )
                    continue
                # already present: overlapping delivery, still batch head
            other_head = ev.hex()
        return other_head

    def prepare_fast_forward(
        self, block: Block, frame: Frame, section=None
    ) -> Tuple[Block, Frame, object]:
        """Validate a fast-forward response WITHOUT mutating any state —
        the node restores the app snapshot only after this passes, so a bad
        donor can never leave the app rolled onto a foreign snapshot.

        Deep-copies through the wire codec: over the in-process transport
        the block/frame/section share mutable state with the responder's
        store, and the frame events carry the responder's cached round/
        lamport/coordinate metadata — it must be stripped so Reset
        recomputes it against the new roots (the Go reference gets this for
        free from value+codec semantics at the RPC boundary; with live
        objects, stale ev.round makes DivideRounds skip witness
        registration and consensus stalls). The section's metadata, by
        contrast, is deliberately carried in its wire form (see
        hashgraph/section.py)."""
        from ..hashgraph import Section

        block = Block.from_json(block.to_json())
        frame = Frame.from_json(frame.to_json())
        if section is not None:
            section = Section.from_json(section.to_json())
        self.hg.check_block(block)
        # SAFETY: if we already committed a block at the anchor's index
        # with a DIFFERENT body, one of us is forked — refuse before the
        # app is touched, and scream (the >1/3-signed anchor is the
        # network's body, so the divergence is ours)
        self.hg.check_block_immutable(block)
        if block.frame_hash() != frame.hash():
            raise ValueError("Invalid Frame Hash")
        if section is not None:
            self.hg.verify_section(block, section)
        return block, frame, section

    def apply_fast_forward(self, block: Block, frame: Frame, section=None) -> None:
        """Apply a validated fast-forward (reset + section replay +
        consensus continuation). Args must come from prepare_fast_forward."""
        # the attached rung's device state and in-flight dispatches were
        # built against the store the reset replaces: drop it (a demotion,
        # visible in /stats), and re-attach (the frontier assembly handles
        # post-reset states) after one one-shot call lets the reset settle
        if self.device_engine is not None:
            self.live_demotions += 1
        self._drop_device_engine()
        self.hg.reset(block, frame)
        if section is not None:
            self.hg.apply_section(section, block.index())
        self.hg.obs.flightrec.record(
            "ladder.fast_forward", block=block.index(),
            round=block.round_received(),
        )
        self.set_head_and_seq()
        self._device_down = False  # reset compacted the state back into range
        self._down_retry = _Backoff(256)
        self._rung_retry.at = self._consensus_calls + 2
        self.run_consensus()

    def fast_forward(
        self, peer: str, block: Block, frame: Frame, section=None
    ) -> None:
        self.apply_fast_forward(*self.prepare_fast_forward(block, frame, section))

    def add_self_event(self, other_head: str) -> None:
        if (
            other_head == ""
            and not self.transaction_pool
            and not self.block_signature_pool
        ):
            return
        obs = self.hg.obs
        with obs.span("sync.self_event", txs=len(self.transaction_pool)):
            new_head = Event(
                transactions=self.transaction_pool,
                block_signatures=self.block_signature_pool,
                parents=[self.head, other_head],
                creator=self.pub_key(),
                index=self.seq + 1,
            )
            self.sign_and_insert_self_event(new_head)
        if self._pooled_at:
            now = obs.clock.monotonic()
            obs.tracer.add(
                "ingress.wait", sum(now - t for t in self._pooled_at),
                len(self._pooled_at))
            obs.tracer.add("self_event.txs", 0.0, len(self._pooled_at))
        self.transaction_pool = []
        self._pooled_at = []
        self.block_signature_pool = []

    def from_wire(self, wire_events: List[WireEvent]) -> List[Event]:
        return [self.hg.read_wire_info(w) for w in wire_events]

    def to_wire(self, events: List[Event]) -> List[WireEvent]:
        return [e.to_wire() for e in events]

    # -- consensus ---------------------------------------------------------

    def run_consensus(self) -> None:
        """Five-pass pipeline through the configured backend, as the root
        of the call's span tree: `core.run_consensus` is the parent of
        every span the ladder below opens, and the tracer's totals are
        checkpointed on entry and on return, so that a reader can take
        any window of whole calls afterwards (`totals_between`)."""
        obs = self.hg.obs
        self._consensus_calls += 1
        # the inserts since the last call, before the checkpoint that ends
        # their window
        self.hg.hand_over_inserts()
        obs.tracer.checkpoint()
        try:
            with obs.span("core.run_consensus",
                          call=self._consensus_calls) as sp:
                try:
                    self._run_ladder()
                    # the sync is durable when the call returns: the point
                    # at which a node answers its peer
                    self.hg.store.flush()
                finally:
                    sp.attrs["rung"] = self.ladder_rung()
        finally:
            obs.tracer.checkpoint()

    def _run_ladder(self) -> None:
        """The device path covers passes 1-3 (grid extraction + fused XLA
        pipeline) and falls back to the host engine on any state the dense
        grid cannot express (reference boundary: src/node/core.go:335-377).
        Its rungs, top down: the attached one (_serve_rung), the one-shot
        device call, the host engine."""
        if self.consensus_backend != "tpu" or (
            self._device_down and self._consensus_calls < self._down_retry.at
        ):
            # host backend, or down but healing: CPU serves until the next
            # retry slot
            self.hg.run_consensus()
            return
        from ..tpu.engine import run_consensus_device
        from ..tpu.grid import GridUnsupported

        rung = self._device_rung()
        if (
            rung is not None
            and self._consensus_calls >= self._rung_retry.at
            and self._serve_rung(rung)
        ):
            return
        mesh = self.mesh_devices > 1
        try:
            run_consensus_device(self.hg, mesh=self._get_mesh() if mesh else None)
        except GridUnsupported as e:
            # unsupported states (rolled windows) tend to persist until a
            # reset compacts them — back off instead of failing every
            # tick, but keep retrying: windows can also roll back into
            # range as consensus advances
            self._mark_device_down(
                "mesh consensus" if mesh else "device consensus", e)
            self.hg.run_consensus()
            return
        self.device_consensus_runs += 1
        self._note_device_up()

    def _device_rung(self) -> Optional[_Rung]:
        """The rung that stays attached across calls: the queued mesh
        dispatch with --mesh-devices > 1 (none without a dispatch queue),
        else the live engine. Read from the knobs on each call, which an
        embedding caller may set after construction."""
        if self.mesh_devices > 1:
            return _MESH_QUEUED if self.dispatch_queue_depth > 0 else None
        return _LIVE

    def _serve_rung(self, rung: _Rung) -> bool:
        """One call on the attached rung, attaching it first if it is not:
        True if it served the call. On any failure the engine is dropped
        and the attach is retried with bounded backoff."""
        from ..tpu.grid import GridUnsupported

        attached = self.device_engine is not None
        try:
            if not attached:
                self.device_engine = rung.factory(self)
                if rung is _LIVE:
                    # mirrored for the benchmark's entries, which read it
                    # off the hashgraph; cleared in _drop_device_engine
                    self.hg._live_device_engine = self.device_engine
            self.device_engine.serve()
        except Exception as e:  # noqa: BLE001 — any failure leaves the
            # engine desynced from the store: drop it (the one-shot path
            # recomputes from the store) and retry the attach with bounded
            # backoff, since the frontier assembly can rebuild from any
            # settled state. Only the fall of an ATTACHED engine is a
            # demotion; a failed re-attach just extends the backoff.
            backoff = self._rung_retry.fail(self._consensus_calls)
            self._drop_device_engine()
            if attached:
                self.live_demotions += 1
                self.hg.obs.flightrec.record(
                    "ladder.demote", rung=rung.name, error=type(e).__name__,
                    backoff=backoff,
                )
                # 3 demotions in 10s = a flapping backend: dump the ring
                # while the evidence is fresh
                self.hg.obs.flightrec.note_flap("demotion")
                log = (
                    self.logger.info
                    if isinstance(e, GridUnsupported)
                    else self.logger.warning
                )
            else:
                self._note_attach_failure(rung.name, e)
                # one log per transition: failed re-attaches stay at debug
                log = self.logger.debug
            log(
                "%s rung unavailable (%s); one-shot path, retry in %d calls",
                rung.name, e, backoff,
            )
            return False
        self.device_consensus_runs += 1
        self._note_device_up()
        if not attached and self.live_demotions > 0:
            self.live_reattaches += 1
            self.hg.obs.flightrec.record(
                "ladder.reattach", rung=rung.name,
                demotions=self.live_demotions,
            )
            self.logger.info(
                "%s rung re-attached (demotions=%d)", rung.name,
                self.live_demotions,
            )
        self._rung_retry.step = 1
        return True

    def _note_attach_failure(self, rung: str, e: Exception) -> None:
        """A rung whose FIRST attach failed. GridUnsupported is routine (a
        state the rung does not model; it retries quietly); anything else
        means the rung could not start at all — counted, and logged at
        warning once per error type."""
        from ..tpu.grid import GridUnsupported

        if isinstance(e, GridUnsupported):
            return
        self.device_attach_failures += 1
        kind = type(e).__name__
        if kind not in self._attach_errors_logged:
            self._attach_errors_logged.add(kind)
            self.logger.warning(
                "%s rung failed to attach (%s: %s); the one-shot device "
                "path serves instead", rung, kind, e,
            )

    def _mark_device_down(self, what: str, e: Exception) -> None:
        # info exactly once per up->down transition; retries that fail
        # while already down only extend the backoff at debug
        first = not self._device_down
        self._device_down = True
        self.device_consensus_fallbacks += 1
        backoff = self._down_retry.fail(self._consensus_calls)
        if first:
            self.hg.obs.flightrec.record(
                "ladder.device_down", what=what, error=type(e).__name__,
                backoff=backoff,
            )
        log = self.logger.info if first else self.logger.debug
        log(
            "%s unsupported (%s); using CPU, retry in %d calls",
            what, e, backoff,
        )

    def _note_device_up(self) -> None:
        if self._device_down:
            self._device_down = False
            self.device_heals += 1
            self.hg.obs.flightrec.record(
                "ladder.device_heal", heals=self.device_heals,
                fallbacks=self.device_consensus_fallbacks,
            )
            self.logger.info(
                "device backend healed after %d fallbacks "
                "(heals=%d)", self.device_consensus_fallbacks, self.device_heals,
            )
        self._down_retry.step = 1

    def _get_mesh(self):
        """The node's device mesh, built once. One axis ("shard", over
        rounds) by default; mesh_validator_shards > 1 folds the same
        devices into a 2-D ("validators", "rounds") layout so the sharded
        pipeline partitions voting state over validators too. Raises
        GridUnsupported when the platform has fewer devices or the shape
        doesn't divide — the caller's ladder then runs the CPU engine
        instead of crashing the node."""
        if self._mesh is None:
            import jax
            import numpy as np
            from jax.sharding import Mesh

            from ..tpu.grid import GridUnsupported

            devs = jax.devices()
            if len(devs) < self.mesh_devices:
                raise GridUnsupported(
                    f"mesh needs {self.mesh_devices} devices, platform has "
                    f"{len(devs)}"
                )
            if self.mesh_validator_shards > 1:
                dv = self.mesh_validator_shards
                if self.mesh_devices % dv != 0:
                    raise GridUnsupported(
                        f"mesh_devices={self.mesh_devices} not divisible by "
                        f"mesh_validator_shards={dv}"
                    )
                self._mesh = Mesh(
                    np.array(devs[: self.mesh_devices]).reshape(
                        dv, self.mesh_devices // dv
                    ),
                    ("validators", "rounds"),
                )
            else:
                self._mesh = Mesh(
                    np.array(devs[: self.mesh_devices]), ("shard",)
                )
        return self._mesh

    def _drop_device_engine(self) -> None:
        """Detach the attached rung: its in-flight results are never
        stamped, and the next attach rebuilds from the store."""
        if self.device_engine is not None:
            self.device_engine.detach()
            self.device_engine = None
            self.hg._live_device_engine = None  # the benchmark's mirror

    def flush_device_dispatch(self) -> None:
        """Blocking barrier for drivers/benches/shutdown: integrate every
        in-flight device dispatch so the store reflects all staged work
        before assertions or exit. An engine whose flush raises (a latch)
        is dropped, but no call fell, so it is no demotion."""
        if self.device_engine is not None:
            try:
                self.device_engine.flush()
            except Exception:
                self._drop_device_engine()
                raise
        self.hg.store.flush()

    def add_transactions(
        self, txs: List[bytes], admitted_at: Optional[List[float]] = None
    ) -> None:
        """Pool transactions for the next self-event. `admitted_at` is,
        per transaction, when the ingress pipeline answered its client
        (`IngressBatch.admitted_at`); without it the wait counts from now."""
        self.transaction_pool.extend(txs)
        if admitted_at is None:
            admitted_at = [self.hg.obs.clock.monotonic()] * len(txs)
        self._pooled_at.extend(admitted_at)

    def add_block_signature(self, bs: BlockSignature) -> None:
        self.block_signature_pool.append(bs)

    # -- accessors ---------------------------------------------------------

    def get_head(self) -> Event:
        return self.hg.store.get_event(self.head)

    def get_event(self, hash_: str) -> Event:
        return self.hg.store.get_event(hash_)

    def get_consensus_events(self) -> List[str]:
        return self.hg.store.consensus_events()

    def get_consensus_events_count(self) -> int:
        return self.hg.store.consensus_events_count()

    def get_undetermined_events(self) -> List[str]:
        return self.hg.undetermined_events

    def get_pending_loaded_events(self) -> int:
        return self.hg.pending_loaded_events

    def get_consensus_transactions(self) -> List[bytes]:
        txs: List[bytes] = []
        for e in self.get_consensus_events():
            txs.extend(self.get_event(e).transactions())
        return txs

    def get_last_consensus_round_index(self) -> Optional[int]:
        return self.hg.last_consensus_round

    def get_consensus_transactions_count(self) -> int:
        return self.hg.consensus_transactions

    def get_last_committed_round_events_count(self) -> int:
        return self.hg.last_committed_round_events

    def get_last_block_index(self) -> int:
        return self.hg.store.last_block_index()

    def get_block_hash_prefix(self, index: int, width: int = 18) -> str:
        """Hex prefix of the committed block BODY hash at `index`, or ""
        when the block is absent (never committed, or pruned past the
        store window). Feeds the cluster frontier-agreement canary
        (ISSUE 20). The body hash — not Block.hex() — is the consensus
        identity: the full-block hash covers attached signatures and is
        frozen at first call, so it legitimately differs across nodes
        (and over time) for byte-identical committed bodies."""
        if index < 0:
            return ""
        try:
            block = self.hg.store.get_block(index)
        except Exception:  # noqa: BLE001 — StoreErr or a rolled window
            return ""
        if not block.body.state_hash:
            # mid-commit window: the hashgraph stores the block before the
            # app commit lands its state hash in the body (node.commit
            # mutates it in place). Hashing the pre-app body would publish
            # a prefix that matches no final chain and read as a phantom
            # fork — report "not comparable" until the hash is final.
            return ""
        return block.body.hash().hex()[:width]

    def ladder_rung(self) -> str:
        """Which engine rung the next consensus pass will take: "cpu"
        (host backend), "live" (incremental device engine attached),
        "mesh_queued" (async dispatch queue up), "cpu_fallback" (device
        marked down), else "one_shot"/"mesh" by device count. Purely
        observational — exported in the HealthDigest so operators can see
        a fleet whose rungs diverged (one node demoted, rest live)."""
        if self.consensus_backend == "cpu":
            return "cpu"
        if self._device_down:
            return "cpu_fallback"
        if self.device_engine is not None:
            return self._device_rung().name
        if self.mesh_devices and int(self.mesh_devices) > 1:
            return "mesh"
        return "one_shot"

    def undecided_witnesses(self) -> Tuple[int, int]:
        """(undecided-witness count, oldest-undecided age in rounds)
        across the pending rounds — the fame-latency input of the cluster
        HealthDigest. Age is measured against the store's last round so a
        witness whose fame stalls while the graph advances reads as a
        growing number."""
        undecided = 0
        oldest: Optional[int] = None
        for pr in self.hg.pending_rounds:
            if pr.decided:
                continue
            try:
                ri = self.hg.store.get_round(pr.index)
            except Exception:  # noqa: BLE001 — round rolled out of window
                continue
            n = sum(
                1
                for e in ri.events.values()
                if e.witness and e.famous == Trilean.UNDEFINED
            )
            if n:
                undecided += n
                if oldest is None:
                    oldest = pr.index
        if oldest is None:
            return 0, 0
        try:
            last = self.hg.store.last_round()
        except Exception:  # noqa: BLE001
            last = oldest
        return undecided, max(0, int(last) - int(oldest))

    def health_digest_body(self) -> Dict[str, object]:
        """The consensus-owned fields of the node's HealthDigest
        (ISSUE 20). The node layer adds identity, timestamps, ingress
        backlog and the peer-staleness vector on top."""
        block = self.get_last_block_index()
        last_round = self.get_last_consensus_round_index()
        undecided, oldest_age = self.undecided_witnesses()
        return {
            "block": int(block),
            "bh": self.get_block_hash_prefix(block),
            "round": int(last_round) if last_round is not None else -1,
            "undecided": undecided,
            "oldest_age": oldest_age,
            "txs": len(self.transaction_pool),
            "sigs": self.hg.pending_signatures(),
            "rung": self.ladder_rung(),
            "forks": int(getattr(self.hg, "fork_evidence", 0)),
            **self.device_fields(),
        }

    def device_fields(self) -> Dict[str, object]:
        """device_platform / device_kind / device_count of a device-backed
        core (empty for the host backend) — the /stats and HealthDigest
        fields that say what "tpu" actually ran on."""
        return {f"device_{k}": v for k, v in (self.device or {}).items()}

    def need_gossip(self) -> bool:
        return (
            self.hg.pending_loaded_events > 0
            or len(self.transaction_pool) > 0
            or len(self.block_signature_pool) > 0
        )
