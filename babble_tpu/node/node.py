"""Top-level node runtime (reference: src/node/node.go).

A Node runs three cooperating loops, mapped from the reference's goroutines
onto daemon threads:

- the state-machine loop (`run`): Babbling -> babble(), CatchingUp ->
  fast_forward(), Shutdown -> return;
- per-source worker threads (`_serve_source`) draining the transport
  consumer, the app submit queue and the consensus commit queue — a
  deliberate unbundling of Go's single select loop (reference:
  src/node/node.go:144-174) so RPC dispatch never queues behind a commit
  that is waiting out a slow consensus pass under core_lock;
- the control timer driving gossip ticks.

`core_lock` serializes all Core/Hashgraph access, exactly like the
reference's coreLock (src/node/node.go:27).
"""

from __future__ import annotations

import json
import logging
import queue
import threading
from typing import Dict, Optional, Tuple

from ..hashgraph import Block, Store, WireEvent
from ..ingress import IngressPipeline
from ..obs import DEFAULT_COUNT_BUCKETS, Observability, SLOEngine
from ..obs.tracectx import trace_id_for
from ..net import (
    EagerSyncRequest,
    EagerSyncResponse,
    FastForwardRequest,
    FastForwardResponse,
    RPC,
    SyncRequest,
    SyncResponse,
    Transport,
)
from ..peers import Peers
from ..proxy import AppProxy
from .config import Config
from .control_timer import new_random_control_timer
from .core import Core
from .peer_selector import RandomPeerSelector
from .state import NodeState, NodeStateMachine
from .watchdog import LivenessWatchdog


def _is_benign_race(e: Exception) -> bool:
    """Errors that are ordinary concurrency races of the gossip protocol
    (e.g. two peers pushing overlapping diffs so an insert sees a stale
    head), not faults worth an error-level line per occurrence."""
    return "Self-parent not last known event by creator" in str(e)


class _CoreLocked:
    """`with node._locked:` is `with node.core_lock:` that also adds the
    time the thread waited for the lock to the tracer's `node.lock_wait`
    total (count: acquisitions; seconds: only where the lock was held by
    another thread, so an uncontended acquire reads no clock)."""

    __slots__ = ("lock", "clock", "tracer")

    def __init__(self, lock, clock, tracer):
        self.lock, self.clock, self.tracer = lock, clock, tracer

    def __enter__(self) -> None:
        waited = 0.0
        if not self.lock.acquire(False):
            t0 = self.clock.monotonic()
            self.lock.acquire()
            waited = self.clock.monotonic() - t0
        self.tracer.add("node.lock_wait", waited)

    def __exit__(self, *exc) -> None:
        self.lock.release()


def _is_missing_parent(e: Exception) -> bool:
    """A sync failed because an event body this store is SUPPOSED to have
    (per its own known-events high-water mark) is gone — the signature of
    the LRU-eviction livelock (see _gossip)."""
    from ..common import StoreErrType, is_store_err

    return is_store_err(e, StoreErrType.KEY_NOT_FOUND)


class Node(NodeStateMachine):
    def __init__(
        self,
        conf: Config,
        id_: int,
        key,
        participants: Peers,
        store: Store,
        trans: Transport,
        proxy: AppProxy,
    ):
        super().__init__()
        self.conf = conf
        self.id = id_
        self.logger = logging.LoggerAdapter(conf.logger, {"this_id": id_})
        # every monotonic read / sleep goes through the clock seam so the
        # deterministic simulator (babble_tpu/sim/) can run nodes on
        # virtual time; production configs carry the SystemClock singleton
        self.clock = conf.clock
        self.local_addr = trans.local_addr()

        pmap = store.participants()
        # UNBOUNDED by design (code review r5): process_decided_rounds puts
        # here while holding core_lock, and the commit worker needs
        # core_lock to sign — a bounded channel deadlocks the node the
        # moment the app-commit backlog hits the bound (putter waits for a
        # slot, consumer waits for the lock). The reference's buffered-400
        # channel has the same latent deadlock (node.go:144-174 commits
        # inline under coreLock); consensus outrunning a slow app is
        # handled instead by capping served anchors at the app's committed
        # height (_app_committed_index).
        self.commit_ch: "queue.Queue[Block]" = queue.Queue()
        # one observability bundle per node: typed metrics registry +
        # span ring, timed by the SAME injected clock as the node loops,
        # so sim runs report deterministic latency histograms
        self.obs = Observability(
            clock=conf.clock, node_id=id_,
            trace_capacity=conf.trace_capacity, tracing=conf.tracing,
            flightrec_capacity=getattr(conf, "flightrec_capacity", 2048),
        )
        # flight-recorder dump artifacts land here (None = in-memory
        # only); dumps are triggered by the watchdog/SLO/flap hooks below
        self.obs.flightrec.dump_dir = getattr(conf, "flightrec_dir", None)
        self.obs.flightrec.logger = conf.logger
        self.core = Core(
            id_, key, pmap, store, self.commit_ch, conf.logger,
            consensus_backend=conf.consensus_backend,
            mesh_devices=getattr(conf, "mesh_devices", 0),
            dispatch_queue_depth=getattr(conf, "dispatch_queue_depth", 4),
            dispatch_batch_deadline=getattr(conf, "dispatch_batch_deadline", 0.0),
            dispatch_batch_rows=getattr(conf, "dispatch_batch_rows", 64),
            mesh_validator_shards=getattr(conf, "mesh_validator_shards", 1),
            packed_voting=getattr(conf, "packed_voting", "auto"),
            obs=self.obs,
        )
        self.core_lock = threading.Lock()
        self._locked = _CoreLocked(self.core_lock, conf.clock, self.obs.tracer)
        self.selector_lock = threading.Lock()
        self.peer_selector = RandomPeerSelector(  # guarded-by: selector_lock
            participants, self.local_addr, rng=conf.rng
        )
        self.trans = trans
        trans.bind_obs(self.obs)
        self.net_ch = trans.consumer()
        self.proxy = proxy
        # trace submissions at the app-ingress edge: the submit->event
        # stage then includes the queue wait (ISSUE 5)
        proxy.bind_obs(self.obs)
        self.submit_ch = proxy.submit_ch()
        # ingress pipeline (ISSUE 16): every proxy submit entry point now
        # routes through admission control + batching before the submit
        # channel; downstream batches (lists) are drained by the tx
        # worker via _add_transactions. Deadline pumping rides the
        # heartbeat tick below (SimCluster._tick in the sim).
        self.ingress = IngressPipeline(
            downstream=self.submit_ch.put,
            clock=conf.clock,
            obs=self.obs,
            batch_bytes=getattr(conf, "ingress_batch_bytes", 65536),
            batch_deadline=getattr(conf, "ingress_batch_deadline", 0.0),
            queue_cap=getattr(conf, "ingress_queue_cap", 8192),
            client_rate=getattr(conf, "ingress_client_rate", 0.0),
            dedup_window=getattr(conf, "ingress_dedup_window", 65536),
            logger=conf.logger,
        )
        proxy.bind_ingress(self.ingress)
        self.shutdown_event = threading.Event()
        self.control_timer = new_random_control_timer(
            conf.heartbeat_timeout, rng=conf.rng, clock=conf.clock
        )

        # unguarded-ok: single-writer babble-loop bookkeeping; the stats
        # endpoint reads are advisory and staleness-tolerant
        self.start_time = self.clock.monotonic()
        # unguarded-ok: single-writer babble-loop counter, advisory reads
        self.sync_requests = 0
        # unguarded-ok: single-writer babble-loop counter, advisory reads
        self.sync_errors = 0
        # CatchingUp->Babbling bounces from the fast-forward rewind guards:
        # self-resolving in ordinary operation, but a node stuck ping-ponging
        # (crashed before gossiping its newest own events while genuinely
        # behind) must be operationally visible (ADVICE r3)
        # unguarded-ok: written only by the babble/catch-up loop (single
        # writer); the stats endpoint reads tolerate staleness
        self.fast_forward_bounces = 0
        # unguarded-ok: same single-writer loop state as above
        self._consecutive_bounces = 0
        # bouncing this many times in a row (no successful fast-forward,
        # no successful exchange in between) licenses an own-chain rewind
        # even without _rewind_ok, provided the exported-bound evidence
        # still holds — see fast_forward
        self._bounce_rewind_after = 3
        # unguarded-ok: same single-writer loop state as above
        self._missing_parent_syncs = 0
        # unguarded-ok: same single-writer loop state as above
        self._missing_parent_threshold = 3
        # set when flipping to CatchingUp because our OWN store lost event
        # bodies (the eviction livelock): licenses fast_forward to accept
        # an own-chain rewind — IF every peer's reported high-water for
        # our chain confirms the tail never reached them (_peer_acks)
        # unguarded-ok: flipped only by the babble/catch-up loop (single
        # writer); consumed by the same loop's fast_forward
        self._rewind_ok = False
        # highest own-chain seq that has ever left this node through a
        # SUCCESSFUL export (our eager push, a served sync diff, or a
        # served fast-forward section). An own event above this bound
        # provably never reached any peer — relays can only carry what an
        # export put on the wire — so the rewind license is decided from
        # local evidence, with no dependency on sampling every peer's
        # sync responses (code review r5 found the sampled-ack version
        # unsound; the all-peers version then proved liveness-fragile:
        # one unreachable peer blocked recovery forever)
        self._last_exported_seq = -1  # guarded-by: _export_lock
        self._export_lock = threading.Lock()
        # highest block index the APP has committed (proxy.commit_block
        # returned). The hashgraph's anchor can run a full commit channel
        # ahead of this; fast-forward serving must never anchor past it or
        # get_snapshot fails ("snapshot N not found") and starves joiners.
        # Single writer (the commit loop); racing readers only ever see a
        # slightly stale floor, which is safe (they serve an older anchor).
        # unguarded-ok: the single-writer/stale-floor argument above
        self._app_committed_index = -1

        # single-writer (the _babble loop) in-flight outbound exchange
        # count; GIL-atomic decrement from the finishing gossip thread
        # unguarded-ok: the single-writer/GIL-atomic argument above
        self._gossip_inflight = 0

        # -- metric declarations (static names: the obs-* lint family
        # rejects computed names and undeclared label sets) -------------
        # headline: end-to-end commit latency, tx submit -> block commit
        self._m_commit_latency = self.obs.histogram(
            "babble_commit_latency_seconds",
            "End-to-end latency from transaction submission to block commit",
        )
        self._m_blocks = self.obs.counter(
            "babble_blocks_committed_total", "Blocks committed by the app",
        )
        self._m_sync = self.obs.histogram(
            "babble_sync_duration_seconds",
            "Outbound gossip exchange round-trip time",
            labels=("result",),
        )
        self._m_payload = self.obs.histogram(
            "babble_sync_payload_events",
            "Events per sync payload by direction",
            labels=("direction",), buckets=DEFAULT_COUNT_BUCKETS,
        )
        # the device latency budget is declared here unconditionally so
        # /metrics carries the full catalog (zero-count histograms) even
        # on CPU-backend nodes; the engines observe into the same names
        self._m_dispatch = self.obs.histogram(
            "babble_device_dispatch_seconds",
            "Host-side device program launch time per advance",
        )
        self._m_fetch = self.obs.histogram(
            "babble_device_fetch_seconds",
            "Blocking device result fetch (round-trip) time",
        )
        self._m_stage = self.obs.histogram(
            "babble_device_stage_seconds",
            "Host staging (restage) time per device consensus call",
            labels=("path",),
        )
        self._m_run = self.obs.histogram(
            "babble_device_run_seconds",
            "Device wall time per device consensus call",
            labels=("path",),
        )
        self.obs.gauge(
            "babble_mesh_staged_events",
            "Events staged onto the mesh in the latest mesh call",
        )
        self._m_pass = self.obs.histogram(
            "babble_consensus_pass_duration_seconds",
            "Wall time of each consensus pipeline pass",
            labels=("phase",),
        )
        self.obs.counter(
            "babble_device_rebases_total",
            "Live-engine grid rebases onto a committed frontier",
        )
        # submit timestamps for the commit-latency histogram, keyed by tx
        # bytes; bounded so a flooded node degrades to sampling (entries
        # for txs submitted while full are simply not measured)
        self._tx_times: Dict[bytes, float] = {}  # guarded-by: _tx_times_lock
        self._tx_times_lock = threading.Lock()
        self._tx_times_cap = 8192

        # live state gauges read at exposition time
        self.obs.gauge(
            "babble_last_block_index", "Last committed block index",
        ).set_function(lambda: self.core.get_last_block_index())
        # commit frontier (ISSUE 20 satellite): the one source of truth
        # the HealthDigest, /stats and the cluster observatory all read
        self.obs.gauge(
            "babble_commit_frontier_block",
            "Committed block frontier (last block index; -1 before any)",
        ).set_function(lambda: float(self.core.get_last_block_index()))
        self.obs.gauge(
            "babble_commit_frontier_round",
            "Committed consensus round frontier (-1 before any)",
        ).set_function(self._frontier_round)
        self.obs.gauge(
            "babble_consensus_events", "Events that reached consensus",
        ).set_function(lambda: self.core.get_consensus_events_count())
        self.obs.gauge(
            "babble_undetermined_events", "Events not yet through consensus",
        ).set_function(lambda: len(self.core.get_undetermined_events()))
        self.obs.gauge(
            "babble_transaction_pool", "Transactions awaiting an own event",
        ).set_function(lambda: len(self.core.transaction_pool))
        self.obs.gauge(
            "babble_fast_forward_bounces",
            "CatchingUp->Babbling bounces from the rewind guards",
        ).set_function(lambda: self.fast_forward_bounces)
        self.obs.gauge(
            "babble_sync_errors", "Failed gossip exchanges",
        ).set_function(lambda: self.sync_errors)
        self.obs.gauge(
            "babble_device_consensus_runs", "Device-backend consensus runs",
        ).set_function(lambda: self.core.device_consensus_runs)
        self.obs.gauge(
            "babble_device_consensus_fallbacks",
            "Device runs that fell back to the CPU pipeline",
        ).set_function(lambda: self.core.device_consensus_fallbacks)
        self.obs.gauge(
            "babble_device_heals",
            "Device runs that cleared a standing device-down",
        ).set_function(lambda: self.core.device_heals)
        self.obs.gauge(
            "babble_live_engine_demotions",
            "Live-engine demotions to the one-shot path",
        ).set_function(lambda: self.core.live_demotions)
        self.obs.gauge(
            "babble_live_engine_reattaches",
            "Successful live-engine re-attaches",
        ).set_function(lambda: self.core.live_reattaches)

        # liveness watchdog (node/watchdog.py): round-advance stall
        # detection + per-peer gossip health. Fed by _obs_sync (shared
        # with the simulator's exchanges) and checked from the heartbeat
        # tick (threaded _babble loop; SimCluster._tick in the sim).
        self.watchdog = LivenessWatchdog(
            clock=self.clock, obs=self.obs, logger=self.logger,
            deadline=conf.stall_deadline,
            round_fn=self.core.get_last_consensus_round_index,
            pending_fn=lambda: (
                len(self.core.get_undetermined_events())
                + len(self.core.transaction_pool)
                # txs held inside the ingress pipeline are pending work
                # too: a stall with a full ingress queue must not read
                # as an idle node
                + self.ingress.pending()
            ),
        )

        # cluster health plane (ISSUE 20): bind the local digest
        # providers, then hand the observatory to the watchdog so a
        # stall can classify itself as local lag vs cluster-wide stall
        self.obs.clusterview.bind_local(
            self.local_addr,
            digest_fn=self._health_digest,
            block_hash_fn=self.core.get_block_hash_prefix,
            enabled=getattr(conf, "cluster_health", True),
            staleness_deadline=getattr(
                conf, "cluster_staleness_deadline", 5.0
            ),
        )
        self.watchdog.clusterview = self.obs.clusterview

        self.obs.gauge(
            "babble_flightrec_records",
            "Records currently held in the flight-recorder ring",
        ).set_function(lambda: float(len(self.obs.flightrec)))
        self.obs.gauge(
            "babble_flightrec_dumps",
            "Flight-recorder dumps emitted since boot",
        ).set_function(lambda: float(self.obs.flightrec.dumps))

        # SLO engine (obs/slo.py): default objectives over series the
        # registry already carries. Objectives over paths this node never
        # takes (e.g. device series on a CPU backend) simply have no data
        # and cannot breach. Evaluated beside watchdog.check() on the
        # heartbeat tick; a breach transition dumps the flight recorder.
        self.slo: Optional[SLOEngine] = None
        if getattr(conf, "slo_enabled", True):
            self.slo = SLOEngine(self.obs, logger=self.logger)
            self.slo.objective(
                "submit_commit_p99",
                series="babble_commit_latency_seconds",
                kind="p_below", quantile=0.99,
                threshold=getattr(conf, "slo_commit_p99", 30.0),
                description="p99 submit->commit latency stays under the "
                            "configured bound",
            )
            self.slo.objective(
                "round_advance",
                series="babble_consensus_stalled",
                kind="below", threshold=0.5,
                description="round-received keeps advancing (the stall "
                            "gauge stays 0)",
            )
            self.slo.objective(
                "device_blocked",
                series="babble_device_run_seconds",
                kind="mean_below", threshold=0.3,
                labels={"path": "mesh_queued"},
                description="queued-mesh integration blocks < 300 ms/call "
                            "on device results",
            )
            self.slo.objective(
                "overlap_utilization",
                series="babble_device_overlap_utilization",
                kind="mean_above", threshold=0.25,
                description="async dispatch overlaps at least a quarter "
                            "of its in-flight time with gossip",
            )
            self.slo.objective(
                "dispatch_queue_depth",
                series="babble_device_queue_depth",
                kind="below",
                threshold=float(max(1, conf.dispatch_queue_depth)) + 0.5,
                description="the dispatch queue is not pinned past its "
                            "configured depth",
            )
            self.slo.objective(
                "ingress_queue_depth",
                series="babble_ingress_queue_depth",
                kind="below",
                threshold=float(
                    max(1, getattr(conf, "ingress_queue_cap", 8192))
                ) + 0.5,
                description="the ingress pipeline is not pinned at its "
                            "admission queue cap",
            )
            self.slo.objective(
                "catchup_replay",
                series="babble_catchup_replay_seconds",
                kind="mean_below",
                threshold=float(getattr(conf, "slo_catchup_replay", 30.0)),
                description="log-diameter cold-path section replay "
                            "(fast-sync / post-reset catch-up) stays under "
                            "the latency cap",
            )
            # cluster-scope objectives (ISSUE 20): evaluated from the
            # local fleet table, so every node alarms on the same
            # cluster-level anomaly without a central evaluator
            self.slo.objective(
                "cluster_commit_skew",
                series="babble_cluster_commit_skew_blocks",
                kind="below", threshold=20.0,
                description="committed-block skew across live digests "
                            "stays under 20 blocks",
            )
            self.slo.objective(
                "cluster_frontier_agreement",
                series="babble_cluster_frontier_agreement",
                kind="above", threshold=0.5,
                description="a majority of comparable peer digests agree "
                            "with our chain at their frontier (safety "
                            "canary)",
            )

        # rate limit for log_stats (satellite: no full dict per heartbeat)
        # unguarded-ok: single-writer babble-loop timestamp
        self._last_stats_log = float("-inf")

        self.need_bootstrap = store.need_bootstrap()
        self.set_starting(True)
        self.set_state(NodeState.BABBLING)

        # unguarded-ok: bound once in run_async at boot; shutdown joins it
        self._run_thread: Optional[threading.Thread] = None

    # ------------------------------------------------------------------
    # lifecycle
    # ------------------------------------------------------------------

    def init(self) -> None:
        if self.need_bootstrap:
            self.logger.debug("Bootstrap")
            self.core.bootstrap()
        self.core.set_head_and_seq()
        # a restored chain was (conservatively) exported by the previous
        # process — without this floor, a post-restart livelock could
        # license rewinding a tail peers already hold (code review r5)
        self._note_export(self.core.seq)

    def run_async(self, gossip: bool) -> None:
        self._run_thread = threading.Thread(
            target=self.run, args=(gossip,), name=f"node-{self.id}", daemon=True
        )
        self._run_thread.start()

    def run(self, gossip: bool) -> None:
        self.start_time = self.clock.monotonic()
        self.control_timer.run()

        # One worker per source instead of a merged queue behind a single
        # dispatcher (deliberate deviation from the reference's select loop,
        # node.go:144-174, which serializes all four channels on one
        # goroutine): block commits and transaction inserts take core_lock
        # inline, so a merged queue parks incoming RPCs behind a commit
        # that is itself waiting out a slow consensus pass — the node stops
        # answering gossip for seconds and the cluster reads it as down
        # (the round-1..4 "node wedge"). Per-source workers keep RPC
        # dispatch independent of the commit path while preserving the
        # orderings that matter: commits apply in block order, submissions
        # in arrival order.
        for src, tag in (
            (self.net_ch, "rpc"),
            (self.submit_ch, "tx"),
            (self.commit_ch, "block"),
        ):
            threading.Thread(
                target=self._serve_source, args=(src, tag), daemon=True,
                name=f"node-{self.id}-{tag}",
            ).start()

        while True:
            state = self.get_state()
            if state == NodeState.BABBLING:
                self._babble(gossip)
            elif state == NodeState.CATCHING_UP:
                self.fast_forward()
            elif state == NodeState.SHUTDOWN:
                return

    def _serve_source(self, src: "queue.Queue", tag: str) -> None:
        while not self.shutdown_event.is_set():
            try:
                item = src.get(timeout=0.1)
            except queue.Empty:
                continue
            if tag == "rpc":
                rpc = item

                def handle(rpc=rpc):
                    self._process_rpc(rpc)
                    if self.core.need_gossip() and not self.control_timer.set:
                        self.control_timer.reset()

                self.go_func(handle, name=f"node-{self.id}-rpc")
            elif tag == "tx":
                # the ingress pipeline emits BATCHES (lists) onto the
                # submit channel; pre-pipeline producers still put single
                # tx bytes — both are handled, one core_lock pass each
                if isinstance(item, list):
                    self._add_transactions(item)
                else:
                    self._add_transactions([item])
                if not self.control_timer.set:
                    self.control_timer.reset()
            elif tag == "block":
                try:
                    self.commit(item)
                except Exception as e:  # commit errors are logged, not fatal
                    self.logger.error("Committing Block: %s", e)

    def _babble(self, gossip: bool) -> None:
        """Heartbeat loop in the Babbling state
        (reference: src/node/node.go:180-204)."""
        return_event = threading.Event()
        while True:
            if self.shutdown_event.is_set() or self.get_state() != NodeState.BABBLING:
                return
            if return_event.is_set():
                return
            try:
                self.control_timer.tick_ch.get(timeout=0.05)
            except queue.Empty:
                continue
            self.watchdog.check()
            # partition-suspicion edge detector + lag matrix refresh
            # (cheap; reads the fleet table the gossip legs maintain)
            self.obs.clusterview.check()
            if self.slo is not None:
                self.slo.tick()
            # deadline pump: ship a partial ingress batch whose hold
            # deadline elapsed even when no new submission arrives
            self.ingress.tick()
            if gossip:
                # At most ONE outbound exchange in flight (deliberate
                # deviation from the reference, node.go:180-196, which
                # spawns a goroutine per tick): Python threads are
                # concurrency, not parallelism — overlapping syncs from
                # one node only lengthen every peer's core_lock queue. A
                # 5ms tick against a 30ms exchange piles up hundreds of
                # doomed handler threads cluster-wide until RPCs time out
                # en masse and lagging peers starve (the round-5 catch-up
                # wedge). The guard also makes pacing adaptive for free:
                # the effective gossip interval is max(heartbeat, actual
                # exchange time).
                proceed = self._pre_gossip() if self._gossip_inflight == 0 else False
                if proceed:
                    with self.selector_lock:
                        peer = self.peer_selector.next()
                    self._gossip_inflight += 1

                    def _exchange(addr=peer.net_addr):
                        try:
                            self._gossip(addr, return_event)
                        finally:
                            self._gossip_inflight -= 1

                    self.go_func(_exchange, name=f"node-{self.id}-gossip")
            # keep ticking while starting: a fresh joiner has nothing to
            # gossip about (need_gossip False) but must retry its first
            # exchange until one peer answers — stopping the timer here
            # would strand it if that first attempt hit a dead peer
            # (the reference's timer free-runs, node.go:180-204)
            if not (self.core.need_gossip() or self.is_starting()):
                self.control_timer.stop()
            elif not self.control_timer.set:
                self.control_timer.reset()

    # ------------------------------------------------------------------
    # RPC handlers
    # ------------------------------------------------------------------

    def _process_rpc(self, rpc: RPC) -> None:
        state = self.get_state()
        if state != NodeState.BABBLING and not (
            state == NodeState.CATCHING_UP
            and isinstance(rpc.command, FastForwardRequest)
        ):
            # Deliberate deviation from the reference (node.go:205-216),
            # which discards every RPC outside Babbling: FastForwardRequest
            # is served from STORED state (anchor block + frame + section)
            # and needs no live consensus, and refusing it while CatchingUp
            # livelocks a cluster where several nodes flip together — each
            # refuses the others with "not ready" and nobody can exit.
            self.logger.debug("Discarding RPC Request in state %s", state)
            # error-only response: both transports short-circuit on the
            # error before deserializing a body, so no command ever gets a
            # mismatched response type
            rpc.respond(None, error=f"not ready: {state}")
            return
        cmd = rpc.command
        if isinstance(cmd, SyncRequest):
            self._process_sync_request(rpc, cmd)
        elif isinstance(cmd, EagerSyncRequest):
            self._process_eager_sync_request(rpc, cmd)
        elif isinstance(cmd, FastForwardRequest):
            self._process_fast_forward_request(rpc, cmd)
        else:
            rpc.respond(None, error="unexpected command")

    def _process_sync_request(self, rpc: RPC, cmd: SyncRequest) -> None:
        resp = SyncResponse(from_id=self.id)
        resp_err: Optional[str] = None

        # The sync-limit check deliberately runs OUTSIDE core_lock: it is
        # a monotone participant-heights comparison (store reads that are
        # GIL-atomic; a torn read is at worst slightly stale, which only
        # delays the verdict by one exchange). The answer is the one RPC a
        # saturated node must never sit on — a peer that has fallen behind
        # learns it should fast-forward FROM THIS RESPONSE, and a busy
        # survivor's lock queue is exactly when the peer is falling behind
        # fastest (round-5 wedge: the joiner's 5s RPCs timed out behind
        # the survivors' own sync traffic, so it never learned it was
        # behind and sat Babbling at block 21 while they ran to 2,552).
        try:
            over_sync_limit = self.core.over_sync_limit(
                cmd.known, self.conf.sync_limit
            )
        except Exception:  # noqa: BLE001 — racing a reset/rebuild: retry
            with self._locked:  # on the consistent path
                over_sync_limit = self.core.over_sync_limit(
                    cmd.known, self.conf.sync_limit
                )
        if over_sync_limit:
            self.logger.debug("SyncLimit")
            resp.sync_limit = True
            try:
                resp.known = self.core.known_events()
            except Exception:  # noqa: BLE001 — same racing-reset fallback
                with self._locked:
                    resp.known = self.core.known_events()
            rpc.respond(resp, error=None)
            return
        else:
            try:
                # `node.serve_sync`: what an inbound request costs this
                # node, the wait for the lock included: the diff and its
                # wire form
                with self.obs.span("node.serve_sync") as sp:
                    with self._locked:
                        diff = self.core.event_diff(cmd.known)
                        exported = self.core.seq
                    resp.events = self.core.to_wire(diff)
                    sp.attrs["events"] = len(diff)
                # piggyback trace contexts for the traced txs the served
                # diff carries (out-of-band: hash-safe by construction)
                resp.traces = self.obs.traces.contexts_for(diff)
                # piggyback the cluster fleet table (ISSUE 20): same
                # out-of-band contract, omitted when empty
                resp.cluster = self.obs.clusterview.wire_digests()
                self._m_payload.labels(direction="served").observe(
                    len(resp.events)
                )
                # serving a diff exports our chain up to `exported` —
                # evidence bound for the rewind license in fast_forward
                self._note_export(exported)
            except Exception as e:
                self.logger.error("Calculating Diff: %s", e)
                resp_err = str(e)

        with self._locked:
            resp.known = self.core.known_events()
        rpc.respond(resp, error=resp_err)

    def _process_eager_sync_request(self, rpc: RPC, cmd: EagerSyncRequest) -> None:
        success = True
        err: Optional[str] = None
        # adopt pushed trace contexts before the insert (same rule as
        # _pull: the consensus hooks must find them)
        if cmd.traces:
            self.obs.traces.absorb(cmd.traces)
        if cmd.cluster:
            self.obs.clusterview.absorb(cmd.cluster)
        with self._locked:
            try:
                self.sync(cmd.events)
            except Exception as e:
                # a stale-head insert is an ordinary race between
                # concurrent pushes, not a fault — keep it off the error
                # path (error logging is hot enough to show in profiles)
                level = (
                    self.logger.debug
                    if _is_benign_race(e) else self.logger.error
                )
                level("sync(): %s", e)
                success = False
                err = str(e)
        rpc.respond(EagerSyncResponse(from_id=self.id, success=success), error=err)

    def _process_fast_forward_request(self, rpc: RPC, cmd: FastForwardRequest) -> None:
        resp = FastForwardResponse(from_id=self.id)
        resp_err: Optional[str] = None
        try:
            with self._locked:
                # anchor + live section must come from one consistent
                # snapshot, capped at the app's committed height so the
                # get_snapshot below cannot race the async commit channel
                block, frame = self.core.get_anchor_block_with_frame(
                    max_index=self._app_committed_index
                )
                try:
                    section = self.core.hg.get_section(frame.round, block.index())
                except Exception as se:  # noqa: BLE001 — degraded serve:
                    # the live section walks history above the anchor; on a
                    # long-lived donor with a lagging anchor that history
                    # can be LRU-evicted. Serving anchor+frame+snapshot
                    # WITHOUT the section still lets the joiner reset and
                    # catch the rest through ordinary gossip — strictly
                    # better than refusing every joiner forever.
                    self.logger.warning(
                        "FastForwardRequest: serving without live section "
                        "(%s)", se, exc_info=True,
                    )
                    section = None
                # the exported bound must be read under the SAME lock that
                # built the section (mirroring the sync-diff path at
                # _process_sync_request): reading seq after the lock is
                # released races concurrent add_self_event calls and would
                # claim export of own events the section does not carry —
                # an over-claimed bound refuses legitimate rewinds, which
                # is exactly the frozen-frame bounce loop the license
                # exists to break
                exported = self.core.seq
            resp.block = block
            resp.frame = frame
            resp.section = section
            resp.snapshot = self.proxy.get_snapshot(block.index())
            # serving a section exports our chain (its events include
            # ours): evidence bound for the rewind license
            if section is not None:
                self._note_export(exported)
        except Exception as e:
            # full traceback: a donor that cannot serve (missing rounds,
            # evicted events, stale anchors) starves every joiner — the
            # exact failure site matters operationally
            self.logger.error("FastForwardRequest: %s", e, exc_info=True)
            resp_err = str(e)
        rpc.respond(resp, error=resp_err)

    # ------------------------------------------------------------------
    # gossip
    # ------------------------------------------------------------------

    def _note_export(self, exported: int) -> None:
        """Raise the exported-chain bound monotonically. Locked: racing
        check-then-set from RPC-handler and gossip threads could lower the
        bound and unsoundly license an own-chain rewind (code review r5)."""
        with self._export_lock:
            if exported > self._last_exported_seq:
                self._last_exported_seq = exported

    def _pre_gossip(self) -> bool:
        with self._locked:
            if not (self.core.need_gossip() or self.is_starting()):
                return False
            return True

    def _gossip(self, peer_addr: str, return_event: threading.Event) -> None:
        """One pull+push exchange (reference: src/node/node.go:363-395)."""
        self.sync_requests += 1
        start = self.clock.monotonic()
        # `node.gossip` is the exchange as a span with totals (children
        # `node.pull`, `node.push`, `core.sync`); the ring record `gossip`
        # of _obs_sync carries the peer and the result for /debug/trace
        with self.obs.span("node.gossip"):
            try:
                sync_limit, other_known = self._pull(peer_addr)
                if sync_limit:
                    self.logger.debug("SyncLimit from %s", peer_addr)
                    self._obs_sync(start, "ok", peer_addr)
                    self.set_state(NodeState.CATCHING_UP)
                    return_event.set()
                    return
                self._push(peer_addr, other_known)
            except Exception as e:
                self._obs_sync(start, "error", peer_addr, err=e)
                if self._gossip_fail(peer_addr, e):
                    return_event.set()
                return
        self._obs_sync(start, "ok", peer_addr)
        self._gossip_ok(peer_addr)

    def _obs_sync(self, start: float, result: str, peer_addr: str,
                  err: Optional[Exception] = None) -> None:
        """Record one outbound exchange into the sync histogram and the
        span ring (shared by the threaded path and the simulator's
        event-driven exchanges in sim/cluster.py). `err` carries the
        failure for the observatory's silence-vs-refusal classifier;
        the exchange START time backdates silence evidence so a long
        transport timeout does not also delay partition detection."""
        now = self.clock.monotonic()
        self._m_sync.labels(result=result).observe(now - start)
        self.obs.tracer.record(
            "gossip", start, now - start,
            {"peer": peer_addr, "result": result},
        )
        self.watchdog.note_sync(peer_addr, result == "ok")
        self.obs.clusterview.note_contact(
            peer_addr, result == "ok", t_start=start, err=err,
        )

    def _gossip_fail(self, peer_addr: str, e: Exception) -> bool:
        """Bookkeeping for a failed exchange. Returns True when the failure
        flipped the node to CatchingUp (the caller's babble loop must
        return). Shared by the threaded gossip path and the deterministic
        simulator (babble_tpu/sim/), which drives exchanges as scheduled
        events but must preserve these exact escape semantics."""
        self.sync_errors += 1
        level = (
            self.logger.debug if _is_benign_race(e) else self.logger.error
        )
        level("gossip(%s): %s", peer_addr, e)
        # EVICTION LIVELOCK ESCAPE (round 5): a node whose undetermined
        # backlog outgrew the store's LRU has evicted event BODIES its
        # peers' diffs still reference as parents — but known_events()
        # (the rolling high-water mark) still claims those events, so
        # peers never resend them and over_sync_limit never trips.
        # Every sync then fails with the same KEY_NOT_FOUND forever
        # (observed: a survivor wedged at block 274 while peers ran to
        # 570). A store that can no longer support incremental sync
        # has exactly one recovery: fast-forward, which rebuilds it
        # compactly from an anchor. Three consecutive missing-parent
        # failures distinguish the livelock from a transient race.
        if _is_missing_parent(e):
            self._missing_parent_syncs += 1
            if self._missing_parent_syncs >= self._missing_parent_threshold:
                self.logger.warning(
                    "sync livelocked on missing events (%s); "
                    "flipping to CatchingUp to rebuild the store", e,
                )
                self._missing_parent_syncs = 0
                # escape attempts back off: when fast-forward cannot
                # help yet (e.g. no anchor above our height), constant
                # flipping would itself stall the cluster — the pinned
                # store makes this path rare, the backoff makes it calm
                self._missing_parent_threshold = min(
                    self._missing_parent_threshold * 2, 96
                )
                # our own store is the broken party: license the
                # own-chain rewind (see fast_forward) — without it the
                # node deadlocks between the unservable store and the
                # rewind guard
                self._rewind_ok = True
                self.set_state(NodeState.CATCHING_UP)
                return True
        return False

    def _gossip_ok(self, peer_addr: str) -> None:
        """Bookkeeping for a completed exchange (also called by the
        simulator's event-driven exchange)."""
        self._missing_parent_syncs = 0
        self._missing_parent_threshold = 3
        self._rewind_ok = False  # a full exchange worked: store is servable
        # a completed exchange ends any bounce streak: only an UNBROKEN
        # run of guard refusals may license the evidence-gated rewind
        self._consecutive_bounces = 0
        with self.selector_lock:
            self.peer_selector.update_last(peer_addr)
        self.log_stats()
        self.set_starting(False)

    def _pull(self, peer_addr: str) -> Tuple[bool, Dict[int, int]]:
        with self._locked:
            known = self.core.known_events()
        # time on the wire and in the peer (its lock, diff and encoding)
        with self.obs.span("node.pull"):
            resp = self.trans.sync(
                peer_addr, SyncRequest(from_id=self.id, known=known))
        if resp.sync_limit:
            return True, {}
        self._m_payload.labels(direction="pulled").observe(
            len(resp.events or [])
        )
        # adopt piggybacked trace contexts BEFORE inserting the payload,
        # so the consensus hooks find them when the events land
        if resp.traces:
            self.obs.traces.absorb(resp.traces)
        if resp.cluster:
            self.obs.clusterview.absorb(resp.cluster)
        if resp.events:
            with self._locked:
                self.sync(resp.events)
        return False, resp.known

    def _push(self, peer_addr: str, known_events: Dict[int, int]) -> None:
        with self._locked:
            self.core.add_self_event("")
        with self._locked:
            if self.core.over_sync_limit(known_events, self.conf.sync_limit):
                self.logger.debug("SyncLimit")
                return
            diff = self.core.event_diff(known_events)
            exported = self.core.seq
        wire_events = self.core.to_wire(diff)
        # note the export BEFORE the send: a push whose response is lost
        # may still have been delivered and inserted, so the bound must
        # cover the attempt, not just confirmed successes (code review
        # r5) — over-counting only refuses rewinds, never licenses one
        self._note_export(exported)
        self._m_payload.labels(direction="pushed").observe(len(wire_events))
        # time on the wire and in the peer (its lock, decode, insert and
        # consensus call)
        with self.obs.span("node.push", events=len(wire_events)):
            self.trans.eager_sync(
                peer_addr,
                EagerSyncRequest(
                    from_id=self.id, events=wire_events,
                    traces=self.obs.traces.contexts_for(diff),
                    cluster=self.obs.clusterview.wire_digests(),
                ),
            )

    def fast_forward(self) -> None:
        """Catch-up via a peer's anchor block + frame + app snapshot
        (reference: src/node/node.go:494-541)."""
        self.logger.debug("IN CATCHING-UP STATE")
        self.wait_routines()

        with self.selector_lock:
            peer = self.peer_selector.next()
        try:
            resp = self.trans.fast_forward(
                peer.net_addr, FastForwardRequest(from_id=self.id)
            )
            # Rewind guards (deliberately beyond the reference,
            # node.go:494-541, which assumes every flip to CatchingUp is
            # genuine). Applying a reset that rewinds OUR OWN chain below
            # events peers have already seen makes our next events re-use
            # indexes — peers then resolve wire parents to the old events
            # and reject our whole diff with invalid-signature/fork
            # errors, permanently. A node that flipped on a transient
            # sync burst is exactly the node with fresh broadcast events,
            # so it bounces back to Babbling here; a node genuinely
            # behind in EVENTS (even at an equal block index) has a stale
            # own chain and applies safely, gaining the section's events.
            if resp.block.index() < self.core.get_last_block_index():
                self._count_bounce(
                    "fast_forward: anchor %d behind our block %d — resuming"
                    % (resp.block.index(), self.core.get_last_block_index())
                )
                self.set_state(NodeState.BABBLING)
                self.set_starting(True)
                return
            my_frame_idx = self._own_index_in(resp.frame, resp.section)
            if self.core.seq > my_frame_idx:
                # The rewind guard exists to protect a chain tail the
                # network has seen: rewinding it re-uses event indexes and
                # peers permanently reject the chain as a fork. But a node
                # that flipped here because its OWN store lost bodies
                # (_rewind_ok — it cannot even build diffs to push) may
                # hold a tail that never reached anyone; refusing to
                # rewind then deadlocks it between the two protections
                # (observed: 999 consecutive bounces on one frozen frame).
                # The license therefore requires EVIDENCE, not just the
                # flag: every own event that ever LEFT this node (pushed
                # diff, served sync, served fast-forward section —
                # tracked as _last_exported_seq) must sit at or below the
                # frame. Peers can only hold, and relays can only spread,
                # what an export put on the wire, so a tail above the
                # exported bound provably never reached anyone. This is
                # local evidence: no dependency on sampling every peer's
                # responses (unsound) or hearing from every peer (blocks
                # recovery when one is unreachable).
                with self._export_lock:
                    exported_bound = self._last_exported_seq
                # The flag is not the only admissible license: the
                # SyncLimit flip (see _gossip) does not set _rewind_ok —
                # the store is servable, the node is merely too far
                # behind to sync incrementally. If such a node holds one
                # unexported own event above the frame, it wedges: every
                # pull answers sync-limit, every fast-forward bounces
                # here, forever (observed: 1268 consecutive bounces at a
                # frozen block). Persistent bouncing with the evidence
                # check passing IS the distinguishing signal — a node
                # that flipped transiently either bounces on the anchor
                # guard above or has exported its tail (pushing diffs is
                # exporting), so its bound sits above the frame.
                licensed = (
                    self._rewind_ok
                    or self._consecutive_bounces >= self._bounce_rewind_after
                )
                if licensed and exported_bound <= my_frame_idx:
                    self.logger.warning(
                        "fast_forward: accepting own-chain rewind (seq %d"
                        " > frame %d; license: %s) — nothing above own "
                        "index %d was ever exported; discarding the tail"
                        " is the only recovery",
                        self.core.seq, my_frame_idx,
                        "unservable store" if self._rewind_ok
                        else "%d consecutive bounces"
                        % self._consecutive_bounces,
                        exported_bound,
                    )
                else:
                    self._count_bounce(
                        "fast_forward: reset would rewind own chain "
                        "(seq %d > frame %d) — not actually behind, resuming"
                        % (self.core.seq, my_frame_idx)
                    )
                    self.set_state(NodeState.BABBLING)
                    self.set_starting(True)
                    return
            self._consecutive_bounces = 0
            # validate first (no state mutated), THEN restore the app, THEN
            # apply: the restore must precede the apply because the section
            # replays blocks above the anchor through the commit channel
            # onto the restored snapshot state — but it must follow
            # validation so a bad donor can't leave the app on a foreign
            # snapshot with the hashgraph unchanged
            with self._locked:
                validated = self.core.prepare_fast_forward(
                    resp.block, resp.frame, resp.section
                )
            # the anchor block's state hash is covered by its >1/3 validator
            # signatures (check_block in prepare) — the restored snapshot
            # must reproduce it, or the donor sent a forged snapshot. The
            # hash can only be computed by the app itself, so the check
            # necessarily runs after the restore; on mismatch we roll the
            # app back to its pre-restore state (best effort — a fresh
            # joiner has nothing to roll back to).
            rollback = None
            last_block = self.core.get_last_block_index()
            if last_block >= 0:
                try:
                    rollback = self.proxy.get_snapshot(last_block)
                except Exception:  # noqa: BLE001 — app may not have one
                    rollback = None
            restored_hash = self.proxy.restore(resp.snapshot)
            if restored_hash != validated[0].state_hash():
                if rollback is not None:
                    self.proxy.restore(rollback)
                raise ValueError(
                    "snapshot state hash does not match the signed anchor block"
                )
            with self._locked:
                self.core.apply_fast_forward(*validated)
            # serve-availability (code review r5): if the app can serve the
            # snapshot at the anchor we just restored, raise the serving
            # floor so this node can act as a donor before its first
            # post-join commit. Probed rather than assumed: the reference
            # dummy's restore does NOT record a snapshot (dummy/state.go),
            # so a blind floor bump would re-open the get_snapshot race.
            anchor_index = validated[0].index()
            if anchor_index > self._app_committed_index:
                try:
                    self.proxy.get_snapshot(anchor_index)
                except Exception:  # noqa: BLE001 — app keeps no snapshot here
                    pass
                else:
                    self._app_committed_index = anchor_index
        except Exception as e:
            self.logger.error("fast_forward: %s", e)
            self.clock.sleep(self.conf.heartbeat_timeout)
            return

        self._rewind_ok = False  # the reset rebuilt the store
        self.logger.info(
            "Fast-Forward OK: anchor block %d (round_received %d, frame round"
            " %d, %d frame events, section %s)",
            validated[0].index(),
            validated[0].round_received(),
            validated[1].round,
            len(validated[1].events),
            "%d events" % len(validated[2].events) if validated[2] else "none",
        )
        self.set_state(NodeState.BABBLING)
        self.set_starting(True)

    # ------------------------------------------------------------------
    # sync / commit / transactions
    # ------------------------------------------------------------------

    def _own_index_in(self, frame, section) -> int:
        """Highest index of OUR OWN events present in incoming fast-forward
        materials (frame root, frame events, section events/frames) — the
        index our chain would continue from after applying the reset. If
        our current seq exceeds it, applying would rewind our broadcast
        chain (see the guard in fast_forward)."""
        me = self.core.hex_id()
        idx = -1
        for i, p in enumerate(self.core.participants.to_peer_slice()):
            if p.pub_key_hex == me:
                idx = frame.roots[i].self_parent.index
                break
        pools = [frame.events]
        if section is not None:
            pools.append(section.events)
            pools.extend(f.events for f in section.frames)
        for pool in pools:
            for ev in pool:
                if ev.creator() == me and ev.index() > idx:
                    idx = ev.index()
        return idx

    def sync(self, events) -> None:
        """Insert events then run the 5-pass pipeline. Caller must hold
        core_lock (reference: src/node/node.go:583-603)."""
        self.core.sync(events)
        self.core.run_consensus()

    def commit(self, block: Block) -> None:
        # the application's time: the hand-over of the block and its answer
        with self.obs.span("commit.deliver", block=block.index()):
            state_hash = self.proxy.commit_block(block)
        if block.index() > self._app_committed_index:
            self._app_committed_index = block.index()
        block.body.state_hash = state_hash
        with self._locked:
            sig = self.core.sign_block(block)
            self.core.add_block_signature(sig)
        self._observe_commit(block)

    def _observe_commit(self, block: Block) -> None:
        """Feed the headline commit-latency histogram: one observation per
        committed transaction this node itself submitted (submit time is
        only known locally; relayed txs are measured by their origin)."""
        now = self.clock.monotonic()
        self._m_blocks.inc()
        latencies = []
        last_traced: Optional[bytes] = None
        with self._tx_times_lock:
            for tx in block.transactions():
                t0 = self._tx_times.pop(bytes(tx), None)
                if t0 is not None:
                    latencies.append(now - t0)
                    last_traced = bytes(tx)
        # exemplar: the last committed traced tx's trace_id rides on the
        # latency histogram (and its /metrics comment line), so a p99
        # breach links straight to a concrete trace in /debug/trace
        exemplar = trace_id_for(last_traced) if last_traced else None
        for dt in latencies:
            self._m_commit_latency.observe(dt, exemplar=exemplar)
        self.obs.tracer.record(
            "commit", now, 0.0,
            {"block": block.index(), "txs": len(block.transactions())},
        )
        # complete (and release) the causal traces this block carried
        self.obs.traces.mark_commit(block.transactions())

    def _add_transaction(self, tx: bytes) -> None:
        self._add_transactions([bytes(tx)])

    def _add_transactions(self, txs) -> None:
        """Insert an ingress batch into the pool: one timestamp pass, one
        trace pass, ONE core_lock acquisition for the whole batch — the
        amortization the ingress pipeline exists to buy."""
        # an IngressBatch says when each client was answered
        admitted_at = getattr(txs, "admitted_at", None)
        txs = [bytes(tx) for tx in txs]
        now = self.clock.monotonic()
        with self._tx_times_lock:
            for tx in txs:
                if len(self._tx_times) >= self._tx_times_cap:
                    break
                # setdefault: re-submitting identical bytes keeps the
                # FIRST submit time (latency must not shrink on retries)
                self._tx_times.setdefault(tx, now)
        # open the causal traces if the proxy hasn't already (bind_obs):
        # idempotent, keeps the earliest submit mark
        for tx in txs:
            self.obs.traces.begin(tx)
        with self._locked:
            self.core.add_transactions(txs, admitted_at)

    def shutdown(self) -> None:
        if self.get_state() == NodeState.SHUTDOWN:
            return
        self.logger.debug("Shutdown")
        self.set_state(NodeState.SHUTDOWN)
        self.shutdown_event.set()
        self.wait_routines()
        self.control_timer.shutdown()
        self.trans.close()
        self.core.hg.store.close()
        if self._run_thread is not None and self._run_thread is not threading.current_thread():
            self._run_thread.join(timeout=5.0)

    # ------------------------------------------------------------------
    # stats
    # ------------------------------------------------------------------

    def _count_bounce(self, msg: str) -> None:
        """Track a fast-forward rewind-guard bounce; escalate the log level
        once bounces repeat without an intervening successful fast-forward
        (a stuck catch-up loop is self-resolving but must be visible above
        debug level, ADVICE r3)."""
        self.fast_forward_bounces += 1
        self._consecutive_bounces += 1
        log = (
            self.logger.info
            if self._consecutive_bounces >= 3
            else self.logger.debug
        )
        log("%s (consecutive bounces: %d)", msg, self._consecutive_bounces)

    def _frontier_round(self) -> float:
        """Committed consensus round frontier; -1 before any commit (the
        gauge callback form of get_last_consensus_round_index)."""
        r = self.core.get_last_consensus_round_index()
        return float(r) if r is not None else -1.0

    def _frontier_gauge(self, name: str) -> float:
        """Read one frontier gauge back through the registry — /stats and
        the HealthDigest deliberately consume the same series /metrics
        exports instead of re-deriving it (ISSUE 20 satellite)."""
        g = self.obs.registry.get(name)
        return float(g.value()) if g is not None else -1.0

    def _health_digest(self) -> Dict[str, object]:
        """HealthDigest body (ISSUE 20): consensus fields from the core,
        frontier indices read through the frontier gauges, plus the
        node-owned ingress backlog. The observatory adds identity,
        timestamp and the peer-staleness vector."""
        d = self.core.health_digest_body()
        block = int(self._frontier_gauge("babble_commit_frontier_block"))
        if block != d["block"]:
            # the frontier advanced between the core snapshot and the
            # gauge read — recompute the prefix so bh always hashes the
            # block the digest claims (else the agreement canary would
            # see a phantom fork under concurrent commits)
            d["bh"] = self.core.get_block_hash_prefix(block)
        d["block"] = block
        d["round"] = int(self._frontier_gauge("babble_commit_frontier_round"))
        d["ingress"] = int(self.ingress.pending())
        return d

    def get_stats(self) -> Dict[str, str]:
        elapsed = self.clock.monotonic() - self.start_time
        consensus_events = self.core.get_consensus_events_count()
        events_per_second = consensus_events / elapsed if elapsed > 0 else 0.0
        last_consensus_round = self.core.get_last_consensus_round_index()
        rounds_per_second = (
            last_consensus_round / elapsed
            if last_consensus_round is not None and elapsed > 0
            else 0.0
        )
        return {
            "last_consensus_round": (
                "nil" if last_consensus_round is None else str(last_consensus_round)
            ),
            "last_block_index": str(self.core.get_last_block_index()),
            "consensus_events": str(consensus_events),
            "consensus_transactions": str(self.core.get_consensus_transactions_count()),
            "undetermined_events": str(len(self.core.get_undetermined_events())),
            "transaction_pool": str(len(self.core.transaction_pool)),
            # unguarded-ok: peers() copies a list; stats tolerate staleness
            "num_peers": str(len(self.peer_selector.peers())),
            "sync_rate": f"{self.sync_rate():.2f}",
            "events_per_second": f"{events_per_second:.2f}",
            "rounds_per_second": f"{rounds_per_second:.2f}",
            "round_events": str(self.core.get_last_committed_round_events_count()),
            "id": str(self.id),
            "state": str(self.get_state()),
            # beyond reference parity: which consensus engine served this
            # node and how often the device path ran / fell back
            "consensus_backend": self.core.consensus_backend,
            # what the device backend actually runs on (absent on
            # cpu-backend nodes): device_platform / device_kind /
            # device_count as JAX reports them
            **{k: str(v) for k, v in self.core.device_fields().items()},
            "device_consensus_runs": str(self.core.device_consensus_runs),
            "device_consensus_fallbacks": str(self.core.device_consensus_fallbacks),
            # first attaches of the live / queued-mesh rung that failed on
            # anything but GridUnsupported (compile error, device memory)
            "device_attach_failures": str(self.core.device_attach_failures),
            # VERDICT r4 #3: the one-shot device path retries with backoff
            # after GridUnsupported; a heal is a successful device run that
            # cleared a standing _device_down
            "device_heals": str(self.core.device_heals),
            # live-engine health: demotions to the one-shot path and
            # successful re-attaches (an operator watching /stats can see
            # a degraded TPU node AND see it heal)
            "live_engine_demotions": str(self.core.live_demotions),
            "live_engine_reattaches": str(self.core.live_reattaches),
            # rewind-guard bounces out of CatchingUp (ADVICE r3): a stuck
            # catch-up ping-pong shows up here instead of hiding at debug
            "fast_forward_bounces": str(self.fast_forward_bounces),
            # ingress pipeline (ISSUE 16): txs held pre-pool (queued for a
            # token refill or coalescing in the open batch)
            "ingress_pending": str(self.ingress.pending()),
            # commit frontier (ISSUE 20): read through the frontier
            # gauges so /stats, the HealthDigest and the observatory
            # report one source of truth
            "commit_frontier_block": str(int(self._frontier_gauge(
                "babble_commit_frontier_block"
            ))),
            "commit_frontier_round": str(int(self._frontier_gauge(
                "babble_commit_frontier_round"
            ))),
            **self._live_engine_stats(),
            **self._mesh_stats(),
            **self._table_bytes_stats(),
            **self._ledger_stats(),
        }

    def _ledger_stats(self):
        """Device-time ledger (ISSUE 19): per-pass ms totals plus the
        compile/retrace counters, flattened into the flat-string /stats
        surface like the sibling adapters. Keys appear only once a
        device pass has actually been ledgered; the retrace count is the
        headline health figure (steady state must read 0)."""
        led = self.obs.devledger
        snap = led.snapshot()
        if not snap["cells"]:
            return {}
        out = {}
        per_pass: Dict[str, float] = {}
        for key, (_calls, secs) in snap["cells"].items():
            rung, pass_name, _layout, _comp = key.split("/")
            k = f"{rung}/{pass_name}"
            per_pass[k] = per_pass.get(k, 0.0) + secs
        for k in sorted(per_pass):
            out[f"ledger_ms_{k.replace('/', '_')}"] = f"{per_pass[k] * 1e3:.2f}"
        compiles = sum(e["compiles"] for e in snap["entries"].values())
        retraces = sum(e["retraces"] for e in snap["entries"].values())
        out["kernel_compiles"] = str(int(compiles))
        out["kernel_retraces"] = str(int(retraces))
        return out

    def _table_bytes_stats(self):
        """Voting-table footprint of the layout the device engine last ran
        (ISSUE 17): snapshot adapter over the babble_device_table_bytes
        gauge written by tpu.packed.observe_table_bytes at every engine
        rung. Keys appear only once a device pass has actually run; both
        layouts are reported if a node flipped mid-life (series persist),
        so an operator can read the wide->packed reduction off /stats."""
        gauge = self.obs.registry.get("babble_device_table_bytes")
        if gauge is None:
            return {}
        out = {"packed_voting": getattr(self.core, "packed_voting", "auto")}
        for layout in ("wide", "packed"):
            total = sum(
                gauge.value(table=t, layout=layout)
                for t in ("strongly_seen", "votes")
            )
            if total:
                out[f"device_table_bytes_{layout}"] = str(int(total))
        return out

    def _mesh_stats(self):
        """Mesh product path (--mesh-devices): per-call staging vs device
        wall time and the staged-event count — the one-shot restage cost
        the config #5 scaling model is built on (VERDICT r4 #8). Snapshot
        adapter over the registry: the underlying accounting moved to
        typed histograms (babble_device_stage/run_seconds{path=mesh}) but
        the /stats key/format surface is unchanged. Registry series
        persist across engine demote/reattach cycles, so the averages
        cover the node's whole life, not just the current engine."""
        calls, run_sum = self._m_run.stats(path="mesh")
        if not calls:
            return {}
        _, stage_sum = self._m_stage.stats(path="mesh")
        staged = self.obs.registry.get("babble_mesh_staged_events")
        return {
            "mesh_calls": str(calls),
            "mesh_stage_ms_avg": f"{stage_sum / calls * 1e3:.2f}",
            "mesh_device_ms_avg": f"{run_sum / calls * 1e3:.2f}",
            "mesh_staged_events": str(int(staged.value()) if staged else 0),
        }

    def _live_engine_stats(self):
        """Latency budget of the live device path: dispatch wall time
        (host-side batch building and program launches) vs fetch wall time
        (the blocking wait for the packed results, which on a colocated
        chip is the wait for the device compute itself). Snapshot
        adapter: durations now come from the registry histograms
        (babble_device_dispatch/fetch_seconds); structural counters
        (dispatches, rebases, pipelining) stay on the engine."""
        eng = getattr(self.core.hg, "_live_device_engine", None)
        if eng is None or eng.consensus_calls == 0:
            return {}
        fetch_calls, fetch_sum = self._m_fetch.stats()
        _, dispatch_sum = self._m_dispatch.stats()
        return {
            "device_dispatches": str(eng.dispatches),
            "device_dispatch_ms_avg": f"{dispatch_sum / max(eng.dispatches, 1) * 1e3:.2f}",
            # under the pipelined discipline this measures only the
            # BLOCKING wait (results normally land during gossip)
            "device_fetch_ms_avg": f"{fetch_sum / max(fetch_calls, 1) * 1e3:.2f}",
            "device_rebases": str(eng.rebases),
            "device_fetch_pipelined": str(eng.async_fetch).lower(),
        }

    def log_stats(self) -> None:
        """Rate-limited structured snapshot from the metrics registry
        (replaces the full get_stats() dict every heartbeat — at test
        heartbeats that was hundreds of dict renders a second)."""
        now = self.clock.monotonic()
        if now - self._last_stats_log < self.conf.stats_log_interval:
            return
        self._last_stats_log = now
        log = self.logger.info if self.conf.metrics_log else self.logger.debug
        log("metrics %s", json.dumps(
            self.obs.registry.snapshot_flat(), sort_keys=True
        ))

    def sync_rate(self) -> float:
        if self.sync_requests == 0:
            return 1.0
        return 1.0 - self.sync_errors / self.sync_requests

    def get_block(self, block_index: int) -> Block:
        return self.core.hg.store.get_block(block_index)
