"""SimCluster: N real nodes on virtual time, choreographed as events.

The simulator runs the production `Node`/`Core`/`Hashgraph` stack — same
locks, same RPC handlers, same state machine — but never starts a single
thread. Instead of `run_async` (control timer + worker threads + gossip
threads), the cluster schedules one *tick* event per node and performs
the work those threads would do, in a deterministic order:

- inbound RPCs are handed straight to `Node._process_rpc` (which always
  responds synchronously) by the network's delivery events;
- the gossip exchange is a split-step state machine (capture known →
  pull RPC → insert+push build → eager RPC), with virtual latency
  between the steps — so the stale-head/overlapping-diff interleavings
  that threads produce by accident are produced here on purpose, and
  reproduce from the seed;
- failure/success bookkeeping reuses `Node._gossip_fail`/`_gossip_ok`,
  so the eviction-livelock escape, missing-parent counting and rewind
  licensing behave byte-for-byte like the threaded path;
- `Node.fast_forward()` runs inline through `SimTransport`'s synchronous
  call path; its `clock.sleep` lands in the SimClock's pending-sleep
  accumulator and is charged to the node's next tick;
- the commit channel (normally drained by a worker thread) is drained
  after every step that can produce blocks.

Every source of nondeterminism is a stream derived from ONE master seed:
node identities (`crypto.derive_key`), per-node protocol RNGs (peer
selection), network faults, and transaction injection. Same seed + same
plan => identical event sequence => identical committed blocks.

Crash/restart: a crash bumps the node's generation counter (orphaning
every scheduled callback that captured the old generation) and marks it
dead on the network. A restart re-creates the Node — a sqlite store is
reopened and bootstrap-replayed (the app state is rebuilt by re-committing
the replayed blocks), an inmem store comes back empty and the node
rejoins via fast-forward.
"""

from __future__ import annotations

import json
import logging
import os
import queue
import random
from hashlib import sha256
from typing import Any, Dict, List, Optional, Tuple

from ..obs import assemble_cluster_trace

from ..crypto import derive_key, pub_key_bytes
from ..hashgraph import InmemStore
from ..hashgraph.sqlite_store import SQLiteStore
from ..net import SyncRequest, EagerSyncRequest
from ..net.transport import TransportError
from ..node import Config, Node
from ..node.state import NodeState
from ..peers import Peer, Peers
from ..proxy import InmemDummyClient
from .checker import DivergenceChecker, DivergenceError
from .clock import SimClock
from .faults import FaultPlan
from .scheduler import SimScheduler
from .transport import SimNetwork, SimTransport

TRACE_CAP = 20_000


class SimNode:
    """Cluster-side handle for one simulated validator."""

    def __init__(self, index: int, addr: str, key, rng: random.Random):
        self.index = index
        self.addr = addr
        self.key = key
        self.rng = rng
        self.node: Optional[Node] = None
        self.proxy: Optional[InmemDummyClient] = None
        self.store_path: Optional[str] = None
        self.crashed = False
        # bumped on every crash AND restart: scheduled callbacks capture
        # the generation they were created under and no-op if it moved —
        # the simulator's version of "that thread died with the process"
        self.gen = 0
        self.exchange_inflight = False
        # stats
        self.restarts = 0
        self.catchup_flips = 0
        self.ff_attempts = 0

    @property
    def name(self) -> str:
        return f"node{self.index}"


class SimCluster:
    def __init__(
        self,
        n: int = 4,
        seed: int = 0,
        plan: Optional[FaultPlan] = None,
        store: str = "inmem",
        backend: Any = "cpu",
        mesh_devices: int = 0,
        dispatch_queue_depth: int = 4,
        dispatch_batch_deadline: float = 0.0,
        dispatch_batch_rows: int = 64,
        mesh_validator_shards: int = 1,
        ingress_batch_bytes: int = 65536,
        ingress_batch_deadline: float = 0.0,
        ingress_queue_cap: int = 8192,
        ingress_client_rate: float = 0.0,
        ingress_dedup_window: int = 65536,
        heartbeat: float = 0.05,
        tcp_timeout: float = 1.0,
        sync_limit: int = 300,
        cache_size: int = 2000,
        store_dir: Optional[str] = None,
        artifact_dir: str = "docs/artifacts",
        inject_interval: float = 0.05,
        logger: Optional[logging.Logger] = None,
        tracing: bool = True,
        stall_deadline: float = 10.0,
        cluster_health: bool = True,
        # staleness deadline scaled to sim time: heartbeats run at 50ms,
        # so 1.5 virtual seconds of silence is ~30 missed exchanges
        cluster_staleness: float = 1.5,
    ):
        if store not in ("inmem", "sqlite"):
            raise ValueError("store must be 'inmem' or 'sqlite'")
        if store == "sqlite" and not store_dir:
            raise ValueError("sqlite store needs store_dir")
        self.n = n
        self.seed = seed
        self.plan = plan or FaultPlan()
        self.store_kind = store
        # backend may be one name for the whole cluster or a per-node
        # sequence — a MIXED cluster (cpu nodes gossiping with mesh
        # nodes) is the strictest differential we have: the divergence
        # checker byte-compares their blocks continuously
        if isinstance(backend, str):
            self.backends = [backend] * n
        else:
            self.backends = list(backend)
            if len(self.backends) != n:
                raise ValueError(f"need {n} backends, got {len(self.backends)}")
        self.backend = backend
        self.mesh_devices = mesh_devices
        self.dispatch_queue_depth = dispatch_queue_depth
        self.dispatch_batch_deadline = dispatch_batch_deadline
        self.dispatch_batch_rows = dispatch_batch_rows
        self.mesh_validator_shards = mesh_validator_shards
        self.ingress_batch_bytes = ingress_batch_bytes
        self.ingress_batch_deadline = ingress_batch_deadline
        self.ingress_queue_cap = ingress_queue_cap
        self.ingress_client_rate = ingress_client_rate
        self.ingress_dedup_window = ingress_dedup_window
        self.heartbeat = heartbeat
        self.tcp_timeout = tcp_timeout
        self.sync_limit = sync_limit
        self.cache_size = cache_size
        self.store_dir = store_dir
        self.logger = logger or logging.getLogger("babble.sim")
        self.inject_interval = inject_interval
        self.tracing = tracing
        self.stall_deadline = stall_deadline
        self.cluster_health = cluster_health
        self.cluster_staleness = cluster_staleness

        self.clock = SimClock()
        self.sched = SimScheduler(self.clock)
        # purpose-split RNG streams off the master seed: string seeding is
        # hashed (not `hash()`-randomized), so streams are stable across
        # processes and mutually independent — consuming from one never
        # shifts another, which keeps fault sequences stable when e.g. the
        # tx workload changes
        self.net_rng = random.Random(f"{seed}|net")
        self.tx_rng = random.Random(f"{seed}|tx")
        self.net = SimNetwork(self.sched, self.plan, self.net_rng, tcp_timeout)
        self.checker = DivergenceChecker(artifact_dir)
        self.trace: List[str] = []
        self.tx_counter = 0
        self.target_block: Optional[int] = None
        self._injecting = False

        # -- boot: identities, peers, nodes -----------------------------
        self.sns: List[SimNode] = []
        keys = []
        for i in range(n):
            secret = int.from_bytes(
                sha256(f"{seed}|key|{i}".encode()).digest(), "big"
            )
            keys.append(derive_key(secret))
        self.participants = Peers()
        peer_of = []
        for i, key in enumerate(keys):
            pub_hex = "0x" + pub_key_bytes(key).hex().upper()
            peer = Peer(net_addr=f"sim-{i}", pub_key_hex=pub_hex)
            self.participants.add_peer(peer)
            peer_of.append(peer)
        for i, key in enumerate(keys):
            sn = SimNode(i, peer_of[i].net_addr, key, random.Random(f"{seed}|node|{i}"))
            if store == "sqlite":
                sn.store_path = f"{store_dir}/node{i}.db"
            self.sns.append(sn)
            self.net.register(i, sn.addr, self._make_handler(sn))
        for sn, peer in zip(self.sns, peer_of):
            self._boot_node(sn, peer.id, existing_db=False)

    # ------------------------------------------------------------------
    # node lifecycle
    # ------------------------------------------------------------------

    def _boot_node(self, sn: SimNode, node_id: int, existing_db: bool) -> None:
        conf = Config(
            heartbeat_timeout=self.heartbeat,
            tcp_timeout=self.tcp_timeout,
            cache_size=self.cache_size,
            sync_limit=self.sync_limit,
            consensus_backend=self.backends[sn.index],
            mesh_devices=self.mesh_devices,
            dispatch_queue_depth=self.dispatch_queue_depth,
            dispatch_batch_deadline=self.dispatch_batch_deadline,
            dispatch_batch_rows=self.dispatch_batch_rows,
            mesh_validator_shards=self.mesh_validator_shards,
            ingress_batch_bytes=self.ingress_batch_bytes,
            ingress_batch_deadline=self.ingress_batch_deadline,
            ingress_queue_cap=self.ingress_queue_cap,
            ingress_client_rate=self.ingress_client_rate,
            ingress_dedup_window=self.ingress_dedup_window,
            clock=self.clock,
            rng=sn.rng,
            logger=self.logger,
            tracing=self.tracing,
            stall_deadline=self.stall_deadline,
            cluster_health=self.cluster_health,
            cluster_staleness_deadline=self.cluster_staleness,
        )
        if self.store_kind == "sqlite":
            node_store = SQLiteStore(
                self.participants, self.cache_size, sn.store_path,
                existing_db=existing_db,
            )
        else:
            node_store = InmemStore(self.participants, self.cache_size)
        trans = SimTransport(self.net, sn.addr)
        proxy = InmemDummyClient(self.logger)
        node = Node(
            conf, node_id, sn.key, self.participants, node_store, trans, proxy
        )
        node.init()
        sn.node = node
        sn.proxy = proxy
        sn.exchange_inflight = False
        # bootstrap replay (sqlite restart) re-emits every committed block
        # through the commit channel: drain it now so the app state is
        # rebuilt before the node talks to anyone
        self._drain(sn)

    def _make_handler(self, sn: SimNode):
        def handler(rpc) -> None:
            if sn.crashed or sn.node is None:
                rpc.respond(None, error=f"node down: {sn.addr}")
                return
            sn.node._process_rpc(rpc)
            # handling a sync can run consensus and produce blocks
            self._drain(sn)

        return handler

    def _drain(self, sn: SimNode) -> None:
        """The work of the node's tx/block worker threads: feed submitted
        transactions into the core, apply committed blocks to the app."""
        node = sn.node
        while True:
            try:
                item = node.submit_ch.get_nowait()
            except queue.Empty:
                break
            # the ingress pipeline emits batches (lists); pre-pipeline
            # producers put single tx bytes — same contract as the
            # threaded _serve_source
            if isinstance(item, list):
                node._add_transactions(item)
            else:
                node._add_transaction(item)
        while True:
            try:
                block = node.commit_ch.get_nowait()
            except queue.Empty:
                break
            try:
                node.commit(block)
            except Exception as e:  # noqa: BLE001 — like _serve_source:
                self.logger.error("sim commit: %s", e)  # logged, not fatal

    # ------------------------------------------------------------------
    # tick: the control-timer + babble-loop work for one node
    # ------------------------------------------------------------------

    def _schedule_tick(self, sn: SimNode, extra_delay: float = 0.0) -> None:
        gen = sn.gen
        # the randomized control timer fires in [base, 2*base) — same
        # distribution new_random_control_timer draws from this node's rng
        delay = sn.rng.uniform(self.heartbeat, 2 * self.heartbeat) + extra_delay
        self.sched.after(delay, lambda: self._tick(sn, gen), label=f"{sn.name}:tick")

    def _tick(self, sn: SimNode, gen: int) -> None:
        if sn.gen != gen or sn.crashed:
            return
        node = sn.node
        self._drain(sn)
        # the threaded _babble loop runs the watchdog (and the SLO
        # engine) on every heartbeat tick; mirror that here so stall
        # detection and burn-rate evaluation are part of the
        # deterministic replay (gauge values ride virtual time)
        node.watchdog.check()
        # partition-suspicion edge detector + lag matrix, exactly like
        # the threaded _babble tick (cluster records ride virtual time)
        node.obs.clusterview.check()
        if node.slo is not None:
            node.slo.evaluate()
        # deadline pump for the ingress pipeline, exactly like the
        # threaded _babble tick: a held partial batch releases on the
        # heartbeat once its deadline elapses on virtual time
        node.ingress.tick()
        self._drain(sn)
        state = node.get_state()
        extra = 0.0
        if state == NodeState.CATCHING_UP:
            sn.ff_attempts += 1
            self._trace(f"{sn.name} fast_forward attempt")
            node.fast_forward()  # inline: SimTransport call path, zero
            # virtual duration; a failure's heartbeat sleep lands in the
            # clock's pending accumulator and is charged below
            self._drain(sn)
            extra = self.clock.take_pending_sleep()
            self._trace(
                f"{sn.name} fast_forward -> {node.get_state()}"
            )
        elif state == NodeState.BABBLING:
            if not sn.exchange_inflight and node._pre_gossip():
                peer = node.peer_selector.next()
                self._start_exchange(sn, peer.net_addr)
        self._schedule_tick(sn, extra)

    # ------------------------------------------------------------------
    # split-step gossip exchange (the threaded _gossip as events)
    # ------------------------------------------------------------------

    def _start_exchange(self, sn: SimNode, peer_addr: str) -> None:
        node = sn.node
        gen = sn.gen
        sn.exchange_inflight = True
        node.sync_requests += 1
        # same sync-duration/span instrumentation as the threaded
        # _gossip: observed against virtual time, so two same-seed runs
        # report byte-identical sync histograms
        ex_start = self.clock.monotonic()
        with node.core_lock:
            known = node.core.known_events()
        self._trace(f"{sn.name} pull -> {peer_addr}")

        def finish_fail(e: TransportError) -> None:
            if sn.gen != gen or sn.crashed:
                return
            sn.exchange_inflight = False
            node._obs_sync(ex_start, "error", peer_addr, err=e)
            if node._gossip_fail(peer_addr, e):
                sn.catchup_flips += 1
                self._trace(f"{sn.name} -> CatchingUp (livelock escape)")

        def on_pull_ok(resp) -> None:
            if sn.gen != gen or sn.crashed:
                return
            if resp.sync_limit:
                sn.exchange_inflight = False
                node._obs_sync(ex_start, "ok", peer_addr)
                sn.catchup_flips += 1
                self._trace(f"{sn.name} SyncLimit from {peer_addr} -> CatchingUp")
                node.set_state(NodeState.CATCHING_UP)
                return
            # insert the pulled diff, then build the push — both can fail
            # locally (stale heads, missing parents) exactly like the
            # threaded path's try block around _pull/_push
            try:
                # adopt piggybacked trace contexts before the insert,
                # exactly like the threaded _pull
                if resp.traces:
                    node.obs.traces.absorb(resp.traces)
                if resp.cluster:
                    node.obs.clusterview.absorb(resp.cluster)
                if resp.events:
                    with node.core_lock:
                        node.sync(resp.events)
                self._drain(sn)
                with node.core_lock:
                    node.core.add_self_event("")
                with node.core_lock:
                    if node.core.over_sync_limit(resp.known, node.conf.sync_limit):
                        sn.exchange_inflight = False
                        node._obs_sync(ex_start, "ok", peer_addr)
                        node._gossip_ok(peer_addr)
                        return
                    diff = node.core.event_diff(resp.known)
                    exported = node.core.seq
                wire_events = node.core.to_wire(diff)
            except Exception as e:  # noqa: BLE001 — mirrors _gossip's
                finish_fail(e)  # catch-all around the exchange
                return
            # export bound BEFORE the send, same as the threaded _push: a
            # push whose response is lost may still have been delivered
            node._note_export(exported)
            self.net.send(
                sn.addr, peer_addr,
                EagerSyncRequest(
                    from_id=node.id, events=wire_events,
                    traces=node.obs.traces.contexts_for(diff),
                    cluster=node.obs.clusterview.wire_digests(),
                ),
                on_ok=on_push_ok, on_fail=finish_fail,
                label=f"{sn.name}:push",
            )

        def on_push_ok(_resp) -> None:
            if sn.gen != gen or sn.crashed:
                return
            sn.exchange_inflight = False
            node._obs_sync(ex_start, "ok", peer_addr)
            node._gossip_ok(peer_addr)
            self._drain(sn)

        self.net.send(
            sn.addr, peer_addr,
            SyncRequest(from_id=node.id, known=known),
            on_ok=on_pull_ok, on_fail=finish_fail,
            label=f"{sn.name}:pull",
        )

    # ------------------------------------------------------------------
    # faults: crash / restart
    # ------------------------------------------------------------------

    def _crash(self, sn: SimNode) -> None:
        if sn.crashed:
            return
        self._trace(f"{sn.name} CRASH at t={self.clock.now:.3f}")
        # black box first: capture what the node was doing as it dies
        # (in-memory doc; export_flight_dumps writes it out on demand)
        try:
            sn.node.obs.flightrec.dump("crash", node=sn.name)
        except Exception:  # noqa: BLE001 — the crash proceeds regardless
            pass
        sn.crashed = True
        sn.gen += 1  # orphan every callback the dead process scheduled
        sn.exchange_inflight = False
        self.net.set_alive(sn.addr, False)
        # drop the store so a sqlite file can be reopened; NOT
        # node.shutdown(): that joins threads we never started and a real
        # crash doesn't run shutdown hooks anyway. Nor `store.close()`,
        # which flushes: what a sqlite store wrote since its last flush
        # dies with the process (the connection closes without a commit)
        store = sn.node.core.hg.store
        try:
            getattr(store, "db", store).close()
        except Exception:  # noqa: BLE001 — a dirty close IS the crash
            pass

    def _restart(self, sn: SimNode) -> None:
        if not sn.crashed:
            return
        self._trace(f"{sn.name} RESTART at t={self.clock.now:.3f}")
        sn.crashed = False
        sn.gen += 1
        sn.restarts += 1
        node_id = sn.node.id
        # sqlite survives the crash (existing_db => bootstrap replay);
        # inmem comes back empty and rejoins via fast-forward
        self._boot_node(sn, node_id, existing_db=self.store_kind == "sqlite")
        self.net.set_alive(sn.addr, True)
        self._schedule_tick(sn)

    # ------------------------------------------------------------------
    # workload
    # ------------------------------------------------------------------

    def _inject(self) -> None:
        if not self._injecting:
            return
        # closed-loop like the integration tests' bombard_and_wait: a
        # node with a backed-up pool gets no more traffic until consensus
        # drains it (open-loop injection just saturates core locks)
        for _ in range(3):
            i = self.tx_rng.randrange(self.n)
            sn = self.sns[i]
            if sn.crashed:
                continue
            if len(sn.node.core.transaction_pool) >= 50:
                continue
            sn.proxy.submit_tx(b"tx %d from %d" % (self.tx_counter, i))
            self.tx_counter += 1
        self.sched.after(self.inject_interval, self._inject, label="inject")

    # ------------------------------------------------------------------
    # run loop
    # ------------------------------------------------------------------

    def live_views(self) -> List[Tuple[str, Any]]:
        return [
            (sn.name, sn.node.core.hg.store)
            for sn in self.sns
            if not sn.crashed
        ]

    def _context(self) -> Dict[str, Any]:
        return {
            "seed": self.seed,
            "plan": self.plan.to_dict(),
            "n": self.n,
            "store": self.store_kind,
            "backend": self.backends,
            "virtual_time": self.clock.now,
            "events_run": self.sched.events_run,
            "trace": self.trace,
            # lazy: the checker only materializes the decision-provenance
            # streams on an actual mismatch (bisection input)
            "provenance_fn": self.provenance_streams,
        }

    def provenance_streams(self) -> Dict[str, Dict[str, Any]]:
        """Every live node's full decision-provenance stream document
        (bisection input; sweep failure export)."""
        return {
            sn.name: sn.node.obs.provenance.to_json()
            for sn in self.sns
            if not sn.crashed
        }

    def check_divergence(self) -> int:
        """Raises DivergenceError (artifact dumped) on any mismatch —
        and dumps every live node's flight recorder beside it, so the
        replay artifact comes with the "what was each node doing"
        record stream. When the checker's bisector localized the first
        divergent provenance cell, every live node gets the
        deterministic `divergence.localized` record before the dump."""
        try:
            return self.checker.check(self.live_views(), self._context())
        except DivergenceError as e:
            if e.localized is not None:
                from ..obs import DivergenceBisector

                fields = DivergenceBisector().flight_fields(e.localized)
                for sn in self.sns:
                    if not sn.crashed:
                        sn.node.obs.flightrec.record(
                            "divergence.localized", **fields,
                        )
            self.dump_flight_recorders("divergence")
            raise

    def dump_flight_recorders(self, reason: str) -> List[str]:
        """Trigger an in-memory flight-recorder dump on every live node
        (file export is separate — export_flight_dumps). Returns the
        node names that actually dumped (suppression may skip some)."""
        dumped = []
        for sn in self.sns:
            if sn.crashed:
                continue
            before = sn.node.obs.flightrec.dumps
            sn.node.obs.flightrec.dump(reason, node=sn.name)
            if sn.node.obs.flightrec.dumps > before:
                dumped.append(sn.name)
        return dumped

    def export_flight_dumps(self, directory: str) -> List[str]:
        """Write every node's accumulated in-memory dump docs as JSON
        artifacts (sweep triage: called on the failure path only, so
        healthy runs stay file-free). Deterministic filenames: node +
        dump ordinal + reason."""
        os.makedirs(directory, exist_ok=True)
        paths = []
        for sn in self.sns:
            node = sn.node
            if node is None:
                continue
            for doc in node.obs.flightrec.dump_docs:
                path = os.path.join(
                    directory,
                    f"flightrec-seed{self.seed}-{sn.name}-"
                    f"{doc['ordinal']:02d}-{doc['reason']}.json",
                )
                with open(path, "w", encoding="utf-8") as f:
                    json.dump(doc, f, indent=1, sort_keys=True)
                paths.append(path)
        return paths

    def _all_reached(self, target: int) -> bool:
        for sn in self.sns:
            if sn.crashed:
                continue
            node = sn.node
            if node.core.get_last_block_index() < target:
                return False
            try:
                if not node.get_block(target).state_hash():
                    return False
            except Exception:  # noqa: BLE001 — joined above the target:
                continue  # its replayed history starts past it
        return True

    def run(
        self,
        until: Optional[float] = None,
        target_block: Optional[int] = None,
        max_events: int = 2_000_000,
        inject: bool = True,
        check_every: float = 0.5,
    ) -> Dict[str, Any]:
        """Drive the cluster on virtual time until the deadline, the
        target block (settled on every live node), or the event budget —
        whichever comes first. Divergence raises immediately."""
        if until is None and target_block is None:
            raise ValueError("need until and/or target_block")
        self.target_block = target_block
        for sn in self.sns:
            self._schedule_tick(sn)
        for crash in self.plan.crashes:
            sn = self.sns[crash.node]
            self.sched.at(crash.at, lambda s=sn: self._crash(s), label="crash")
            if crash.restart_at is not None:
                self.sched.at(
                    crash.restart_at, lambda s=sn: self._restart(s),
                    label="restart",
                )
        if inject:
            self._injecting = True
            self.sched.after(0.0, self._inject, label="inject")

        deadline = float("inf") if until is None else until
        next_check = 0.0
        reached = False
        while self.sched.events_run < max_events:
            nt = self.sched.peek_time()
            if nt is None or nt > deadline:
                break
            self.sched.step()
            if self.clock.now >= next_check:
                self.check_divergence()
                next_check = self.clock.now + check_every
                if target_block is not None and self._all_reached(target_block):
                    reached = True
                    break
        self._injecting = False
        self.check_divergence()
        return self.result(reached)

    def result(self, reached_target: bool = False) -> Dict[str, Any]:
        return {
            "seed": self.seed,
            "plan": self.plan.name,
            "virtual_time": round(self.clock.now, 3),
            "events_run": self.sched.events_run,
            "reached_target": reached_target,
            "blocks_checked": self.checker.blocks_checked,
            "checked_upto": self.checker.checked_upto,
            "block_indices": {
                sn.name: (
                    -1 if sn.crashed else sn.node.core.get_last_block_index()
                )
                for sn in self.sns
            },
            "txs_injected": self.tx_counter,
            "restarts": sum(sn.restarts for sn in self.sns),
            "catchup_flips": sum(sn.catchup_flips for sn in self.sns),
            "ff_attempts": sum(sn.ff_attempts for sn in self.sns),
            "net": dict(self.net.stats),
            "commit_latency": self.latency_histograms(),
            "stage_latency": self.stage_histograms(),
            "mesh_dispatch": self.dispatch_histograms(),
            "ingress": self.ingress_counters(),
            "trace_fingerprint": self.trace_fingerprint(),
            "flightrec_fingerprint": self.flightrec_fingerprint(),
            "cluster_health": self.cluster_health_doc(),
            "cluster_health_fingerprint": self.cluster_health_fingerprint(),
            "provenance_fingerprint": self.provenance_fingerprint(),
            "ledger_fingerprint": self.ledger_fingerprint(),
            "flightrec_records": {
                sn.name: len(sn.node.obs.flightrec)
                for sn in self.sns
                if not sn.crashed
            },
            "digest": self.digest(),
        }

    def latency_histograms(self) -> Dict[str, Any]:
        """Per-live-node commit-latency histogram snapshots, measured on
        VIRTUAL time: deterministic — two runs of the same seed+plan
        produce byte-identical snapshots (the obs counterpart of
        digest())."""
        out: Dict[str, Any] = {}
        for sn in self.sns:
            if sn.crashed:
                continue
            snap = sn.node.obs.registry.snapshot()
            out[sn.name] = snap.get("babble_commit_latency_seconds")
        return out

    DISPATCH_HISTOGRAMS = (
        "babble_mesh_batch_rows",
        "babble_mesh_rounds_per_dispatch",
    )

    def dispatch_histograms(self) -> Dict[str, Any]:
        """Per-live-node snapshots of the round-batched dispatch
        histograms (delta rows staged per dispatch, consensus rounds
        newly covered per integration). Both are DAG facts counted on the
        deterministic serve path, so same-seed runs must produce
        byte-identical snapshots — the batching counterpart of
        commit_latency."""
        out: Dict[str, Any] = {}
        for sn in self.sns:
            if sn.crashed:
                continue
            snap = sn.node.obs.registry.snapshot()
            out[sn.name] = {k: snap.get(k) for k in self.DISPATCH_HISTOGRAMS}
        return out

    INGRESS_SERIES = (
        "babble_ingress_verdicts_total",
        "babble_ingress_shed_total",
        "babble_ingress_dedup_hits_total",
        "babble_ingress_batch_txs",
    )

    def ingress_counters(self) -> Dict[str, Any]:
        """Per-live-node snapshots of the ingress admission series
        (verdicts, sheds by reason, dedup hits, batch-size histogram).
        Admission decisions are pure functions of the seeded workload and
        virtual time, so same-seed runs must produce byte-identical
        snapshots — the ingress entry in the determinism contract."""
        out: Dict[str, Any] = {}
        for sn in self.sns:
            if sn.crashed:
                continue
            snap = sn.node.obs.registry.snapshot()
            out[sn.name] = {k: snap.get(k) for k in self.INGRESS_SERIES}
        return out

    STAGE_HISTOGRAMS = (
        "babble_trace_stage_submit_to_event_seconds",
        "babble_trace_stage_event_to_round_seconds",
        "babble_trace_stage_round_to_famous_seconds",
        "babble_trace_stage_famous_to_commit_seconds",
    )

    def stage_histograms(self) -> Dict[str, Any]:
        """Per-live-node snapshots of the causal-trace stage histograms
        (submit->event, event->round, round->famous, famous->commit).
        Measured on virtual time: part of the determinism contract, like
        commit_latency."""
        out: Dict[str, Any] = {}
        for sn in self.sns:
            if sn.crashed:
                continue
            snap = sn.node.obs.registry.snapshot()
            out[sn.name] = {k: snap.get(k) for k in self.STAGE_HISTOGRAMS}
        return out

    def cluster_trace(self, trace_id: Optional[str] = None) -> dict:
        """Assemble the cross-node Chrome-trace timeline from every live
        node's span ring — the sim-side twin of the HTTP
        `/debug/trace/cluster` federation, built from virtual time.
        Unresolvable parent spans (crashed nodes, ring wrap) are cleanly
        truncated by the assembler: no orphan parent span ids."""
        docs = [
            (sn.node.id,
             sn.node.obs.tracer.to_chrome_trace(pid=sn.node.id,
                                                trace_id=trace_id))
            for sn in self.sns
            if not sn.crashed
        ]
        return assemble_cluster_trace(docs)

    def trace_fingerprint(self) -> str:
        """SHA-256 over the canonical JSON of every causal-trace span in
        the assembled cluster trace — two runs of the same seed+plan must
        produce byte-identical fingerprints (the tracing counterpart of
        digest())."""
        doc = self.cluster_trace()
        events = [
            ev for ev in doc["traceEvents"]
            if isinstance(ev.get("args"), dict) and ev["args"].get("trace")
        ]
        return sha256(
            json.dumps(events, sort_keys=True).encode()
        ).hexdigest()

    def flightrec_fingerprint(self) -> str:
        """SHA-256 over every live node's canonical flight-record stream
        bytes, in node order — the recorder's entry in the determinism
        fingerprint: two runs of the same seed+plan must produce
        byte-identical record streams (docs/sim.md)."""
        h = sha256()
        for sn in self.sns:
            if sn.crashed:
                continue
            h.update(sn.name.encode())
            h.update(sn.node.obs.flightrec.stream_bytes())
        return h.hexdigest()

    def cluster_health_doc(self) -> Dict[str, Any]:
        """Per-live-node derived cluster series + partition suspicion
        (the deterministic slice of each observatory's health plane),
        plus a cluster summary row for sweep tables: max commit skew,
        min frontier agreement, partitions suspected anywhere, and the
        union of suspected components. All floats pre-rounded — part of
        the determinism contract (docs/sim.md)."""
        nodes: Dict[str, Any] = {}
        max_skew = 0.0
        min_agreement = 1.0
        suspected = 0
        components: List[List[str]] = []
        for sn in self.sns:
            # disabled observatories report the plane as absent, not as
            # a table of zeroes (the cluster_health=False differential)
            if sn.crashed or not sn.node.obs.clusterview.enabled:
                continue
            doc = sn.node.obs.clusterview.health_doc()
            nodes[sn.name] = doc
            d = doc["derived"]
            max_skew = max(max_skew, d["babble_cluster_commit_skew_blocks"])
            min_agreement = min(
                min_agreement, d["babble_cluster_frontier_agreement"]
            )
            if doc["suspicion"]["suspected"]:
                suspected += 1
                for comp in doc["suspicion"]["components"]:
                    if comp not in components:
                        components.append(comp)
        return {
            "nodes": nodes,
            "summary": {
                "max_commit_skew_blocks": max_skew,
                "min_frontier_agreement": min_agreement,
                "partitions_suspected": suspected,
                "suspected_components": sorted(components),
            },
        }

    def cluster_health_fingerprint(self) -> str:
        """SHA-256 over every live node's canonical health-plane bytes,
        in node order — the cluster observatory's entry in the
        determinism fingerprint (ISSUE 20)."""
        h = sha256()
        for sn in self.sns:
            if sn.crashed or not sn.node.obs.clusterview.enabled:
                continue
            h.update(sn.name.encode())
            h.update(sn.node.obs.clusterview.stream_bytes())
        return h.hexdigest()

    def ledger_fingerprint(self) -> str:
        """SHA-256 over every live node's canonical device-ledger
        snapshot, in node order — the device-time ledger's entry in the
        determinism fingerprint (ISSUE 19): under the sim clock every
        duration records as 0.0, so two runs of the same seed+plan must
        produce byte-identical ledgers (same cells, same call counts,
        same compile/retrace tallies)."""
        h = sha256()
        for sn in self.sns:
            if sn.crashed:
                continue
            h.update(sn.name.encode())
            h.update(sn.node.obs.devledger.fingerprint().encode())
        return h.hexdigest()

    def provenance_fingerprint(self) -> str:
        """SHA-256 over every live node's canonical decision-provenance
        stream bytes, in node order — the provenance entry in the
        determinism fingerprint: two runs of the same seed+plan must
        produce byte-identical streams (docs/sim.md)."""
        h = sha256()
        for sn in self.sns:
            if sn.crashed:
                continue
            h.update(sn.name.encode())
            h.update(sn.node.obs.provenance.stream_bytes())
        return h.hexdigest()

    def export_provenance(self, directory: str) -> List[str]:
        """Write every live node's provenance stream as a JSON artifact
        (sweep failure export — `babble-tpu explain --bisect` replays
        the bisection offline from these). Deterministic filenames:
        seed + node name."""
        os.makedirs(directory, exist_ok=True)
        paths = []
        for sn in self.sns:
            if sn.crashed:
                continue
            path = os.path.join(
                directory, f"provenance-seed{self.seed}-{sn.name}.json"
            )
            with open(path, "w", encoding="utf-8") as f:
                json.dump(sn.node.obs.provenance.to_json(), f,
                          indent=1, sort_keys=True)
            paths.append(path)
        return paths

    def digest(self) -> str:
        """SHA-256 over every settled block body on every live node, in
        node order — the CLI's determinism fingerprint: two runs of the
        same seed+plan must produce the same digest."""
        h = sha256()
        for sn in self.sns:
            if sn.crashed:
                continue
            node = sn.node
            h.update(sn.name.encode())
            last = node.core.get_last_block_index()
            for i in range(last + 1):
                try:
                    blk = node.get_block(i)
                except Exception:  # noqa: BLE001 — history starts above i
                    continue
                if not blk.state_hash():
                    break
                h.update(blk.body.marshal())
        return h.hexdigest()

    def shutdown(self) -> None:
        for sn in self.sns:
            if not sn.crashed and sn.node is not None:
                # a mesh node may have a dispatch worker mid-execution;
                # an orphaned daemon thread inside JAX at interpreter
                # exit aborts the process, so wait it out first
                core = sn.node.core
                if core.ladder_rung() == "mesh_queued":
                    try:
                        core.device_engine.quiesce()
                    except Exception:  # noqa: BLE001
                        pass
                try:
                    sn.node.core.hg.store.close()
                except Exception:  # noqa: BLE001
                    pass

    def _trace(self, msg: str) -> None:
        self.trace.append(f"t={self.clock.now:.3f} {msg}")
        if len(self.trace) > TRACE_CAP:
            del self.trace[: TRACE_CAP // 2]
