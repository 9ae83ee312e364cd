"""IngressPipeline: the batched, back-pressured front door (ISSUE 16).

Transactions used to enter one at a time through framed JSON-RPC
(`proxy/socket_app.py` -> `submit_ch`), unbounded and unfair — the
cheapest flooding attack on a leaderless mesh. The pipeline sits between
every proxy submit entry point and the node's transaction worker and
applies, in order:

1. **dedup** — the sha256 trace_id (obs/tracectx.py) over an LRU window
   (common/lru.py), so client retries are idempotent: a duplicate gets
   the `accepted` verdict back (its first submission stands) and never
   re-enters the pool.
2. **admission control** — a bounded queue with EXPLICIT verdicts: every
   submission is answered `accepted` (released with the current batch),
   `queued` (admitted, held until the client's token bucket refills) or
   `shed` (queue full / sustained overrate). Never a silent drop.
3. **fairness** — per-client token buckets (client = peer addr or the
   app-supplied client_id) drained by a deficit-round-robin scheduler,
   so one flooder cannot starve the mesh: a meek client's transactions
   release ahead of a flooder's backlog.
4. **batching** — released transactions coalesce into size/deadline-
   bounded batches on the injected Clock (the dispatch-batching
   discipline of PR 9, applied at ingress: amortize many small submits
   into one `core.add_transactions` per batch). An oversize transaction
   bypasses coalescing and ships alone.

Every time read goes through the injected Clock — never wallclock — so
the deterministic simulator replays identical verdicts, batch shapes and
shed decisions for a given seed (the `ingress` entry in SimCluster's
result is part of the determinism fingerprint).

Thread model: RPC handler threads, the node's tx worker and the
heartbeat tick all call in; one pipeline lock serializes admission and
release. Released batches are handed downstream OUTSIDE the lock so the
pipeline never holds its lock across node-side queues.
"""

from __future__ import annotations

import logging
import threading
from collections import deque
from dataclasses import dataclass
from typing import Any, Callable, Deque, Dict, List, Optional, Tuple

from ..common import LRU, Clock, SYSTEM_CLOCK
from ..obs.metrics import DEFAULT_COUNT_BUCKETS, log_buckets
from ..obs.tracectx import trace_id_for

VERDICT_ACCEPTED = "accepted"
VERDICT_QUEUED = "queued"
VERDICT_SHED = "shed"

# bound on distinct live token buckets / client queues: admission state,
# not consensus state, so an LRU bound (evicted flooders simply start a
# fresh bucket) beats unbounded growth under a client-id churn attack
DEFAULT_CLIENT_CAP = 8192

# sheds inside one rolling window that flag a shed storm (flight record
# + dump): distinguishes sustained overload from an isolated rejection
SHED_STORM_WINDOW = 1.0
SHED_STORM_THRESHOLD = 64


@dataclass
class IngressVerdict:
    """The pipeline's answer to one submission — returned to the client
    (in-mem: as this object; JSON-RPC: as `to_wire()`), never implied."""

    verdict: str  # accepted | queued | shed
    reason: str = ""
    deduped: bool = False
    trace_id: str = ""

    def to_wire(self) -> Dict[str, Any]:
        return {
            "verdict": self.verdict,
            "reason": self.reason,
            "deduped": self.deduped,
            "trace_id": self.trace_id,
        }


def verdict_from_wire(res: Any) -> IngressVerdict:
    """Decode a SubmitTx/SubmitTxBatch JSON-RPC result. A pre-pipeline
    server answers plain `True` — mapped to a bare `accepted`."""
    if isinstance(res, dict):
        return IngressVerdict(
            verdict=str(res.get("verdict", "")),
            reason=str(res.get("reason", "")),
            deduped=bool(res.get("deduped", False)),
            trace_id=str(res.get("trace_id", "")),
        )
    if res:
        return IngressVerdict(verdict=VERDICT_ACCEPTED, reason="legacy")
    return IngressVerdict(verdict=VERDICT_SHED, reason="rejected")


class IngressBatch(list):
    """A released batch: the transactions, as the list the downstream has
    always been handed, and beside them `admitted_at`, per transaction the
    Clock reading at which its client was answered. The node hands it on
    to `Core.add_transactions`, which totals the waits as `ingress.wait`
    when the self-event that carries them is made."""

    __slots__ = ("admitted_at",)

    def __init__(self, txs=(), admitted_at=()):
        super().__init__(txs)
        self.admitted_at: List[float] = list(admitted_at)


class SubmitRejected(RuntimeError):
    """A submission did not land: `verdict` distinguishes server-side
    backpressure (``shed`` — retry later, the node is protecting itself)
    from transport/server failure (``error`` — the submission may never
    have been seen). Raised by the app-side socket proxy so callers can
    branch on backpressure instead of parsing a bare RuntimeError."""

    def __init__(self, verdict: str, reason: str = "",
                 server_verdict: Optional[IngressVerdict] = None):
        self.verdict = verdict
        self.reason = reason
        self.server_verdict = server_verdict
        super().__init__(f"SubmitTx rejected ({verdict}): {reason}")


class TokenBucket:
    """Per-client rate limiter. Pure state — refills are computed from
    the caller-provided Clock reading, and all access happens under the
    pipeline lock, so the bucket itself needs none."""

    __slots__ = ("rate", "burst", "tokens", "stamp")

    def __init__(self, rate: float, burst: float, now: float):
        self.rate = rate
        self.burst = burst
        self.tokens = burst
        self.stamp = now

    def take(self, now: float) -> bool:  # requires-lock: IngressPipeline._lock
        if now > self.stamp:
            self.tokens = min(self.burst, self.tokens + (now - self.stamp) * self.rate)
            self.stamp = now
        if self.tokens >= 1.0:
            self.tokens -= 1.0
            return True
        return False


class _ClientQueue:
    """Pending (tx, paid, admitted at) entries for one client plus its DRR deficit.
    All access under the pipeline lock."""

    __slots__ = ("entries", "deficit")

    def __init__(self) -> None:
        self.entries: Deque[Tuple[bytes, bool, float]] = deque()
        self.deficit = 0.0


class IngressPipeline:
    def __init__(
        self,
        downstream: Callable[[List[bytes]], None],
        clock: Clock = SYSTEM_CLOCK,
        obs=None,
        batch_bytes: int = 65536,
        batch_deadline: float = 0.0,
        queue_cap: int = 8192,
        client_rate: float = 0.0,
        client_burst: Optional[float] = None,
        dedup_window: int = 65536,
        client_cap: int = DEFAULT_CLIENT_CAP,
        logger: Optional[logging.Logger] = None,
    ):
        if batch_bytes < 1:
            raise ValueError("batch_bytes must be >= 1")
        if batch_deadline < 0:
            raise ValueError("batch_deadline must be >= 0")
        if queue_cap < 0:
            raise ValueError("queue_cap must be >= 0 (0 = unbounded)")
        if client_rate < 0:
            raise ValueError("client_rate must be >= 0 (0 = unlimited)")
        self.downstream = downstream
        self.clock = clock
        self.logger = logger or logging.getLogger("babble.ingress")
        if obs is None:
            from ..obs import Observability

            obs = Observability(clock=clock)
        self.obs = obs
        self.batch_bytes = batch_bytes
        self.batch_deadline = batch_deadline
        self.queue_cap = queue_cap
        self.client_rate = client_rate
        # default burst: one second's worth of tokens (>= 1 so a single
        # submit from a fresh client always has a token to take)
        self.client_burst = (
            client_burst if client_burst is not None else max(1.0, client_rate)
        )
        # DRR quantum: bytes a client may release per scheduler round —
        # a quarter-batch keeps several clients' traffic in every batch
        self.drr_quantum = max(1.0, batch_bytes / 4.0)

        self._lock = threading.Lock()
        # dedup window over trace_ids (retry idempotency horizon)
        self._dedup = LRU(max(1, dedup_window))  # guarded-by: _lock
        # token bucket per live client, LRU-bounded (see DEFAULT_CLIENT_CAP)
        self._buckets = LRU(max(1, client_cap))  # guarded-by: _lock
        # per-client pending queues, insertion-ordered (the DRR rotation
        # order); a queue is dropped the moment it drains
        self._queues: Dict[str, _ClientQueue] = {}  # guarded-by: _lock
        self._pending = 0  # guarded-by: _lock
        # the open batch: released txs waiting for size/deadline flush
        self._batch = IngressBatch()  # guarded-by: _lock
        self._batch_size = 0  # guarded-by: _lock
        self._batch_open_t = 0.0  # guarded-by: _lock
        # shed-storm detection window state
        self._shed_window_start = 0.0  # guarded-by: _lock
        self._shed_window_count = 0  # guarded-by: _lock
        self._storm_flagged = False  # guarded-by: _lock

        # -- metric declarations (static names; obs-* lint) -------------
        self._m_verdicts = self.obs.counter(
            "babble_ingress_verdicts_total",
            "Ingress admission verdicts returned to clients",
            labels=("verdict",),
        )
        self._m_shed = self.obs.counter(
            "babble_ingress_shed_total",
            "Submissions shed by the ingress pipeline, by reason",
            labels=("reason",),
        )
        self._m_dedup = self.obs.counter(
            "babble_ingress_dedup_hits_total",
            "Retries absorbed by the trace_id dedup window",
        )
        self._m_batch_txs = self.obs.histogram(
            "babble_ingress_batch_txs",
            "Transactions per released ingress batch",
            buckets=DEFAULT_COUNT_BUCKETS,
        )
        self._m_batch_bytes = self.obs.histogram(
            "babble_ingress_batch_bytes",
            "Bytes per released ingress batch",
            buckets=log_buckets(64, 4.0, 10),
        )
        self.obs.gauge(
            "babble_ingress_queue_depth",
            "Transactions held in the ingress pipeline (queued + batching)",
        ).set_function(self.pending)

    # ------------------------------------------------------------------
    # public surface
    # ------------------------------------------------------------------

    def pending(self) -> int:
        """Held transactions: rate-deferred queues plus the open batch.
        Feeds the queue-depth gauge and the watchdog's pending_fn (a
        stall with ingress work held must not read as an idle node)."""
        with self._lock:
            return self._pending + len(self._batch)

    def submit(self, tx: bytes, client_id: str = "local") -> IngressVerdict:
        """Admit one transaction; returns its verdict immediately."""
        return self.submit_batch([tx], client_id=client_id)[0]

    def submit_batch(
        self, txs: List[bytes], client_id: str = "local"
    ) -> List[IngressVerdict]:
        """Admit a client batch: per-tx verdicts, one release pump at the
        end — so a wire batch coalesces into (at least) one downstream
        batch instead of one per transaction."""
        out: List[IngressVerdict] = []
        with self._lock:
            now = self.clock.monotonic()
            for tx in txs:
                out.append(self._admit_locked(bytes(tx), client_id, now))
            released = self._pump_locked(now)
        self._emit(released)
        return out

    def tick(self) -> None:
        """Deadline pump: called from the heartbeat tick (threaded node)
        or SimCluster._tick (virtual time) so a partial batch's deadline
        fires even when no new submission arrives."""
        with self._lock:
            released = self._pump_locked(self.clock.monotonic())
        self._emit(released)

    def flush(self) -> None:
        """Release everything releasable and ship the open batch even if
        under both thresholds (shutdown/test seam)."""
        with self._lock:
            released = self._pump_locked(self.clock.monotonic())
            if self._batch:
                released.append(self._close_batch_locked())
        self._emit(released)

    # ------------------------------------------------------------------
    # admission (lock held)
    # ------------------------------------------------------------------

    # requires-lock: _lock
    def _admit_locked(
        self, tx: bytes, client_id: str, now: float
    ) -> IngressVerdict:
        tid = trace_id_for(tx)
        _, seen = self._dedup.get(tid)
        if seen:
            # idempotent retry: the first submission stands, the client
            # gets a success verdict (not an error) and nothing re-enters
            self._m_dedup.inc()
            self._m_verdicts.labels(verdict="accepted").inc()
            return IngressVerdict(
                VERDICT_ACCEPTED, reason="duplicate", deduped=True,
                trace_id=tid,
            )
        if self.queue_cap and self._pending + len(self._batch) >= self.queue_cap:
            return self._shed_locked(tid, "queue_full", now)
        paid = True
        if self.client_rate > 0:
            bucket, ok = self._buckets.get(client_id)
            if not ok:
                bucket = TokenBucket(self.client_rate, self.client_burst, now)
                self._buckets.add(client_id, bucket)
            paid = bucket.take(now)
            if not paid:
                # overrate: the tx may wait for a refill, but only a
                # bounded backlog per client — past it, shed (a flooder
                # must not park the whole admission queue behind itself)
                q = self._queues.get(client_id)
                backlog = len(q.entries) if q is not None else 0
                if self.queue_cap and backlog >= max(1, self.queue_cap // 4):
                    return self._shed_locked(tid, "rate_limited", now)
        q = self._queues.get(client_id)
        if q is None:
            q = self._queues[client_id] = _ClientQueue()
        q.entries.append((tx, paid, now))
        self._pending += 1
        self._dedup.add(tid, True)
        verdict = VERDICT_ACCEPTED if paid else VERDICT_QUEUED
        self._m_verdicts.labels(verdict=verdict).inc()
        return IngressVerdict(
            verdict,
            reason="" if paid else "rate_limited",
            trace_id=tid,
        )

    # requires-lock: _lock
    def _shed_locked(
        self, tid: str, reason: str, now: float
    ) -> IngressVerdict:
        self._m_verdicts.labels(verdict="shed").inc()
        self._m_shed.labels(reason=reason).inc()
        # storm detection: sheds are expected in isolation (that is the
        # backpressure contract working); a burst of them inside one
        # window is an overload event worth a flight record + dump
        if now - self._shed_window_start >= SHED_STORM_WINDOW:
            self._shed_window_start = now
            self._shed_window_count = 0
            self._storm_flagged = False
        self._shed_window_count += 1
        if (
            self._shed_window_count >= SHED_STORM_THRESHOLD
            and not self._storm_flagged
        ):
            self._storm_flagged = True
            self.obs.flightrec.record(
                "ingress.shed_storm",
                sheds=self._shed_window_count,
                window_s=SHED_STORM_WINDOW,
                reason=reason,
                queue_depth=self._pending + len(self._batch),
            )
            self.obs.flightrec.dump("ingress-shed-storm")
        return IngressVerdict(VERDICT_SHED, reason=reason, trace_id=tid)

    # ------------------------------------------------------------------
    # release: DRR scheduler + batch former (lock held)
    # ------------------------------------------------------------------

    def _pump_locked(self, now: float) -> List[List[bytes]]:  # requires-lock: _lock
        """Move releasable txs from the client queues into the open
        batch (deficit round robin), flushing on the size threshold;
        then apply the deadline rule. Returns closed batches for the
        caller to emit outside the lock."""
        out: List[List[bytes]] = []
        # DRR: every full round grants each waiting client one quantum
        # of bytes; rounds repeat while at least one tx released OR a
        # head is blocked only on deficit (a few more grants always free
        # it — deficits grow a quantum per round, so that loop is
        # bounded by max_tx_len/quantum; rate-starved heads do NOT
        # extend rounds or a drained bucket would spin this forever).
        # A burst thus drains in one pump, interleaved fairly — a
        # quantum per client at a time, not flooder-first.
        progressed = True
        deficit_starved = False
        while (progressed or deficit_starved) and self._queues:
            progressed = False
            deficit_starved = False
            for cid in list(self._queues.keys()):
                q = self._queues.get(cid)
                if q is None or not q.entries:
                    self._queues.pop(cid, None)
                    continue
                q.deficit += self.drr_quantum
                while q.entries:
                    tx, paid, admitted = q.entries[0]
                    oversize = len(tx) >= self.batch_bytes
                    if not oversize and q.deficit < len(tx):
                        deficit_starved = True
                        break  # quantum spent — next client's turn
                    if not paid:
                        bucket, ok = self._buckets.get(cid)
                        if not ok or not bucket.take(now):
                            # still overrate — wait for a refill. The
                            # deficit is forfeited: an ineligible queue
                            # is idle in DRR terms, and banking credit
                            # across the wait would let it burst past
                            # its quantum share once tokens return
                            q.deficit = 0.0
                            break
                    q.entries.popleft()
                    self._pending -= 1
                    progressed = True
                    if oversize:
                        # oversize bypasses coalescing: ship the open
                        # batch as-is, then the big tx alone (deficit is
                        # zeroed — it consumed far more than a quantum)
                        q.deficit = 0.0
                        if self._batch:
                            out.append(self._close_batch_locked())
                        self._observe_batch([tx])
                        out.append(IngressBatch([tx], [admitted]))
                        continue
                    q.deficit -= len(tx)
                    if not self._batch:
                        self._batch_open_t = now
                    self._batch.append(tx)
                    self._batch.admitted_at.append(admitted)
                    self._batch_size += len(tx)
                    if self._batch_size >= self.batch_bytes:
                        out.append(self._close_batch_locked())
                if not q.entries:
                    self._queues.pop(cid, None)
        # deadline rule: 0 => release every pump (no hold); > 0 => hold
        # the partial batch until the deadline elapses on the Clock
        if self._batch and (
            self.batch_deadline <= 0.0
            or now - self._batch_open_t >= self.batch_deadline
        ):
            out.append(self._close_batch_locked())
        return out

    def _close_batch_locked(self) -> IngressBatch:  # requires-lock: _lock
        batch = self._batch
        self._batch = IngressBatch()
        self._batch_size = 0
        self._observe_batch(batch)
        return batch

    def _observe_batch(self, batch: List[bytes]) -> None:
        self._m_batch_txs.observe(len(batch))
        self._m_batch_bytes.observe(sum(len(t) for t in batch))

    def _emit(self, batches: List[List[bytes]]) -> None:
        """Hand released batches downstream, outside the pipeline lock
        (the downstream is the node's submit queue; never hold our lock
        across someone else's)."""
        for batch in batches:
            if batch:
                self.downstream(batch)
