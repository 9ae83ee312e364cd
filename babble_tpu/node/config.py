"""Node runtime configuration (reference: src/node/config.go).

Durations are seconds (floats), not Go time.Durations.
"""

from __future__ import annotations

import logging
import random
from dataclasses import dataclass, field
from typing import Optional

from ..common import Clock, SYSTEM_CLOCK


def _default_logger() -> logging.Logger:
    return logging.getLogger("babble.node")


@dataclass
class Config:
    heartbeat_timeout: float = 1.0
    tcp_timeout: float = 1.0
    cache_size: int = 500
    sync_limit: int = 100
    # consensus backend: "cpu" runs the scalar five-pass pipeline on host;
    # "tpu" dispatches DivideRounds/DecideFame/DecideRoundReceived to the
    # device kernels (babble_tpu/tpu/), falling back to the CPU path on any
    # state the dense grid cannot express (SURVEY §7 swappable-backend plan;
    # reference boundary: src/node/core.go:335-377)
    consensus_backend: str = "cpu"
    # with consensus_backend="tpu": shard the device passes over this many
    # chips as a jax.sharding.Mesh (0/1 = single device). The mesh path
    # routes through babble_tpu/tpu/sharded.py (rounds-sharded fame with
    # ppermute ring shifts, events/chains-sharded tables); any state it
    # cannot express falls down the same ladder as the single-device path
    mesh_devices: int = 0
    # async device dispatch (tpu/live.py multi-slot pipeline and the
    # queued-mesh rung in tpu/dispatch.py): the cap on dispatches in
    # flight. The mesh rung fills it before the serve path blocks to
    # integrate the oldest; the live engine's pipelined fetch keeps one in
    # flight and goes deeper, up to this cap, only while the oldest fetch
    # is really waited on. 1 is the single-slot overlap; 0 disables queuing.
    dispatch_queue_depth: int = 4
    # cross-round dispatch batching: hold gossip-staged rows for up to
    # this many Clock seconds (or until a size threshold) before
    # dispatching, so the frontier walk amortizes across syncs. 0.0 =
    # dispatch every call (no hold). Deadlines are measured on the
    # injected Clock below — never wallclock — so the deterministic
    # simulator replays the same batching decisions.
    dispatch_batch_deadline: float = 0.0
    # round-batched dispatch (ISSUE 9): the delta-row count at which a
    # queued mesh dispatch (a) stops holding for more gossip and (b)
    # prefers the pointer-doubling cold path so one dispatch carries the
    # whole multi-round batch. Also sizes the live engine's device batch
    # (tpu/live.py batch_cap). Only meaningful with dispatch_queue_depth
    # > 0 — the CLI rejects a non-default value when queuing is disabled.
    dispatch_batch_rows: int = 64
    # validator-axis sharding (ISSUE 9): fold mesh_devices into a 2-D
    # (validators, rounds) mesh with this many validator shards, so fame
    # voting state (witness/vote/strongly-seen tables) is partitioned
    # over validators as well as rounds. Must divide mesh_devices; 1 =
    # the original rounds-only layout.
    mesh_validator_shards: int = 1
    # voting-table layout (ISSUE 17, tpu/packed.py): "1" packs the
    # validator axis of the strongly-seen/vote tables into uint32 lanes
    # with popcount tallies (byte-equal results, ~8x smaller voting
    # state), "0" keeps the wide bool layout, "auto" packs from
    # tpu.packed.PACKED_AUTO_MIN_N validators up. The env var
    # BABBLE_PACKED_VOTING overrides this at call time.
    packed_voting: str = "auto"
    # time-source seam: every monotonic read and sleep in the node layer
    # goes through this Clock, so the deterministic simulator
    # (babble_tpu/sim/) can drive nodes on virtual time. Production uses
    # the shared SystemClock singleton.
    clock: Clock = SYSTEM_CLOCK
    # randomness seam for protocol choices (peer selection, heartbeat
    # jitter). None = the module-level `random` generator (production);
    # the simulator passes a per-node random.Random seeded from the run
    # seed so replays reproduce every choice.
    rng: Optional[random.Random] = None
    # cross-node causal tracing (ISSUE 5): propagate TraceContexts on
    # gossip payloads and record per-stage spans/histograms. Tracing is
    # out-of-band by construction (never in signed event bytes), so
    # flipping it changes no consensus behaviour — only telemetry.
    tracing: bool = True
    # LRU cap on live TraceContexts per node (evictions count into
    # obs_traces_dropped_total)
    trace_capacity: int = 4096
    # liveness watchdog (node/watchdog.py): warn + set the
    # babble_consensus_stalled gauge when round-received has not advanced
    # for this many Clock seconds despite pending work
    stall_deadline: float = 10.0
    # cluster health plane (ISSUE 20, obs/clusterview.py): piggyback
    # versioned HealthDigests on sync payloads (out-of-band, like
    # tracing) and derive cluster series + partition suspicion from the
    # federated fleet table. Flipping it changes no consensus behaviour.
    cluster_health: bool = True
    # Clock seconds without contact before a peer counts as stale for
    # partition inference and before its digest stops feeding the
    # derived series (at 3x this deadline)
    cluster_staleness_deadline: float = 5.0
    # black-box flight recorder (obs/flightrec.py): bounded ring of typed
    # structured records dumped on stall/divergence/flap/SLO breach
    flightrec_capacity: int = 2048
    # directory flight-recorder dump artifacts are written to; None keeps
    # dumps in memory only (served at GET /debug/flightrec either way)
    flightrec_dir: Optional[str] = None
    # SLO engine (obs/slo.py): declare default objectives over the
    # registry and evaluate burn rates on the heartbeat tick; a breach
    # triggers a flight-recorder dump
    slo_enabled: bool = True
    # submit->commit p99 objective threshold, Clock seconds
    slo_commit_p99: float = 30.0
    # ---- ingress pipeline (ISSUE 16, babble_tpu/ingress/) ------------
    # byte threshold at which the open ingress batch ships to the node's
    # tx worker; an individual tx at/over this size bypasses coalescing
    ingress_batch_bytes: int = 65536
    # Clock seconds a partial ingress batch may be held waiting for more
    # submissions. 0.0 = release on every pump (no hold) — the safe
    # default for latency and the setting under which batched and
    # single-tx submission commit byte-identical digests.
    ingress_batch_deadline: float = 0.0
    # bound on transactions held inside the ingress pipeline (queued +
    # open batch); past it submissions get the `shed` verdict. 0 =
    # unbounded (not recommended outside tests).
    ingress_queue_cap: int = 8192
    # per-client token-bucket rate, tx/s (client = peer addr or the
    # app-supplied client_id). 0.0 = no per-client limit; > 0 enables
    # the deficit-round-robin fairness scheduler between clients.
    ingress_client_rate: float = 0.0
    # trace_id LRU window within which a client retry of the same tx
    # bytes is answered `accepted` without re-entering the pool
    ingress_dedup_window: int = 65536
    # minimum seconds between Node.log_stats() snapshot lines — the
    # heartbeat fires every successful gossip exchange, which at test
    # heartbeats would be hundreds of log records a second
    stats_log_interval: float = 10.0
    # log the registry snapshot at info (CLI --metrics); default debug
    metrics_log: bool = False
    logger: logging.Logger = field(default_factory=_default_logger)


def default_config() -> Config:
    return Config()


def test_config() -> Config:
    """Fast heartbeat for in-process integration tests
    (reference: src/node/config.go:48-53 + test usage)."""
    return Config(heartbeat_timeout=0.005, tcp_timeout=1.0, cache_size=1000, sync_limit=300)
