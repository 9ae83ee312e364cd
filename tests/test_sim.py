"""Deterministic-simulator tests (babble_tpu/sim/): seeded determinism,
fault-plan convergence, crash-restart with a persistent store, and the
round-5 divergence shape (late witness during fast-forward under load)
as a regression scenario.

All of these run entire 4-node clusters, but on VIRTUAL time — a run
that simulates ~10 seconds of cluster activity takes well under a
second of wall clock, so none of them need the `slow` marker.
"""

import json
import logging

import pytest

from babble_tpu.sim import (
    CrashSpec,
    DivergenceChecker,
    FaultPlan,
    LatencySpec,
    Partition,
    SimCluster,
    SimClock,
    SimScheduler,
    preset_plan,
    run_one,
)

# node-level logging is meaningless noise across hundreds of simulated
# exchanges; failures surface through assertions and artifacts
logging.getLogger("babble.sim").setLevel(logging.CRITICAL)


# ----------------------------------------------------------------------
# primitives
# ----------------------------------------------------------------------

def test_scheduler_orders_ties_by_insertion():
    sched = SimScheduler()
    seen = []
    sched.at(1.0, lambda: seen.append("a"))
    sched.at(0.5, lambda: seen.append("b"))
    sched.at(1.0, lambda: seen.append("c"))
    sched.run_until(2.0)
    assert seen == ["b", "a", "c"]
    assert sched.clock.now == 2.0


def test_sim_clock_captures_sleep():
    clock = SimClock()
    clock.sleep(0.25)
    clock.sleep(0.5)
    assert clock.monotonic() == 0.0  # sleep never advances virtual time
    assert clock.take_pending_sleep() == 0.75
    assert clock.take_pending_sleep() == 0.0


def test_fault_plan_json_round_trip():
    plan = FaultPlan(
        name="custom",
        latency=LatencySpec(base=0.02, jitter=0.08),
        drop_rate=0.1,
        dup_rate=0.05,
        partitions=[Partition(start=1.0, end=4.0, groups=((0,), (1, 2, 3)))],
        crashes=[CrashSpec(node=3, at=1.5, restart_at=5.0)],
    )
    back = FaultPlan.from_json(plan.to_json())
    assert back.to_dict() == plan.to_dict()
    # partition semantics survive the trip
    assert back.partitioned(0, 2, 2.0)
    assert not back.partitioned(1, 2, 2.0)  # same group
    assert not back.partitioned(0, 2, 5.0)  # healed


def test_preset_plans_exist():
    for name in ("clean", "lossy", "partition_heal", "crash_restart", "chaos"):
        plan = preset_plan(name, 4)
        assert plan.name == name
    with pytest.raises(ValueError):
        preset_plan("nope", 4)


# ----------------------------------------------------------------------
# seeded determinism (ISSUE 1 acceptance: same seed => byte-identical
# committed blocks on every node, twice)
# ----------------------------------------------------------------------

def test_seeded_determinism_same_seed_twice():
    a = run_one(5, plan="lossy", n=4, until=None, target_block=3)
    b = run_one(5, plan="lossy", n=4, until=None, target_block=3)
    assert a["ok"] and b["ok"]
    assert a["reached_target"] and b["reached_target"]
    assert a["digest"] == b["digest"]
    # the whole event sequence replayed, not just the outcome
    assert a["events_run"] == b["events_run"]
    assert a["virtual_time"] == b["virtual_time"]
    assert a["net"] == b["net"]


def test_different_seeds_diverge_in_schedule():
    a = run_one(5, plan="clean", n=4, until=None, target_block=2)
    b = run_one(6, plan="clean", n=4, until=None, target_block=2)
    assert a["ok"] and b["ok"]
    # different seeds drive different workloads/schedules — if these were
    # equal the seed would not actually be feeding the streams
    assert a["digest"] != b["digest"]


# ----------------------------------------------------------------------
# fault convergence
# ----------------------------------------------------------------------

def test_partition_heal_converges():
    res = run_one(3, plan="partition_heal", n=4, until=30.0, target_block=10)
    assert res["ok"], res["error"]
    assert res["reached_target"]
    assert res["net"]["severed"] > 0  # the partition actually bit
    assert res["blocks_checked"] >= 10


def test_crash_restart_sqlite_store(tmp_path):
    """The crashed node's sqlite store survives; on restart it bootstraps
    from disk (replaying its own history through consensus) and rejoins
    the cluster without diverging."""
    res = run_one(
        9,
        plan="crash_restart",
        n=4,
        store="sqlite",
        store_dir=str(tmp_path),
        until=40.0,
        target_block=10,
    )
    assert res["ok"], res["error"]
    assert res["reached_target"]
    assert res["restarts"] == 1
    # all four db files exist — including the crashed node's
    assert len(list(tmp_path.glob("node*.db"))) == 4


def test_crash_restart_inmem_rejoins():
    """An inmem node loses its store in the crash and rejoins as an
    effective fresh joiner — convergence must still hold."""
    res = run_one(9, plan="crash_restart", n=4, until=40.0, target_block=10)
    assert res["ok"], res["error"]
    assert res["reached_target"]
    assert res["restarts"] == 1


# ----------------------------------------------------------------------
# round-5 divergence shape: a node that comes back far behind, under
# sustained load, with a sync limit tight enough to force the
# fast-forward path (late witness arriving during catch-up was the r5
# reception-divergence shape — this pins the scenario as a regression)
# ----------------------------------------------------------------------

def test_r5_shape_fast_forward_under_load():
    plan = FaultPlan(
        name="deep_crash",
        latency=LatencySpec(base=0.01, jitter=0.03),
        crashes=[CrashSpec(node=3, at=1.0, restart_at=8.0)],
    )
    cluster = SimCluster(n=4, seed=11, plan=plan, sync_limit=30)
    try:
        res = cluster.run(until=60.0, target_block=20)
    finally:
        cluster.shutdown()
    assert res["reached_target"], res
    # the restarted node MUST have gone through the catch-up state
    # machine (sync-limit flip + fast-forward), not ordinary sync —
    # otherwise this test is not exercising the r5 shape at all
    assert res["catchup_flips"] >= 1
    assert res["ff_attempts"] >= 1
    flipped = [sn for sn in cluster.sns if sn.catchup_flips]
    assert [sn.index for sn in flipped] == [3]
    # and every settled block byte-matched across nodes during the run
    assert res["blocks_checked"] >= 20


# ----------------------------------------------------------------------
# divergence detection + artifact (inject a fake divergence: the checker
# itself must catch it and dump a replayable artifact)
# ----------------------------------------------------------------------

def test_divergence_dumps_artifact(tmp_path):
    from babble_tpu.sim.checker import DivergenceError

    cluster = SimCluster(
        n=4, seed=2, artifact_dir=str(tmp_path / "artifacts")
    )
    try:
        cluster.run(until=None, target_block=2)
        # corrupt one node's copy of block 1 behind the checker's back
        store = cluster.sns[2].node.core.hg.store
        blk = store.get_block(1)
        blk.body.transactions.append(b"byzantine extra tx")
        store.set_block(blk)
        cluster.checker.checked_upto = -1  # force a full re-check
        with pytest.raises(DivergenceError) as ei:
            cluster.check_divergence()
        # the failure auto-dumped every live node's flight recorder —
        # the triage bundle the sweep exports beside the artifact
        for sn in cluster.sns:
            docs = sn.node.obs.flightrec.dump_docs
            assert docs and docs[-1]["reason"] == "divergence"
        exported = cluster.export_flight_dumps(str(tmp_path / "artifacts"))
        assert len(exported) == 4
        for p in exported:
            with open(p) as f:
                assert json.load(f)["reason"] == "divergence"
    finally:
        cluster.shutdown()
    artifact_path = ei.value.artifact_path
    assert artifact_path is not None
    with open(artifact_path) as f:
        artifact = json.load(f)
    assert artifact["kind"] == "babble-tpu-sim-divergence"
    assert artifact["block_index"] == 1
    assert artifact["seed"] == 2
    # the embedded plan replays: it must round-trip through FaultPlan
    assert FaultPlan.from_dict(artifact["plan"]).name == "clean"
    assert "node2" in artifact["blocks"]


def test_checker_skips_unsettled_blocks():
    """A block missing its state hash on one node is mid-commit, not a
    divergence — the watermark must stop below it."""

    class FakeBlock:
        def __init__(self, index, hashed):
            from babble_tpu.hashgraph import Block

            self._b = Block(index, 1, b"fh", [b"tx"])
            if hashed:
                self._b.body.state_hash = b"H"
            self.body = self._b.body

        def state_hash(self):
            return self._b.body.state_hash

    class FakeStore:
        def __init__(self, blocks):
            self.blocks = blocks

        def last_block_index(self):
            return max(self.blocks)

        def get_block(self, i):
            return self.blocks[i]

    a = FakeStore({0: FakeBlock(0, True), 1: FakeBlock(1, True)})
    b = FakeStore({0: FakeBlock(0, True), 1: FakeBlock(1, False)})
    checker = DivergenceChecker()
    upto = checker.check([("a", a), ("b", b)])
    assert upto == 0  # block 1 not settled on b: not compared yet


# ----------------------------------------------------------------------
# CLI entry point
# ----------------------------------------------------------------------

def test_cli_sim_single_seed(capsys, tmp_path):
    from babble_tpu.cli import main

    rc = main([
        "sim", "--seed", "4", "--plan", "clean",
        "--target-block", "2", "--until", "20",
        "--artifact-dir", str(tmp_path),
    ])
    assert rc == 0
    out = json.loads(capsys.readouterr().out)
    assert out["ok"] is True
    assert out["seed"] == 4
    assert len(out["digest"]) == 64


def test_cli_sim_plan_file(capsys, tmp_path):
    from babble_tpu.cli import main

    plan_path = tmp_path / "plan.json"
    plan_path.write_text(preset_plan("lossy", 4).to_json())
    rc = main([
        "sim", "--seed", "4", "--plan", str(plan_path),
        "--target-block", "2", "--until", "20",
        "--artifact-dir", str(tmp_path),
    ])
    assert rc == 0
    out = json.loads(capsys.readouterr().out)
    assert out["ok"] is True
    assert out["plan"] == "lossy"


# ----------------------------------------------------------------------
# cross-node causal tracing (ISSUE 5): fingerprint determinism, the
# hash-safety differential, fault-plan trace completeness, watchdog
# ----------------------------------------------------------------------

def test_trace_fingerprint_deterministic():
    """Same seed+plan => byte-identical cross-node trace fingerprints and
    stage-latency histogram snapshots: tracing is part of the determinism
    contract, not an exception to it."""
    a = run_one(5, plan="lossy", n=4, until=None, target_block=3)
    b = run_one(5, plan="lossy", n=4, until=None, target_block=3)
    assert a["ok"] and b["ok"]
    assert a["trace_fingerprint"] == b["trace_fingerprint"]
    assert (
        json.dumps(a["stage_latency"], sort_keys=True)
        == json.dumps(b["stage_latency"], sort_keys=True)
    )
    # the fingerprint covers real spans and the stage histograms measured
    # every stage on every node
    counts = [
        snap[name]["series"][""]["count"]
        for snap in a["stage_latency"].values()
        for name in SimCluster.STAGE_HISTOGRAMS
    ]
    assert counts and all(c > 0 for c in counts)


def test_tracing_is_hash_safe_differential():
    """Tracing on vs off must not change what the cluster commits: trace
    context never reaches signed event bytes, so the block digest — the
    replay fingerprint over every committed body — is identical."""
    traced = run_one(7, plan="clean", n=4, until=None, target_block=3)
    untraced = run_one(7, plan="clean", n=4, until=None, target_block=3,
                       tracing=False)
    assert traced["ok"] and untraced["ok"]
    assert traced["digest"] == untraced["digest"]
    assert traced["events_run"] == untraced["events_run"]
    assert traced["virtual_time"] == untraced["virtual_time"]
    # and tracing was actually on in the traced run
    assert traced["trace_fingerprint"] != untraced["trace_fingerprint"]


@pytest.mark.parametrize("preset", ["lossy", "partition_heal", "crash_restart"])
def test_traces_complete_or_cleanly_truncated_under_faults(preset):
    """Under drop/dup/partition/crash faults every assembled cluster
    trace is complete or cleanly truncated: no span references a parent
    span id that is missing from the merged document, and the per-node
    stores stay within their capacity bound."""
    cluster = SimCluster(n=4, seed=7, plan=preset_plan(preset, 4))
    try:
        cluster.run(until=12.0)
        doc = cluster.cluster_trace()
        evs = [e for e in doc["traceEvents"]
               if e.get("args", {}).get("trace")]
        assert evs  # faults thin the traces but cannot erase them all
        span_ids = {e["args"]["span"] for e in evs}
        orphans = [e for e in evs
                   if e["args"].get("parent")
                   and e["args"]["parent"] not in span_ids]
        assert orphans == []
        for sn in cluster.sns:
            assert len(sn.node.obs.traces) <= sn.node.obs.traces.capacity
    finally:
        cluster.shutdown()


def test_watchdog_trips_on_injected_stall():
    """A full four-way partition freezes round advance on every node; the
    watchdog must raise babble_consensus_stalled within one deadline of
    virtual time (stall begins ~t=1, deadline 2s, asserted at t=8)."""
    plan = FaultPlan(
        name="total_partition",
        partitions=(
            Partition(start=1.0, end=99.0,
                      groups=((0,), (1,), (2,), (3,))),
        ),
    )
    cluster = SimCluster(n=4, seed=3, plan=plan, stall_deadline=2.0)
    try:
        cluster.run(until=8.0)
        for sn in cluster.sns:
            snap = sn.node.obs.registry.snapshot()
            assert snap["babble_consensus_stalled"]["series"][""] == 1.0
            # peer gauges were populated from the sync feed, with labels
            health = snap["babble_peer_health"]["series"]
            assert health and all(0.0 <= v <= 1.0 for v in health.values())
    finally:
        cluster.shutdown()


def test_watchdog_quiet_on_healthy_run():
    """Rounds keep advancing on a clean plan — the stall gauge must sit
    at 0 even with a deadline short enough to be trippable."""
    cluster = SimCluster(n=4, seed=5, plan=preset_plan("clean", 4),
                         stall_deadline=2.0)
    try:
        cluster.run(until=12.0)
        for sn in cluster.sns:
            snap = sn.node.obs.registry.snapshot()
            assert snap["babble_consensus_stalled"]["series"][""] == 0.0
    finally:
        cluster.shutdown()


# ----------------------------------------------------------------------
# device-backend differential (ISSUE 6: the queued-mesh dispatch rung
# must commit the same blocks as the CPU engine)
# ----------------------------------------------------------------------

def test_mixed_cpu_and_queued_mesh_cluster_byte_identical():
    """Two CPU nodes and two queued-mesh nodes in ONE cluster. The
    divergence checker byte-compares their settled blocks every 0.5
    virtual seconds, so this is the strictest cross-backend gate the sim
    has: a queued-mesh node whose async dispatch stamped a wrong round,
    or integrated results out of FIFO order, commits different bytes and
    the run raises immediately. Dispatch lag is allowed to shift WHEN a
    mesh node seals (decisions are DAG facts) — the checker compares the
    common settled prefix, so timing skew passes and content skew
    fails."""
    res = run_one(
        7, plan="clean", n=4,
        backend=("cpu", "cpu", "tpu", "tpu"),
        mesh_devices=2,
        dispatch_queue_depth=4,
        dispatch_batch_deadline=0.2,
        until=None, target_block=2,
    )
    assert res["ok"], res["error"]
    assert res["reached_target"]
    assert res["blocks_checked"] >= 2


def test_queued_mesh_run_to_run_deterministic():
    """The queued rung's integration triggers are functions of queue
    occupancy and the call sequence — never of whether a worker thread
    happens to have finished — so two same-seed runs must replay the
    identical schedule: same digest, same causal-trace fingerprint, same
    event count (tpu/dispatch.py's determinism discipline)."""
    kwargs = dict(
        plan="clean", n=4, backend="tpu", mesh_devices=2,
        dispatch_queue_depth=4, dispatch_batch_deadline=0.2,
        until=None, target_block=2,
    )
    a = run_one(9, **kwargs)
    b = run_one(9, **kwargs)
    assert a["ok"] and b["ok"], (a["error"], b["error"])
    assert a["reached_target"] and b["reached_target"]
    assert a["digest"] == b["digest"]
    assert a["trace_fingerprint"] == b["trace_fingerprint"]
    assert a["events_run"] == b["events_run"]
    assert a["virtual_time"] == b["virtual_time"]


def test_sync_mesh_rung_matches_cpu_digest():
    """dispatch_queue_depth=0 disables the queued rung, leaving the sync
    one-shot mesh path — which blocks call-for-call, so decisions land on
    the same serve call as the CPU engine and the two backends produce
    byte-identical committed history for the same seed. (The queued rung
    is excluded from THIS gate on purpose: dispatch lag shifts which
    self-event carries a block signature, signatures are inside event
    hashes, and frame hashes cover event bytes — so cross-RUN digest
    equality only holds for zero-lag rungs; the mixed-cluster test above
    is the queued rung's equality gate.)"""
    cpu = run_one(9, plan="clean", n=4, backend="cpu",
                  until=None, target_block=2)
    mesh = run_one(9, plan="clean", n=4, backend="tpu", mesh_devices=2,
                   dispatch_queue_depth=0,
                   until=None, target_block=2)
    assert cpu["ok"] and mesh["ok"], (cpu["error"], mesh["error"])
    assert cpu["digest"] == mesh["digest"]
    assert cpu["events_run"] == mesh["events_run"]
    assert cpu["virtual_time"] == mesh["virtual_time"]


# ----------------------------------------------------------------------
# round-batched mesh rung (ISSUE 9: one dispatch carries many rounds)
# ----------------------------------------------------------------------

def _rounds_per_dispatch_count(res, node):
    hist = (res["mesh_dispatch"].get(node) or {}).get(
        "babble_mesh_rounds_per_dispatch"
    )
    if not hist:
        return 0
    return sum(s["count"] for s in hist["series"].values())


def test_mixed_cpu_and_round_batched_mesh_cluster_byte_identical():
    """CPU nodes gossiping with ROUND-BATCHED mesh nodes (small
    dispatch_batch_rows so batches actually form and ride the doubling-
    preferred path) under the continuous divergence checker. Batching
    only shifts WHEN a mesh node seals — decisions stay DAG facts — so
    the common settled prefix must stay byte-identical, and the
    rounds-per-dispatch histogram must show the batched rung actually
    integrated dispatches."""
    res = run_one(
        7, plan="clean", n=4,
        backend=("cpu", "cpu", "tpu", "tpu"),
        mesh_devices=2,
        dispatch_queue_depth=4,
        dispatch_batch_deadline=0.2,
        dispatch_batch_rows=8,
        until=None, target_block=2,
    )
    assert res["ok"], res["error"]
    assert res["reached_target"]
    assert res["blocks_checked"] >= 2
    assert (
        _rounds_per_dispatch_count(res, "node2")
        + _rounds_per_dispatch_count(res, "node3")
    ) > 0, "round-batched rung never integrated a dispatch"


def test_round_batched_dispatch_deterministic():
    """Same-seed determinism of the batched rung's NEW observable
    surface: the babble_mesh_rounds_per_dispatch / babble_mesh_batch_rows
    histograms (observed on the serve thread from DAG facts, never from
    worker timing) and the flight-record stream must be byte-identical
    across two runs while batching is active."""
    kwargs = dict(
        plan="clean", n=4, backend="tpu", mesh_devices=2,
        dispatch_queue_depth=4, dispatch_batch_deadline=0.2,
        dispatch_batch_rows=8, until=None, target_block=2,
    )
    a = run_one(11, **kwargs)
    b = run_one(11, **kwargs)
    assert a["ok"] and b["ok"], (a["error"], b["error"])
    assert a["reached_target"] and b["reached_target"]
    assert a["digest"] == b["digest"]
    assert a["mesh_dispatch"] == b["mesh_dispatch"]
    assert a["flightrec_fingerprint"] == b["flightrec_fingerprint"]
    assert sum(
        _rounds_per_dispatch_count(a, f"node{i}") for i in range(4)
    ) > 0, "batching never active — the determinism assertion is vacuous"


# ----------------------------------------------------------------------
# live rung, pipelined fetch: the lag is one call under the virtual clock
# ----------------------------------------------------------------------

@pytest.mark.parametrize("depth", [1, 4])
def test_live_rung_pipelined_lag_is_one_and_deterministic(monkeypatch, depth):
    """The live rung's pipelined fetch on the virtual clock: a wait reads
    0 there, so whatever `dispatch_queue_depth` caps, every result is
    integrated on the call after its dispatch (tpu/live.py _note_wait
    deepens the lag on waits read from the obs clock, never on a reader
    thread's state), and two same-seed runs replay the same schedule:
    same digest, same flight records, same trace."""
    from babble_tpu.tpu import live as live_mod

    monkeypatch.setitem(live_mod.ENGINE_DEFAULTS, "async_fetch", True)
    kwargs = dict(n=4, seed=9, plan=FaultPlan(name="clean"), backend="tpu",
                  dispatch_queue_depth=depth)

    def run_once():
        cluster = SimCluster(**kwargs)
        try:
            res = cluster.run(until=None, target_block=2)
            integrated = [
                r.fields for sn in cluster.sns
                for r in sn.node.obs.flightrec.records()
                if r.name == "live.integrate"
            ]
            lags = {
                s.attrs["lag_calls"] for sn in cluster.sns
                for s in sn.node.obs.tracer.spans()
                if s.name == "device.fetch"
                and s.attrs["discipline"] == "pipelined"
            }
            return res, integrated, lags
        finally:
            cluster.shutdown()

    a, integrated, lags = run_once()
    b, _, _ = run_once()
    assert integrated and lags == {1}
    assert {(r["lag"], r["blocked"]) for r in integrated} == {(1, 0.0)}
    assert a["digest"] == b["digest"]
    assert a["flightrec_fingerprint"] == b["flightrec_fingerprint"]
    assert a["trace_fingerprint"] == b["trace_fingerprint"]
    assert a["events_run"] == b["events_run"]
