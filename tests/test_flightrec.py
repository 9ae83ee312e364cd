"""Black-box flight recorder + SLO engine tests (ISSUE 7:
babble_tpu/obs/flightrec.py, babble_tpu/obs/slo.py, and their wiring
through the node, the watchdog and the simulator).

The unit tests drive a SimClock by hand; the cluster tests run full
4-node simulations on virtual time (well under a second of wall clock
each — no `slow` markers, same rationale as tests/test_sim.py).
"""

import json
import logging

from babble_tpu.obs import FlightRecorder, Observability, SLOEngine
from babble_tpu.obs.flightrec import (
    DEFAULT_DUMP_SUPPRESS_S,
    FLAP_THRESHOLD,
    MAX_DUMP_DOCS,
)
from babble_tpu.sim import FaultPlan, Partition, SimCluster, SimClock

logging.getLogger("babble.sim").setLevel(logging.CRITICAL)
logging.getLogger("babble.flightrec").setLevel(logging.CRITICAL)
logging.getLogger("babble.slo").setLevel(logging.CRITICAL)

# the stall scenario of test_sim.py: a full four-way partition freezes
# round advance on every node while work stays pending
TOTAL_PARTITION = FaultPlan(
    name="total_partition",
    partitions=(
        Partition(start=1.0, end=99.0, groups=((0,), (1,), (2,), (3,))),
    ),
)


# ----------------------------------------------------------------------
# recorder unit tests
# ----------------------------------------------------------------------

def test_ring_bounds_order_and_fingerprint():
    clock = SimClock()
    fr = FlightRecorder(clock=clock, node_id=7, capacity=4)
    for i in range(6):
        clock.advance_to(float(i))
        fr.record("ladder.demote", rung="live", backoff=i)
    assert len(fr) == 4
    assert fr.dropped == 2
    recs = fr.records()
    # oldest-first, the two oldest overwritten
    assert [r.seq for r in recs] == [2, 3, 4, 5]
    assert [r.t for r in recs] == [2.0, 3.0, 4.0, 5.0]
    assert all(r.name == "ladder.demote" for r in recs)

    # byte-identical replay: an identical recorder produces the same
    # stream bytes and fingerprint
    clock2 = SimClock()
    fr2 = FlightRecorder(clock=clock2, node_id=7, capacity=4)
    for i in range(6):
        clock2.advance_to(float(i))
        fr2.record("ladder.demote", rung="live", backoff=i)
    assert fr.stream_bytes() == fr2.stream_bytes()
    assert fr.fingerprint() == fr2.fingerprint()
    # a diverging field diverges the fingerprint
    fr2.record("watchdog.stall", waited=1.0)
    assert fr.fingerprint() != fr2.fingerprint()


def test_dump_document_and_global_suppression():
    clock = SimClock()
    fr = FlightRecorder(clock=clock, node_id=1)
    fr.record("watchdog.stall", waited=2.5, round=3)
    clock.advance_to(5.0)
    assert fr.dump("consensus-stall", waited=2.5) is None  # in-memory
    assert fr.dumps == 1 and len(fr.dump_docs) == 1
    doc = fr.dump_docs[0]
    assert doc["reason"] == "consensus-stall"
    assert doc["node"] == 1
    assert doc["ordinal"] == 1
    assert doc["context"] == {"waited": 2.5}
    assert [r["name"] for r in doc["records"]] == ["watchdog.stall"]

    # suppression is GLOBAL across reasons: the first trigger of an
    # episode owns the ring; the cascade it causes (stall -> SLO breach
    # -> flap) must not dump near-identical copies
    fr.dump("slo-breach", objective="round_advance")
    clock.advance_to(6.0)
    fr.dump("demotion-flap")
    assert fr.dumps == 1
    assert fr.dumps_suppressed == 2
    # ... and expires on the Clock
    clock.advance_to(5.0 + DEFAULT_DUMP_SUPPRESS_S)
    fr.dump("slo-breach", objective="round_advance")
    assert fr.dumps == 2
    assert fr.dump_docs[-1]["reason"] == "slo-breach"

    # the in-memory dump list is bounded
    for i in range(MAX_DUMP_DOCS + 3):
        clock.advance_to(clock.now + DEFAULT_DUMP_SUPPRESS_S)
        fr.dump("crash")
    assert len(fr.dump_docs) == MAX_DUMP_DOCS


def test_dump_writes_deterministic_artifact(tmp_path):
    clock = SimClock()
    fr = FlightRecorder(clock=clock, node_id=3, dump_dir=str(tmp_path))
    fr.record("fork.evidence", creator="abcd", index=2)
    path = fr.dump("fork", creator="abcd")
    assert path is not None
    # deterministic name: node + ordinal + reason, no timestamps
    assert path.endswith("flightrec-node3-01-fork.json")
    with open(path) as f:
        doc = json.load(f)
    assert doc["reason"] == "fork"
    assert [r["name"] for r in doc["records"]] == ["fork.evidence"]


def test_flap_detection_dumps_once():
    clock = SimClock()
    fr = FlightRecorder(clock=clock, node_id=0)
    for i in range(FLAP_THRESHOLD - 1):
        clock.advance_to(float(i))
        fr.record("ladder.demote", rung="live")
        assert fr.note_flap("demotion") is None
    assert fr.dumps == 0
    clock.advance_to(float(FLAP_THRESHOLD - 1))
    fr.record("ladder.demote", rung="live")
    fr.note_flap("demotion")
    assert fr.dumps == 1
    assert fr.dump_docs[0]["reason"] == "demotion-flap"
    # spaced-out demotions (outside the window) never count as a flap
    fr2 = FlightRecorder(clock=clock, node_id=0)
    for i in range(FLAP_THRESHOLD * 2):
        clock.advance_to(clock.now + 20.0)
        fr2.note_flap("demotion")
    assert fr2.dumps == 0


# ----------------------------------------------------------------------
# SLO engine unit tests
# ----------------------------------------------------------------------

def test_slo_tick_evaluates_once_a_second_whatever_the_heartbeat():
    """A node's heartbeat calls `tick`: a pass a sixtieth of the shortest
    window, so the samples a pass walks stay a few hundred (one a 10 ms
    heartbeat was 30,000 by the longest window's end, walked on the gossip
    loop's thread); `evaluate` itself still passes every time."""
    clock = SimClock()
    obs = Observability(clock=clock)
    depth = obs.gauge("babble_device_queue_depth", "x")
    slo = SLOEngine(obs)
    slo.objective("queue_depth", series="babble_device_queue_depth",
                  kind="below", threshold=4.5)
    depth.set(40.0)
    for k in range(1000):  # ten seconds of a 10 ms heartbeat
        clock.advance_to(k * 0.01)
        slo.tick()
    assert len(slo._samples) == 10
    assert slo.breached() == ["queue_depth"]  # and it still judges
    slo.evaluate()
    slo.evaluate()
    assert len(slo._samples) == 12


def test_slo_gauge_breach_fires_gauges_counter_and_dump():
    clock = SimClock()
    obs = Observability(clock=clock)
    depth = obs.gauge("babble_device_queue_depth", "x")
    slo = SLOEngine(obs)
    slo.objective("queue_depth", series="babble_device_queue_depth",
                  kind="below", threshold=4.5)

    depth.set(2.0)
    status = slo.evaluate()
    assert slo.breached() == []
    (obj,) = status["objectives"]
    assert obj["breached"] is False and obj["burn"]["60s"] is not None

    depth.set(40.0)
    clock.advance_to(1.0)
    slo.evaluate()
    # young engine: no sample predates the windows, so evaluation is
    # cumulative — mean(2, 40) over threshold 4.5 burns in every window
    assert slo.breached() == ["queue_depth"]
    snap = obs.registry.snapshot()
    assert snap["babble_slo_breached"]["series"]["queue_depth"] == 1.0
    assert snap["babble_slo_breaches_total"]["series"]["queue_depth"] == 1.0
    # the breach transition recorded itself and dumped the ring
    names = [r.name for r in obs.flightrec.records()]
    assert "slo.breach" in names
    assert obs.flightrec.dump_docs[-1]["reason"] == "slo-breach"
    breaches_before = snap["babble_slo_breaches_total"]["series"]["queue_depth"]

    # still breached next tick: no second transition, no second dump
    clock.advance_to(2.0)
    slo.evaluate()
    snap = obs.registry.snapshot()
    assert (
        snap["babble_slo_breaches_total"]["series"]["queue_depth"]
        == breaches_before
    )
    assert obs.flightrec.dumps == 1


def test_slo_histogram_p_below_breach_and_recovery_shape():
    clock = SimClock()
    obs = Observability(clock=clock)
    lat = obs.histogram("babble_commit_latency_seconds", "x")
    slo = SLOEngine(obs)
    slo.objective("commit_p99", series="babble_commit_latency_seconds",
                  kind="p_below", threshold=0.5, quantile=0.99)

    # all observations comfortably under the threshold: no breach
    for _ in range(10):
        lat.observe(0.01)
    slo.evaluate()
    assert slo.breached() == []

    # every new observation blows the threshold: bad/budget burns hot
    for _ in range(10):
        lat.observe(8.0)
    clock.advance_to(1.0)
    status = slo.evaluate()
    assert slo.breached() == ["commit_p99"]
    (obj,) = status["objectives"]
    assert obj["burn"]["60s"] > 1.0


def test_slo_multi_window_spike_does_not_breach():
    """A brief spike burns the short window but not the long one —
    multi-window burn rate pages nobody. A sustained regression burns
    both and does."""
    clock = SimClock()
    obs = Observability(clock=clock)
    g = obs.gauge("babble_consensus_stalled", "x")
    slo = SLOEngine(obs, windows=(10.0, 60.0))
    slo.objective("round_advance", series="babble_consensus_stalled",
                  kind="below", threshold=0.5)

    # 65s of healthy samples age the engine past its longest window
    for i in range(14):
        clock.advance_to(i * 5.0)
        g.set(0.0)
        slo.evaluate()
    assert slo.breached() == []

    # one 5s spike: the 10s window burns, the 60s window stays cool
    g.set(1.0)
    clock.advance_to(70.0)
    status = slo.evaluate()
    assert slo.breached() == []
    (obj,) = status["objectives"]
    assert obj["burn"]["10s"] >= 1.0
    assert obj["burn"]["60s"] < 1.0

    # sustained: once the long window's mean crosses too, it breaches
    t = 70.0
    while t < 140.0 and not slo.breached():
        t += 5.0
        clock.advance_to(t)
        slo.evaluate()
    assert slo.breached() == ["round_advance"]


def test_bench_slo_gates():
    """bench.py --slo passes at the r05 headline (1.55M events/s) and
    fails a degraded run; bench_dispatch.py --slo mirrors it over the
    blocked-ms ceiling. Gates run against synthetic registries — no
    device pipeline in unit tests."""
    import bench
    import bench_dispatch

    obs = Observability()
    obs.gauge("babble_bench_events_per_second", "x").set(1_550_165.4)
    ok, status = bench.slo_gate(obs, 1_000_000.0)
    assert ok
    (obj,) = status["objectives"]
    assert obj["breached"] is False

    degraded = Observability()
    degraded.gauge("babble_bench_events_per_second", "x").set(400_000.0)
    ok, status = bench.slo_gate(degraded, 1_000_000.0)
    assert not ok

    dobs = Observability()
    hist = dobs.histogram("babble_bench_dispatch_blocked_seconds", "x",
                          labels=("path",))
    hist.labels(path="queued_mesh").observe(0.020)
    ok, _ = bench_dispatch.slo_gate(dobs, 0.150)
    assert ok
    slow = Observability()
    shist = slow.histogram("babble_bench_dispatch_blocked_seconds", "x",
                           labels=("path",))
    shist.labels(path="queued_mesh").observe(0.500)
    ok, _ = bench_dispatch.slo_gate(slow, 0.150)
    assert not ok


# ----------------------------------------------------------------------
# simulator integration (the acceptance scenarios)
# ----------------------------------------------------------------------

def _stall_cluster(seed=3):
    return SimCluster(n=4, seed=seed, plan=TOTAL_PARTITION,
                      stall_deadline=2.0)


def test_stall_run_exactly_one_auto_dump_per_node():
    """A full four-way partition stalls every node: the watchdog's stall
    detection must auto-dump the ring exactly once per node (reason
    consensus-stall, containing the watchdog.stall record), with the SLO
    breach that follows suppressed by the global dump window rather than
    producing a second near-identical dump."""
    cluster = _stall_cluster()
    try:
        cluster.run(until=8.0)
        for sn in cluster.sns:
            fr = sn.node.obs.flightrec
            assert fr.dumps == 1, sn.name
            doc = fr.dump_docs[0]
            assert doc["reason"] == "consensus-stall"
            assert "watchdog.stall" in [r["name"] for r in doc["records"]]
            # the round-advance SLO also breached — recorded in the
            # ring, its dump suppressed by the stall's
            names = [r.name for r in fr.records()]
            assert "slo.breach" in names
            assert fr.dumps_suppressed >= 1
            snap = sn.node.obs.registry.snapshot()
            assert snap["babble_consensus_stalls_total"]["series"][""] == 1.0
            assert (
                snap["babble_slo_breached"]["series"]["round_advance"] == 1.0
            )
    finally:
        cluster.shutdown()


def test_stall_run_streams_and_dumps_byte_identical_across_replays():
    """Same-seed replays must produce byte-identical record streams AND
    byte-identical dump documents on every node — the flight recorder
    joins the sim's determinism fingerprint, so any nondeterministic
    field (wall-clock, thread identity) fails here."""
    def capture():
        cluster = _stall_cluster()
        try:
            res = cluster.run(until=8.0)
            streams = {
                sn.name: sn.node.obs.flightrec.stream_bytes()
                for sn in cluster.sns
            }
            dumps = {
                sn.name: json.dumps(sn.node.obs.flightrec.dump_docs,
                                    sort_keys=True)
                for sn in cluster.sns
            }
            return res, streams, dumps
        finally:
            cluster.shutdown()

    res_a, streams_a, dumps_a = capture()
    res_b, streams_b, dumps_b = capture()
    assert streams_a == streams_b
    assert dumps_a == dumps_b
    assert res_a["flightrec_fingerprint"] == res_b["flightrec_fingerprint"]
    assert res_a["flightrec_records"] == res_b["flightrec_records"]
    # non-empty: the stall actually put records in the rings
    assert all(n > 0 for n in res_a["flightrec_records"].values())


def test_slo_breach_run_auto_dumps_and_replays_identically():
    """A run whose only incident is an SLO breach (commit-latency
    objective tightened to an unmeetable threshold on one node) must
    auto-produce exactly one slo-breach dump on that node, byte-identical
    across same-seed replays."""
    def run_once():
        cluster = SimCluster(n=4, seed=11, plan=FaultPlan(name="clean"))
        try:
            # every commit is now an SLO violation on node0; the other
            # nodes keep the default objective and stay healthy
            obj = cluster.sns[0].node.slo._objectives["submit_commit_p99"]
            obj.threshold = 1e-9
            cluster.run(until=12.0)
            sn0 = cluster.sns[0]
            fr = sn0.node.obs.flightrec
            reasons = [d["reason"] for d in fr.dump_docs]
            healthy = [
                d
                for sn in cluster.sns[1:]
                for d in sn.node.obs.flightrec.dump_docs
            ]
            return (
                reasons,
                json.dumps(fr.dump_docs, sort_keys=True),
                healthy,
                sn0.node.obs.registry.snapshot()["babble_slo_breached"],
            )
        finally:
            cluster.shutdown()

    reasons_a, dumps_a, healthy_a, breached_a = run_once()
    reasons_b, dumps_b, _, _ = run_once()
    assert reasons_a == ["slo-breach"]
    assert reasons_a == reasons_b
    assert dumps_a == dumps_b
    assert healthy_a == []  # untampered nodes breach nothing
    assert breached_a["series"]["submit_commit_p99"] == 1.0


def test_queued_mesh_run_records_dispatch_lifecycle_deterministically():
    """On the queued-mesh backend the recorder captures the dispatch
    lifecycle (enqueue/integrate) — the records the ISSUE wants in the
    ring ahead of a dump — and the stream stays replay-identical, which
    pins that no record leaks wall-clock or thread state from the
    dispatch worker."""
    kwargs = dict(n=4, seed=9, plan=FaultPlan(name="clean"), backend="tpu",
                  mesh_devices=2, dispatch_queue_depth=4,
                  dispatch_batch_deadline=0.2)

    def run_once():
        cluster = SimCluster(**kwargs)
        try:
            res = cluster.run(until=None, target_block=2)
            names = {
                r.name
                for sn in cluster.sns
                for r in sn.node.obs.flightrec.records()
            }
            return res["flightrec_fingerprint"], names
        finally:
            cluster.shutdown()

    fp_a, names_a = run_once()
    fp_b, names_b = run_once()
    assert fp_a == fp_b
    assert names_a == names_b
    assert "dispatch.enqueue" in names_a
    assert "dispatch.integrate" in names_a
