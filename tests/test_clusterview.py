"""Cluster health plane tests (ISSUE 20, babble_tpu/obs/clusterview.py,
docs/observability.md):

- digest federation mechanics: versioned-entry validation, newest-t-wins
  merge, own-addr exclusion, opaque unknown keys, MAX_FLEET bound;
- failure-kind classification and the contact ledger (silence
  accumulates, refusal and success clear);
- partition inference on the sim fabric: the partition_heal preset must
  trip `cluster.partition_suspected` with the exact ground-truth
  components on majority-side nodes (the isolated minority never
  self-suspects), emit `cluster.partition_healed` after the heal, and
  replay byte-identically across same-seed runs; lossy and crash plans
  must never trip (false-positive guard);
- the out-of-band piggyback contract: a cluster_health=False run commits
  the byte-identical digest of an enabled run (wire payloads unchanged
  when the "Cluster" key is empty, the Traces differential argument);
- determinism of result()["cluster_health"] / cluster_health_fingerprint
  for CPU-only and mixed CPU + queued-mesh clusters;
- the live TCP surfaces: GET /health/digest + GET /debug/cluster on a
  real Service over a gossiping cluster, the `babble-tpu status`
  renderer over that document, and the commit-frontier gauges serving
  digest, /stats and observatory from one source of truth.
"""

import json
import time
import urllib.request

import pytest

from babble_tpu.cli import render_status
from babble_tpu.obs import Observability, failure_kind
from babble_tpu.obs.clusterview import MAX_FLEET, MIN_SILENT_FAILS
from babble_tpu.service import Service
from babble_tpu.sim import SimCluster, SimClock, preset_plan, run_one

from test_node import (
    bombard_and_wait,
    init_nodes,
    run_nodes,
    shutdown_nodes,
)

# partition_heal preset geometry (sim/faults.py): minority {sim-0} cut
# from {sim-1, sim-2, sim-3} over virtual [1.0, 4.0)
PARTITION_START = 1.0
PARTITION_END = 4.0
GROUND_TRUTH = [["sim-0"], ["sim-1", "sim-2", "sim-3"]]


def _partition_records(cluster):
    """[(node_name, record_name, fields)] for every cluster.partition_*
    flight record across the cluster's live nodes."""
    out = []
    for sn in cluster.sns:
        if sn.node is None:
            continue
        for r in sn.node.obs.flightrec.to_json()["records"]:
            if r["name"].startswith("cluster.partition"):
                out.append((sn.name, r["name"], r["fields"], r["t"]))
    return out


# ----------------------------------------------------------------------
# unit: failure classification + contact ledger
# ----------------------------------------------------------------------

def test_failure_kind_classification():
    # silence: the far side never answered
    assert failure_kind("partitioned: sim-0 -/- sim-1") == "silence"
    assert failure_kind("dropped: sim-2 -> sim-0") == "silence"
    assert failure_kind("command timed out") == "silence"
    assert failure_kind(TimeoutError("connect timeout")) == "silence"
    # refusal: the path answered with an error — proves reachability
    assert failure_kind("peer down") == "refusal"
    assert failure_kind("node not ready") == "refusal"
    assert failure_kind(ConnectionRefusedError("refused")) == "refusal"
    assert failure_kind(None) == "refusal"


def _bound_observatory(clock, addr="n0", block=5, deadline=1.0):
    obs = Observability(clock=clock)
    cv = obs.clusterview
    cv.bind_local(
        addr, digest_fn=lambda: {"block": block, "round": 3},
        staleness_deadline=deadline,
    )
    return obs, cv


def _digest(addr, t, block, **extra):
    d = {"v": 1, "addr": addr, "t": t, "block": block}
    d.update(extra)
    return d


def test_absorb_validates_and_merges_newest_t_wins():
    clock = SimClock()
    _, cv = _bound_observatory(clock)
    # invalid entries: dropped wholesale (compat rule)
    cv.absorb([
        "not a dict",
        {"addr": "n1", "t": 1.0, "block": 2},          # no v
        _digest("n1", 1.0, 2, v=0),                     # v < 1
        {"v": 1, "t": 1.0, "block": 2},                 # no addr
        {"v": 1, "addr": "n1", "block": 2},             # no t
        {"v": 1, "addr": "n1", "t": 1.0},               # no block
        _digest("n0", 1.0, 2),                          # own addr
    ])
    assert set(cv.fleet()) == {"n0"}
    # valid entry lands; unknown keys ride opaquely; newest-t wins
    cv.absorb([_digest("n1", 1.0, 2, future_field="kept")])
    assert cv.fleet()["n1"]["future_field"] == "kept"
    cv.absorb([_digest("n1", 0.5, 9)])  # older t: ignored
    assert cv.fleet()["n1"]["block"] == 2
    cv.absorb([_digest("n1", 2.0, 3)])
    assert cv.fleet()["n1"]["block"] == 3
    # a v=2 digest from a newer node is accepted field-wise
    cv.absorb([_digest("n2", 1.0, 7, v=2)])
    assert cv.fleet()["n2"]["v"] == 2


def test_absorb_bounds_fleet_table():
    clock = SimClock()
    _, cv = _bound_observatory(clock)
    cv.fleet()  # stores the own digest, as every gossip exchange does
    cv.absorb([_digest(f"p{i}", 1.0, i) for i in range(MAX_FLEET + 10)])
    assert len(cv.fleet()) == MAX_FLEET  # own + MAX_FLEET-1 others
    # known origins still update when the table is full
    survivor = sorted(a for a in cv.fleet() if a != "n0")[0]
    cv.absorb([_digest(survivor, 2.0, 99)])
    assert cv.fleet()[survivor]["block"] == 99


def test_note_contact_refusal_and_success_clear_silence():
    clock = SimClock()
    _, cv = _bound_observatory(clock)
    for _ in range(MIN_SILENT_FAILS):
        cv.note_contact("n1", False, t_start=clock.now, err="timed out")
    c = cv._contacts["n1"]
    assert c.silent_since is not None
    assert c.silent_fails == MIN_SILENT_FAILS
    # a refusal proves the path answers: silence state resets
    cv.note_contact("n1", False, err="peer down")
    assert c.silent_since is None and c.silent_fails == 0
    # rebuild silence, then a success clears it and stamps last_ok
    cv.note_contact("n1", False, t_start=clock.now, err="timed out")
    cv.note_contact("n1", True)
    assert c.silent_since is None and c.last_ok == clock.now


def test_suspicion_state_machine_edges():
    """Unit-level rising/falling edge: a silent peer whose digest also
    went stale, plus fresh counter-evidence postdating the silence,
    trips suspicion; the silent peer answering heals it."""
    clock = SimClock()
    obs, cv = _bound_observatory(clock, deadline=1.0)
    cv.absorb([_digest("n1", 0.0, 1), _digest("n2", 0.0, 1)])
    # n1 goes silent at t=0.5; n2 keeps answering (fresh digest + ok)
    clock.now = 0.5
    cv.note_contact("n1", False, t_start=0.5, err="timed out")
    clock.now = 1.0
    cv.note_contact("n1", False, t_start=0.9, err="timed out")
    clock.now = 1.6  # silence span 1.1 >= deadline; n1 digest age 1.6
    cv.absorb([_digest("n2", 1.5, 2)])
    cv.note_contact("n2", True)
    cv.check()
    s = cv.suspicion()
    assert s["suspected"] is True
    assert s["components"] == [["n0", "n2"], ["n1"]]
    assert cv.series_value("babble_cluster_partition_suspected") == 1.0
    names = [
        r["name"] for r in obs.flightrec.to_json()["records"]
        if r["name"].startswith("cluster.")
    ]
    assert names == ["cluster.partition_suspected"]
    # falling edge: the silent peer answers again
    cv.note_contact("n1", True)
    cv.check()
    assert cv.suspicion()["suspected"] is False
    names = [
        r["name"] for r in obs.flightrec.to_json()["records"]
        if r["name"].startswith("cluster.")
    ]
    assert names == [
        "cluster.partition_suspected", "cluster.partition_healed",
    ]


def test_no_suspicion_without_fresh_counter_evidence():
    """A fully isolated node sees every path silent and NO fresh peers
    — it must never self-diagnose a partition (that is the watchdog's
    stall, not a partition verdict)."""
    clock = SimClock()
    _, cv = _bound_observatory(clock, deadline=1.0)
    for peer in ("n1", "n2"):
        cv.note_contact(peer, False, t_start=0.0, err="timed out")
        cv.note_contact(peer, False, t_start=0.1, err="timed out")
    clock.now = 2.0
    cv.check()
    assert cv.suspicion()["suspected"] is False


# ----------------------------------------------------------------------
# sim: partition inference end to end
# ----------------------------------------------------------------------

def test_partition_heal_trips_exact_components_then_heals():
    cluster = SimCluster(
        n=4, seed=0, plan=preset_plan("partition_heal", 4),
        cluster_staleness=1.5,
    )
    try:
        res = cluster.run(until=30.0, target_block=8)
        assert res["net"]["severed"] > 0
        recs = _partition_records(cluster)
    finally:
        cluster.shutdown()
    # A node learns of the cut from dials that time out and of the heal
    # only when news crosses it, so a suspicion may trail the heal, but
    # only on evidence from inside the window: a dial to the minority that
    # started before PARTITION_END surfaces as silence up to `tcp_timeout`
    # later; the node then needs one exchange with a peer it can reach (a
    # tick to start it, a round trip) for its counter-evidence, and one
    # more tick to run the check. Every later dial to the minority
    # succeeds, and nothing can trip any more.
    tick = 2 * cluster.heartbeat  # the control timer fires in [1, 2) beats
    lat = cluster.plan.latency
    heal_lag = cluster.tcp_timeout + tick + 2 * (lat.base + lat.jitter) + tick
    suspects = [r for r in recs if r[1] == "cluster.partition_suspected"]
    heals = [r for r in recs if r[1] == "cluster.partition_healed"]
    assert suspects, "no node suspected the partition"
    by_node = {r[0] for r in suspects}
    # the isolated minority (sim-0 = node0) must never self-suspect
    assert "node0" not in by_node
    for _node, _name, fields, t in suspects:
        assert json.loads(fields["components"]) == GROUND_TRUTH
        assert PARTITION_START < t < PARTITION_END + heal_lag
    # detected while the partition was live, not only in retrospect
    assert min(t for *_, t in suspects) < PARTITION_END
    # every suspicion episode healed once the partition lifted
    assert {r[0] for r in heals} == by_node
    for _node, _name, _fields, t in heals:
        assert t >= PARTITION_END


def test_partition_inference_byte_identical_same_seed():
    def one():
        cluster = SimCluster(
            n=4, seed=0, plan=preset_plan("partition_heal", 4),
            cluster_staleness=1.5,
        )
        try:
            res = cluster.run(until=30.0, target_block=8)
            return (
                json.dumps(_partition_records(cluster), sort_keys=True),
                json.dumps(res["cluster_health"], sort_keys=True),
                res["cluster_health_fingerprint"],
            )
        finally:
            cluster.shutdown()

    a, b = one(), one()
    assert a[0] == b[0]  # every partition record, byte for byte
    assert a[1] == b[1]
    assert a[2] == b[2]


@pytest.mark.parametrize("plan_name", ["lossy", "crash_restart"])
def test_lossy_and_crash_plans_never_trip(plan_name):
    """False-positive guard: loss leaves the peer's digest flowing via
    relays, a crash fails with refusals — neither is a partition."""
    for seed in (0, 1):
        cluster = SimCluster(
            n=4, seed=seed, plan=preset_plan(plan_name, 4),
            cluster_staleness=1.5,
        )
        try:
            cluster.run(until=30.0, target_block=6)
            recs = _partition_records(cluster)
        finally:
            cluster.shutdown()
        assert recs == [], f"{plan_name} seed {seed} tripped: {recs}"


# ----------------------------------------------------------------------
# sim: piggyback differential + determinism fingerprint
# ----------------------------------------------------------------------

def test_disabling_health_plane_leaves_commit_digest_unchanged():
    """The Traces argument, applied to the "Cluster" wire key: digests
    ride out-of-band, so a health-plane-disabled cluster must commit the
    byte-identical history of an enabled one for the same seed."""
    a = run_one(5, plan="clean", n=4, until=None, target_block=3,
                cluster_health=True)
    b = run_one(5, plan="clean", n=4, until=None, target_block=3,
                cluster_health=False)
    assert a["ok"] and b["ok"], (a["error"], b["error"])
    assert a["digest"] == b["digest"]
    assert a["events_run"] == b["events_run"]
    assert a["virtual_time"] == b["virtual_time"]
    # the disabled run reports the plane as absent, not as zeroes
    assert a["cluster_health"]["nodes"]
    assert b["cluster_health"]["nodes"] == {}


def test_cluster_health_deterministic_cpu_and_mixed_mesh():
    cases = {
        "cpu": dict(plan="clean", n=4, until=None, target_block=3),
        "mixed": dict(
            plan="clean", n=4, backend=("cpu", "cpu", "tpu", "tpu"),
            mesh_devices=2, dispatch_queue_depth=4,
            dispatch_batch_deadline=0.2, until=None, target_block=2,
        ),
    }
    for label, kwargs in cases.items():
        a = run_one(7, **kwargs)
        b = run_one(7, **kwargs)
        assert a["ok"] and b["ok"], (label, a["error"], b["error"])
        assert (
            a["cluster_health_fingerprint"]
            == b["cluster_health_fingerprint"]
        ), label
        assert json.dumps(a["cluster_health"], sort_keys=True) == (
            json.dumps(b["cluster_health"], sort_keys=True)
        ), label
        summary = a["cluster_health"]["summary"]
        assert summary["min_frontier_agreement"] == 1.0, label
        assert summary["partitions_suspected"] == 0, label


def test_sweep_summary_carries_cluster_health_row():
    from babble_tpu.sim import run_sweep

    summary = run_sweep(range(2), plan="clean", n=4, until=None,
                        target_block=2)
    assert summary["failed"] == 0
    row = summary["cluster_health"]
    assert row["min_frontier_agreement"] == 1.0
    assert row["partitions_suspected"] == 0
    assert row["suspected_components"] == []
    assert row["max_commit_skew_blocks"] >= 0.0


# ----------------------------------------------------------------------
# live TCP: /health/digest, /debug/cluster, the status renderer
# ----------------------------------------------------------------------

def _get(url):
    with urllib.request.urlopen(url, timeout=5) as resp:
        return json.loads(resp.read())


def test_live_service_cluster_endpoints_and_renderer():
    nodes, proxies = init_nodes(3)
    svc = Service("127.0.0.1:0", nodes[0])
    try:
        run_nodes(nodes)
        svc.serve()
        base = f"http://{svc.local_addr()}"
        bombard_and_wait(nodes, proxies, target_block=1)
        # let the frontier settle (no new txs -> no new blocks) so the
        # digest/gauge/stats triple is read from a stable index
        import time

        idx = -2
        for _ in range(200):
            cur = nodes[0].core.get_last_block_index()
            if cur == idx:
                break
            idx = cur
            time.sleep(0.05)

        # the cluster keeps gossiping and may commit once more between
        # two requests: take the triple between two equal readings of the
        # frontier gauge (it only rises), so that the three are compared
        # at one index
        g = nodes[0].obs.registry.get("babble_commit_frontier_block")
        for _ in range(50):
            gauge = int(g.value())
            digest = _get(base + "/health/digest")
            stats = _get(base + "/stats")
            if int(g.value()) == gauge:
                break
        assert digest["addr"] == nodes[0].local_addr
        assert digest["v"] >= 1
        assert isinstance(digest["block"], int) and digest["block"] >= 1
        assert digest["rung"] in (
            "cpu", "cpu_fallback", "one_shot", "live", "mesh",
            "mesh_queued",
        )

        # one source of truth: digest block == frontier gauge == /stats
        assert int(stats["commit_frontier_block"]) == digest["block"]
        assert gauge == digest["block"]
        assert int(stats["commit_frontier_round"]) == digest["round"]

        # gossip has run to a committed block, so the fleet table
        # federates promptly — but digest piggyback rides on exchanges
        # node 0 happens to make, so poll briefly rather than snapshot
        doc = _get(base + "/debug/cluster")
        for _ in range(200):
            if len(doc["fleet"]) == 3:
                break
            time.sleep(0.05)
            doc = _get(base + "/debug/cluster")
        assert doc["enabled"] is True
        assert doc["addr"] == nodes[0].local_addr
        assert len(doc["fleet"]) == 3
        assert doc["suspicion"]["suspected"] is False
        assert (
            doc["derived"]["babble_cluster_frontier_agreement"] == 1.0
        )

        out = render_status(doc)
        assert "babble-tpu cluster status" in out
        assert nodes[0].local_addr in out
        assert "partition: none suspected" in out
    finally:
        svc.shutdown()
        shutdown_nodes(nodes)


def test_render_status_flags_disagreement_and_partition():
    doc = {
        "addr": "a:1",
        "fleet": {
            "a:1": {"block": 5, "round": 7, "rung": "cpu", "undecided": 0,
                    "txs": 0, "sigs": 0, "ingress": 0, "forks": 0,
                    "age": 0.0},
            "b:2": {"block": 3, "round": 6, "rung": "mesh_queued",
                    "undecided": 2, "txs": 1, "sigs": 0, "ingress": 4,
                    "forks": 0, "age": 1.2},
        },
        "derived": {
            "babble_cluster_commit_skew_blocks": 2.0,
            "babble_cluster_round_skew": 1.0,
            "babble_cluster_frontier_agreement": 0.5,
            "babble_cluster_fame_latency_rounds": 2.0,
        },
        "suspicion": {"suspected": True,
                      "components": [["a:1"], ["b:2"]]},
    }
    out = render_status(doc)
    assert "2 nodes" in out
    assert "commit skew: 2 blocks" in out
    assert "FRONTIER DISAGREEMENT" in out
    assert "PARTITION SUSPECTED" in out
    assert "mesh_queued" in out


# ----------------------------------------------------------------------
# watchdog satellite: local lag vs cluster-wide stall
# ----------------------------------------------------------------------

def test_watchdog_cluster_context_classifies_lag():
    clock = SimClock()
    obs, cv = _bound_observatory(clock, block=3)
    from babble_tpu.node.watchdog import LivenessWatchdog

    wd = LivenessWatchdog(
        clock, obs, __import__("logging").getLogger("t"),
        deadline=1.0, round_fn=lambda: 1, pending_fn=lambda: 1,
    )
    # no observatory bound: neutral context
    assert wd._cluster_context() == (0.0, [])
    wd.clusterview = cv
    # peers ahead of our frontier -> local lag, named peers
    cv.absorb([_digest("n1", 0.1, 9), _digest("n2", 0.1, 3)])
    skew, ahead = wd._cluster_context()
    assert skew == 6.0
    assert ahead == ["n1"]
