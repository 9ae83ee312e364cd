"""The served path's spans and totals (docs/observability.md "Trace spans"):
every one appears on node 0 of a four-node cluster under load, under the
parent it should have; `totals_between` reads them over a window; and
`core.run_consensus` minus its top-level children still accounts for the
call (PERF.md section 5)."""

import pytest

from test_served_reference import Cluster

# span -> the names its parent may have (None: no span open on the thread)
PARENTS = {
    "node.pull": {"node.gossip"},
    "node.push": {"node.gossip"},
    "core.sync": {"node.gossip", None},  # outbound pull; inbound eager sync
    "sync.decode": {"core.sync"},
    "sync.insert": {"core.sync"},
    "sync.self_event": {"core.sync", "node.gossip"},  # a sync; a push's own event
    "core.run_consensus": {"node.gossip", None},
    "node.gossip": {None},
    "node.serve_sync": {None},
    "commit.deliver": {None},
}
COUNT_TOTALS = ("node.lock_wait", "sync.events", "self_event.txs",
                "ingress.wait", "live.launch.step", "insert")
RING_RECORDS = ("gossip", "commit")  # kept, with attributes, for /debug/trace
CALL_CHILDREN = ("device.dispatch", "live.pack", "device.fetch",
                 "live.integrate", "live.rebase",
                 "consensus.process_decided_rounds",
                 "consensus.process_sig_pool")


class Run:
    """Node 0's tracer after the cluster of test_served_reference has run:
    the ring, the totals, and the totals over the offered window."""

    def __init__(self):
        cluster = Cluster()
        tracer = cluster.nodes[0].obs.tracer
        # a push's own event is followed by no consensus call
        cluster.nodes[0].core.hg.hand_over_inserts()
        self.offered = cluster.offered
        self.spans = tracer.spans()
        self.totals = tracer.totals()
        self.window = tracer.totals_between(cluster.t0, cluster.t1)


@pytest.fixture(scope="module")
def run():
    return Run()


@pytest.mark.parametrize("name", sorted(PARENTS))
def test_span_appears_under_its_parent(run, name):
    by_id = {sp.id: sp for sp in run.spans}
    mine = [sp for sp in run.spans if sp.name == name]
    assert mine, f"no {name} span in the ring"
    assert run.totals[name][0] >= len(mine)
    # a parent may have left the ring already; judge those still in it
    seen = {by_id[sp.parent].name for sp in mine if sp.parent in by_id}
    seen |= {None for sp in mine if sp.parent is None}
    assert seen and seen <= PARENTS[name], (name, seen)


@pytest.mark.parametrize("name", COUNT_TOTALS)
def test_total_is_kept_and_read_over_a_window(run, name):
    assert run.totals.get(name, (0, 0.0))[0] > 0, name
    assert run.window.get(name, (0, 0.0))[0] > 0, name
    assert run.window[name][0] <= run.totals[name][0]
    assert run.window[name][1] >= 0.0


@pytest.mark.parametrize("name", RING_RECORDS)
def test_ring_record_keeps_its_attributes(run, name):
    want = {"gossip": {"peer", "result"}, "commit": {"block", "txs"}}[name]
    mine = [sp for sp in run.spans if sp.name == name]
    assert mine and all(want <= set(sp.attrs) for sp in mine)


def test_totals_agree_with_each_other(run):
    t = run.totals
    # every pooled transaction waited once, and its self-event carried it
    assert t["ingress.wait"][0] == t["self_event.txs"][0] == len(run.offered)
    assert t["ingress.wait"][1] > 0.0
    # a sync's events are inserted, and so is each self-event
    assert t["sync.events"][0] + t["sync.self_event"][0] == t["insert"][0]
    assert t["sync.decode"][0] == t["sync.insert"][0] == t["core.sync"][0]
    # an exchange pulls once and pushes at most once
    assert t["node.push"][0] <= t["node.pull"][0] == t["node.gossip"][0]
    assert t["node.pull"][1] + t["node.push"][1] <= t["node.gossip"][1]
    # every block was handed to the application
    assert t["commit.deliver"][0] == t["commit"][0] > 0
    # four validators' small syncs take the straight-line program
    assert t["live.launch.step"][0] >= t.get("live.launch.train", (0, 0.0))[0]


def test_consensus_call_is_accounted_for(run):
    w = run.window
    calls, total = w["core.run_consensus"]
    assert calls > 10
    children = sum(w.get(name, (0, 0.0))[1] for name in CALL_CHILDREN)
    assert 0.0 <= total - children < 0.5 * total
