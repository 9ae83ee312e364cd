"""The served path against the plain reference, in one process on XLA:CPU:
four nodes over the in-memory transport, node 0 device-backed, a few hundred
transactions offered to node 0 on `OpenLoopLoadGen`'s schedule. Each node's
DAG, handed to the benchmark's plain reference
(benchmark/reference/hashgraph.py) in that node's insertion order, gives that
node's stamps and blocks; block bodies are equal across nodes; every
transaction is committed exactly once. This is what entry `served` compares
after a window, at a size a test run holds."""

import time

import pytest

from babble_tpu.ingress.loadgen import OpenLoopLoadGen

from benchmark.entries import replay, served
from benchmark.reference import hashgraph as reference
from test_device_backend import build_mixed_cluster
from test_node import load_scale, run_nodes, shutdown_nodes

BACKENDS = ["tpu", "cpu", "cpu", "cpu"]


class Cluster:
    def __init__(self):
        nodes, proxies, _keys, _peers, participants, _ = build_mixed_cluster(
            BACKENDS, sync_limit=500)
        self.nodes, self.participants = nodes, participants
        gen = OpenLoopLoadGen(rate=150.0, clients=1000, burst=8, tx_bytes=32,
                              seed=31)
        self.offered = []
        self.verdicts = []

        class Recording:
            def submit_tx_batch(_, txs, client_id=None):
                self.offered.extend(txs)
                out = proxies[0].submit_tx_batch(txs, client_id=client_id)
                self.verdicts.extend(v.verdict for v in out)
                return out

        try:
            def wait_committed():
                deadline = time.monotonic() + 120 * load_scale()
                want = set(self.offered)
                while not all(
                        want <= set(p.state.get_committed_transactions())
                        for p in proxies):
                    assert time.monotonic() < deadline, "not committed"
                    time.sleep(0.05)

            run_nodes(nodes)
            # one transaction first: node 0 attaches its engine and compiles
            Recording().submit_tx_batch([b"first"], client_id="c0")
            wait_committed()
            self.t0 = time.monotonic()
            self.stats = gen.drive_tcp(Recording(), 3.0)
            wait_committed()
            self.t1 = time.monotonic()
        finally:
            shutdown_nodes(nodes)
        # quiet now: take in what a node's last self-event added
        for node in nodes:
            node.core.run_consensus()
            node.core.flush_device_dispatch()
        self.committed = [p.state.get_committed_transactions() for p in proxies]
        self.chains = [served.chain_of(node.core) for node in nodes]


@pytest.fixture(scope="module")
def cluster():
    return Cluster()


def test_schedule_was_offered_and_acknowledged(cluster):
    assert len(cluster.offered) >= 100
    assert cluster.stats["errors"] == 0
    assert set(cluster.verdicts) <= set(served.ACKED)


def test_node0_was_served_by_the_live_rung(cluster):
    core = cluster.nodes[0].core
    assert core.ladder_rung() == "live"
    assert core.device_consensus_runs > 0
    assert (core.device_consensus_fallbacks, core.live_demotions,
            core.device_attach_failures) == (0, 0, 0)


@pytest.mark.parametrize("i", range(len(BACKENDS)))
def test_reference_gives_each_nodes_stamps_and_blocks(cluster, i):
    core = cluster.nodes[i].core
    inputs, stamps = served.dag_of(core, cluster.participants)
    assert len(stamps) > 50
    want = reference.order(*inputs)
    assert len(want.blocks) > 3
    got = (stamps, [(b.index(), b.round_received(), b.transactions())
                    for b in cluster.chains[i]])
    assert replay.mismatches(got, want) == {
        "events_mismatched": 0, "blocks_mismatched": 0}


def test_block_bodies_are_equal_across_nodes(cluster):
    common = min(len(c) for c in cluster.chains)
    assert common > 3
    assert served.diverged(cluster.chains[0][:common],
                           [c[:common] for c in cluster.chains[1:]]) == 0


def test_every_transaction_is_committed_exactly_once(cluster):
    for got in cluster.committed:
        assert sorted(got) == sorted(cluster.offered)
    lost, duplicated = served.lost_and_duplicated(
        set(cluster.offered), cluster.committed[0],
        [[b.transactions() for b in chain] for chain in cluster.chains],
        quorum=3)
    assert (lost, duplicated) == (0, 0)


def test_lost_and_duplicated_see_what_they_are_for():
    a, b, c = b"a", b"b", b"c"
    chains = [[[a], [b]], [[a], [b]], [[a], [b]], [[a]]]
    assert served.lost_and_duplicated({a, b}, [a, b], chains, 3) == (0, 0)
    # not handed to the client; held by too few; missing on node 0
    assert served.lost_and_duplicated({a, b}, [a], chains, 3)[0] == 1
    assert served.lost_and_duplicated({a, b}, [a, b], chains, 4)[0] == 1
    assert served.lost_and_duplicated({c}, [c], chains, 3)[0] == 1
    # twice in one validator's chain; handed to the client twice
    assert served.lost_and_duplicated(
        {a}, [a], [[[a], [a]], [[a]], [[a]]], 2) == (1, 1)
    assert served.lost_and_duplicated({a}, [a, a], [[[a]]] * 3, 2) == (1, 1)
