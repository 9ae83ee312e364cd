#!/usr/bin/env python3
"""Adversarial-timing soak harness for the fast-sync state machine
(VERDICT r3 #5: the committed repro path for wedge-family bugs).

Two scenarios, both derived from the /tmp instrumented harness that found
round 3's three fast-sync livelocks (unservable anchors, mass-flip
refusals, chain rewinds — commit 57ea9c7):

- ``chained``: three phases ending with a joiner whose ONLY donor is a
  node that itself fast-synced (chained-donor fast-forward: the donor
  serves a section assembled from its own post-reset store).
- ``reattach``: a device-backend node is killed, left behind past the
  sync limit, recycled, and must fast-sync back in and re-attach its
  live device engine under trickle traffic.

On stall: per-node state lines (node state, block index, core-lock
state, work-queue depth, sync errors) plus full faulthandler thread
dumps, repeated over several minutes to show whether the cluster is
wedged or merely slow. A watchdog thread dump fires every 10 minutes
regardless.

Usage:
    python scripts/soak_fastsync.py [chained|reattach|all] [--iters N]
    make soak            # 10 iterations of both scenarios

The reference's analog is demo/watch.sh polling /stats on a long-running
testnet (reference: README.md:270-300); this harness compresses the
adversarial timing (die-offs, recycles, saturation) into a repeatable
in-process scenario instead of waiting for production timing to produce
it.
"""

import argparse
import copy
import faulthandler
import logging
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tests"))

faulthandler.dump_traceback_later(600, repeat=True, file=sys.stderr)
logging.basicConfig(level=logging.WARNING)

import conftest  # noqa: F401,E402 — forces the virtual CPU platform

from babble_tpu.hashgraph import InmemStore  # noqa: E402
from babble_tpu.net.inmem_transport import InmemTransport  # noqa: E402
from babble_tpu.node.node import Node  # noqa: E402
from babble_tpu.proxy import InmemDummyClient  # noqa: E402


class Stall(Exception):
    pass


def dump_states(nodes, tag):
    print(f"--- {tag} ---", flush=True)
    for i, n in enumerate(nodes):
        try:
            print(
                f"  node{i}: state={n.get_state().name} "
                f"block={n.core.get_last_block_index()} "
                f"app_block={n._app_committed_index} "
                f"core_locked={n.core_lock.locked()} "
                f"commit_q={n.commit_ch.qsize()} sync_err={n.sync_errors} "
                f"bounces={n.fast_forward_bounces}",
                flush=True,
            )
        except Exception as e:  # noqa: BLE001 — a dead node is still a data point
            print(f"  node{i}: <{e}>", flush=True)


def check_spread(nodes, tag, limit=200):
    """Runaway tripwire (VERDICT r4: survivor minting to 33,613 while its
    peers sat at ~361): no live node's chain may run `limit` blocks past
    the slowest live node — consensus needs >2/3 participation, so a
    spread like that means re-minted or fabricated rounds, not speed."""
    idx = [
        n.core.get_last_block_index()
        for n in nodes
        if n is not None and n.get_state().name != "SHUTDOWN"
    ]
    if idx and max(idx) - min(idx) > limit:
        dump_states(nodes, f"runaway[{tag}]")
        raise Stall(f"{tag}: runaway chain spread {idx}")


def watched_wait(nodes, alive, prox, target, budget, tag):
    """bombard_and_wait that converts a timeout into a diagnosed stall."""
    from test_node import bombard_and_wait

    try:
        bombard_and_wait(alive, prox, target_block=target, timeout_s=budget)
        check_spread(nodes, tag)
    except AssertionError as e:
        print(f"STALL[{tag}]: {e}", flush=True)
        dump_states(nodes, "stall")
        faulthandler.dump_traceback(file=sys.stderr)
        for k in range(6):
            time.sleep(30)
            dump_states(nodes, f"post-stall +{30 * (k + 1)}s")
        faulthandler.dump_traceback(file=sys.stderr)
        raise Stall(tag) from e


def scenario_chained():
    """Chained-donor fast-forward under die-off: the final joiner's only
    donor has itself fast-synced."""
    from test_fastsync import build_cluster, make_config
    from test_node import run_nodes, shutdown_nodes

    conf = make_config()
    nodes, proxies, keys, peer_list, participants, transports = build_cluster(
        4, conf
    )
    try:
        # phase 1: 3 nodes run past the sync limit; node 3 joins late
        run_nodes(nodes[:3])
        target = 3
        while True:
            watched_wait(nodes, nodes[:3], proxies[:3], target, 180, "p1-base")
            total = sum(i + 1 for i in nodes[0].core.known_events().values())
            if total > conf.sync_limit + 50:
                break
            target += 1
        nodes[3].run_async(True)
        target = max(n.core.get_last_block_index() for n in nodes[:3]) + 2
        watched_wait(nodes, nodes, proxies, target, 240, "p1-join")

        # phase 2: kill node 2; the rest run past the sync limit again so
        # node 3 (a fast-synced node) accumulates an anchor of its own
        victim_addr = peer_list[2].net_addr
        nodes[2].shutdown()
        transports[2].disconnect_all()
        for t in (transports[0], transports[1], transports[3]):
            t.disconnect(victim_addr)
        alive = [nodes[0], nodes[1], nodes[3]]
        alive_prox = [proxies[0], proxies[1], proxies[3]]
        goal = max(n.core.get_last_block_index() for n in alive) + 3
        while True:
            watched_wait(nodes, alive, alive_prox, goal, 240, "p2")
            total = sum(i + 1 for i in nodes[0].core.known_events().values())
            if total > conf.sync_limit + 50:
                break
            goal += 1
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            if nodes[3].core.hg.anchor_block is not None:
                break
            watched_wait(
                nodes, alive, alive_prox,
                max(n.core.get_last_block_index() for n in alive) + 1,
                120, "p2-anchor",
            )
        if nodes[3].core.hg.anchor_block is None:
            raise Stall("p2: node 3 never gained an anchor")

        # phase 3: halt nodes 0/1; recycle node 2 connected ONLY to node 3
        for i in (0, 1):
            nodes[i].shutdown()
            transports[i].disconnect_all()
            transports[3].disconnect(peer_list[i].net_addr)
        trans = InmemTransport(victim_addr, timeout=5.0)
        trans.connect(transports[3].local_addr(), transports[3])
        transports[3].connect(victim_addr, trans)
        transports[2] = trans
        prox = InmemDummyClient()
        store = InmemStore(participants, conf.cache_size)
        node = Node(
            copy.copy(conf), peer_list[2].id, keys[2], participants, store,
            trans, prox,
        )
        node.init()
        nodes[2] = node
        proxies[2] = prox
        node.run_async(True)
        deadline = time.monotonic() + 240
        while time.monotonic() < deadline:
            if node.core.get_last_block_index() >= 0:
                break
            time.sleep(0.25)
        if node.core.get_last_block_index() < 0:
            print("STALL[p3]: joiner never fast-synced", flush=True)
            dump_states(nodes, "stall")
            faulthandler.dump_traceback(file=sys.stderr)
            raise Stall("p3: chained-donor fast-forward never completed")
    finally:
        shutdown_nodes([n for n in nodes if n is not None])


def scenario_reattach():
    """Device-backend recycle + fast-sync + live-engine re-attach under
    trickle traffic (the test_device_backend reattach scenario, soaked)."""
    from test_device_backend import build_mixed_cluster, make_config
    from test_fastsync import connect_transport
    from test_node import run_nodes, shutdown_nodes

    nodes, proxies, keys, peer_list, participants, transports = (
        build_mixed_cluster(["tpu"] * 4)
    )
    conf = make_config()
    try:
        run_nodes(nodes)
        watched_wait(nodes, nodes, proxies, 2, 180, "base")

        nodes[3].shutdown()
        transports[3].disconnect_all()
        for t in transports[:3]:
            t.disconnect(transports[3].local_addr())
        goal = max(n.core.get_last_block_index() for n in nodes[:3]) + 3
        while True:
            watched_wait(nodes, nodes[:3], proxies[:3], goal, 180, "ahead")
            total = sum(i + 1 for i in nodes[0].core.known_events().values())
            if total > conf.sync_limit + 50:
                break
            goal += 1

        trans = InmemTransport(peer_list[3].net_addr, timeout=5.0)
        connect_transport(transports[:3], trans)
        transports[3] = trans
        prox = InmemDummyClient()
        node = Node(
            conf, peer_list[3].id, keys[3], participants,
            InmemStore(participants, conf.cache_size), trans, prox,
        )
        node.init()
        nodes[3] = node
        proxies[3] = prox
        node.run_async(True)

        import random

        deadline = time.monotonic() + 300
        target = goal + 5
        while time.monotonic() < deadline:
            if min(n.core.get_last_block_index() for n in nodes) >= target:
                break
            proxies[random.randrange(3)].submit_tx(
                f"soak-{time.monotonic()}".encode()
            )
            time.sleep(0.1)
        if min(n.core.get_last_block_index() for n in nodes) < target:
            print("STALL[reattach]: joiner failed to catch up", flush=True)
            dump_states(nodes, "stall")
            faulthandler.dump_traceback(file=sys.stderr)
            raise Stall("reattach: joiner failed to catch up")

        # the engine must re-attach with traffic flowing
        deadline = time.monotonic() + 300
        while time.monotonic() < deadline:
            if node.core.ladder_rung() == "live":
                break
            target += 1
            watched_wait(nodes, nodes, proxies, target, 240, "reattach-poll")
        if node.core.ladder_rung() != "live":
            raise Stall("reattach: live engine never re-attached")
    finally:
        shutdown_nodes(nodes)


def scenario_snapshot_race():
    """Fast-forward serving under a SATURATED commit channel (VERDICT r4
    #2): every donor's app commit is artificially slowed so the hashgraph
    anchor runs far ahead of the app's committed height. Before the
    app-height anchor cap, the donor's get_snapshot raced the commit loop
    ("snapshot N not found") and starved every joiner; with the cap the
    join must succeed by construction."""
    from test_fastsync import build_cluster, make_config
    from test_node import run_nodes, shutdown_nodes

    conf = make_config()
    nodes, proxies, keys, peer_list, participants, transports = build_cluster(
        4, conf
    )

    def slow_commit(state, dt=0.05):
        orig = state.commit_handler

        def commit(block):
            time.sleep(dt)
            return orig(block)

        state.commit_handler = commit

    for prox in proxies[:3]:
        slow_commit(prox.state)
    try:
        run_nodes(nodes[:3])
        target = 2
        while True:
            watched_wait(nodes[:3], nodes[:3], proxies[:3], target, 240, "sat-base")
            total = sum(i + 1 for i in nodes[0].core.known_events().values())
            if total > conf.sync_limit + 50:
                break
            target += 1
        # the race window must be OPEN when the joiner arrives: hashgraph
        # anchors ahead of the app's committed height on some donor
        lag_open = any(
            n.core.hg.anchor_block is not None
            and n.core.hg.anchor_block > n._app_committed_index
            for n in nodes[:3]
        )
        print(f"  snapshot-race window open: {lag_open}", flush=True)

        nodes[3].run_async(True)
        deadline = time.monotonic() + 240
        while time.monotonic() < deadline:
            if nodes[3].core.get_last_block_index() >= 0:
                break
            time.sleep(0.25)
        if nodes[3].core.get_last_block_index() < 0:
            print("STALL[snapshot-race]: joiner never fast-synced", flush=True)
            dump_states(nodes, "stall")
            faulthandler.dump_traceback(file=sys.stderr)
            raise Stall("snapshot-race: joiner starved by commit-lagged donors")
        check_spread(nodes, "snapshot-race")
    finally:
        shutdown_nodes(nodes)


SCENARIOS = {
    "chained": scenario_chained,
    "reattach": scenario_reattach,
    "snapshot-race": scenario_snapshot_race,
}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("scenario", nargs="?", default="all",
                    choices=[*SCENARIOS, "all"])
    ap.add_argument("--iters", type=int, default=10)
    args = ap.parse_args()

    names = list(SCENARIOS) if args.scenario == "all" else [args.scenario]
    for i in range(args.iters):
        for name in names:
            t0 = time.monotonic()
            try:
                SCENARIOS[name]()
            except Stall as e:
                print(f"iter {i} {name}: STALLED after "
                      f"{time.monotonic() - t0:.0f}s — {e}", flush=True)
                return 1
            print(f"iter {i} {name}: clean in {time.monotonic() - t0:.0f}s",
                  flush=True)
    print("soak complete: all iterations clean", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
