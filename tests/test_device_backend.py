"""Live-node integration of the device consensus backend.

The strongest oracle is the MIXED cluster: nodes running the CPU engine and
nodes running the device engine participate in the SAME network, so every
block body must come out byte-identical across backends on the very same
DAG (the check_gossip discipline of reference src/node/node_test.go:741-771,
upgraded from cross-node to cross-backend).

Also covers the post-reset path: a device-backend node that joins late
fast-forwards (Reset + section replay) and must keep committing through the
device engine afterwards — the state VERDICT r1 flagged as fatal
(GridUnsupported on any post-reset state).
"""

import copy
import pytest

from babble_tpu.crypto import generate_key, pub_key_bytes
from babble_tpu.hashgraph import InmemStore
from babble_tpu.net import InmemTransport
from babble_tpu.node import Config, Node
from babble_tpu.peers import Peer, Peers
from babble_tpu.proxy import InmemDummyClient
from babble_tpu.tpu import run_consensus_device

import dsl
from test_node import (
    bombard_and_wait,
    check_gossip,
    run_nodes,
    shutdown_nodes,
)
from test_fastsync import connect_transport, first_available_block
from test_tpu_differential import clone_hashgraph


@pytest.mark.parametrize("fixture", ["consensus", "funky", "sparse"])
def test_one_shot_frames_read_the_stamped_rounds(fixture):
    """The one-shot write-back (run_consensus_device) stamps events and
    fills no memo dict: frame building reads the stamps, derives no round,
    and the block bodies stay the host engine's byte for byte."""
    init = getattr(dsl, f"init_{fixture}_hashgraph")
    hg = (init(full=True) if fixture == "funky" else init())[0]
    cpu, dev = clone_hashgraph(hg), clone_hashgraph(hg)
    cpu_blocks, dev_blocks = [], []
    cpu.commit_callback = cpu_blocks.append
    dev.commit_callback = dev_blocks.append
    cpu.run_consensus()
    run_consensus_device(dev)

    frames = [s for s in dev.obs.tracer.spans() if s.name == "commit.frame"]
    assert len(frames) >= len(dev_blocks) > 0
    assert [s.attrs["rounds_derived"] for s in frames] == [0] * len(frames)
    totals = dev.obs.tracer.totals()
    assert "round.derive" not in totals and totals["round.stamp"][0] > 0
    assert cpu.obs.tracer.totals()["round.derive"][0] > 0
    assert [b.body.marshal() for b in dev_blocks] \
        == [b.body.marshal() for b in cpu_blocks]


def make_config(backend="tpu", sync_limit=150):
    return Config(
        heartbeat_timeout=0.005,
        tcp_timeout=1.0,
        cache_size=1000,
        sync_limit=sync_limit,
        consensus_backend=backend,
    )


def build_mixed_cluster(backends, sync_limit=150, mesh_devices=None):
    """One node per entry of `backends` ("cpu" | "tpu"), full-mesh inmem.
    `mesh_devices` optionally maps node index -> chip count for the
    sharded device backend (node.Config.mesh_devices)."""
    n = len(backends)
    keys = [generate_key() for _ in range(n)]
    participants = Peers()
    peer_list = []
    for i, key in enumerate(keys):
        pub_hex = "0x" + pub_key_bytes(key).hex().upper()
        peer = Peer(net_addr=f"127.0.0.1:{9990 + i}", pub_key_hex=pub_hex)
        participants.add_peer(peer)
        peer_list.append(peer)

    transports = [InmemTransport(p.net_addr, timeout=5.0) for p in peer_list]
    for t in transports:
        for u in transports:
            if t is not u:
                t.connect(u.local_addr(), u)

    nodes, proxies = [], []
    for i, key in enumerate(keys):
        conf = make_config(backend=backends[i], sync_limit=sync_limit)
        if mesh_devices and i in mesh_devices:
            conf.mesh_devices = mesh_devices[i]
        prox = InmemDummyClient()
        node = Node(
            copy.copy(conf), peer_list[i].id, key, participants,
            InmemStore(participants, conf.cache_size), transports[i], prox,
        )
        node.init()
        nodes.append(node)
        proxies.append(prox)
    return nodes, proxies, keys, peer_list, participants, transports


def test_device_backend_cluster():
    """All-device 4-node cluster reaches blocks; no silent CPU fallback."""
    nodes, proxies, *_ = build_mixed_cluster(["tpu"] * 4)
    try:
        run_nodes(nodes)
        bombard_and_wait(nodes, proxies, target_block=2, timeout_s=180)
        check_gossip(nodes, upto=2)
        for node in nodes:
            assert node.core.device_consensus_runs > 0, (
                f"node {node.id} never ran the device engine"
            )
            assert node.core.device_consensus_fallbacks == 0, (
                f"node {node.id} silently fell back to CPU "
                f"{node.core.device_consensus_fallbacks} times"
            )
    finally:
        shutdown_nodes(nodes)


def test_mixed_backend_cluster_byte_identical():
    """2 CPU + 2 device nodes in one network: every block body byte-equal
    across backends, and the app state hashes agree at every block."""
    nodes, proxies, *_ = build_mixed_cluster(["cpu", "tpu", "cpu", "tpu"])
    try:
        run_nodes(nodes)
        bombard_and_wait(nodes, proxies, target_block=3, timeout_s=180)
        check_gossip(nodes, upto=3)
        for i in range(3 + 1):
            hashes = {n.get_block(i).state_hash() for n in nodes}
            assert len(hashes) == 1, f"state hash diverged at block {i}"
        for node in (nodes[1], nodes[3]):
            assert node.core.device_consensus_runs > 0
            assert node.core.device_consensus_fallbacks == 0
    finally:
        shutdown_nodes(nodes)


@pytest.mark.parametrize("lag", ["one", "cap"])
def test_pipelined_fetch_cluster_byte_identical(monkeypatch, lag):
    """VERDICT r3 #2: with the device->host result fetch forced OFF the
    consensus critical path (pipelined discipline — decisions integrate
    one sync late, or as many as the queue's cap where every wait counts
    as one too long), a mixed cpu/tpu cluster must still commit
    byte-identical blocks: reception/fame values are DAG facts, so the
    lag shifts only WHEN a block seals, never what goes into it. Also
    forces rebases (tiny round axis) so the rebase-between-integrations
    ordering is exercised under lag."""
    from babble_tpu.tpu import live as live_mod

    monkeypatch.setitem(live_mod.ENGINE_DEFAULTS, "async_fetch", True)
    monkeypatch.setitem(live_mod.ENGINE_DEFAULTS, "r_cap", 16)
    # no wait deepens the lag / every wait does, three calls a step
    monkeypatch.setattr(
        live_mod, "ASYNC_FETCH_MIN_S", 1e9 if lag == "one" else -1.0,
    )

    nodes, proxies, *_ = build_mixed_cluster(
        ["cpu", "tpu", "cpu", "tpu"], sync_limit=2000
    )
    try:
        run_nodes(nodes)
        bombard_and_wait(nodes, proxies, target_block=12, timeout_s=300)
        check_gossip(nodes, upto=12)
        pipelined = 0
        deepened = 0
        for node in (nodes[1], nodes[3]):
            assert node.core.device_consensus_runs > 0
            eng = getattr(node.core.hg, "_live_device_engine", None)
            if eng is not None and eng.async_fetch:
                pipelined += 1
                assert 1 <= eng.fetch_lag <= eng.queue_depth == 4
            deepened += node.core.hg.obs.tracer.totals().get(
                "fetch.deepen", (0, 0.0))[0]
        assert pipelined > 0, "no node ran the pipelined fetch discipline"
        assert (deepened > 0) == (lag == "cap")
    finally:
        shutdown_nodes(nodes)


def test_device_backend_rebases_past_round_capacity(monkeypatch):
    """A live device engine with a tiny round axis must REBASE through it
    (round_base advances, not a CPU fallback) while the mixed cluster's
    blocks stay byte-identical — the streaming/windowing axis of
    SURVEY §5 and BASELINE config #5 at live-node scale."""
    from babble_tpu.tpu import live as live_mod

    monkeypatch.setitem(live_mod.ENGINE_DEFAULTS, "r_cap", 16)
    monkeypatch.setitem(live_mod.ENGINE_DEFAULTS, "e_cap", 4096)
    monkeypatch.setitem(live_mod.ENGINE_DEFAULTS, "e_win", 4096)

    # sync_limit large enough that ordinary virtual-device dispatch lag
    # doesn't flip nodes into CatchingUp, but finite so a genuinely
    # stuck node can still escape via fast-sync instead of deadlocking
    # against the others' rolled windows
    nodes, proxies, *_ = build_mixed_cluster(
        ["cpu", "tpu", "tpu", "tpu"], sync_limit=2000
    )
    try:
        run_nodes(nodes)
        # past the 16-round device axis: forces rebases (the trigger
        # fires at shifted round r_cap - 8 = 8). Kept modest: on the
        # virtual CPU device every sync pays a real dispatch, and too
        # ambitious a target can starve the slowest node of gossip.
        bombard_and_wait(nodes, proxies, target_block=15, timeout_s=300)
        # byte-equality across backends is unconditional
        check_gossip(nodes, upto=15)
        # under adversarial timing an engine may legitimately retire
        # through its safety valves (fast-sync reset, late-witness latch,
        # host-frozen round) — but the round-axis WINDOWING must have
        # carried at least one node past the tiny r_cap: either an
        # in-place rebase or a drop-and-re-attach (the healing path),
        # both of which advance round_base past the initial window
        windowed = [
            eng for node in nodes[1:]
            if (eng := getattr(node.core.hg, "_live_device_engine", None))
            is not None and eng.round_base > 0
        ]
        assert windowed, "no device node survived past r_cap via windowing"
    finally:
        shutdown_nodes(nodes)


def test_device_backend_survives_fast_sync():
    """A device-backend node killed and recycled must fast-forward (Reset +
    section replay) and KEEP running the device engine on the post-reset
    hashgraph — byte-identical to the rest of the cluster."""
    nodes, proxies, keys, peer_list, participants, transports = (
        build_mixed_cluster(["tpu"] * 4)
    )
    conf = make_config()
    try:
        run_nodes(nodes)
        bombard_and_wait(nodes, proxies, target_block=2, timeout_s=180)

        victim = nodes[3]
        victim.shutdown()
        transports[3].disconnect_all()
        for t in transports[:3]:
            t.disconnect(transports[3].local_addr())

        # run the survivors beyond the joiner's sync limit
        goal_ahead = max(n.core.get_last_block_index() for n in nodes[:3]) + 3
        while True:
            bombard_and_wait(
                nodes[:3], proxies[:3], target_block=goal_ahead, timeout_s=180
            )
            total_events = sum(
                i + 1 for i in nodes[0].core.known_events().values()
            )
            if total_events > conf.sync_limit + 50:
                break
            goal_ahead += 1

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

        # generous: under full-suite load the joiner may need several
        # fast-forward attempts while the survivors keep racing ahead
        goal = goal_ahead + 5
        bombard_and_wait(nodes, proxies, target_block=goal, timeout_s=240)
        # compare over the committed range every node shares: the joiner's
        # anchor may sit above `goal` if the survivors raced ahead
        upto = min(n.core.get_last_block_index() for n in nodes)
        start = first_available_block(node, upto)
        check_gossip(nodes, from_block=start, upto=upto)

        # the recycled node must have committed through the device engine
        # on its post-reset hashgraph, with no CPU fallback
        assert node.core.device_consensus_runs > 0
        assert node.core.device_consensus_fallbacks == 0
    finally:
        shutdown_nodes(nodes)


def test_mixed_backend_fast_sync_byte_identical():
    """VERDICT r3 #1 closure: a MIXED cluster (cpu and tpu backends in the
    same network) where a tpu node is killed, left behind past the sync
    limit, and rejoins by fast-sync UNDER LIVE TRAFFIC — every block body
    in the shared committed range must be byte-equal across all four
    nodes (the check_gossip oracle of reference
    src/node/node_test.go:741-772, crossed with both backend and
    post-reset state)."""
    nodes, proxies, keys, peer_list, participants, transports = (
        build_mixed_cluster(["cpu", "tpu", "cpu", "tpu"])
    )
    conf = make_config()
    try:
        run_nodes(nodes)
        bombard_and_wait(nodes, proxies, target_block=2, timeout_s=180)

        victim = nodes[3]
        victim.shutdown()
        transports[3].disconnect_all()
        for t in transports[:3]:
            t.disconnect(transports[3].local_addr())

        # run the survivors beyond the joiner's sync limit
        goal_ahead = max(n.core.get_last_block_index() for n in nodes[:3]) + 3
        while True:
            bombard_and_wait(
                nodes[:3], proxies[:3], target_block=goal_ahead, timeout_s=180
            )
            total_events = sum(
                i + 1 for i in nodes[0].core.known_events().values()
            )
            if total_events > conf.sync_limit + 50:
                break
            goal_ahead += 1

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

        # live traffic while the joiner catches up: trickle submissions
        # (full bombardment saturates the survivors' core locks and
        # starves the joiner's FastForwardRequests — see the reattach
        # test below); consensus needs SOME traffic to integrate it
        import random as _random
        import time as _time

        from test_node import load_scale

        deadline = _time.monotonic() + 240 * load_scale()
        goal = goal_ahead + 5
        while _time.monotonic() < deadline:
            if min(n.core.get_last_block_index() for n in nodes) >= goal:
                break
            k = _random.randrange(3)
            proxies[k].submit_tx(f"mixed-join-{_time.monotonic()}".encode())
            _time.sleep(0.1)
        assert min(n.core.get_last_block_index() for n in nodes) >= goal, (
            f"joiner failed to catch up: indices="
            f"{[n.core.get_last_block_index() for n in nodes]}"
        )
        upto = min(n.core.get_last_block_index() for n in nodes)
        start = first_available_block(node, upto)
        check_gossip(nodes, from_block=start, upto=upto)
        assert node.core.device_consensus_runs > 0
    finally:
        shutdown_nodes(nodes)


@pytest.mark.slow
def test_live_engine_reattaches_after_fast_sync():
    """VERDICT r2 #4: demotions must heal. A device-backend node that
    fast-syncs must RETURN to the incremental live engine afterwards (via
    the frontier attach on its post-reset state), with the demotion and
    re-attach visible in the core counters."""
    nodes, proxies, keys, peer_list, participants, transports = (
        build_mixed_cluster(["tpu"] * 4)
    )
    conf = make_config()
    try:
        run_nodes(nodes)
        bombard_and_wait(nodes, proxies, target_block=2, timeout_s=180)

        victim = nodes[3]
        victim.shutdown()
        transports[3].disconnect_all()
        for t in transports[:3]:
            t.disconnect(transports[3].local_addr())

        goal_ahead = max(n.core.get_last_block_index() for n in nodes[:3]) + 3
        while True:
            bombard_and_wait(
                nodes[:3], proxies[:3], target_block=goal_ahead, timeout_s=180
            )
            total_events = sum(
                i + 1 for i in nodes[0].core.known_events().values()
            )
            if total_events > conf.sync_limit + 50:
                break
            goal_ahead += 1

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

        # Rejoin under TRICKLE traffic, not full bombardment: the
        # survivors run at a 5ms heartbeat and saturate their core locks
        # when blasted with transactions, so the joiner's
        # FastForwardRequests queue behind gossip and time out while the
        # survivors' height compounds away from it (observed: survivors
        # at block 2481, joiner pinned at 11 for 9 minutes). A join under
        # saturation is a known limitation of the 5s-timeout in-memory
        # transport, not the property under test; consensus still needs
        # SOME traffic for the joiner to integrate.
        import random as _random
        import time as _time

        from test_node import load_scale

        deadline = _time.monotonic() + 240 * load_scale()
        goal = goal_ahead + 5
        while _time.monotonic() < deadline:
            if min(n.core.get_last_block_index() for n in nodes) >= goal:
                break
            k = _random.randrange(3)
            proxies[k].submit_tx(f"join-tx-{_time.monotonic()}".encode())
            _time.sleep(0.1)
        assert min(n.core.get_last_block_index() for n in nodes) >= goal, (
            f"joiner failed to catch up: indices="
            f"{[n.core.get_last_block_index() for n in nodes]}"
        )
        upto = min(n.core.get_last_block_index() for n in nodes)
        start = first_available_block(node, upto)
        check_gossip(nodes, from_block=start, upto=upto)

        # the joiner fast-forwarded (possibly repeatedly while the
        # survivors raced ahead); once it settles into Babbling, the live
        # engine must attach on its post-reset hashgraph — poll with
        # traffic flowing, the attach needs consensus calls to happen

        deadline = _time.monotonic() + 240 * load_scale()
        target = upto + 2
        while _time.monotonic() < deadline:
            if getattr(node.core.hg, "_live_device_engine", None) is not None:
                break
            bombard_and_wait(nodes, proxies, target_block=target, timeout_s=240)
            target += 1
        eng = getattr(node.core.hg, "_live_device_engine", None)
        assert eng is not None, (
            "live engine did not re-attach after fast-sync "
            f"(demotions={node.core.live_demotions}, "
            f"calls={node.core._consensus_calls}, "
            f"state={node.get_state()})"
        )
        # ... and KEEPS serving (the r05 joiner-liveness gap): runs must
        # grow on the SAME attached engine with no fresh demotion —
        # device_consensus_runs alone would also count one-shot ladder
        # runs after a silent drop, which is exactly the gap
        runs_before = node.core.device_consensus_runs
        demotions_at_attach = node.core.live_demotions
        deadline = _time.monotonic() + 120 * load_scale()
        while (
            node.core.device_consensus_runs <= runs_before
            and _time.monotonic() < deadline
        ):
            target += 1
            bombard_and_wait(nodes, proxies, target_block=target, timeout_s=240)
        assert node.core.device_consensus_runs > runs_before
        assert getattr(node.core.hg, "_live_device_engine", None) is eng, (
            "live engine dropped again after re-attach "
            f"(demotions={node.core.live_demotions})"
        )
        assert node.core.live_demotions == demotions_at_attach, (
            "fresh demotion after re-attach: the engine is flapping, "
            "not serving"
        )
    finally:
        shutdown_nodes(nodes)


def test_live_engine_attaches_large_history(monkeypatch):
    """VERDICT r2 #4: a node whose DAG exceeds the write-back window must
    attach via the frontier assembly (kept rows = undecided frontier), not
    refuse. Round-2 behavior was GridUnsupported('DAG exceeds the
    write-back window')."""
    from babble_tpu.tpu import live as live_mod

    nodes, proxies, *_ = build_mixed_cluster(["cpu"] * 4)
    try:
        run_nodes(nodes)
        bombard_and_wait(nodes, proxies, target_block=28, timeout_s=300)
    finally:
        shutdown_nodes(nodes)

    hg = nodes[0].core.hg
    total = sum(i + 1 for i in hg.store.known_events().values())
    # shrink the window BELOW the DAG size: the old bootstrap would refuse
    monkeypatch.setitem(live_mod.ENGINE_DEFAULTS, "e_win", 256)
    monkeypatch.setitem(live_mod.ENGINE_DEFAULTS, "batch_cap", 16)
    assert total > 256, f"test DAG too small ({total} events)"

    eng = live_mod.LiveDeviceEngine(hg)
    try:
        assert len(eng.hashes) < total, "frontier attach kept the full DAG"
        assert len(eng.hashes) <= 256
        # kept rows' device rounds must mirror the store (base-relative)
        import numpy as np

        rounds = np.asarray(eng.state.rounds)
        for h, row in list(eng.row_of.items())[:50]:
            ev = hg.store.get_event(h)
            if ev.round is None:
                continue
            # a still-undetermined event below the base is kept with the
            # sentinel (live._install_state): its round is not
            # representable base-relative and stays host-side
            want = ev.round - eng.round_base if ev.round >= eng.round_base else -1
            assert rounds[row] == want
    finally:
        eng.detach()
