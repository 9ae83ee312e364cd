"""chip_smoke.py — the quickest proof that the served consensus path runs
on the chip.

    python3 chip_smoke.py            # strict: needs a TPU, full sizes
    python3 chip_smoke.py --tiny     # same phases at toy sizes, any platform

One process, the only one that touches JAX; it starts no child. Phases run
in order, cheapest failure first, and the run stops at the first phase
that fails — nothing here lets a failed phase pass as another path:

0. device     JAX's default platform is a TPU (else exit 3, no result line)
1. served4    BASELINE.json config 1 at the upstream demo tuning: four
              validators through the composition root (Babble/BabbleConfig)
              over real TCP sockets and the HTTP service; node 0 on the
              device backend, nodes 1-3 on the CPU engine as the reference
              inside the same cluster
2. replay64   BASELINE.json config 3 at bench.py's size: 64 validators x
              32,768 signed events, 500 per sync, into a "cpu" and a "tpu"
              Core; blocks and per-event rounds/lamports/receptions equal
3. cold64     a restarted validator: a fresh "tpu" Core attached to the
              whole DAG at once (pointer-doubling cold replay, then the
              frontier attach); also re-runs the associative_scan repro
4. width1024  the widest deployment the repo claims, compile and run only:
              frontier walk, level scan and doubling, each wide and packed,
              six results equal — on bench_scale.py's 1024-validator grid
              (which never leaves round 0) and on a 256-validator grid
              deep enough for real fame votes and receptions
5. mesh4      only with >= 4 devices: the 2-D (validators, rounds) mesh on
              phase 2's stream and the sharded engines on phase 4's grid

Stdout ends with two JSON lines: the report (versions, cache directory
and what each phase saw), then the verdict, which is the last line and
holds nothing but `{"ok": ..., "device": {"platform", "kind", "count"}}`.
Seconds in the report are host wall clock around work that ends in a
fetch or block_until_ready; the device ledger's figures are printed as
host-seam seconds, never as device time (a seam returns when the launch
does). No speed is claimed: these are set-up observations, not benchmark
results.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import logging
import os
import socket
import sys
import threading
import time
import urllib.request
from dataclasses import dataclass, field
from typing import Dict, List, Optional

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)

PHASES = ("device", "served4", "replay64", "cold64", "width1024", "mesh4")

# upstream demo tuning (demo/run-testnet.sh; reference
# demo/scripts/run-testnet.sh:28-30)
DEMO_TUNING = dict(
    heartbeat_timeout=0.01, tcp_timeout=0.2, cache_size=50000, sync_limit=500,
)


@dataclass(frozen=True)
class Sizes:
    served_txs: int
    served_rate: float  # tx/s per node, the demo bots' trickle
    replay_n: int
    replay_events: int
    sync_events: int
    min_blocks: int
    cold_depth: int  # least depth the catchup.replay record must show
    width_grids: tuple  # (validators, events) per width grid
    # --tiny only: shrink the live engine and the doubling crossover so the
    # toy DAG still takes the rebase and cold-replay paths
    engine: Dict[str, int] = field(default_factory=dict)
    crossover: Optional[int] = None


FULL = Sizes(
    served_txs=1000, served_rate=5.0,
    replay_n=64, replay_events=32768, sync_events=500, min_blocks=20,
    cold_depth=1024, width_grids=((1024, 32768), (256, 32768)),
)
TINY = Sizes(
    served_txs=60, served_rate=20.0,
    replay_n=8, replay_events=2048, sync_events=100, min_blocks=5,
    cold_depth=64, width_grids=((128, 16384),),
    engine=dict(e_cap=4096, e_win=1024), crossover=64,
)


class PhaseFailed(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise PhaseFailed(msg)


# ---------------------------------------------------------------------------
# process-wide compile accounting (jax.monitoring)
# ---------------------------------------------------------------------------


class CompileMeter:
    """Every XLA compilation in the process: count, wall seconds and the
    jitted function's name. `backend_compile_duration` wraps the persistent
    cache lookup too, so a warm cache shows as the same count with fewer
    seconds and `cache_hits` > 0."""

    def __init__(self) -> None:
        from jax import monitoring

        self.compiles = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.names: List[str] = []
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, name: str, secs: float, **kw) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += secs
            self.names.append(str(kw.get("fun_name", "?")))

    def _event(self, name: str, **_kw) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def mark(self):
        return self.compiles, self.seconds, self.cache_hits

    def since(self, mark) -> Dict[str, object]:
        return {
            "compiles": self.compiles - mark[0],
            "compile_seconds": round(self.seconds - mark[1], 3),
            "cache_hits": self.cache_hits - mark[2],
        }


def peak_bytes() -> Optional[List[int]]:
    """peak_bytes_in_use per device, where the backend reports it."""
    import jax

    out = []
    for d in jax.devices():
        stats = d.memory_stats()
        if not stats or "peak_bytes_in_use" not in stats:
            return None
        out.append(int(stats["peak_bytes_in_use"]))
    return out


@contextlib.contextmanager
def phase(name: str, report: dict, meter: CompileMeter):
    """Time one phase and book its compile accounting; the body fills the
    yielded dict. An exception leaves the phase out of the report and
    ends the run."""
    print(f"[chip_smoke] phase {name} ...", file=sys.stderr, flush=True)
    doc: Dict[str, object] = {}
    t0, mark = time.monotonic(), meter.mark()
    yield doc
    doc["seconds"] = round(time.monotonic() - t0, 3)
    doc.update(meter.since(mark))
    doc["peak_bytes_in_use"] = peak_bytes()
    report["phases"][name] = doc
    print(f"[chip_smoke] phase {name} ok: {json.dumps(doc)}",
          file=sys.stderr, flush=True)


def ledger_summary(obs) -> Dict[str, object]:
    """A node's device-time ledger as /stats reads it: compile/retrace
    counts and host-seam seconds per (rung, pass)."""
    snap = obs.devledger.snapshot()
    seams: Dict[str, float] = {}
    for key, (_calls, secs) in snap["cells"].items():
        rung, pass_name, _layout, _comp = key.split("/")
        k = f"{rung}/{pass_name}"
        seams[k] = round(seams.get(k, 0.0) + secs, 3)
    return {
        "kernel_compiles": sum(e["compiles"] for e in snap["entries"].values()),
        "kernel_retraces": sum(e["retraces"] for e in snap["entries"].values()),
        "host_seam_seconds": seams,
    }


# ---------------------------------------------------------------------------
# seeded workload: keys, peers, signed event streams
# ---------------------------------------------------------------------------


def seeded_keys(n: int, seed: int):
    from babble_tpu.crypto import derive_key

    return [derive_key((seed + 1) * 1_000_003 + i) for i in range(n)]


def pub_hex(key) -> str:
    from babble_tpu.crypto import pub_key_bytes

    return "0x" + pub_key_bytes(key).hex().upper()


class Stream:
    """synthetic_grid(n, e_count, seed, zipf_a) materialised as real signed
    events in topological order (the pattern of
    tests/test_tpu_differential.py build_hashgraph_from_grid), with keys
    derived from the seed so the whole stream is reproducible."""

    def __init__(self, n: int, e_count: int, seed: int, zipf_a: float):
        from babble_tpu.crypto import pub_key_bytes
        from babble_tpu.hashgraph import Event, root_self_parent
        from babble_tpu.tpu.grid import synthetic_grid

        grid = synthetic_grid(n, e_count, seed=seed, zipf_a=zipf_a)
        keys = seeded_keys(n, seed)
        by_pub = {pub_hex(k): k for k in keys}
        self.pub_hexes = list(by_pub)
        # synthetic creator positions index the sorted peer slice
        plist = self.peers().to_peer_slice()
        keys = [by_pub[p.pub_key_hex] for p in plist]
        self.key = keys[0]
        self.levels = int(grid.num_levels)
        self.events: List = []
        for i in range(grid.e):
            c = int(grid.creator[i])
            sp, op = int(grid.self_parent[i]), int(grid.other_parent[i])
            ev = Event(
                transactions=[f"tx{i}".encode()],
                parents=[
                    self.events[sp].hex() if sp >= 0
                    else root_self_parent(plist[c].id),
                    self.events[op].hex() if op >= 0 else "",
                ],
                creator=pub_key_bytes(keys[c]),
                index=int(grid.index[i]),
            )
            ev.sign(keys[c])
            self.events.append(ev)

    def peers(self):
        from babble_tpu.peers import Peer, Peers

        return Peers.from_slice(
            [Peer(net_addr="", pub_key_hex=h) for h in self.pub_hexes]
        )

    def core(self, backend: str, **kw):
        """An observer Core on this validator set: it is fed the stream
        and never creates an event of its own."""
        from babble_tpu.hashgraph import InmemStore
        from babble_tpu.node import Core

        peers = self.peers()
        return Core(
            0, self.key, peers, InmemStore(peers, DEMO_TUNING["cache_size"]),
            consensus_backend=backend, **kw,
        )

    def feed(self, core, lo: int, hi: int) -> None:
        """Insert events [lo, hi) as a sync would. Inserting stamps
        coordinates into the event, so every Core gets its own copy
        (body and signature shared, the signature is checked again)."""
        from babble_tpu.hashgraph import Event

        for ev in self.events[lo:hi]:
            b = ev.body
            cp = Event(
                transactions=b.transactions, parents=b.parents,
                creator=b.creator, index=b.index,
            )
            cp.signature = ev.signature
            core.insert_event(cp, True)


def block_bodies(core) -> List[bytes]:
    return [
        core.hg.store.get_block(i).body.marshal()
        for i in range(core.get_last_block_index() + 1)
    ]


def check_same_chain(ref: List[bytes], got: List[bytes], what: str) -> None:
    check(len(got) == len(ref),
          f"{what}: {len(got)} blocks, the CPU reference has {len(ref)}")
    for i, (a, b) in enumerate(zip(ref, got)):
        check(a == b, f"{what}: block {i} differs from the CPU reference")


def check_device_core(core, what: str) -> Dict[str, object]:
    """The invariants every device-backed Core must hold at the end of a
    phase: served by the live rung, nothing fell back, nothing retraced."""
    led = ledger_summary(core.hg.obs)
    check(core.ladder_rung() == "live", f"{what}: rung {core.ladder_rung()}")
    check(core.device_consensus_runs > 0, f"{what}: no device run")
    check(core.device_consensus_fallbacks == 0,
          f"{what}: {core.device_consensus_fallbacks} CPU fallbacks")
    check(core.live_demotions == 0, f"{what}: {core.live_demotions} demotions")
    check(core.device_attach_failures == 0,
          f"{what}: {core.device_attach_failures} failed attaches")
    check(led["kernel_retraces"] == 0,
          f"{what}: {led['kernel_retraces']} retraces")
    eng = core.device_engine
    return {
        "rung": core.ladder_rung(),
        "device_consensus_runs": core.device_consensus_runs,
        "device_fetch_pipelined": bool(eng.async_fetch),
        "rebases": eng.rebases,
        **led,
    }


# ---------------------------------------------------------------------------
# phase 1: served4
# ---------------------------------------------------------------------------


def warm_live_programs(n: int, node_conf, seed: int) -> Dict[str, object]:
    """Compile every program the live rung can launch at this validator
    count — `step`, `multi_step` at K=4 and K=16, `_pack_results`
    (tpu/live.py advance/_dispatch) — on a scratch engine, so node 0
    attaches with a hot jit cache: a compile under core_lock in mid-run
    would put it more than sync_limit events behind and the phase would
    test the post-fast-forward path instead of the served one. Each
    program is timed twice around block_until_ready: first call (compile
    + run) and a steady call."""
    import jax
    import numpy as np

    from babble_tpu.hashgraph import Hashgraph, InmemStore
    from babble_tpu.peers import Peer, Peers
    from babble_tpu.tpu.incremental import multi_step, step
    from babble_tpu.tpu.live import (
        LiveDeviceEngine, _pack_results,
    )

    peers = Peers.from_slice([
        Peer(net_addr="", pub_key_hex=pub_hex(k))
        for k in seeded_keys(n, seed + 7919)
    ])
    hg = Hashgraph(peers, InmemStore(peers, 100))
    eng = LiveDeviceEngine(
        hg, queue_depth=node_conf.dispatch_queue_depth,
        batch_deadline=node_conf.dispatch_batch_deadline,
        batch_cap=node_conf.dispatch_batch_rows,
    )
    kw = dict(e_win=eng.e_win, r_win=eng.r_win, packed=eng.packed)
    empty = eng.empty_batch
    led = hg.obs.devledger

    def advance(entry, fn, batch):
        with led.activate("live"):
            eng.state = led.call(
                entry, fn, eng.state, batch, eng.batch_layout,
                hg.super_majority, eng.n, **kw
            )
        return eng.state

    programs = {
        "step": lambda: advance("_step_full", step, empty),
        "multi_step_k4": lambda: advance(
            "multi_step", multi_step, np.stack([empty] * 4)),
        "multi_step_k16": lambda: advance(
            "multi_step", multi_step, np.stack([empty] * 16)),
        "pack_results": lambda: led.call(
            "_pack_results", _pack_results,
            eng.state, np.int32(0), eng.e_win, eng.r_cap, eng.n),
    }
    out: Dict[str, object] = {}
    for name, run in programs.items():
        t0 = time.monotonic()
        jax.block_until_ready(run())
        t1 = time.monotonic()
        jax.block_until_ready(run())
        out[name] = {
            "first_call_s": round(t1 - t0, 3),
            "steady_call_ms": round((time.monotonic() - t1) * 1e3, 3),
        }
    eng.detach()
    # the compile/retrace listener is process-wide: a scratch ledger that
    # counted these compiles proves node 0's "kernel_retraces == 0" means
    # something (node 0 itself, warm, compiles nothing)
    compiles = ledger_summary(hg.obs)["kernel_compiles"]
    check(compiles >= len(programs),
          f"warm-up ledger counted {compiles} compiles: the jax.monitoring "
          f"listener is not live")
    return out


def free_ports(k: int) -> List[int]:
    socks = [socket.socket() for _ in range(k)]
    try:
        for s in socks:
            s.bind(("127.0.0.1", 0))
        return [s.getsockname()[1] for s in socks]
    finally:
        for s in socks:
            s.close()


def http_json(addr: str, path: str):
    with urllib.request.urlopen(f"http://{addr}{path}", timeout=10) as r:
        return json.loads(r.read())


def phase_served4(sz: Sizes, seed: int, doc: dict, meter: CompileMeter) -> None:
    import jax

    from babble_tpu import Babble, BabbleConfig
    from babble_tpu.node import Config as NodeConfig
    from babble_tpu.peers import Peer, Peers
    from babble_tpu.proxy import InmemDummyClient

    n = 4
    keys = seeded_keys(n, seed)
    ports = free_ports(2 * n)
    addrs = [f"127.0.0.1:{p}" for p in ports[:n]]
    services = [f"127.0.0.1:{p}" for p in ports[n:]]
    confs = [
        NodeConfig(
            consensus_backend="tpu" if i == 0 else "cpu", **DEMO_TUNING,
        )
        for i in range(n)
    ]
    doc["warm"] = warm_live_programs(n, confs[0], seed)

    engines: List[Babble] = []
    polls: List[dict] = []
    stop_poll = threading.Event()

    def poll_node0() -> None:
        # what an operator sees: node 0 over HTTP, twice a second
        while not stop_poll.wait(0.5):
            polls.append({
                "stats": http_json(services[0], "/stats"),
                "digest": http_json(services[0], "/health/digest"),
            })

    poller = threading.Thread(target=poll_node0, name="smoke-poll", daemon=True)
    try:
        for i in range(n):
            engine = Babble(BabbleConfig(
                bind_addr=addrs[i], service_addr=services[i], store=False,
                load_peers=False, proxy=InmemDummyClient(), key=keys[i],
                node=confs[i],
            ))
            engine.peers = Peers.from_slice([
                Peer(net_addr=a, pub_key_hex=pub_hex(k))
                for a, k in zip(addrs, keys)
            ])
            engine.init()
            engines.append(engine)
        window = meter.mark()
        for engine in engines:
            engine.run_async()
        poller.start()

        txs = [f"smoke tx {seed}/{k}".encode() for k in range(sz.served_txs)]
        gap = 1.0 / (sz.served_rate * n)
        t_load = time.monotonic()
        for k, tx in enumerate(txs):
            engines[k % n].config.proxy.submit_tx(tx)
            time.sleep(gap)

        def committed(engine) -> List[bytes]:
            return engine.config.proxy.state.get_committed_transactions()

        deadline = time.monotonic() + 180.0
        while not all(len(committed(e)) >= len(txs) for e in engines):
            check(time.monotonic() < deadline,
                  "nodes did not commit every transaction within 180 s of "
                  "the last submit: "
                  + str([len(committed(e)) for e in engines]))
            time.sleep(0.2)
        doc["load_seconds"] = round(time.monotonic() - t_load, 3)
        stop_poll.set()
        poller.join(timeout=15)
        final = {
            "stats": http_json(services[0], "/stats"),
            "digest": http_json(services[0], "/health/digest"),
        }
        doc["sync_errors"] = [e.node.sync_errors for e in engines]
    finally:
        stop_poll.set()
        # nodes leave one by one, and those still up log every failed
        # exchange with one that left at error level: not this phase's news
        logging.getLogger("babble.node").setLevel(logging.CRITICAL)
        for engine in engines:
            engine.shutdown()
    polls.append(final)

    # every submitted transaction exactly once, on every node
    for i, engine in enumerate(engines):
        got = committed(engine)
        check(sorted(got) == sorted(txs),
              f"node {i} committed {len(got)} transactions "
              f"({len(set(got))} distinct) for {len(txs)} submitted")
    # byte-identical block bodies over the whole (common) chain
    chains = [block_bodies(e.node.core) for e in engines]
    common = min(len(c) for c in chains)
    check(common > 0, "no block committed")
    for i, chain in enumerate(chains[1:], start=1):
        for b in range(common):
            check(chain[b] == chains[0][b],
                  f"block {b} differs between node 0 (tpu) and node {i} (cpu)")

    node0 = engines[0].node
    records = node0.obs.flightrec.records()
    bad = sorted({
        r.name for r in records
        if r.name in ("ladder.demote", "ladder.device_down",
                      "ladder.fast_forward")
    })
    check("ladder.fast_forward" not in bad,
          "node 0 fast-forwarded inside the window: the phase ran the "
          "post-reset path, not the served one")
    check(not bad, f"node 0 flight records: {bad}")
    served_polls = [
        p for p in polls if int(p["stats"]["device_consensus_runs"]) > 0
    ]
    check(bool(served_polls), "no poll saw a device run")
    for p in served_polls:
        check(p["digest"]["rung"] == "live",
              f"node 0 on rung {p['digest']['rung']} at a poll")
        check(p["stats"]["state"] == "Babbling",
              f"node 0 in state {p['stats']['state']} at a poll")
    stats, digest = final["stats"], final["digest"]
    check(stats["consensus_backend"] == "tpu", "node 0 is not tpu-backed")
    platform = jax.devices()[0].platform
    for doc_, where in ((stats, "/stats"), (digest, "/health/digest")):
        check(doc_.get("device_platform") == platform,
              f"{where} device_platform {doc_.get('device_platform')!r}, "
              f"JAX says {platform!r}")
        check("device_kind" in doc_ and "device_count" in doc_,
              f"{where} lacks device_kind/device_count")
    for key in ("device_consensus_fallbacks", "live_engine_demotions",
                "device_attach_failures", "kernel_retraces"):
        check(int(stats[key]) == 0, f"node 0 /stats {key} = {stats[key]}")
    in_window = meter.since(window)
    check(in_window["compiles"] == 0,
          f"{in_window['compiles']} compiles inside the served window "
          f"(warm-up missed {meter.names[window[0]:]})")
    doc.update(
        transactions=len(txs),
        blocks=common,
        events=int(stats["consensus_events"]),
        rung=digest["rung"],
        polls=len(served_polls),
        device_consensus_runs=int(stats["device_consensus_runs"]),
        device_fetch_pipelined=stats.get("device_fetch_pipelined"),
        device_dispatch_ms_avg=stats.get("device_dispatch_ms_avg"),
        device_fetch_ms_avg=stats.get("device_fetch_ms_avg"),
        device_rebases=stats.get("device_rebases"),
        flight_records_dropped=node0.obs.flightrec.dropped,
        in_window_compiles=in_window["compiles"],
        **ledger_summary(node0.obs),
    )


# ---------------------------------------------------------------------------
# phases 2-3: replay64, cold64
# ---------------------------------------------------------------------------


def phase_replay64(sz: Sizes, stream: Stream, doc: dict) -> List[bytes]:
    """Feed the stream, sync by sync, into a "cpu" and a "tpu" Core.
    Returns the CPU Core's block bodies, the reference of the phases that
    follow."""
    cpu, tpu = stream.core("cpu"), stream.core("tpu")
    e = len(stream.events)
    t_cpu = t_tpu = 0.0
    for lo in range(0, e, sz.sync_events):
        hi = min(lo + sz.sync_events, e)
        stream.feed(cpu, lo, hi)
        t0 = time.monotonic()
        cpu.run_consensus()
        t_cpu += time.monotonic() - t0
        stream.feed(tpu, lo, hi)
        t0 = time.monotonic()
        tpu.run_consensus()
        t_tpu += time.monotonic() - t0
        check(tpu.ladder_rung() == "live",
              f"tpu Core on rung {tpu.ladder_rung()} after sync at {hi}")
    tpu.flush_device_dispatch()

    ref = block_bodies(cpu)
    check(len(ref) >= sz.min_blocks,
          f"CPU reference committed only {len(ref)} blocks")
    check_same_chain(ref, block_bodies(tpu), "replay64")

    def stamps(core, h):
        ev = core.get_event(h)
        return ev.round, ev.lamport_timestamp, ev.round_received

    for ev in stream.events:
        h = ev.hex()
        check(stamps(tpu, h) == stamps(cpu, h),
              f"replay64: event {h[:18]} (round, lamport, received) "
              f"{stamps(tpu, h)} != {stamps(cpu, h)}")
    doc.update(
        events=e, blocks=len(ref),
        last_round=cpu.get_last_consensus_round_index(),
        cpu_consensus_seconds=round(t_cpu, 3),
        tpu_consensus_seconds=round(t_tpu, 3),
        **check_device_core(tpu, "replay64"),
    )
    return ref


def phase_cold64(sz: Sizes, stream: Stream, ref: List[bytes],
                 doc: dict) -> None:
    """A restarted validator: a fresh "tpu" Core meets the whole DAG in one
    consensus call. LiveDeviceEngine._bootstrap must settle it through
    maybe_cold_replay -> run_doubling_passes, then attach from the
    frontier."""
    sys.path.insert(0, os.path.join(ROOT, "scripts"))
    import repro_associative_scan_corruption as repro

    core = stream.core("tpu")
    stream.feed(core, 0, len(stream.events))
    t0 = time.monotonic()
    core.run_consensus()
    core.flush_device_dispatch()
    doc["attach_seconds"] = round(time.monotonic() - t0, 3)

    replays = [
        r.fields for r in core.hg.obs.flightrec.records()
        if r.name == "catchup.replay"
    ]
    check(bool(replays),
          "no catchup.replay flight record: the doubling path did not run "
          "or gave way to another engine")
    check(replays[0]["depth"] >= sz.cold_depth,
          f"catchup.replay depth {replays[0]['depth']} < {sz.cold_depth}")
    check_same_chain(ref, block_bodies(core), "cold64")
    doc.update(
        events=len(stream.events), blocks=len(ref),
        levels=stream.levels, catchup_replay=replays[0],
        **check_device_core(core, "cold64"),
    )
    # does the installed stack still corrupt lax.associative_scan, which
    # doubling._closure_la uses along the chain axis? (kernels.suffix_min
    # avoids the primitive for that reason)
    scan = repro.check()
    doc["associative_scan"] = scan
    check(not any(scan.values()),
          f"lax.associative_scan corrupts on this stack: {scan} — "
          f"doubling.py chain_prefix needs the suffix_min-style doubling")


# ---------------------------------------------------------------------------
# phase 4: width1024
# ---------------------------------------------------------------------------

def check_same_passes(ref, got, what: str) -> None:
    import numpy as np

    check(got.last_round == ref.last_round,
          f"{what}: last_round {got.last_round} != {ref.last_round}")
    for f in ("rounds", "witness", "lamport", "received"):
        check(np.array_equal(getattr(got, f), getattr(ref, f)),
              f"{what}: {f} differs")
    # fame tables are padded to engine-specific round axes
    r = ref.last_round + 1
    check(np.array_equal(got.fame_decided[:r], ref.fame_decided[:r]),
          f"{what}: fame_decided differs")
    check(
        np.array_equal(
            (got.famous & got.fame_decided)[:r],
            (ref.famous & ref.fame_decided)[:r],
        ),
        f"{what}: famous differs",
    )


def phase_width1024(sz: Sizes, seed: int, doc: dict) -> list:
    """Compile and run each one-shot engine, wide and packed, on every
    width grid; the six results of a grid must be equal. Returns
    [(grid, reference result)] for mesh4.

    bench_scale.py's config-5 grid (1024 x 32,768) never leaves round 0 —
    no chain head strongly sees 683 round-0 witnesses in 32 events per
    validator — so it proves that the widest programs compile, fit and
    agree, with fame and reception loops that find nothing to do. The
    second grid is the widest one whose rounds advance far enough, inside
    the time limit, for real fame votes and receptions."""
    from babble_tpu.tpu.doubling import run_doubling_passes
    from babble_tpu.tpu.engine import run_frontier_passes, run_passes
    from babble_tpu.tpu.grid import synthetic_grid

    out = []
    for n, e_count in sz.width_grids:
        sub: Dict[str, object] = {}
        t0 = time.monotonic()
        grid = synthetic_grid(n, e_count, seed=seed + 7, zipf_a=1.02)
        sub["grid_seconds"] = round(time.monotonic() - t0, 3)
        engines = {
            "frontier": lambda pk: run_frontier_passes(grid, packed=pk),
            "level_scan": lambda pk: run_passes(
                grid, bucketed=True, adaptive_r=True, packed=pk),
            "doubling": lambda pk: run_doubling_passes(grid, packed=pk),
        }
        ref = None
        runs: Dict[str, float] = {}
        for name, run in engines.items():
            for pk in (False, True):
                label = f"{name}/{'packed' if pk else 'wide'}"
                print(f"[chip_smoke]   {n}: {label} ...",
                      file=sys.stderr, flush=True)
                t0 = time.monotonic()
                res = run(pk)  # host arrays: the fetch closes the timing
                runs[label] = round(time.monotonic() - t0, 3)
                if ref is None:
                    ref = res
                else:
                    check_same_passes(
                        ref, res, f"{n}: {label} vs frontier/wide")
        sub.update(
            events=grid.e, levels=int(grid.num_levels),
            last_round=int(ref.last_round),
            fame_decided=int(ref.fame_decided.sum()),
            received=int((ref.received >= 0).sum()),
            first_call_seconds=runs,
        )
        doc[f"n{n}"] = sub
        out.append((grid, ref))
    # the last grid is the one that must do real voting
    check(sub["fame_decided"] > 0 and sub["received"] > 0,
          f"width grid {sz.width_grids[-1]} decided no fame or received no "
          f"event: {sub}")
    return out


# ---------------------------------------------------------------------------
# phase 5: mesh4
# ---------------------------------------------------------------------------


def phase_mesh4(sz: Sizes, stream: Stream, ref: List[bytes], grids: list,
                doc: dict) -> None:
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from babble_tpu.tpu.sharded import (
        sharded_doubling_passes, sharded_frontier_passes,
    )

    core = stream.core("tpu", mesh_devices=4, mesh_validator_shards=2)
    e = len(stream.events)
    for lo in range(0, e, sz.sync_events):
        stream.feed(core, lo, min(lo + sz.sync_events, e))
        core.run_consensus()
    core.flush_device_dispatch()
    check(core.device_consensus_runs > 0, "mesh4: no device run")
    check(core.device_consensus_fallbacks == 0,
          f"mesh4: {core.device_consensus_fallbacks} CPU fallbacks")
    check(core.device_attach_failures == 0,
          f"mesh4: {core.device_attach_failures} failed attaches")
    check_same_chain(ref, block_bodies(core), "mesh4")

    mesh = Mesh(
        np.array(jax.devices()[:4]).reshape(2, 2), ("validators", "rounds")
    )
    for grid, grid_ref in grids:
        check_same_passes(
            grid_ref, sharded_frontier_passes(mesh, grid),
            f"mesh4: sharded frontier vs single-device at {grid.n}",
        )
        check_same_passes(
            grid_ref, sharded_doubling_passes(mesh, grid),
            f"mesh4: sharded doubling vs single-device at {grid.n}",
        )
    doc.update(
        blocks=len(ref), rung=core.ladder_rung(),
        device_consensus_runs=core.device_consensus_runs,
        mesh_shape=dict(mesh.shape),
        **ledger_summary(core.hg.obs),
    )


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------


def versions() -> Dict[str, Optional[str]]:
    from importlib import metadata

    out: Dict[str, Optional[str]] = {}
    for pkg in ("jax", "jaxlib", "libtpu"):
        try:
            out[pkg] = metadata.version(pkg)
        except metadata.PackageNotFoundError:
            out[pkg] = None
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="Seed of every key, DAG and transaction")
    ap.add_argument("--tiny", action="store_true",
                    help="Toy sizes on whatever platform JAX has: the CPU "
                         "dry run; never reports ok for a chip")
    ap.add_argument("--phases", default=",".join(PHASES),
                    help="Comma-separated subset to run (a partial run "
                         "never reports ok)")
    args = ap.parse_args(argv)
    wanted = [p for p in args.phases.split(",") if p]
    unknown = sorted(set(wanted) - set(PHASES))
    if unknown:
        ap.error(f"unknown phases {unknown}; known: {list(PHASES)}")
    sz = TINY if args.tiny else FULL

    import jax

    from babble_tpu.tpu.runtime import (
        device_info, enable_compile_cache, tpu_init_error,
    )

    t_start = time.monotonic()
    cache_dir = enable_compile_cache()
    meter = CompileMeter()
    device = device_info()
    if device["platform"] != "tpu" and not args.tiny:
        print(
            f"[chip_smoke] phase device FAILED: JAX platform is "
            f"{device['platform']!r}, not a TPU: {tpu_init_error()}",
            file=sys.stderr,
        )
        return 3
    report: Dict[str, object] = {
        "ok": False,
        "device": device,
        "seed": args.seed,
        "versions": versions(),
        "compile_cache_dir": cache_dir,
        "phases": {"device": device},
    }
    if args.tiny:
        import babble_tpu.tpu.doubling as doubling
        from babble_tpu.tpu.live import ENGINE_DEFAULTS

        report["tiny"] = True
        ENGINE_DEFAULTS.update(sz.engine)
        doubling._CROSSOVER_BASE = sz.crossover

    def on(name: str) -> bool:
        return name in wanted

    try:
        if on("served4"):
            with phase("served4", report, meter) as doc:
                phase_served4(sz, args.seed, doc, meter)
        stream = ref = grids = None
        if on("replay64") or on("cold64") or on("mesh4"):
            t0 = time.monotonic()
            stream = Stream(sz.replay_n, sz.replay_events, args.seed, 1.1)
            report["stream_seconds"] = round(time.monotonic() - t0, 3)
            with phase("replay64", report, meter) as doc:
                ref = phase_replay64(sz, stream, doc)
        if on("cold64"):
            with phase("cold64", report, meter) as doc:
                phase_cold64(sz, stream, ref, doc)
        if on("width1024") or on("mesh4"):
            with phase("width1024", report, meter) as doc:
                grids = phase_width1024(sz, args.seed, doc)
        if on("mesh4"):
            if jax.device_count() >= 4:
                with phase("mesh4", report, meter) as doc:
                    phase_mesh4(sz, stream, ref, grids, doc)
            else:
                report["phases"]["mesh4"] = (
                    f"skipped: {jax.device_count()} device"
                )
    except Exception as e:  # noqa: BLE001 — boundary: report, then fail
        import traceback

        traceback.print_exc()
        report["failed"] = f"{type(e).__name__}: {e}"
    report["seconds"] = round(time.monotonic() - t_start, 3)
    report.update(meter.since((0, 0.0, 0)))
    # a second run over a warm cache says what the cache saved: each run
    # leaves its compile seconds beside the cache for the next to quote
    last_path = os.path.join(cache_dir, "chip_smoke.last.json")
    if os.path.exists(last_path):
        with open(last_path) as f:
            report["previous_run"] = json.load(f)
    os.makedirs(cache_dir, exist_ok=True)
    with open(last_path, "w") as f:
        json.dump({
            "phases": list(report["phases"]),
            **{k: report[k] for k in
               ("compiles", "compile_seconds", "cache_hits")},
        }, f)
    complete = all(p in report["phases"] for p in PHASES)
    if not complete and "failed" not in report:
        report["partial"] = True
    # ok is a statement about the chip: never true for a tiny or partial run
    report["ok"] = complete and not args.tiny and "failed" not in report
    print(json.dumps(report))
    print(json.dumps({"ok": report["ok"], "device": device}), flush=True)
    return 1 if "failed" in report else 0


if __name__ == "__main__":
    sys.exit(main())
