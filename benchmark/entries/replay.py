"""Entry `replay`: a seeded, signed gossip stream handed sync by sync to one
device-backed `Core` (lifted from chip_smoke.py `Stream`, `Stream.core`,
`Stream.feed`, `phase_replay64`).

Set-up: draw the DAG, sign every event, build the Core as an observer on
the validator set (in-memory store, upstream demo tuning), and hand it a
lead-in, the head of the stream, so that every program this cell's syncs
launch is compiled or loaded.

Window: hand over the next `sync_events` events with `insert_event`
(signature checked), call `run_consensus`, repeat until the clock has ended
and a sync has ended on a commit; then `flush_device_dispatch`, and stop the
clock after it. A validator's
gossip loop is closed on syncs, so the next sync is handed over when the
last call returns. Commit instants are stamped inside the Core's own
commit path (`Core(commit_ch=...)` hands every block to `commit_ch.put`).

After the window: the plain reference orders the events that were handed
over, and every event's round, lamport timestamp and round received and
every block's round and transactions are compared with it.

From the program this takes the public surface only (Core, InmemStore,
Peers, Event, crypto, the Core's counters and two histograms), so that a
later change inside the engines needs no edit here.
"""

from __future__ import annotations

import gc
import importlib
import logging
import sys
import time
from typing import Dict, List

import numpy as np

from benchmark import traffic as gen

# upstream demo tuning (demo/run-testnet.sh; reference
# demo/scripts/run-testnet.sh:28-30); a configuration may state its own
CACHE_SIZE = 50000
BACKEND = "tpu"  # the cell measures the device path; there is no other


def seeded_keys(n: int, seed: int):
    from babble_tpu.crypto import derive_key

    return [derive_key((seed + 1) * 1_000_003 + i) for i in range(n)]


def pub_hex(key) -> str:
    from babble_tpu.crypto import pub_key_bytes

    return "0x" + pub_key_bytes(key).hex().upper()


class Stream:
    """The DAG as signed events in creation order, keys derived from the
    seed. `handed` are the copies a Core is given: body and signature of
    the signed event, no cached hash, as a decoded wire event arrives."""

    def __init__(self, n: int, events: int, seed: int, zipf_a: float,
                 tx_per_event: int, topology_seed: int = None):
        from babble_tpu.crypto import pub_key_bytes
        from babble_tpu.hashgraph import Event, root_self_parent
        from babble_tpu.peers import Peer, Peers

        if topology_seed is None:
            self.dag = gen.gossip_dag(n, events, seed, zipf_a)
        else:
            self.dag = gen.relabel(
                gen.gossip_dag(n, events, topology_seed, zipf_a), seed)
        by_pub = {pub_hex(k): k for k in seeded_keys(n, seed)}
        self.peers = Peers.from_slice(
            [Peer(net_addr="", pub_key_hex=h) for h in by_pub])
        # creator positions index the sorted peer slice
        plist = self.peers.to_peer_slice()
        keys = [by_pub[p.pub_key_hex] for p in plist]
        pubs = [pub_key_bytes(k) for k in keys]
        self.key = keys[0]
        dag = self.dag
        signed: List = []
        for i in range(dag.e):
            c = int(dag.creator[i])
            sp, op = int(dag.self_parent[i]), int(dag.other_parent[i])
            ev = Event(
                transactions=gen.payload(i, tx_per_event),
                parents=[
                    signed[sp].hex() if sp >= 0 else root_self_parent(plist[c].id),
                    signed[op].hex() if op >= 0 else "",
                ],
                creator=pubs[c],
                index=int(dag.index[i]),
            )
            ev.sign(keys[c])
            signed.append(ev)
        self.signed = signed
        self.handed = [self.copy(ev) for ev in signed]

    @staticmethod
    def copy(ev):
        from babble_tpu.hashgraph import Event

        b = ev.body
        cp = Event(transactions=b.transactions, parents=b.parents,
                   creator=b.creator, index=b.index)
        cp.signature = ev.signature
        return cp

    def core(self, backend: str, cache_size: int, commit_ch=None, **knobs):
        """An observer Core on this validator set: it is fed the stream
        and never creates an event of its own."""
        from babble_tpu.hashgraph import InmemStore
        from babble_tpu.node import Core

        return Core(0, self.key, self.peers,
                    InmemStore(self.peers, cache_size),
                    commit_ch=commit_ch, consensus_backend=backend, **knobs)


class CommitStamps:
    """What `Core(commit_ch=...)` calls `put` on: every committed block,
    stamped when the Core's commit path hands it over."""

    def __init__(self) -> None:
        self.blocks: List[tuple] = []

    def put(self, block) -> None:
        self.blocks.append((time.monotonic(), block))


class Served:
    """Whether each sync was served by the device's live rung: after it the
    Core stands on rung `live` with one more device run and no new
    fallback, demotion or failed attach."""

    def __init__(self, core) -> None:
        self.core = core
        self.unserved = 0
        self.last = self._counts()

    def _counts(self) -> tuple:
        c = self.core
        return (c.device_consensus_runs, c.device_consensus_fallbacks
                + c.live_demotions + c.device_attach_failures)

    def note(self) -> None:
        (runs, bad), now = self.last, self._counts()
        self.last = now
        if self.core.ladder_rung() != "live" or now != (runs + 1, bad):
            self.unserved += 1


class LadderLog(logging.Handler):
    """What the Core's own logger said about a rung it left or could not
    reach: the reason a sync went unserved, for the run's standard error."""

    def __init__(self) -> None:
        super().__init__(level=logging.DEBUG)
        self.lines: List[str] = []

    def emit(self, record: logging.LogRecord) -> None:
        text = record.getMessage()
        if "unavailable" in text or "failed to attach" in text or "unsupported" in text:
            self.lines.append(text)


def reference_inputs(stream: Stream, consumed: int) -> tuple:
    """What the plain reference reads of the first `consumed` events."""
    dag, sl = stream.dag, slice(0, consumed)
    sig_r = [int(ev.signature.split("|")[0], 36) for ev in stream.signed[sl]]
    coin = [bytes.fromhex(ev.hex()[2:])[16] != 0 for ev in stream.signed[sl]]
    txs = [ev.transactions() for ev in stream.signed[sl]]
    return (dag.n, dag.creator[sl], dag.index[sl], dag.self_parent[sl],
            dag.other_parent[sl], sig_r, coin, txs)


def observed(stream: Stream, consumed: int, blocks: list) -> tuple:
    """What the Core stamped into the events it was handed, and the blocks
    its commit path handed over: ((E, 3) stamps, [(index, round, txs)])."""
    def stamp(v):
        return -1 if v is None else int(v)

    got = np.array(
        [(stamp(ev.round), stamp(ev.lamport_timestamp), stamp(ev.round_received))
         for ev in stream.handed[:consumed]], dtype=np.int64).reshape(consumed, 3)
    return got, [(b.index(), b.round_received(), b.transactions())
                 for _, b in blocks]


def as_observed(ordering) -> tuple:
    """An ordering of the reference's in the place of the program's: the
    control's side of the comparison."""
    got = np.stack([ordering.rounds, ordering.lamport, ordering.received], axis=1)
    return got, [(k, rr, txs) for k, (rr, txs) in enumerate(ordering.blocks)]


def mismatches(got: tuple, want) -> Dict[str, int]:
    """Events whose (round, lamport, round received) differ from the
    reference's, and blocks that differ in index, round or transactions
    (a missing or extra block counts as one)."""
    stamps, blocks = got
    ref = np.stack([want.rounds, want.lamport, want.received], axis=1)
    events_off = int((stamps != ref).any(axis=1).sum())
    blocks_off = abs(len(blocks) - len(want.blocks))
    for k, ((index, rr, txs), (want_rr, want_txs)) in enumerate(
            zip(blocks, want.blocks)):
        if index != k or rr != want_rr or txs != want_txs:
            blocks_off += 1
    return {"events_mismatched": events_off, "blocks_mismatched": blocks_off}


def tampered_accepted(core, stream: Stream, at: int) -> int:
    """1 if the Core takes an event whose payload no longer matches its
    signature (the next event of the stream, one transaction changed)."""
    if at >= len(stream.signed):
        return 0
    from babble_tpu.hashgraph import Event

    b = stream.signed[at].body
    forged = Event(transactions=[b"forged"] + list(b.transactions[1:]),
                   parents=b.parents, creator=b.creator, index=b.index)
    forged.signature = stream.signed[at].signature
    try:
        core.insert_event(forged, True)
    except ValueError:
        return 0
    return 1


def run(ctx) -> dict:
    cfg, mix = ctx.config, ctx.traffic
    if ctx.tiny:
        cfg = {**cfg, **cfg["tiny"]}
        mix = {**mix, **mix.get("tiny", {})}
        # shrink the live engine so that the toy stream still rebases
        from babble_tpu.tpu.live import ENGINE_DEFAULTS

        ENGINE_DEFAULTS.update(cfg.get("engine", {}))
    n, events = int(cfg["validators"]), int(cfg["events"])
    zipf_a = float(cfg["zipf_a"])
    cache_size = int(cfg.get("cache_size", CACHE_SIZE))
    sync_events = int(mix["sync_events"])
    tx_per_event = int(mix.get("tx_per_event", 1))
    rec = ctx.rec

    with rec.span("setup.stream"):
        stream = Stream(n, events, ctx.seed, zipf_a, tx_per_event,
                        cfg.get("topology_seed"))
        # the stream is the benchmark's own: a node holds no such list. Keep
        # its ~10^6 objects out of the collector's passes over the program's
        gc.collect()
        gc.freeze()
    stamps = CommitStamps()
    # deployment settings of the Core the configuration states (the node's
    # --dispatch-batch-rows, --dispatch-queue-depth), else its defaults
    core = stream.core(BACKEND, cache_size, commit_ch=stamps,
                       **cfg.get("core", {}))
    served = Served(core)
    ladder = LadderLog()
    core.logger.addHandler(ladder)
    core.logger.setLevel(logging.DEBUG)
    core.logger.propagate = False
    handed = stream.handed
    handed_at = np.zeros(events)  # per event: when its sync was handed over
    syncs = 0

    def hand_over(lo: int, hi: int) -> bool:
        """One sync; True if a block was committed during it."""
        blocks = len(stamps.blocks)
        handed_at[lo:hi] = time.monotonic()
        with rec.span("insert"):
            for ev in handed[lo:hi]:
                core.insert_event(ev, True)
        with rec.span("run_consensus"):
            core.run_consensus()
        served.note()
        return len(stamps.blocks) > blocks

    # lead-in (set-up): the head of the stream, first in small syncs so that
    # the engine attaches and gets past the young DAG's first-descendant
    # bursts, then in a few syncs of the window's own size, which compile or
    # load every program the window launches and settle the fetch discipline.
    # It goes on until a sync ends on a commit: blocks are whole rounds
    # (~1,200 transactions at 64 validators), and a window that opens and
    # closes just after a commit holds whole rounds of work, not a count
    # that depends on where in a round the clock happened to fall
    lo = 0
    on_commit = False
    with rec.span("setup.lead_in"):
        for phase in mix.get("lead_in", []):
            end = min(lo + int(phase["events"]), events)
            for a, b in gen.syncs(end - lo, int(phase["sync_events"])):
                on_commit = hand_over(lo + a, lo + b)
            lo = end
        while not on_commit and lo < events:
            hi = min(lo + sync_events, events)
            on_commit = hand_over(lo, hi)
            lo = hi
        core.flush_device_dispatch()
    lead_in_unserved, served.unserved = served.unserved, 0
    first = lo

    def engine_counts() -> dict:
        eng = getattr(core.hg, "_live_device_engine", None)
        hist = core.hg.obs.histogram  # (count, summed seconds): host times
        d = hist("babble_device_dispatch_seconds").stats()
        f = hist("babble_device_fetch_seconds").stats()
        return {
            "dispatch_calls": d[0], "dispatch_seconds": d[1],
            "fetch_calls": f[0], "fetch_seconds": f[1],
            "rebases": getattr(eng, "rebases", 0),
            "rounds": int(core.get_last_consensus_round_index() or 0),
            "in_window_compiles": ctx.meter.compiles,
        }

    before = engine_counts()
    t0 = time.monotonic()
    setup_s = t0 - ctx.t_process
    while lo < events:
        now = time.monotonic()
        if now - t0 >= ctx.seconds and on_commit:
            break  # the clock has ended, and the last sync ended on a commit
        ctx.trace.poll(now - t0)
        hi = min(lo + sync_events, events)
        on_commit = hand_over(lo, hi)
        syncs += 1
        lo = hi
    with rec.span("flush"):
        core.flush_device_dispatch()
    t1 = time.monotonic()
    ctx.trace.close()
    window_s = t1 - t0
    for text in ladder.lines[:8]:
        print(f"[bench] ladder: {text[:300]}", file=sys.stderr)
    after = engine_counts()
    in_window = {k: after[k] - before[k] for k in after}
    compiles = in_window["in_window_compiles"]
    peak = ctx.peak_bytes()

    # committed: every transaction whose block was committed in the window.
    # latency: of those, every one that was handed over in the window, from
    # the hand-over of the sync that carried its event to its block's commit
    # (the lead-in's transactions were handed over during set-up, so their
    # clock did not start in the window)
    lat: List[float] = []
    committed = blocks_in_window = 0
    for t_commit, block in stamps.blocks:
        if t_commit < t0:
            continue  # committed during the lead-in
        blocks_in_window += 1
        for tx in block.transactions():
            committed += 1
            i = gen.payload_event(tx)
            if i >= first:
                lat.append(t_commit - handed_at[i])
    lat_ms = np.asarray(lat) * 1e3
    eng = getattr(core.hg, "_live_device_engine", None)

    reference = importlib.import_module(
        "benchmark.reference." + cfg["reference"])
    with rec.span("check.reference"):
        want = reference.order(*reference_inputs(stream, lo))
        diff = mismatches(observed(stream, lo, stamps.blocks), want)
    compared = [
        ("events_mismatched", diff["events_mismatched"], 0),
        ("blocks_mismatched", diff["blocks_mismatched"], 0),
        ("unserved_syncs", served.unserved, 0),
        ("unserved_lead_in_syncs", lead_in_unserved, 0),
        ("in_window_compiles", compiles, 0),
        ("tampered_accepted", tampered_accepted(core, stream, lo), 0),
        ("blocks_short_of_min",
         max(0, int(mix.get("min_blocks", 1)) - blocks_in_window), 0),
    ]
    return {
        "attempted": syncs,
        "failed": served.unserved,
        "setup_s": setup_s,
        "window": (t0, t1),
        "memory_peak_bytes": peak,
        "end_to_end": {
            "committed_tx_per_s": committed / window_s,
            "commit_latency_p50_ms": float(np.percentile(lat_ms, 50)) if lat else None,
            "commit_latency_p95_ms": float(np.percentile(lat_ms, 95)) if lat else None,
        },
        "compared": compared,
        "counters": {
            "syncs": syncs,
            "syncs_served": syncs - served.unserved,
            "events_inserted": lo - first,
            "events_lead_in": first,
            "events_in_stream": events,
            "tx_committed": committed,
            "tx_timed": len(lat),
            "blocks_committed": blocks_in_window,
            **in_window,
            "fetch_pipelined": int(bool(getattr(eng, "async_fetch", False))),
            "validators": n,
            "sync_events": sync_events,
        },
    }
