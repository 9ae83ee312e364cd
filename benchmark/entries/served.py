"""Entry `served`: a cluster of validators as operating-system processes
over TCP on loopback, node 0 device-backed in this process, and an open-loop
client process that is node 0's application. The window is measured from
the client's side.

Processes. Node 0 is a `Babble` engine in the harness process (the process
that holds the chip), `consensus_backend="tpu"`, in-memory store, real
`TCPTransport`, application side a `SocketAppProxy`. Every other validator is
`python -m babble_tpu run --standalone --consensus-backend cpu ...` with
`JAX_PLATFORMS=cpu` and `--service-listen`, so that its blocks can be read.
The client is `benchmark/served_client.py`. All children are started through
`benchmark/with_parent.py` and die with the harness. Keys come from
`--seed`; free ports are drawn each run; data directories live under
`tempfile.mkdtemp()` and are removed.

The configuration states `validators`, `heartbeat`, `tcp_timeout`,
`cache_size`, `sync_limit` and, under `core`, any other `node.Config` field
it has to set (none by default). The traffic states `rate` (offered tx/s),
`burst`, `clients`, `tx_bytes`, `retry_every`, `lead_in_s`, `drain_s`,
`min_blocks`, `warm` (sync sizes for the warm-up) and `trace`.

Set-up: the live rung's programs are compiled or loaded by handing a scratch
observer Core of the same validator count a short signed stream in syncs of
the sizes `warm` states (public surface only, as entry `replay`); the client
starts and listens; node 0 and the other validators start; once every
validator answers on its service the client is told to offer. The lead-in is `lead_in_s`
seconds of the window's own offered rate and ends on a commit, at t0.

Window: `--seconds` of offering on the clock, t0 to t1 = t0 + seconds. Then
offering stops, and a drain of at most `drain_s` seconds lets every
acknowledged transaction commit.

End to end, all from the client's stamps (`time.monotonic()`, one host):
`committed_tx_per_s` = transactions whose block reached the client in
[t0, t1] over the window; `commit_latency_p50_ms` / `p95_ms` = submit stamp
to commit stamp over the transactions submitted and committed in [t0, t1];
`setup_s` = process start to t0.

`compared`, every limit 0:
- `events_mismatched`, `blocks_mismatched`: the plain reference orders the
  DAG node 0 holds after the drain, in node 0's insertion order; every
  event's round, lamport timestamp and round received and every block's
  index, round received and transactions equal the reference's.
- `blocks_diverged`: over the common chain, the block bodies of every other
  validator (read over its service) equal node 0's, byte for byte.
- `acked_tx_lost`, `tx_duplicated`: every transaction the client was answered
  `accepted` or `queued` for reached the client's commit handler and is in
  exactly one block on node 0 and on a supermajority of validators.
- `unserved_syncs`, `unserved_lead_in_syncs`: every `Core.run_consensus` of
  node 0 (from t0 on; before t0) ended on rung `live` with one more device run
  and no new fallback, demotion or failed attach.
- `in_window_compiles`, `tampered_accepted` (a forged event offered to node 0
  after the drain is refused, and its honest twin is taken),
  `blocks_short_of_min`, `sync_errors` (node 0's failed exchanges in the
  window), `shed_in_window`, `client_errors`.
`attempted` is the transactions offered in the window; `failed` the unserved
syncs plus the lost transactions.

For the readers, the benchmark's own `run_consensus` span is recorded around
node 0's `Core.run_consensus` (wrapped on the instance before the node
starts), and `counters` holds `syncs`, `syncs_served`, `dispatch_seconds`,
`fetch_seconds`, `in_window_compiles`, `validators`, `sync_events` (mean
events a consensus call) and `rounds`.

The entry needs a program with the served path's spans (`core.sync`,
`ingress.wait` and the rest: docs/observability.md): on one without them the
import below fails, the run exits 1 before anything is started, and the cell
is measured on the change alone.
"""

from __future__ import annotations

try:  # the program's side of `ingress.wait`: absent before the served spans
    from babble_tpu.ingress.pipeline import IngressBatch  # noqa: F401
except ImportError:
    raise SystemExit(
        "[bench] entry `served` needs the served path's spans "
        "(babble_tpu.ingress.pipeline.IngressBatch); this program has none")

import importlib
import json
import logging
import os
import queue
import shutil
import socket
import subprocess
import sys
import tempfile
import threading
import time
import urllib.request
from typing import Dict, List, Optional

import numpy as np

from benchmark.entries import replay

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BACKEND = "tpu"  # node 0; the cell measures the device path
ACKED = ("accepted", "queued")
START_TIMEOUT_S = 60.0  # for a child to say it is up
STOP_MARGIN_S = 30.0  # past the lead-in, the window or the drain before giving up


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


class Children:
    """The processes the entry starts. Each dies with the harness
    (`with_parent.py`); `stop` ends them all and waits."""

    def __init__(self, workdir: str) -> None:
        self.workdir = workdir
        self.procs: List[subprocess.Popen] = []

    def start(self, name: str, command: List[str], **popen) -> subprocess.Popen:
        env = {**os.environ, "JAX_PLATFORMS": "cpu"}
        env.pop("BENCH_RUN", None)
        log = open(os.path.join(self.workdir, name + ".err"), "w")
        proc = subprocess.Popen(
            [sys.executable, os.path.join(ROOT, "benchmark", "with_parent.py"),
             str(os.getpid()), *command],
            cwd=ROOT, env=env, stderr=log, **popen)
        log.close()
        self.procs.append(proc)
        return proc

    def errors(self, name: str, last: int = 600) -> str:
        try:
            with open(os.path.join(self.workdir, name + ".err")) as f:
                return f.read()[-last:]
        except OSError:
            return ""

    def stop(self) -> None:
        for p in self.procs:
            if p.poll() is None:
                p.terminate()
        for p in self.procs:
            try:
                p.wait(timeout=5)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()
            for pipe in (p.stdin, p.stdout):
                if pipe is not None:
                    pipe.close()
        self.procs = []


class Client:
    """The client process and its lines."""

    def __init__(self, children: Children, args: List[str]) -> None:
        self.children = children
        self.proc = children.start(
            "client",
            [sys.executable, os.path.join(ROOT, "benchmark", "served_client.py"),
             *args],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
        self.lines: "queue.Queue[Optional[str]]" = queue.Queue()
        threading.Thread(target=self._read, name="bench-client-lines",
                         daemon=True).start()

    def _read(self) -> None:
        for line in self.proc.stdout:
            self.lines.put(line.strip())
        self.lines.put(None)

    def tell(self, word: str) -> None:
        self.proc.stdin.write(word + "\n")
        self.proc.stdin.flush()

    def heard(self, word: str, timeout: float, idle=None) -> List[str]:
        """The values of the next line, which has to start with `word`;
        `idle()` is called every 20 ms while waiting."""
        deadline = time.monotonic() + timeout
        while True:
            try:
                line = self.lines.get(timeout=0.02)
            except queue.Empty:
                if time.monotonic() > deadline:
                    raise RuntimeError(
                        f"client: no {word!r} within {timeout:.0f} s; "
                        + self.children.errors("client"))
                if idle is not None:
                    idle()
                continue
            if line is None or not line.startswith(word):
                raise RuntimeError(
                    f"client: {line!r} where {word!r} was due; "
                    + self.children.errors("client"))
            return line.split()[1:]


class Calls:
    """Node 0's `Core.run_consensus`, wrapped on the instance: the
    benchmark's own `run_consensus` span around each call, when it began, and
    whether the live rung served it (after it the Core stands on rung `live`
    with one more device run and no new fallback, demotion or failed attach).
    Every call is made under the node's core lock, so one at a time."""

    def __init__(self, core, rec) -> None:
        self.core, self.rec = core, rec
        self.inner = core.run_consensus
        self.began: List[float] = []
        self.served: List[bool] = []
        self.last = self._counts()
        core.run_consensus = self

    def _counts(self) -> tuple:
        c = self.core
        return (c.device_consensus_runs, c.device_consensus_fallbacks
                + c.live_demotions + c.device_attach_failures)

    def __call__(self) -> None:
        self.began.append(time.monotonic())
        try:
            with self.rec.span("run_consensus"):
                self.inner()
        finally:
            (runs, bad), now = self.last, self._counts()
            self.last = now
            self.served.append(self.core.ladder_rung() == "live"
                               and now == (runs + 1, bad))

    def unserved(self, since: float, until: float) -> int:
        return sum(1 for t, ok in zip(self.began, self.served)
                   if since <= t < until and not ok)

    def between(self, since: float, until: float) -> int:
        return sum(1 for t in self.began if since <= t < until)


def warm(n: int, sizes: List[int], seed: int, knobs: dict) -> None:
    """Hand a scratch observer Core of `n` validators a signed stream in
    syncs of the given sizes, so that every program node 0's live rung can
    launch is compiled or loaded before the cluster starts."""
    stream = replay.Stream(n, sum(sizes), seed + 7919, 0.0, 1)
    core = stream.core(BACKEND, 1000, **knobs)
    lo = 0
    for size in sizes:
        for ev in stream.handed[lo:lo + size]:
            core.insert_event(ev, True)
        core.run_consensus()
        lo += size
    core.flush_device_dispatch()


def client_record(path: str) -> dict:
    """What the client wrote at the end of the drain: its stamps, the
    verdicts it was answered and the blocks it was handed."""
    with open(path) as f:
        return json.load(f)


def dag_of(core, peers) -> tuple:
    """The events node 0 holds, in its insertion order, as the plain
    reference reads them, and the stamps node 0 gave them."""
    events = core.event_diff({p.id: -1 for p in peers.to_peer_slice()})
    position = {p.pub_key_hex: k for k, p in enumerate(peers.to_peer_slice())}
    row = {ev.hex(): k for k, ev in enumerate(events)}

    def stamp(v):
        return -1 if v is None else int(v)

    creator = np.array([position[ev.creator()] for ev in events], dtype=np.int64)
    index = np.array([ev.index() for ev in events], dtype=np.int64)
    self_parent = np.array([row.get(ev.self_parent(), -1) for ev in events])
    other_parent = np.array([row.get(ev.other_parent(), -1) for ev in events])
    sig_r = [int(ev.signature.split("|")[0], 36) for ev in events]
    coin = [bytes.fromhex(ev.hex()[2:])[16] != 0 for ev in events]
    txs = [ev.transactions() for ev in events]
    stamps = np.array(
        [(stamp(ev.round), stamp(ev.lamport_timestamp), stamp(ev.round_received))
         for ev in events], dtype=np.int64).reshape(len(events), 3)
    inputs = (len(position), creator, index, self_parent, other_parent,
              sig_r, coin, txs)
    return inputs, stamps


def chain_of(core) -> list:
    """Node 0's blocks, as the store holds them."""
    return [core.hg.store.get_block(i)
            for i in range(core.get_last_block_index() + 1)]


def peer_chain(service: str, upto: int) -> list:
    """Blocks 0..upto-1 of one validator, read over its service."""
    from babble_tpu.hashgraph import Block

    return [Block.from_json(http_json(service, f"/block/{i}"))
            for i in range(upto)]


def diverged(mine: list, theirs: List[list]) -> int:
    """Blocks of the other validators whose body differs from node 0's,
    byte for byte."""
    return sum(1 for chain in theirs for a, b in zip(mine, chain)
               if a.body.marshal() != b.body.marshal())


def lost_and_duplicated(acked: set, delivered: List[bytes],
                        chains: List[List[List[bytes]]], quorum: int) -> tuple:
    """(acknowledged transactions that did not reach the client's commit
    handler, or are not in exactly one block of node 0 — `chains[0]` — and of
    at least `quorum` validators in all; transactions in more than one block
    of some validator or handed to the client twice)."""
    def counts(txs) -> Dict[bytes, int]:
        out: Dict[bytes, int] = {}
        for tx in txs:
            out[tx] = out.get(tx, 0) + 1
        return out

    at_client = counts(delivered)
    per_node = [counts(tx for block in chain for tx in block) for chain in chains]
    lost = 0
    for tx in acked:
        holders = sum(1 for c in per_node if c.get(tx, 0) == 1)
        if (at_client.get(tx, 0) != 1 or per_node[0].get(tx, 0) != 1
                or holders < quorum):
            lost += 1
    duplicated = sum(1 for c in [at_client, *per_node]
                     for k in c.values() if k > 1)
    return lost, duplicated


def tampered_accepted(core, peers, keys) -> int:
    """1 unless node 0 refuses an event whose payload does not match its
    signature and takes the same event honestly signed: the next event of
    another validator's chain, on top of node 0's head."""
    from babble_tpu.crypto import pub_key_bytes
    from babble_tpu.hashgraph import Event

    plist = peers.to_peer_slice()
    other = next(p for p in plist if p.pub_key_hex != core.hex_id())
    key = next(k for k in keys if replay.pub_hex(k) == other.pub_key_hex)
    last, _ = core.hg.store.last_event_from(other.pub_key_hex)
    head = core.get_event(last)

    def next_event(payload: bytes):
        return Event(transactions=[payload], parents=[head.hex(), core.head],
                     creator=pub_key_bytes(key), index=head.index() + 1)

    honest = next_event(b"honest")
    honest.sign(key)
    forged = next_event(b"forged")
    forged.signature = honest.signature
    try:
        core.insert_event(forged, True)
        return 1
    except ValueError:
        pass
    try:
        core.insert_event(honest, True)
    except ValueError:
        return 1  # the probe itself was refused: the refusal above says nothing
    return 0


def run(ctx) -> dict:
    cfg, mix = ctx.config, ctx.traffic
    seconds = ctx.seconds
    if ctx.tiny:
        cfg = {**cfg, **cfg.get("tiny", {})}
        mix = {**mix, **mix.get("tiny", {})}
        seconds = float(mix.get("seconds", seconds))
    rec = ctx.rec
    n = int(cfg["validators"])
    knobs = dict(cfg.get("core", {}))

    from babble_tpu import Babble, BabbleConfig
    from babble_tpu.crypto import PemKey
    from babble_tpu.node import Config as NodeConfig
    from babble_tpu.peers import JSONPeers, Peer, Peers
    from babble_tpu.proxy import SocketAppProxy

    with rec.span("setup.warm"):
        warm(n, [int(s) for s in mix["warm"]], ctx.seed,
             {k: v for k, v in knobs.items() if k.startswith("dispatch_")})

    keys = replay.seeded_keys(n, ctx.seed)
    ports = free_ports(2 * n + 1)
    addrs = [f"127.0.0.1:{p}" for p in ports[:n]]
    services = [f"127.0.0.1:{p}" for p in ports[n:2 * n - 1]]  # validators 1..n-1
    proxy_addr, client_addr = (f"127.0.0.1:{p}" for p in ports[2 * n - 1:])
    peer_list = [Peer(net_addr=a, pub_key_hex=replay.pub_hex(k))
                 for a, k in zip(addrs, keys)]
    workdir = tempfile.mkdtemp(prefix="bench-served-")
    children = Children(workdir)
    engine = proxy = None
    ladder = replay.LadderLog()
    logger = logging.getLogger("babble.bench.node0")
    logger.addHandler(ladder)
    logger.setLevel(logging.INFO)
    logger.propagate = False
    try:
        with rec.span("setup.cluster"):
            record_path = os.path.join(workdir, "client.json")
            client = Client(children, [
                "--node", proxy_addr, "--listen", client_addr,
                "--seed", str(ctx.seed), "--rate", str(mix["rate"]),
                "--burst", str(mix["burst"]), "--clients", str(mix["clients"]),
                "--tx-bytes", str(mix["tx_bytes"]),
                "--retry-every", str(mix.get("retry_every", 0)),
                "--lead-in", str(mix["lead_in_s"]), "--seconds", str(seconds),
                "--drain", str(mix["drain_s"]), "--out", record_path])
            client.heard("ready", START_TIMEOUT_S)

            proxy = SocketAppProxy(client_addr=client_addr, bind_addr=proxy_addr,
                                   logger=logger)
            engine = Babble(BabbleConfig(
                bind_addr=addrs[0], store=False, load_peers=False, proxy=proxy,
                key=keys[0],
                node=NodeConfig(
                    consensus_backend=BACKEND,
                    heartbeat_timeout=float(cfg["heartbeat"]),
                    tcp_timeout=float(cfg["tcp_timeout"]),
                    cache_size=int(cfg["cache_size"]),
                    sync_limit=int(cfg["sync_limit"]),
                    logger=logger, **knobs)))
            engine.peers = Peers.from_slice(peer_list)
            engine.init()
            node, core = engine.node, engine.node.core
            calls = Calls(core, rec)

            for i in range(1, n):
                datadir = os.path.join(workdir, f"node{i}")
                PemKey(datadir).write_key(keys[i])
                JSONPeers(datadir).set_peers(peer_list)
                children.start(f"node{i}", [
                    sys.executable, "-m", "babble_tpu", "run", "--standalone",
                    "--consensus-backend", "cpu", "--datadir", datadir,
                    "--listen", addrs[i], "--service-listen", services[i - 1],
                    "--heartbeat", str(cfg["heartbeat"]),
                    "--timeout", str(cfg["tcp_timeout"]),
                    "--cache-size", str(cfg["cache_size"]),
                    "--sync-limit", str(cfg["sync_limit"]), "--log", "error"],
                    stdin=subprocess.DEVNULL, stdout=subprocess.DEVNULL)
            engine.run_async()
            # a cluster with nothing to order exchanges nothing: it is up
            # once every validator answers on its service
            deadline = time.monotonic() + START_TIMEOUT_S
            waiting = list(services)
            while waiting:
                try:
                    http_json(waiting[0], "/stats")
                    waiting.pop(0)
                except OSError:
                    if time.monotonic() > deadline:
                        raise RuntimeError(
                            "validator %d not up within %.0f s; %s" % (
                                n - len(waiting), START_TIMEOUT_S,
                                children.errors(f"node{n - len(waiting)}")))
                    time.sleep(0.05)

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
                "events": int(core.hg.topological_index),
                "in_window_compiles": ctx.meter.compiles,
                "sync_errors": node.sync_errors,
                "sync_requests": node.sync_requests,
            }

        client.tell("go")
        t0 = float(client.heard(
            "window", float(mix["lead_in_s"]) + STOP_MARGIN_S)[0])
        before = engine_counts()
        setup_s = t0 - ctx.t_process
        t1 = float(client.heard(
            "offered", seconds + STOP_MARGIN_S,
            idle=lambda: ctx.trace.poll(time.monotonic() - t0))[0])
        after = engine_counts()
        ctx.trace.close()
        peak = ctx.peak_bytes()
        client.heard("done", float(mix["drain_s"]) + STOP_MARGIN_S)
        record = client_record(record_path)

        # the other validators: wait (inside what the drain has left) until
        # each holds node 0's chain as far as the client was handed it, then
        # read their blocks while they run
        with rec.span("check.peers"):
            upto = max((b[0] for b in record["blocks"]), default=-1) + 1
            deadline = (record["t1"] + float(mix["drain_s"]))

            def last_block(service: str) -> int:
                return int(http_json(service, "/stats")["last_block_index"])

            while (time.monotonic() < deadline
                   and any(last_block(s) < upto - 1 for s in services)):
                time.sleep(0.1)
            common = min([upto] + [last_block(s) + 1 for s in services])
            peer_chains = [peer_chain(s, common) for s in services]
    finally:
        children.stop()
        if engine is not None:
            logging.getLogger("babble.bench.node0").setLevel(logging.CRITICAL)
            engine.shutdown()
        if proxy is not None:
            proxy.close()
        shutil.rmtree(workdir, ignore_errors=True)

    # node 0 is quiet now: one more call and the flush take in whatever a
    # self-event of its own added after the last sync
    core.run_consensus()
    core.flush_device_dispatch()
    for text in ladder.lines[:8]:
        print(f"[bench] ladder: {text[:300]}", file=sys.stderr)

    window_s = t1 - t0
    in_window = {k: after[k] - before[k] for k in after}
    syncs = calls.between(t0, t1)
    unserved = calls.unserved(t0, float("inf"))
    unserved_window = calls.unserved(t0, t1)

    # the client's record: what it offered, what it was answered, what it
    # was handed
    submitted = [(bytes.fromhex(tx), at, verdict)
                 for tx, at, verdict in record["submitted"]]
    delivered = [(index, at, [bytes.fromhex(tx) for tx in txs])
                 for index, at, txs in record["blocks"]]
    submit_at = {tx: at for tx, at, _ in submitted}
    offered = [row for row in submitted if t0 <= row[1] <= t1]
    verdicts: Dict[str, int] = {}
    for _, _, verdict in offered:
        verdicts[str(verdict)] = verdicts.get(str(verdict), 0) + 1
    lat: List[float] = []
    lat_at: List[float] = []  # when each timed transaction was submitted
    committed = blocks_in_window = 0
    for _, at, txs in delivered:
        if not t0 <= at <= t1:
            continue
        blocks_in_window += 1
        committed += len(txs)
        for tx in txs:
            if submit_at.get(tx, 0.0) >= t0:
                lat.append(at - submit_at[tx])
                lat_at.append(submit_at[tx])
    lat_ms = np.asarray(lat) * 1e3
    third = (np.asarray(lat_at) - t0) // (window_s / 3)  # 0, 1, 2

    def p50_of_third(k: int):
        part = lat_ms[third == k]
        return float(np.percentile(part, 50)) if len(part) else None

    reference = importlib.import_module("benchmark.reference." + cfg["reference"])
    peers = engine.peers
    with rec.span("check.reference"):
        inputs, stamps = dag_of(core, peers)
        chain = chain_of(core)
        want = reference.order(*inputs)
        diff = replay.mismatches(
            (stamps, [(b.index(), b.round_received(), b.transactions())
                      for b in chain]), want)
    # who made the events node 0 holds: a validator makes one a sync it
    # takes part in, so the slowest validator of the four makes the fewest
    made = np.bincount(inputs[1], minlength=n)
    me = [p.pub_key_hex for p in peers.to_peer_slice()].index(core.hex_id())
    others = np.delete(made, me)
    acked = {tx for tx, _, verdict in submitted if verdict in ACKED}
    lost, duplicated = lost_and_duplicated(
        acked, [tx for _, _, txs in delivered for tx in txs],
        [[b.transactions() for b in c] for c in [chain, *peer_chains]],
        quorum=2 * n // 3 + 1)
    compared = [
        ("events_mismatched", diff["events_mismatched"], 0),
        ("blocks_mismatched", diff["blocks_mismatched"], 0),
        ("blocks_diverged", diverged(chain, peer_chains), 0),
        ("acked_tx_lost", lost, 0),
        ("tx_duplicated", duplicated, 0),
        ("unserved_syncs", unserved, 0),
        ("unserved_lead_in_syncs", calls.unserved(0.0, t0), 0),
        ("in_window_compiles", in_window["in_window_compiles"], 0),
        ("tampered_accepted", tampered_accepted(core, peers, keys), 0),
        ("blocks_short_of_min",
         max(0, int(mix.get("min_blocks", 1)) - blocks_in_window), 0),
        ("sync_errors", in_window["sync_errors"], 0),
        ("shed_in_window", verdicts.get("shed", 0), 0),
        ("client_errors", int(record["errors"]), 0),
    ]
    eng = getattr(core.hg, "_live_device_engine", None)
    return {
        "attempted": len(offered),
        "failed": unserved + lost,
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
            "syncs_served": syncs - unserved_window,
            "offered_rate": float(mix["rate"]),
            "tx_offered": len(offered),
            # under offered_rate where the generator itself fell behind
            "offered_tx_per_s": len(offered) / window_s,
            "tx_acked": sum(verdicts.get(v, 0) for v in ACKED),
            "tx_committed": committed,
            "tx_timed": len(lat),
            # a queue that grows through the window shows as a last third
            # slower than the first (the sweep's knee reads these)
            "latency_p50_first_third_ms": p50_of_third(0),
            "latency_p50_last_third_ms": p50_of_third(2),
            "blocks_committed": blocks_in_window,
            "blocks_compared": common,
            "events_held": len(stamps),
            "events_made_by_node0": int(made[me]),
            "events_made_by_others_min": int(others.min()),
            "events_made_by_others_max": int(others.max()),
            "drain_s": record["drained"] - record["t1"],
            **in_window,
            "fetch_pipelined": int(bool(getattr(eng, "async_fetch", False))),
            "validators": n,
            "sync_events": in_window["events"] / syncs if syncs else 0.0,
        },
    }
