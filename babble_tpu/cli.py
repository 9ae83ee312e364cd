"""babble-tpu command line: `run`, `keygen`, `sim`, `explain`, `status`,
`lint`, `version`
(reference: cmd/babble/main.go:11-15, cmd/babble/commands/run.go:28-155).

Flags mirror the reference's run command; values may also come from an
optional config file `<datadir>/babble.json` or `<datadir>/babble.toml`
(flag > config file > default, the viper merge order of run.go:93-155).
One addition: `--consensus-backend {cpu,tpu}` selects the host or device
consensus engine (SURVEY §7).
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import sys

from . import version as version_mod
from .babble import Babble, BabbleConfig, default_data_dir, keygen
from .node import Config as NodeConfig
from .proxy import InmemDummyClient, SocketAppProxy

LOG_LEVELS = {
    "debug": logging.DEBUG,
    "info": logging.INFO,
    "warn": logging.WARNING,
    "error": logging.ERROR,
    "fatal": logging.CRITICAL,
    "panic": logging.CRITICAL,
}


def _load_config_file(datadir: str) -> dict:
    """`babble.{json,toml}` under the datadir (reference: run.go:129-155)."""
    jpath = os.path.join(datadir, "babble.json")
    if os.path.exists(jpath):
        with open(jpath) as f:
            return json.load(f)
    tpath = os.path.join(datadir, "babble.toml")
    if os.path.exists(tpath):
        import tomllib

        with open(tpath, "rb") as f:
            return tomllib.load(f)
    return {}


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="babble-tpu", description="TPU-native hashgraph consensus node")
    sub = p.add_subparsers(dest="command", required=True)

    run = sub.add_parser("run", help="Run a babble node")
    run.add_argument("--datadir", default=default_data_dir(),
                     help="Top-level directory for configuration and data")
    run.add_argument("--log", default="info", choices=sorted(LOG_LEVELS),
                     help="Log level")
    run.add_argument("-l", "--listen", default=":1337",
                     help="Listen IP:Port for the babble node")
    run.add_argument("-t", "--timeout", type=float, default=1.0,
                     help="TCP timeout in seconds")
    run.add_argument("--max-pool", type=int, default=2,
                     help="Connection pool size max")
    run.add_argument("--standalone", action="store_true",
                     help="Do not create a proxy (use the built-in dummy app)")
    run.add_argument("-p", "--proxy-listen", default="127.0.0.1:1338",
                     help="Listen IP:Port for the babble proxy")
    run.add_argument("-c", "--client-connect", default="127.0.0.1:1339",
                     help="IP:Port to connect to the client app")
    run.add_argument("-s", "--service-listen", default="",
                     help="Listen IP:Port for the HTTP service")
    run.add_argument("--service-remote-debug", action="store_true",
                     help="Allow /debug/* (profiler, stack dumps) from "
                          "non-loopback clients")
    run.add_argument("--store", action="store_true",
                     help="Keep the hashgraph in <datadir>/babble.db (SQLite, "
                          "WAL, synchronous=FULL) instead of in memory: a "
                          "sync is durable when it ends, a block before the "
                          "application sees it, and a restart replays the "
                          "file (docs/store.md)")
    run.add_argument("--cache-size", type=int, default=500,
                     help="Number of items in LRU caches")
    run.add_argument("--heartbeat", type=float, default=1.0,
                     help="Time between gossips in seconds")
    run.add_argument("--sync-limit", type=int, default=100,
                     help="Max number of events for sync")
    run.add_argument("--consensus-backend", default="cpu", choices=("cpu", "tpu"),
                     help="Run the five-pass pipeline on host (cpu) or device (tpu)")
    run.add_argument("--mesh-devices", type=int, default=0,
                     help="With --consensus-backend=tpu: shard the device "
                          "passes over this many chips (0 = single device)")
    run.add_argument("--dispatch-queue-depth", type=int, default=4,
                     help="Max device dispatches in flight in the async "
                          "dispatch queue (1 = single-slot overlap, 0 = "
                          "disable the queued-mesh rung)")
    run.add_argument("--dispatch-batch-deadline", type=float, default=0.0,
                     help="Hold gossip-staged rows up to this many seconds "
                          "(or until a size threshold) before dispatching, "
                          "batching device work across syncs (0 = no hold)")
    run.add_argument("--dispatch-batch-rows", type=int, default=64,
                     help="Delta-row threshold that releases a held batch "
                          "and switches the dispatch onto the round-batched "
                          "(pointer-doubling) path; also sizes the live "
                          "engine's device batch")
    run.add_argument("--mesh-validator-shards", type=int, default=1,
                     help="With --mesh-devices N: fold the mesh into a 2-D "
                          "(validators, rounds) layout with this many "
                          "validator shards (must divide N; 1 = rounds-only)")
    run.add_argument("--packed-voting", choices=("0", "1", "auto"),
                     default="auto",
                     help="Voting-table layout: 1 packs the validator axis "
                          "into uint32 lanes with popcount tallies "
                          "(byte-equal, ~8x smaller voting state), 0 keeps "
                          "the wide bool layout, auto packs at large N; "
                          "env BABBLE_PACKED_VOTING overrides at call time")
    run.add_argument("--ingress-batch-bytes", type=int, default=65536,
                     help="Byte threshold that releases an ingress batch "
                          "to the tx worker; a single tx at/over it "
                          "bypasses coalescing and ships alone")
    run.add_argument("--ingress-batch-deadline", type=float, default=0.0,
                     help="Hold a partial ingress batch up to this many "
                          "seconds waiting for more submissions "
                          "(0 = release on every pump)")
    run.add_argument("--ingress-queue-cap", type=int, default=8192,
                     help="Max transactions held in the ingress pipeline "
                          "before submissions get the shed verdict "
                          "(0 = unbounded)")
    run.add_argument("--ingress-client-rate", type=float, default=0.0,
                     help="Per-client token-bucket rate in tx/s (client = "
                          "peer addr or app-supplied client_id); enables "
                          "deficit-round-robin fairness (0 = unlimited)")
    run.add_argument("--metrics", action="store_true",
                     help="Log periodic metrics-registry snapshots at info "
                          "(the registry always serves GET /metrics on the "
                          "HTTP service regardless)")
    run.add_argument("--flightrec-dir", default="",
                     help="Write flight-recorder dump artifacts (stall/"
                          "flap/SLO-breach triage) into this directory; "
                          "empty keeps dumps in memory, served at "
                          "GET /debug/flightrec either way")
    run.add_argument("--no-slo", action="store_true",
                     help="Disable the SLO engine (GET /debug/slo and the "
                          "babble_slo_* burn-rate gauges)")

    kg = sub.add_parser("keygen", help="Create new key pair")
    kg.add_argument("--datadir", default=default_data_dir(),
                    help="Directory to write priv_key.pem into")

    sim = sub.add_parser(
        "sim",
        help="Deterministic cluster simulation / seed sweep (docs/sim.md)",
    )
    sim.add_argument("--seed", type=int, default=0,
                     help="Master seed (first seed when sweeping)")
    sim.add_argument("--sweep", type=int, default=0, metavar="N",
                     help="Run N consecutive seeds starting at --seed")
    sim.add_argument("--nodes", type=int, default=4,
                     help="Cluster size")
    sim.add_argument("--plan", default="clean",
                     help="Fault plan: preset name (clean, lossy, "
                          "partition_heal, crash_restart, chaos) or a "
                          "FaultPlan JSON file path")
    sim.add_argument("--store", default="inmem", choices=("inmem", "sqlite"),
                     help="Per-node store backend (sqlite survives crashes)")
    sim.add_argument("--consensus-backend", default="cpu",
                     choices=("cpu", "tpu"),
                     help="Consensus engine for the simulated nodes")
    sim.add_argument("--target-block", type=int, default=15,
                     help="Stop once every live node commits this block")
    sim.add_argument("--until", type=float, default=60.0,
                     help="Virtual-time deadline in seconds")
    sim.add_argument("--artifact-dir", default="docs/artifacts",
                     help="Where divergence replay artifacts are written")
    sim.add_argument("--log", default="error", choices=sorted(LOG_LEVELS),
                     help="Log level for the simulated nodes")

    ex = sub.add_parser(
        "explain",
        help="Decision provenance: explain one round (live node or "
             "offline bisect; docs/observability.md)",
    )
    ex.add_argument("--addr", default="127.0.0.1:8000",
                    help="HTTP service address of a running node "
                         "(GET /debug/explain)")
    ex.add_argument("--block", type=int, default=None,
                    help="Explain the round that received this block")
    ex.add_argument("--round", type=int, default=None,
                    help="Explain this consensus round directly")
    ex.add_argument("--bisect", nargs=2, metavar=("A.json", "B.json"),
                    default=None,
                    help="Offline: diff two exported provenance streams "
                         "(sim export_provenance files) and print the "
                         "earliest divergent cell")
    ex.add_argument("--artifact-dir", default="",
                    help="With --bisect: also export the localization "
                         "triage artifact into this directory")
    ex.add_argument("--smoke", type=int, default=0, metavar="N",
                    help="Self-test: run the N-seed bisector smoke "
                         "(seeded synthetic divergence must localize "
                         "exactly; clean pairs must localize nothing)")

    st = sub.add_parser(
        "status",
        help="Cluster health dashboard: fleet frontier table, skew/"
             "agreement series and partition suspicion from a live "
             "node's GET /debug/cluster (docs/observability.md)",
    )
    st.add_argument("--addr", default="127.0.0.1:8000",
                    help="HTTP service address of a running node")
    st.add_argument("--watch", type=float, default=0.0, metavar="SECS",
                    help="Re-render every SECS seconds until interrupted "
                         "(0 = render once and exit)")
    st.add_argument("--json", action="store_true",
                    help="Print the raw /debug/cluster document instead "
                         "of the rendered dashboard")

    # `lint` is dispatched before the main parse (main()): the analysis
    # runner owns its own argparse, and argparse.REMAINDER inside a
    # subparser mis-handles leading optionals. Registered here so it
    # shows up in --help.
    sub.add_parser(
        "lint",
        help="Consensus-grade static analysis (docs/analysis.md)",
        add_help=False,
    )

    sub.add_parser("version", help="Show version info")
    return p


_SENTINEL = object()


def _explicit_attrs(argv) -> set:
    """Which run-command dests the user actually passed on the command
    line. Detected by re-parsing with every default swapped for a
    sentinel — argparse itself then accounts for glued short options
    (-t5), '=' forms, and prefix abbreviations (--heart 2)."""
    p = build_parser()
    sub = next(
        a for a in p._actions if isinstance(a, argparse._SubParsersAction)
    )
    for act in sub.choices["run"]._actions:
        if act.dest != "help":
            act.default = _SENTINEL
    ns = p.parse_args(argv)
    return {
        k for k, v in vars(ns).items()
        if v is not _SENTINEL and k != "command"
    }


def _merge_config_file(args: argparse.Namespace, argv=None) -> None:
    """Config-file values fill in anything the user did not pass
    explicitly (flags win, like the reference's viper binding,
    run.go:93-127). Explicitness is detected by argparse itself, not by
    comparing against defaults — a flag explicitly set TO its default
    must still beat the file."""
    cfg = _load_config_file(args.datadir)
    if not cfg:
        return
    argv = list(sys.argv[1:] if argv is None else argv)
    explicit = _explicit_attrs(argv)

    mapping = {
        "log": "log", "listen": "listen", "timeout": "timeout",
        "max-pool": "max_pool", "standalone": "standalone",
        "proxy-listen": "proxy_listen", "client-connect": "client_connect",
        "service-listen": "service_listen",
        "service-remote-debug": "service_remote_debug", "store": "store",
        "cache-size": "cache_size", "heartbeat": "heartbeat",
        "sync-limit": "sync_limit", "consensus-backend": "consensus_backend",
        "mesh-devices": "mesh_devices", "metrics": "metrics",
        "dispatch-queue-depth": "dispatch_queue_depth",
        "dispatch-batch-deadline": "dispatch_batch_deadline",
        "dispatch-batch-rows": "dispatch_batch_rows",
        "mesh-validator-shards": "mesh_validator_shards",
        "packed-voting": "packed_voting",
        "ingress-batch-bytes": "ingress_batch_bytes",
        "ingress-batch-deadline": "ingress_batch_deadline",
        "ingress-queue-cap": "ingress_queue_cap",
        "ingress-client-rate": "ingress_client_rate",
    }
    for file_key, attr in mapping.items():
        if file_key in cfg and attr not in explicit:
            setattr(args, attr, cfg[file_key])


def run_command(args: argparse.Namespace) -> int:
    logging.basicConfig(
        level=LOG_LEVELS[args.log],
        format="%(asctime)s %(levelname)s %(name)s: %(message)s",
    )
    logger = logging.getLogger("babble")

    # knob validation: batch sizing is a property of the dispatch queue,
    # so a non-default --dispatch-batch-rows with queuing disabled is a
    # configuration contradiction, not something to silently ignore
    if args.dispatch_batch_rows < 1:
        logger.error("--dispatch-batch-rows must be >= 1")
        return 1
    if args.dispatch_batch_rows != 64 and args.dispatch_queue_depth == 0:
        logger.error(
            "--dispatch-batch-rows requires --dispatch-queue-depth > 0 "
            "(the queued-mesh rung is what batches rows)"
        )
        return 1
    if args.mesh_validator_shards < 1:
        logger.error("--mesh-validator-shards must be >= 1")
        return 1
    if (
        args.mesh_validator_shards > 1
        and (
            args.mesh_devices < 2
            or args.mesh_devices % args.mesh_validator_shards != 0
        )
    ):
        logger.error(
            "--mesh-validator-shards=%d must divide --mesh-devices=%d",
            args.mesh_validator_shards, args.mesh_devices,
        )
        return 1
    if str(args.packed_voting) not in ("0", "1", "auto"):
        # config-file values bypass argparse choices — validate here too
        logger.error("--packed-voting must be 0, 1 or auto")
        return 1

    if args.ingress_batch_bytes < 1:
        logger.error("--ingress-batch-bytes must be >= 1")
        return 1
    if args.ingress_batch_deadline < 0:
        logger.error("--ingress-batch-deadline must be >= 0")
        return 1
    if args.ingress_queue_cap < 0:
        logger.error("--ingress-queue-cap must be >= 0 (0 = unbounded)")
        return 1
    if args.ingress_client_rate < 0:
        logger.error("--ingress-client-rate must be >= 0 (0 = unlimited)")
        return 1
    # contradiction, not something to silently ignore (the rate limiter's
    # overrate shed bound is derived from the queue cap — unbounded
    # admission with a per-client rate would park flooder backlogs forever)
    if args.ingress_client_rate > 0 and args.ingress_queue_cap == 0:
        logger.error(
            "--ingress-client-rate requires --ingress-queue-cap > 0 "
            "(rate limiting needs a bounded admission queue to shed into)"
        )
        return 1

    if args.standalone:
        proxy = InmemDummyClient(logger)
    else:
        proxy = SocketAppProxy(
            client_addr=args.client_connect,
            bind_addr=args.proxy_listen,
            timeout=args.heartbeat,
            logger=logger,
        )

    config = BabbleConfig(
        data_dir=args.datadir,
        bind_addr=args.listen,
        service_addr=args.service_listen,
        service_remote_debug=args.service_remote_debug,
        max_pool=args.max_pool,
        store=args.store,
        log_level=args.log,
        proxy=proxy,
        node=NodeConfig(
            heartbeat_timeout=args.heartbeat,
            tcp_timeout=args.timeout,
            cache_size=args.cache_size,
            sync_limit=args.sync_limit,
            consensus_backend=args.consensus_backend,
            mesh_devices=args.mesh_devices,
            dispatch_queue_depth=args.dispatch_queue_depth,
            dispatch_batch_deadline=args.dispatch_batch_deadline,
            dispatch_batch_rows=args.dispatch_batch_rows,
            mesh_validator_shards=args.mesh_validator_shards,
            packed_voting=str(args.packed_voting),
            ingress_batch_bytes=args.ingress_batch_bytes,
            ingress_batch_deadline=args.ingress_batch_deadline,
            ingress_queue_cap=args.ingress_queue_cap,
            ingress_client_rate=args.ingress_client_rate,
            metrics_log=args.metrics,
            flightrec_dir=args.flightrec_dir or None,
            slo_enabled=not args.no_slo,
            logger=logger,
        ),
    )

    engine = Babble(config)
    try:
        engine.init()
    except Exception as e:  # noqa: BLE001 — startup errors go to the operator
        logger.error("Cannot initialize engine: %s", e)
        return 1
    try:
        engine.run()
    except KeyboardInterrupt:
        engine.shutdown()
    return 0


def sim_command(args: argparse.Namespace) -> int:
    """Deterministic simulation driver. Single-seed mode prints the run
    result plus its block digest (the replay fingerprint: two invocations
    with the same seed and plan must print the same digest). Sweep mode
    runs N consecutive seeds and exits nonzero if any seed diverged —
    each failure leaves a replay artifact under --artifact-dir."""
    logging.basicConfig(
        level=LOG_LEVELS[args.log],
        format="%(asctime)s %(levelname)s %(name)s: %(message)s",
    )
    from .sim import FaultPlan, run_one, run_sweep

    if os.path.exists(args.plan):
        with open(args.plan) as f:
            plan = FaultPlan.from_json(f.read())
    else:
        plan = args.plan  # preset name; run_one/run_sweep resolve it

    common = dict(
        plan=plan,
        n=args.nodes,
        store=args.store,
        backend=args.consensus_backend,
        until=args.until,
        target_block=args.target_block,
        artifact_dir=args.artifact_dir,
    )
    if args.sweep > 0:
        def progress(row):
            status = "ok" if row["ok"] else f"DIVERGED ({row['artifact']})"
            print(
                f"seed {row['seed']:>6}: {status}  "
                f"blocks={row['blocks_checked']} t={row['virtual_time']}"
                f" restarts={row['restarts']} flips={row['catchup_flips']}"
            )
            if not row["ok"] and row.get("localized"):
                loc = row["localized"]
                print(
                    "  localized: round %s %s/%s cell %s (%s)" % (
                        loc["round"], loc["pass"], loc["table"],
                        (loc.get("cell") or "")[:18],
                        row.get("bisect_artifact"),
                    )
                )
            if not row["ok"] and row.get("flightrec"):
                print(f"  flight-recorder triage: {row['flightrec']}")

        summary = run_sweep(
            range(args.seed, args.seed + args.sweep),
            progress=progress, **common,
        )
        print(
            f"\n{summary['seeds']} seeds, {summary['failed']} failed, "
            f"{summary['total_blocks_checked']} blocks byte-checked"
        )
        if summary["failed"]:
            print(f"failing seeds: {summary['failed_seeds']}")
            print(f"replay artifacts: {summary['artifacts']}")
            if summary.get("flightrec_artifacts"):
                print(
                    "flight-recorder triage: "
                    f"{summary['flightrec_artifacts']}"
                )
            if summary.get("bisect_artifacts"):
                print(
                    "bisection triage: "
                    f"{summary['bisect_artifacts']}"
                )
            return 1
        return 0

    res = run_one(args.seed, **common)
    out = {k: v for k, v in res.items() if k != "rows"}
    print(json.dumps(out, indent=2, sort_keys=True))
    return 0 if res["ok"] else 1


def explain_command(args: argparse.Namespace) -> int:
    """`babble-tpu explain` — three modes, one triage surface:

    - `--smoke N` (CI entry): seeded synthetic bisector self-test; the
      injected fame flip must localize to its exact cell and a clean
      pair must localize nothing. Nonzero exit on any failure.
    - `--bisect A.json B.json`: offline first-divergence bisection of
      two exported provenance streams (sim `export_provenance` files).
    - `--addr/--block/--round`: fetch the decision dossier from a live
      node's GET /debug/explain.
    """
    from .obs import DivergenceBisector, run_bisector_smoke

    if args.smoke > 0:
        failures = run_bisector_smoke(seeds=args.smoke)
        for f in failures:
            print(f"FAIL: {f}")
        print(
            f"bisector smoke: {args.smoke} seeds, "
            f"{len(failures)} failures"
        )
        return 1 if failures else 0

    if args.bisect is not None:
        a_path, b_path = args.bisect
        with open(a_path) as f:
            a_doc = json.load(f)
        with open(b_path) as f:
            b_doc = json.load(f)
        a_name = os.path.splitext(os.path.basename(a_path))[0]
        b_name = os.path.splitext(os.path.basename(b_path))[0]
        bis = DivergenceBisector(args.artifact_dir or "docs/artifacts")
        loc = bis.bisect(a_name, a_doc, b_name, b_doc)
        if loc is None:
            print("streams agree: no divergent cell")
            return 0
        print(json.dumps(loc, indent=2, sort_keys=True))
        if args.artifact_dir:
            path = bis.export(
                loc, f"bisect-{a_name}-vs-{b_name}.json",
                context={"a": a_path, "b": b_path},
            )
            print(f"triage artifact: {path}")
        return 1

    if args.block is None and args.round is None:
        print("explain needs --block, --round, --bisect or --smoke",
              file=sys.stderr)
        return 2
    url = f"http://{args.addr}/debug/explain?"
    url += (f"round={args.round}" if args.round is not None
            else f"block={args.block}")
    import urllib.request

    with urllib.request.urlopen(url, timeout=5.0) as resp:
        doc = json.loads(resp.read().decode())
    print(json.dumps(doc, indent=2, sort_keys=True))
    return 0


def render_status(doc: dict) -> str:
    """Render one GET /debug/cluster document as a one-screen dashboard.
    Pure (doc -> str), so the status smoke and tests exercise the exact
    strings an operator sees."""
    lines = []
    addr = doc.get("addr") or "?"
    derived = doc.get("derived") or {}
    fleet = doc.get("fleet") or {}
    susp = doc.get("suspicion") or {}
    lines.append(
        f"babble-tpu cluster status  (via {addr}, "
        f"{len(fleet)} node{'s' if len(fleet) != 1 else ''})"
    )
    lines.append("")
    hdr = (
        f"{'node':<22} {'block':>6} {'round':>6} {'rung':<12} "
        f"{'undec':>5} {'txs':>5} {'sigs':>5} {'ingr':>5} "
        f"{'forks':>5} {'age':>7}"
    )
    lines.append(hdr)
    lines.append("-" * len(hdr))
    for a in sorted(fleet):
        d = fleet[a]
        mark = "*" if a == addr else " "
        age = d.get("age")
        lines.append(
            f"{mark}{a:<21} {d.get('block', '?'):>6} "
            f"{d.get('round', '?'):>6} {str(d.get('rung', '?')):<12} "
            f"{d.get('undecided', '?'):>5} {d.get('txs', '?'):>5} "
            f"{d.get('sigs', '?'):>5} {d.get('ingress', '?'):>5} "
            f"{d.get('forks', '?'):>5} "
            f"{('%.1fs' % age) if isinstance(age, (int, float)) else '?':>7}"
        )
    lines.append("")
    skew = derived.get("babble_cluster_commit_skew_blocks", 0.0)
    rskew = derived.get("babble_cluster_round_skew", 0.0)
    agree = derived.get("babble_cluster_frontier_agreement", 1.0)
    fame = derived.get("babble_cluster_fame_latency_rounds", 0.0)
    lines.append(
        f"commit skew: {skew:g} blocks   round skew: {rskew:g}   "
        f"frontier agreement: {agree:g}   fame latency: {fame:g} rounds"
    )
    if agree < 1.0:
        lines.append(
            "!! FRONTIER DISAGREEMENT: a peer committed a different "
            "block at a common index — investigate immediately"
        )
    if susp.get("suspected"):
        lines.append(
            f"!! PARTITION SUSPECTED: components "
            f"{susp.get('components')}"
        )
    else:
        lines.append("partition: none suspected")
    return "\n".join(lines)


def status_command(args: argparse.Namespace) -> int:
    """`babble-tpu status` — fetch GET /debug/cluster from a live node
    and render the cluster dashboard; `--watch SECS` re-renders in a
    loop (docs/observability.md)."""
    import time
    import urllib.request

    url = f"http://{args.addr}/debug/cluster"

    def once() -> int:
        try:
            with urllib.request.urlopen(url, timeout=5.0) as resp:
                doc = json.loads(resp.read().decode())
        except Exception as e:  # noqa: BLE001 — operator-facing fetch
            print(f"status: cannot fetch {url}: {e}", file=sys.stderr)
            return 1
        if args.json:
            print(json.dumps(doc, indent=2, sort_keys=True))
        else:
            print(render_status(doc))
        return 0

    if args.watch <= 0:
        return once()
    try:
        while True:
            # clear-screen escape, like `watch`: the dashboard is a
            # fixed-height single screen
            sys.stdout.write("\x1b[2J\x1b[H")
            rc = once()
            sys.stdout.flush()
            time.sleep(args.watch)  # det-ok: operator watch loop on a real terminal, never under the sim clock
            if rc != 0:
                # keep watching through transient fetch errors
                continue
    except KeyboardInterrupt:
        return 0


def keygen_command(args: argparse.Namespace) -> int:
    try:
        key = keygen(args.datadir)
    except ValueError as e:
        print(f"Error: {e}", file=sys.stderr)
        return 1
    from .crypto import pub_key_bytes

    print(f"Public Key: 0x{pub_key_bytes(key).hex().upper()}")
    print(f"Key written to {os.path.join(args.datadir, 'priv_key.pem')}")
    return 0


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if argv[:1] == ["lint"]:
        from .analysis import main as lint_main

        return lint_main(argv[1:])
    args = build_parser().parse_args(argv)
    if args.command == "run":
        _merge_config_file(args, argv)
        return run_command(args)
    if args.command == "sim":
        return sim_command(args)
    if args.command == "explain":
        return explain_command(args)
    if args.command == "status":
        return status_command(args)
    if args.command == "keygen":
        return keygen_command(args)
    if args.command == "version":
        print(version_mod.version)
        return 0
    return 2


if __name__ == "__main__":
    sys.exit(main())
