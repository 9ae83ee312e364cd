"""HTTP status service and CLI tests: /stats and /block over a live
node (reference: src/service/service.go:28-63), keygen datadir output
(cmd/babble/commands/keygen.go), and the flag/config-file merge
precedence (run.go:93-155)."""

import json
import os
import time
import urllib.error
import urllib.request

from babble_tpu.cli import _merge_config_file, build_parser, keygen_command
from babble_tpu.service import Service

from test_node import (
    bombard_and_wait, init_nodes, load_scale, run_nodes, shutdown_nodes,
)

REFERENCE_STAT_KEYS = {
    "last_consensus_round", "last_block_index", "consensus_events",
    "consensus_transactions", "undetermined_events", "transaction_pool",
    "num_peers", "sync_rate", "events_per_second", "rounds_per_second",
    "round_events", "id", "state",
}


def _get(url):
    with urllib.request.urlopen(url, timeout=5) as resp:
        return json.loads(resp.read())


def test_service_stats_and_block():
    nodes, proxies = init_nodes(4)
    svc = Service("127.0.0.1:0", nodes[0])
    try:
        run_nodes(nodes)
        svc.serve()
        base = f"http://{svc.local_addr()}"

        stats = _get(base + "/stats")
        # parity: every reference metric present (node.go:660-695), plus
        # the backend extensions
        assert REFERENCE_STAT_KEYS <= set(stats)
        assert stats["consensus_backend"] in ("cpu", "tpu")
        assert stats["num_peers"] == "4"

        bombard_and_wait(nodes, proxies, target_block=1)
        blk = _get(base + "/block/0")
        assert blk["Body"]["Index"] == 0
        assert isinstance(blk["Body"]["Transactions"], list)

        # round_events is actually maintained here (the reference declares
        # but never updates it): events in the round before the last
        # consensus round
        stats = _get(base + "/stats")
        assert int(stats["round_events"]) > 0

        # missing block -> HTTP error, service stays up
        try:
            _get(base + "/block/99999")
            raise AssertionError("expected HTTP error for missing block")
        except urllib.error.HTTPError as e:
            assert e.code in (404, 500)
        assert _get(base + "/stats")["num_peers"] == "4"
    finally:
        svc.shutdown()
        shutdown_nodes(nodes)


def test_keygen_writes_pem(tmp_path):
    class Args:
        datadir = str(tmp_path)

    assert keygen_command(Args()) == 0
    pem = os.path.join(str(tmp_path), "priv_key.pem")
    assert os.path.exists(pem)
    assert b"EC PRIVATE KEY" in open(pem, "rb").read()
    # refuses to overwrite an existing key
    assert keygen_command(Args()) == 1


def test_config_file_merge_flags_win(tmp_path):
    (tmp_path / "babble.json").write_text(json.dumps({
        "heartbeat": 0.25,
        "sync-limit": 42,
        "consensus-backend": "tpu",
    }))
    # file fills defaults...
    argv = ["run", "--datadir", str(tmp_path)]
    args = build_parser().parse_args(argv)
    _merge_config_file(args, argv)
    assert args.heartbeat == 0.25
    assert args.sync_limit == 42
    assert args.consensus_backend == "tpu"
    # ...but explicit flags win over the file
    argv = ["run", "--datadir", str(tmp_path), "--heartbeat", "0.5",
            "--consensus-backend", "cpu"]
    args = build_parser().parse_args(argv)
    _merge_config_file(args, argv)
    assert args.heartbeat == 0.5
    assert args.consensus_backend == "cpu"
    assert args.sync_limit == 42  # still from the file

    # argparse's glued short options and prefix abbreviations also count
    # as explicit (argparse itself does the accounting)
    (tmp_path / "babble.json").write_text(json.dumps({
        "timeout": 3.0, "heartbeat": 9.0,
    }))
    argv = ["run", "--datadir", str(tmp_path), "-t5", "--heart", "2"]
    args = build_parser().parse_args(argv)
    _merge_config_file(args, argv)
    assert args.timeout == 5.0
    assert args.heartbeat == 2.0


def test_service_metrics_and_trace():
    """GET /metrics (Prometheus text exposition from the node's registry)
    and GET /debug/trace (Chrome trace-event JSON from the span ring) —
    the scrape/trace surface of ISSUE 4."""
    nodes, proxies = init_nodes(2)
    svc = Service("127.0.0.1:0", nodes[0])
    try:
        run_nodes(nodes)
        svc.serve()
        base = f"http://{svc.local_addr()}"
        bombard_and_wait(nodes, proxies, target_block=1)

        # Block 1 on every node does not yet mean that node 0 has observed
        # a commit of a transaction it submitted itself (the histogram
        # counts no other: bombard_and_wait picks nodes at random), nor
        # that a `commit` record still lies in the 4,096-span ring, which
        # two nodes at a 5 ms heartbeat wrap in about a second. So keep
        # node 0 committing its own transactions until one scrape shows
        # both, and assert on that scrape.
        def scrape():
            req = urllib.request.urlopen(base + "/metrics", timeout=5)
            return req, req.read().decode(), _get(base + "/debug/trace")

        def commit_count(text):
            for ln in text.splitlines():
                if ln.startswith("babble_commit_latency_seconds_count"):
                    return int(ln.split()[-1])
            return 0

        deadline = time.monotonic() + 60 * load_scale()
        k = 0
        while True:
            req, text, trace = scrape()
            if commit_count(text) >= 1 and any(
                    e["name"] == "commit" for e in trace["traceEvents"]):
                break
            assert time.monotonic() < deadline, "no commit observed on node 0"
            proxies[0].submit_tx(f"own tx {k}".encode())
            k += 1
            time.sleep(0.05)
        assert req.headers["Content-Type"].startswith(
            "text/plain; version=0.0.4"
        )
        # headline + subsystem histograms declared, with valid shape
        for name in (
            "babble_commit_latency_seconds",
            "babble_sync_duration_seconds",
            "babble_consensus_pass_duration_seconds",
            "babble_device_dispatch_seconds",
            "babble_device_fetch_seconds",
        ):
            assert f"# TYPE {name} histogram" in text, name
        assert "# TYPE babble_blocks_committed_total counter" in text
        assert "# TYPE babble_last_block_index gauge" in text
        # the commit actually landed in the headline histogram
        count_lines = [
            ln for ln in text.splitlines()
            if ln.startswith("babble_commit_latency_seconds_count")
        ]
        assert count_lines and int(count_lines[0].split()[-1]) >= 1
        assert 'le="+Inf"' in text
        # consensus passes ran and were labeled by phase
        assert (
            'babble_consensus_pass_duration_seconds_count'
            '{phase="divide_rounds"}'
        ) in text

        assert trace["displayTimeUnit"] == "ms"
        evs = trace["traceEvents"]
        xs = [e for e in evs if e["ph"] == "X"]
        assert xs, "no spans recorded during a committing run"
        names = {e["name"] for e in xs}
        assert "commit" in names
        assert any(n.startswith("consensus.") for n in names)
        assert any(e["ph"] == "M" and e["name"] == "thread_name"
                   for e in evs)
    finally:
        svc.shutdown()
        shutdown_nodes(nodes)


def test_service_debug_endpoints():
    """/debug/stacks (thread dump) and /debug/profile (all-thread stack
    sampler) — the profiling channel of the reference's
    pprof-on-the-service-mux (reference: cmd/babble/main.go:4). The
    profile must cover the NODE's threads, not just the HTTP handler: a
    gossiping node's loops live in node.py, which must show up among the
    sampled frames."""
    import urllib.request

    nodes, proxies = init_nodes(2)
    svc = Service("127.0.0.1:0", nodes[0])
    try:
        run_nodes(nodes)
        svc.serve()
        base = f"http://{svc.local_addr()}"

        with urllib.request.urlopen(base + "/debug/stacks", timeout=10) as r:
            stacks = r.read().decode()
        assert "thread" in stacks and "File" in stacks

        with urllib.request.urlopen(
            base + "/debug/profile?seconds=0.5", timeout=30
        ) as r:
            prof = r.read().decode()
        assert "hottest frames" in prof
        assert "node.py" in prof, "profile missed the node's own threads"

        # collapsed (folded-stack) output: `frame;frame;... count` lines,
        # root-first, ready for flamegraph.pl / speedscope
        with urllib.request.urlopen(
            base + "/debug/profile?seconds=0.5&format=collapsed", timeout=30
        ) as r:
            folded = r.read().decode()
        lines = [ln for ln in folded.splitlines() if ln]
        assert lines
        for ln in lines:
            stack, count = ln.rsplit(" ", 1)
            assert int(count) >= 1
            assert stack
        assert any(";" in ln for ln in lines), "no multi-frame stacks"
    finally:
        svc.shutdown()
        shutdown_nodes(nodes)


def test_service_flightrec_and_slo_endpoints():
    """GET /debug/flightrec (the flight recorder's full state: ring,
    counters, fingerprint) and GET /debug/slo (a fresh SLO evaluation) —
    the triage surface of ISSUE 7."""
    nodes, proxies = init_nodes(2)
    svc = Service("127.0.0.1:0", nodes[0])
    try:
        run_nodes(nodes)
        svc.serve()
        base = f"http://{svc.local_addr()}"
        bombard_and_wait(nodes, proxies, target_block=1)

        fr = _get(base + "/debug/flightrec")
        assert fr["node"] == nodes[0].id
        assert fr["capacity"] >= 1
        assert isinstance(fr["records"], list)
        assert len(fr["fingerprint"]) == 64  # sha256 hex
        for key in ("dropped", "dumps", "dumps_suppressed"):
            assert fr[key] >= 0

        slo = _get(base + "/debug/slo")
        assert slo["windows"] == ["60s", "300s"]
        names = {o["name"] for o in slo["objectives"]}
        assert {"submit_commit_p99", "round_advance"} <= names
        for obj in slo["objectives"]:
            assert set(obj["burn"]) == {"60s", "300s"}
            assert isinstance(obj["breached"], bool)
        # a healthy committing run breaches nothing
        commit = next(o for o in slo["objectives"]
                      if o["name"] == "submit_commit_p99")
        assert commit["breached"] is False
        # the SLO gauges reached the scrape surface
        with urllib.request.urlopen(base + "/metrics", timeout=5) as r:
            text = r.read().decode()
        assert "# TYPE babble_slo_breached gauge" in text
        assert "# TYPE babble_flightrec_records gauge" in text
    finally:
        svc.shutdown()
        shutdown_nodes(nodes)
