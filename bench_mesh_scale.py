"""Mesh-scale benchmark: validator sweep across dispatch disciplines for
the round-batched sharded backend (babble_tpu/tpu/dispatch.py +
sharded.py; ROADMAP item 1, ISSUE 9).

For each validator count in the sweep the workload is a stream of CALLS
gossip syncs delivering one synthetic DAG, and three disciplines move it
through ordering:

- sync          — every sync blocks on a full sharded pipeline (the r05
                  one-shot rung);
- queued        — bounded multi-slot dispatch queue, one dispatch per
                  sync (the r06 queued rung: 51.3 ms/call device-blocked);
- round_batched — the ISSUE 9 rung: BATCH_SYNCS syncs accumulate into
                  ONE dispatch that rides the pointer-doubling cold path
                  (use_doubling prefer=True), so the fixed dispatch
                  overhead amortizes across every round in the batch;
- packed        — the ISSUE 17 rung: the sync discipline with the
                  uint32 bit-packed voting-table layout (tpu/packed.py —
                  lane packing + popcount tallies). Byte-equality-gated
                  against the same oracle as the wide sync column it is
                  compared to; the per-rung speedup_vs_wide and
                  table-bytes reduction are the packed headline.

Every discipline's pass results are byte-equality-gated against the CPU
oracle (run_frontier_passes) before any number is reported — the
discipline may only change WHEN the device runs, never what comes out.

Rounds-per-dispatch accounting: the gossip stream delivers the grid's
rounds over CALLS syncs, so a discipline that dispatches once per k
syncs covers k/CALLS of the grid's rounds per dispatch — the bench-side
mirror of the babble_mesh_rounds_per_dispatch histogram the live queue
observes at integration time. A sweep point's rounds/dispatch is bounded
by the rounds its workload contains, and interactive-scale grids are
shallow (4 generations per validator ≈ a single round), so the sweep
numbers stay in the JSON as bookkeeping while the histogram — and the
--slo floor — are fed by a dedicated deep CATCH-UP ANCHOR
(ANCHOR_N validators, --anchor-events events ≈ 128 generations ≈ 12
rounds): the stream a node replays when it is many rounds behind, which
is exactly the regime round batching exists for.

Prints the headline as the LAST line (driver-parsable):
  {"metric": ..., "value": <batched events/s at the largest sweep
   point>, "unit": "events/s", "vs_baseline": <batched/sync>,
   "rounds_per_dispatch": ..., "validator_shards": ...,
   "validators": {...}, "metrics": {...}}

`--slo` gates the run on the rounds-per-dispatch floor: the batched
discipline must sustain a mean of at least --slo-min-rounds (default 4)
rounds per dispatch, declared as a mean_above SLO objective (obs/slo.py)
and evaluated once; breach exits nonzero with the report on stderr.
When the sweep reaches --slo-packed-n validators (default 1024 — the
ISSUE 17 crossover point), --slo additionally gates on the packed
discipline's speedup over wide sync at the largest such rung staying at
or above --slo-min-packed-speedup (default 1.0: packed ms/call must not
exceed wide ms/call).

The default sweep (8,64,128) plus the anchor runs in a few minutes on
the CPU mesh — the 8-validator rung is directly comparable to
dryrun_multichip's r06 51.3 ms/call queued figure; pass
--validators 64,256,1024,4096 on real hardware for the full ISSUE 9
range.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

SEED = 13
CALLS = 16          # gossip syncs per discipline
QUEUE_DEPTH = 4     # queued: max dispatches in flight
BATCH_SYNCS = 8     # round_batched: syncs accumulated per dispatch
ANCHOR_N = 64       # catch-up anchor: validators (smallest sweep rung)
# finite gossip arrival cadence — overlap and batching only show up
# against an arrival model (see bench_dispatch.py)
GOSSIP_INTERVAL_S = 0.005


def _bisect_gate(grid, out, ref, label):
    """On an oracle-gate failure: bisect the two result streams to the
    earliest divergent (pass, table, round, witness) cell and export the
    triage artifact (obs/provenance.py) before the caller re-raises."""
    from babble_tpu.obs import bisect_pass_results

    loc, path = bisect_pass_results(
        grid, "device", out, "oracle", ref, label=label,
    )
    if loc is not None:
        print(
            "bisected: round %s %s/%s cell %s (%s)" % (
                loc["round"], loc["pass"], loc["table"],
                (loc.get("cell") or "")[:18], path,
            ),
            file=sys.stderr,
        )


def slo_gate(obs, min_rounds: float, packed_floor=None, packed_n=None):
    """Declare the rounds-per-dispatch floor — and, when the sweep
    reached the packed crossover rung, the packed-speedup floor — then
    evaluate once. Returns (ok, status_doc)."""
    from babble_tpu.obs import SLOEngine

    slo = SLOEngine(obs)
    slo.objective(
        "mesh_rounds_per_dispatch",
        series="babble_mesh_rounds_per_dispatch",
        kind="mean_above", threshold=min_rounds,
        description="round-batched dispatches keep covering at least "
                    "this many consensus rounds each",
    )
    if packed_n is not None:
        slo.objective(
            "mesh_packed_speedup",
            series="babble_bench_packed_speedup",
            kind="above", threshold=packed_floor,
            labels={"validators": str(packed_n)},
            description="bit-packed voting tables stay at least this "
                        "much faster than the wide layout at the "
                        "largest crossover-scale rung",
        )
    # steady-state retrace budget (ISSUE 19): zero kernel retraces past
    # each sweep point's warmup — nonzero means a staged callable is
    # being silently rebuilt inside the timed loops
    slo.objective(
        "retrace_budget",
        series="babble_bench_retrace_delta",
        kind="below", threshold=1.0,
        description="steady-state kernel retraces past warmup stay at "
                    "zero",
    )
    status = slo.evaluate()
    return not slo.breached(), status


def build_mesh(devices, validator_shards):
    import numpy as np
    from jax.sharding import Mesh

    n_dev = 1
    while n_dev * 2 <= min(8, len(devices)):
        n_dev *= 2
    dv = validator_shards
    if dv > 1 and (n_dev < 2 * dv or n_dev % dv):
        dv = 1
    if dv > 1:
        mesh = Mesh(
            np.array(devices[:n_dev]).reshape(dv, n_dev // dv),
            ("validators", "rounds"),
        )
    else:
        mesh = Mesh(np.array(devices[:n_dev]), ("rounds",))
    return mesh, n_dev, dv


def run_sweep_point(mesh, n, events, oracle_cache, obs=None):
    """One validator count: build the grid, gate every discipline against
    the CPU oracle, return the per-discipline numbers."""
    import contextlib

    import numpy as np

    from babble_tpu.obs import retrace_baseline, retrace_delta
    from babble_tpu.tpu.dispatch import _AsyncPass
    from babble_tpu.tpu.engine import run_frontier_passes
    from babble_tpu.tpu.grid import build_levels, synthetic_grid
    from babble_tpu.tpu.sharded import sharded_frontier_passes

    led = obs.devledger if obs is not None else None

    def act(layout="wide"):
        if led is None:
            return contextlib.nullcontext()
        return led.activate("sharded", layout=layout)

    grid = synthetic_grid(n, events, seed=SEED)
    ref = run_frontier_passes(grid)  # CPU oracle
    oracle_cache[n] = ref

    def gossip_stage():
        time.sleep(GOSSIP_INTERVAL_S)
        return build_levels(n, grid.self_parent, grid.other_parent)

    def gate(out):
        try:
            np.testing.assert_array_equal(
                np.asarray(out.rounds), np.asarray(ref.rounds)
            )
            np.testing.assert_array_equal(
                np.asarray(out.received), np.asarray(ref.received)
            )
            assert int(out.last_round) == int(ref.last_round)
        except AssertionError:
            _bisect_gate(grid, out, ref, f"mesh-sweep-n{n}")
            raise

    # compile + warm every device path outside the timed loops; the
    # packed warm call doubles as the per-point byte-equality gate the
    # ISSUE 17 discipline requires (gate() bisects on divergence). The
    # device-time ledger watches the warmup so every legitimate compile
    # lands before the retrace baseline below.
    with act():
        gate(sharded_frontier_passes(mesh, grid))
    with act(layout="packed"):
        gate(sharded_frontier_passes(mesh, grid, packed=True))
    gate(_AsyncPass(mesh, grid, prefer_doubling=True, ledger=led).result())
    retrace_base = retrace_baseline(obs) if obs is not None else {}
    cells0 = led.snapshot()["cells"] if led is not None else {}

    wall, blocked, dispatches = {}, {}, {}

    # -- sync -------------------------------------------------------------
    t0 = time.perf_counter()
    b = 0.0
    for _ in range(CALLS):
        gossip_stage()
        tb = time.perf_counter()
        with act():
            out = sharded_frontier_passes(mesh, grid)
        b += time.perf_counter() - tb
    wall["sync"] = time.perf_counter() - t0
    blocked["sync"], dispatches["sync"] = b, CALLS

    # -- packed: the sync discipline under the uint32 lane layout ---------
    t0 = time.perf_counter()
    b = 0.0
    for _ in range(CALLS):
        gossip_stage()
        tb = time.perf_counter()
        with act(layout="packed"):
            out = sharded_frontier_passes(mesh, grid, packed=True)
        b += time.perf_counter() - tb
    gate(out)
    wall["packed"] = time.perf_counter() - t0
    blocked["packed"], dispatches["packed"] = b, CALLS

    # -- queued: bounded queue, one dispatch per sync ---------------------
    t0 = time.perf_counter()
    b = 0.0
    inflight = []
    for _ in range(CALLS):
        gossip_stage()
        while len(inflight) >= QUEUE_DEPTH:
            tb = time.perf_counter()
            out = inflight.pop(0).result()
            b += time.perf_counter() - tb
        inflight.append(_AsyncPass(mesh, grid, ledger=led))
    while inflight:
        tb = time.perf_counter()
        out = inflight.pop(0).result()
        b += time.perf_counter() - tb
    gate(out)
    wall["queued"] = time.perf_counter() - t0
    blocked["queued"], dispatches["queued"] = b, CALLS

    # -- round_batched: BATCH_SYNCS syncs -> one doubling dispatch --------
    t0 = time.perf_counter()
    b = 0.0
    inflight = []
    pending = 0
    n_disp = 0
    for _ in range(CALLS):
        gossip_stage()
        pending += 1
        if pending < BATCH_SYNCS:
            continue
        while len(inflight) >= QUEUE_DEPTH:
            tb = time.perf_counter()
            out = inflight.pop(0).result()
            b += time.perf_counter() - tb
        inflight.append(
            _AsyncPass(mesh, grid, prefer_doubling=True, ledger=led)
        )
        n_disp += 1
        pending = 0
    if pending:
        inflight.append(
            _AsyncPass(mesh, grid, prefer_doubling=True, ledger=led)
        )
        n_disp += 1
    while inflight:
        tb = time.perf_counter()
        out = inflight.pop(0).result()
        b += time.perf_counter() - tb
    gate(out)
    wall["round_batched"] = time.perf_counter() - t0
    blocked["round_batched"], dispatches["round_batched"] = b, n_disp

    total_rounds = int(ref.last_round) + 1
    point = {
        name: {
            "events_per_sec": round(events / wall[name], 1),
            "ms_per_call": round(blocked[name] / CALLS * 1e3, 2),
            "dispatches": dispatches[name],
            "rounds_per_dispatch": round(total_rounds / dispatches[name], 2),
            "wall_s": round(wall[name], 3),
        }
        for name in ("sync", "packed", "queued", "round_batched")
    }
    # the packed column's two headline figures: blocked-time speedup over
    # the wide sync column it differs from by layout alone, and the
    # device-resident voting-table footprint of each layout
    from babble_tpu.tpu.packed import observe_table_bytes, voting_table_bytes

    r_tab = int(ref.witness_table.shape[0])
    tb_wide = sum(voting_table_bytes(n, r_tab, False).values())
    tb_packed = sum(voting_table_bytes(n, r_tab, True).values())
    if obs is not None:
        # both layouts into the babble_device_table_bytes gauge so the
        # registry snapshot in the archived JSON carries the footprint
        # (last sweep rung wins — the headline scale)
        observe_table_bytes(obs, n, r_tab, False)
        observe_table_bytes(obs, n, r_tab, True)
    point["packed"]["speedup_vs_wide"] = round(
        blocked["sync"] / max(blocked["packed"], 1e-9), 2
    )
    point["packed"]["table_bytes"] = tb_packed
    point["packed"]["table_bytes_wide"] = tb_wide
    point["packed"]["table_bytes_reduction"] = round(tb_wide / tb_packed, 2)
    if led is not None:
        # per-point device-time ledger (ISSUE 19): this sweep point's
        # share of attributed seconds per (rung, pass, layout) — the
        # cumulative cells diffed against the point's post-warmup state
        cells1 = led.snapshot()["cells"]
        delta_s = {}
        for key, (_calls, secs) in cells1.items():
            prev = cells0.get(key, (0, 0.0))[1]
            d = secs - prev
            if d > 0:
                delta_s[key] = d
        total_s = sum(delta_s.values())
        point["ledger"] = {
            "seconds": round(total_s, 6),
            "shares": {
                k: round(v / total_s, 4) if total_s > 0 else 0.0
                for k, v in sorted(delta_s.items())
            },
            "retrace_delta": retrace_delta(obs, retrace_base),
        }
    return point


def run_catchup_anchor(mesh, events, rpd_hist, obs=None):
    """Deep catch-up stream: one grid of ~events/ANCHOR_N generations
    replayed through the round-batched discipline only. Every dispatch's
    round coverage is observed into rpd_hist — this is the series the
    --slo floor gates on."""
    import numpy as np

    from babble_tpu.tpu.dispatch import _AsyncPass
    from babble_tpu.tpu.engine import run_frontier_passes
    from babble_tpu.tpu.grid import synthetic_grid

    led = obs.devledger if obs is not None else None
    grid = synthetic_grid(ANCHOR_N, events, seed=SEED)
    ref = run_frontier_passes(grid)
    total_rounds = int(ref.last_round) + 1

    def gate(out):
        try:
            np.testing.assert_array_equal(
                np.asarray(out.rounds), np.asarray(ref.rounds)
            )
            np.testing.assert_array_equal(
                np.asarray(out.received), np.asarray(ref.received)
            )
            assert int(out.last_round) == int(ref.last_round)
        except AssertionError:
            _bisect_gate(grid, out, ref, "mesh-catchup-anchor")
            raise

    gate(_AsyncPass(mesh, grid, prefer_doubling=True, ledger=led).result())  # compile

    t0 = time.perf_counter()
    b = 0.0
    inflight = []
    pending = 0
    n_disp = 0
    for _ in range(CALLS):
        time.sleep(GOSSIP_INTERVAL_S)
        pending += 1
        if pending < BATCH_SYNCS:
            continue
        while len(inflight) >= QUEUE_DEPTH:
            tb = time.perf_counter()
            out = inflight.pop(0).result()
            b += time.perf_counter() - tb
        inflight.append(_AsyncPass(mesh, grid, prefer_doubling=True, ledger=led))
        n_disp += 1
        pending = 0
    if pending:
        inflight.append(_AsyncPass(mesh, grid, prefer_doubling=True, ledger=led))
        n_disp += 1
    while inflight:
        tb = time.perf_counter()
        out = inflight.pop(0).result()
        b += time.perf_counter() - tb
    gate(out)
    wall = time.perf_counter() - t0

    # each dispatch carries BATCH_SYNCS/CALLS of the stream's rounds
    per_dispatch = round(total_rounds * BATCH_SYNCS / CALLS, 2)
    for _ in range(n_disp):
        rpd_hist.observe(per_dispatch)
    return {
        "validators": ANCHOR_N,
        "events": events,
        "rounds": total_rounds,
        "events_per_sec": round(events / wall, 1),
        "ms_per_call": round(b / CALLS * 1e3, 2),
        "dispatches": n_disp,
        "rounds_per_dispatch": per_dispatch,
        "wall_s": round(wall, 3),
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--validators", default="8,64,128",
                    help="Comma-separated validator sweep (8 is the "
                         "r06-comparable rung — dryrun_multichip's 51.3 "
                         "ms/call queued figure was measured at 8 "
                         "validators; full ISSUE 9 range: "
                         "64,256,1024,4096 — the CPU virtual mesh "
                         "serializes collectives onto shared cores, so "
                         "256+ belongs on real hardware)")
    ap.add_argument("--events", type=int, default=0,
                    help="Events per sweep point (0 = 4x validators, "
                         "capped at 2048)")
    ap.add_argument("--anchor-events", type=int, default=8192,
                    help="Events in the deep catch-up anchor grid that "
                         "feeds babble_mesh_rounds_per_dispatch and the "
                         "--slo floor (0 skips the anchor)")
    ap.add_argument("--validator-shards", type=int, default=2,
                    help="Validator-axis shards for the 2-D mesh (falls "
                         "back to 1-D when the platform is too small)")
    ap.add_argument("--slo", action="store_true",
                    help="Gate the run on the rounds-per-dispatch floor: "
                         "exit 1 when the batched discipline's mean drops "
                         "under --slo-min-rounds")
    ap.add_argument("--slo-min-rounds", type=float, default=4.0,
                    help="Floor on mean consensus rounds covered per "
                         "batched dispatch for --slo")
    ap.add_argument("--slo-min-packed-speedup", type=float, default=1.0,
                    help="Floor on the packed discipline's blocked-time "
                         "speedup over wide sync at the largest rung at "
                         "or past --slo-packed-n (1.0 = packed ms/call "
                         "must not exceed wide ms/call)")
    ap.add_argument("--slo-packed-n", type=int, default=1024,
                    help="Validator count from which the packed-speedup "
                         "floor applies (the ISSUE 17 crossover scale); "
                         "sweeps that stay under it skip that objective")
    ap.add_argument("--headline", choices=("round_batched", "packed"),
                    default="round_batched",
                    help="Which discipline's events/s at the largest "
                         "sweep point is the driver-parsable headline "
                         "value (make bench-packed archives the packed "
                         "series as BENCH_PACKED_r*.json)")
    args = ap.parse_args(argv)

    if args.headline == "packed":
        # a packed headline over kernels whose contract violations were
        # baselined instead of fixed is a green number on unproven code
        # (ISSUE 18): refuse until the baseline carries no kernel-* entry
        from babble_tpu.analysis.staged import kernel_baseline_entries

        stale = kernel_baseline_entries()
        if stale:
            rules = ", ".join(sorted({e.get("rule", "?") for e in stale}))
            print(
                f"bench_mesh_scale: REFUSING --headline packed — the lint "
                f"baseline carries {len(stale)} kernel-* finding(s) "
                f"({rules}). Fix them (`babble-tpu lint --staged`) rather "
                f"than baselining; the packed headline must only be "
                f"measured over contract-proven kernels.",
                file=sys.stderr,
            )
            return 2

    import jax

    from babble_tpu.tpu.runtime import enable_compile_cache

    enable_compile_cache()

    sweep = [int(x) for x in args.validators.split(",") if x.strip()]
    devices = jax.devices()
    mesh, n_dev, dv = build_mesh(devices, args.validator_shards)

    from babble_tpu.obs import Observability, log_buckets
    from babble_tpu.obs.metrics import DEFAULT_COUNT_BUCKETS

    obs = Observability()
    lat = obs.histogram(
        "babble_bench_mesh_blocked_seconds",
        "Blocked device wall time per gossip sync, by discipline and "
        "validator count",
        labels=("path", "validators"),
        buckets=log_buckets(0.0001, 4.0, 20),
    )
    thr = obs.gauge(
        "babble_bench_mesh_events_per_second",
        "Mesh-scale benchmark throughput, by discipline and validator "
        "count",
        labels=("path", "validators"),
    )
    rpd = obs.histogram(
        "babble_mesh_rounds_per_dispatch",
        "Consensus rounds newly covered per integrated mesh dispatch",
        buckets=DEFAULT_COUNT_BUCKETS,
    )
    obs.gauge(
        "babble_mesh_validator_shards",
        "Validator-axis shards in the active mesh layout",
    ).set(dv)
    spd = obs.gauge(
        "babble_bench_packed_speedup",
        "Blocked-time speedup of the bit-packed voting-table layout over "
        "the wide layout, by validator count",
        labels=("validators",),
    )

    oracle_cache = {}
    per_n = {}
    for n in sweep:
        events = args.events or min(4 * n, 2048)
        per_n[str(n)] = run_sweep_point(mesh, n, events, oracle_cache, obs)
        for name, d in per_n[str(n)].items():
            lat.labels(path=name, validators=str(n)).observe(
                d["ms_per_call"] / 1e3
            )
            thr.labels(path=name, validators=str(n)).set(d["events_per_sec"])
        spd.labels(validators=str(n)).set(
            per_n[str(n)]["packed"]["speedup_vs_wide"]
        )

    anchor = None
    if args.anchor_events:
        anchor = run_catchup_anchor(mesh, args.anchor_events, rpd, obs)

    # steady-state retrace budget across the whole sweep: each point's
    # delta is measured against its own post-warmup baseline, so fresh
    # compiles at new shapes never count — only silent rebuilds do
    retraces = {}
    for point in per_n.values():
        for entry, d in point.get("ledger", {}).get(
            "retrace_delta", {}
        ).items():
            retraces[entry] = retraces.get(entry, 0.0) + d
    obs.gauge(
        "babble_bench_retrace_delta",
        "Steady-state kernel retraces past the warmup baseline "
        "(budget: zero)",
    ).set(float(sum(retraces.values())))

    # cluster health plane (ISSUE 20): a short seeded SimCluster run on
    # the device backend — the health summary (worst skew, frontier
    # agreement, partition suspicions) rides in the headline so
    # bench_trend gates cluster convergence alongside kernel throughput
    from babble_tpu.sim import SimCluster

    probe = SimCluster(n=4, seed=0, backend="tpu", heartbeat=0.05)
    try:
        probe_res = probe.run(until=30.0, target_block=5)
        cluster_health = (probe_res.get("cluster_health") or {}).get(
            "summary"
        )
    finally:
        probe.shutdown()

    top = per_n[str(sweep[-1])]
    headline_rpd = (
        anchor["rounds_per_dispatch"] if anchor
        else top["round_batched"]["rounds_per_dispatch"]
    )
    hname = {"round_batched": "round-batched", "packed": "bit-packed"}
    print(
        json.dumps(
            {
                "metric": (
                    f"events ordered/sec through the {hname[args.headline]} "
                    f"sharded mesh, validator sweep {sweep[0]}..{sweep[-1]}, "
                    f"mesh={n_dev}dev x{dv} validator shards, "
                    f"platform={devices[0].platform}"
                ),
                "value": top[args.headline]["events_per_sec"],
                "unit": "events/s",
                "vs_baseline": round(
                    top[args.headline]["events_per_sec"]
                    / max(top["sync"]["events_per_sec"], 1e-9), 2
                ),
                "rounds_per_dispatch": headline_rpd,
                "validator_shards": dv,
                "packed_speedup": top["packed"]["speedup_vs_wide"],
                "table_bytes_reduction": (
                    top["packed"]["table_bytes_reduction"]
                ),
                "catchup_anchor": anchor,
                "cluster_health": cluster_health,
                "validators": per_n,
                "metrics": obs.registry.snapshot(),
            }
        )
    )

    if args.slo:
        packed_rungs = [n for n in sweep if n >= args.slo_packed_n]
        ok, status = slo_gate(
            obs, args.slo_min_rounds,
            packed_floor=args.slo_min_packed_speedup,
            packed_n=max(packed_rungs) if packed_rungs else None,
        )
        print(
            "SLO gate:",
            json.dumps(status["objectives"], sort_keys=True),
            file=sys.stderr,
        )
        if not ok:
            breached = [
                o["name"] for o in status["objectives"] if o["breached"]
            ]
            if retraces and "retrace_budget" in breached:
                print(
                    "RETRACE BUDGET BLOWN: "
                    + ", ".join(
                        f"{e} (+{int(d)})"
                        for e, d in sorted(retraces.items())
                    ),
                    file=sys.stderr,
                )
                print(
                    "flight ring: "
                    + json.dumps(obs.flightrec.to_json(), sort_keys=True),
                    file=sys.stderr,
                )
            print(
                f"SLO BREACH ({', '.join(breached)}): round-batched "
                f"dispatches covered {headline_rpd} rounds/dispatch "
                f"(floor {args.slo_min_rounds}); packed speedup at the "
                f"top rung {top['packed']['speedup_vs_wide']}x (floor "
                f"{args.slo_min_packed_speedup} from "
                f"N={args.slo_packed_n})",
                file=sys.stderr,
            )
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
