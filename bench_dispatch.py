"""Dispatch-discipline benchmark: events/sec and blocked device ms/call
for the three ways a live node can drive the sharded mesh backend
(babble_tpu/tpu/dispatch.py; ROADMAP open item 1).

The workload is a stream of CALLS gossip syncs. Each sync does the real
O(E) host restage work (build_levels over the full coordinate arrays —
the 0.3 ms/call side of the MULTICHIP_r05 breakdown), then the dispatch
discipline decides when the device runs:

- sync        — every sync blocks on a full sharded three-pass pipeline
                (the r05 one-shot rung: 273.8 ms/call on device);
- pipelined   — single-slot overlap: dispatch sync i, block on sync i-1
                (tpu/live.py's original discipline applied to the mesh);
- queued_mesh — bounded multi-slot queue with cross-round batching: syncs
                accumulate while dispatches are in flight, and ONE
                execution covers every pending sync (the one-shot restage
                property: device cost is per-dispatch, not per-sync).

Because decisions are DAG facts, all three disciplines produce identical
pass results — asserted below — so the only thing that varies is when
the device runs, which is the whole point.

Prints the headline as the LAST line (driver-parsable), carrying the
per-discipline numbers and the metrics-registry snapshot:
  {"metric": ..., "value": <queued events/s>, "unit": "events/s",
   "vs_baseline": <queued/sync speedup>, "disciplines": {...},
   "metrics": {...}}

`--slo` gates the run: the queued-mesh discipline's blocked device time
per call is declared as a mean-below SLO objective (obs/slo.py) and the
process exits nonzero on breach (report on stderr; the headline stays
the last stdout line).

Runs on whatever JAX platform is available (real TPU under the driver);
the mesh uses up to 8 local devices.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

N_VALIDATORS = 8
N_EVENTS = 256
SEED = 11
CALLS = 16          # gossip syncs per discipline
QUEUE_DEPTH = 4     # queued_mesh: max dispatches in flight
BATCH_SYNCS = 4     # queued_mesh: syncs accumulated per dispatch
# gossip syncs arrive from the network at a finite cadence; a dispatch
# discipline that overlaps device work with this interval hides it, one
# that blocks serializes behind it. Without an arrival model every
# discipline is purely device-bound and overlap cannot show up at all.
GOSSIP_INTERVAL_S = 0.01


def slo_gate(obs, max_blocked_s: float):
    """Declare the queued-mesh blocked-time objective and evaluate once
    (cumulative single-sample evaluation). Returns (ok, status_doc)."""
    from babble_tpu.obs import SLOEngine

    slo = SLOEngine(obs)
    slo.objective(
        "dispatch_blocked",
        series="babble_bench_dispatch_blocked_seconds",
        kind="mean_below", threshold=max_blocked_s,
        labels={"path": "queued_mesh"},
        description="queued-mesh blocked device time per sync stays "
                    "under the ceiling",
    )
    # steady-state retrace budget (ISSUE 19): zero kernel retraces past
    # the warmup baseline — a nonzero delta means some staged callable
    # is being rebuilt per call and the compile cache never serves it
    slo.objective(
        "retrace_budget",
        series="babble_bench_retrace_delta",
        kind="below", threshold=1.0,
        description="steady-state kernel retraces past warmup stay at "
                    "zero",
    )
    status = slo.evaluate()
    return not slo.breached(), status


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--slo", action="store_true",
                    help="Gate the run on the queued-mesh blocked-time "
                         "SLO: exit 1 when mean blocked s/call exceeds "
                         "the ceiling")
    ap.add_argument("--slo-max-blocked-ms", type=float, default=150.0,
                    help="Ceiling on queued-mesh mean blocked device "
                         "ms per gossip sync for --slo")
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from babble_tpu.tpu.dispatch import _AsyncPass
    from babble_tpu.tpu.grid import build_levels, synthetic_grid
    from babble_tpu.tpu.sharded import sharded_frontier_passes
    from babble_tpu.tpu.runtime import enable_compile_cache

    enable_compile_cache()

    devices = jax.devices()
    n_dev = 1
    while n_dev * 2 <= min(8, len(devices)):
        n_dev *= 2
    from jax.sharding import Mesh

    mesh = Mesh(np.array(devices[:n_dev]), ("rounds",))
    grid = synthetic_grid(N_VALIDATORS, N_EVENTS, seed=SEED)

    def gossip_stage():
        # the per-sync work every discipline pays: the gossip arrival
        # interval (overlappable — this is where in-flight device work
        # hides) plus the O(E) restage of the level schedule
        time.sleep(GOSSIP_INTERVAL_S)
        return build_levels(N_VALIDATORS, grid.self_parent, grid.other_parent)

    from babble_tpu.obs import (
        Observability,
        log_buckets,
        retrace_baseline,
        retrace_delta,
    )

    obs = Observability()
    led = obs.devledger

    # compile + warm outside every timed loop (shapes are shared across
    # disciplines, so this is the only compilation in the process). The
    # device-time ledger watches the warmup so every legitimate compile
    # lands here; anything after the baseline below is a silent retrace.
    with led.activate("sharded"):
        ref = sharded_frontier_passes(mesh, grid)
        sharded_frontier_passes(mesh, grid)
    retrace_base = retrace_baseline(obs)

    results = {}
    blocked = {}

    # -- sync: block on the device every call -----------------------------
    t0 = time.perf_counter()
    b = 0.0
    for _ in range(CALLS):
        gossip_stage()
        tb = time.perf_counter()
        with led.activate("sharded"):
            out = sharded_frontier_passes(mesh, grid)
        b += time.perf_counter() - tb
    results["sync"] = time.perf_counter() - t0
    blocked["sync"] = b

    # -- pipelined: single-slot overlap (dispatch i, wait for i-1) --------
    t0 = time.perf_counter()
    b = 0.0
    prev = None
    for _ in range(CALLS):
        gossip_stage()
        task = _AsyncPass(mesh, grid, ledger=led)
        if prev is not None:
            tb = time.perf_counter()
            out = prev.result()
            b += time.perf_counter() - tb
        prev = task
    tb = time.perf_counter()
    out = prev.result()
    b += time.perf_counter() - tb
    results["pipelined"] = time.perf_counter() - t0
    blocked["pipelined"] = b

    # -- queued_mesh: bounded queue + cross-round batching ----------------
    t0 = time.perf_counter()
    b = 0.0
    inflight = []
    pending = 0
    for _ in range(CALLS):
        gossip_stage()
        pending += 1
        while len(inflight) >= QUEUE_DEPTH:
            tb = time.perf_counter()
            out = inflight.pop(0).result()
            b += time.perf_counter() - tb
        if pending >= BATCH_SYNCS or not inflight:
            # one dispatch covers every pending sync: the one-shot
            # restage stages the whole graph, so integration of this
            # result lands the rounds for all of them at once
            inflight.append(_AsyncPass(mesh, grid, ledger=led))
            pending = 0
    while inflight:
        tb = time.perf_counter()
        out = inflight.pop(0).result()
        b += time.perf_counter() - tb
    results["queued_mesh"] = time.perf_counter() - t0
    blocked["queued_mesh"] = b

    # steady-state retrace budget (ISSUE 19): shapes are shared across
    # disciplines, so after the warmup the compile cache must serve every
    # timed call — any retrace here is a staging bug
    retraces = retrace_delta(obs, retrace_base)

    # correctness gate: dispatch discipline must not change results
    np.testing.assert_array_equal(np.asarray(out.rounds), np.asarray(ref.rounds))
    np.testing.assert_array_equal(
        np.asarray(out.received), np.asarray(ref.received)
    )
    assert out.last_round == ref.last_round

    # each sync delivers N_EVENTS / CALLS new events; a discipline's
    # throughput is how fast it moves the whole stream through ordering
    disciplines = {
        name: {
            "events_per_sec": round(N_EVENTS / results[name], 1),
            "ms_per_call": round(blocked[name] / CALLS * 1e3, 2),
            "wall_s": round(results[name], 3),
        }
        for name in ("sync", "pipelined", "queued_mesh")
    }

    eps = {k: v["events_per_sec"] for k, v in disciplines.items()}
    assert eps["queued_mesh"] >= eps["pipelined"] >= eps["sync"], (
        f"dispatch disciplines out of order: {eps}"
    )

    lat = obs.histogram(
        "babble_bench_dispatch_blocked_seconds",
        "Blocked device wall time per gossip sync, by dispatch discipline",
        labels=("path",),
        buckets=log_buckets(0.0001, 4.0, 20),
    )
    thr = obs.gauge(
        "babble_bench_dispatch_events_per_second",
        "Dispatch benchmark throughput, by dispatch discipline",
        labels=("path",),
    )
    for name in disciplines:
        lat.labels(path=name).observe(blocked[name] / CALLS)
        thr.labels(path=name).set(eps[name])
    # SLO-visible gauge for the retrace budget (the objective below
    # reads it; operators see the same series on /metrics)
    obs.gauge(
        "babble_bench_retrace_delta",
        "Steady-state kernel retraces past the warmup baseline "
        "(budget: zero)",
    ).set(float(sum(retraces.values())))

    led_snap = led.snapshot()
    print(
        json.dumps(
            {
                "metric": (
                    "events ordered/sec through the queued sharded mesh "
                    f"dispatch, {N_VALIDATORS} validators, {N_EVENTS} "
                    f"events, {CALLS} gossip syncs, mesh={n_dev}dev, "
                    f"platform={devices[0].platform}"
                ),
                "value": eps["queued_mesh"],
                "unit": "events/s",
                "vs_baseline": round(
                    eps["queued_mesh"] / max(eps["sync"], 1e-9), 2
                ),
                "disciplines": disciplines,
                "ledger": {
                    "shares": led_snap["shares"],
                    "compiles": sum(
                        e["compiles"] for e in led_snap["entries"].values()
                    ),
                    "retraces": sum(
                        e["retraces"] for e in led_snap["entries"].values()
                    ),
                    "retrace_delta": retraces,
                },
                "metrics": obs.registry.snapshot(),
            }
        )
    )

    if args.slo:
        ok, status = slo_gate(obs, args.slo_max_blocked_ms / 1e3)
        print(
            "SLO gate:",
            json.dumps(status["objectives"], sort_keys=True),
            file=sys.stderr,
        )
        if not ok:
            if retraces:
                # name the offending entry points and dump the flight
                # ring — the last dispatch lifecycle records are the
                # context an operator needs to see WHICH dispatch pattern
                # forced the rebuild
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
                f"SLO BREACH: queued_mesh blocked "
                f"{disciplines['queued_mesh']['ms_per_call']} ms/call over "
                f"the {args.slo_max_blocked_ms} ms ceiling"
                if disciplines["queued_mesh"]["ms_per_call"]
                > args.slo_max_blocked_ms
                else "SLO BREACH: steady-state retrace budget exceeded",
                file=sys.stderr,
            )
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
