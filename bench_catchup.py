"""Cold-ingest / fast-sync replay benchmark: wall time to consensus-order
a DEEP dag section from a standing start, at depths the steady-state
bench (bench.py) never visits. This is the catch-up story of the paper's
device pipeline — a node joining from a fast-sync frame or restarting
from a reset replays thousands of rounds in one call, where the
steady-state path amortizes one round at a time.

Three engines are compared at each depth, every one asserted byte-equal
to the others before any number is reported:

- level-scan (engine.run_passes): the exact reference walk, one scan
  step per topological level — O(depth) steps;
- frontier (engine.run_frontier_passes): the flagship walk, one step per
  ROUND — base grids only;
- doubling (tpu/doubling.py): the log-diameter cold path — pointer-
  doubling ancestry closure + contracted frontier walk, O(log depth)
  device passes for the closure and O(rounds) scanned-in-bulk steps.

Post-reset replay is measured on section grids (grid.section_grid) cut
from the deep fixture: there the frontier walk refuses (external round
seeds) and the ladder's prior fallback was the level scan, so the
section rows are the numbers the cold path exists for. The `passes`
count per fixture is asserted logarithmic (<= 3*log2(depth) + 16).

Prints the headline as the LAST stdout line, carrying the
metrics-registry snapshot under its "metrics" key (same contract as
bench.py); `--slo` declares the replay-latency objective over the
babble_catchup_replay_seconds histogram and exits nonzero on breach.
"""

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

N_VALIDATORS = 8
SEED = 0
ZIPF_A = 1.2
DEPTHS = (256, 1024, 4096, 16384)
# the exact one-step-per-level reference is only timed where its O(depth)
# walk stays cheap enough to keep the bench under a few minutes
LEVEL_SCAN_MAX_DEPTH = 16384
REPS = 3


def _best(fn, reps=REPS):
    best = math.inf
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def _assert_equal(a, b, what, grid=None, a_name="a", b_name="b"):
    import numpy as np

    def _fail(msg):
        # first-divergence bisection (obs/provenance.py): when the grid
        # is supplied, name the earliest divergent cell so the equality
        # gate reports a localization, not just a field name
        if grid is not None:
            from babble_tpu.obs import bisect_pass_results

            loc, path = bisect_pass_results(
                grid, a_name, a, b_name, b,
                label=what.replace(" ", "-").replace(":", ""),
            )
            if loc is not None:
                msg += (
                    "; localized to round %s %s/%s cell %s (%s)" % (
                        loc["round"], loc["pass"], loc["table"],
                        (loc.get("cell") or "")[:18], path,
                    )
                )
        raise AssertionError(msg)

    for f in ("rounds", "witness", "received"):
        if not bool((np.asarray(getattr(a, f)) == np.asarray(getattr(b, f))).all()):
            _fail(f"{what}: {f} mismatch")
    if int(a.last_round) != int(b.last_round):
        raise AssertionError(f"{what}: last_round mismatch")


def _divide_rounds_timer(grid):
    """Jitted level-scan DivideRounds alone — the walk-stage comparator
    (rounds + witnesses + lamports, no fame/received)."""
    import jax

    from babble_tpu.tpu import kernels

    div = jax.jit(
        kernels._divide_rounds, static_argnames=("super_majority", "r_max")
    )

    def run():
        res = div(
            grid.levels, grid.creator, grid.index, grid.self_parent,
            grid.other_parent, grid.last_ancestors, grid.first_descendants,
            grid.ext_sp_round, grid.ext_op_round, grid.fixed_round,
            grid.ext_sp_lamport, grid.ext_op_lamport, grid.fixed_lamport,
            super_majority=grid.super_majority, r_max=grid.r_max,
        )
        res.rounds.block_until_ready()

    return run


def bench_fixture(grid, obs, label, base):
    """Time every applicable engine on one grid; returns the row dict.
    Correctness is asserted BEFORE timing: the doubling result is gated
    byte-equal against the exact level scan (and the frontier walk on
    base grids) or no number is reported at all."""
    import jax

    from babble_tpu.tpu.doubling import (
        observe_catchup,
        run_doubling_passes,
    )
    from babble_tpu.tpu.engine import run_frontier_passes, run_passes

    depth = int(grid.num_levels)
    stats = {}
    dres = run_doubling_passes(grid, stats=stats)
    ref = run_passes(grid) if depth <= LEVEL_SCAN_MAX_DEPTH else None
    if ref is not None:
        _assert_equal(dres, ref, f"{label}: doubling vs level scan",
                      grid=grid, a_name="doubling", b_name="levelscan")
    if base:
        fres = run_frontier_passes(grid)
        _assert_equal(dres, fres, f"{label}: doubling vs frontier",
                      grid=grid, a_name="doubling", b_name="frontier")

    pass_cap = 3 * math.log2(max(depth, 2)) + 16
    if stats["passes"] > pass_cap:
        raise AssertionError(
            f"{label}: {stats['passes']} device passes at depth {depth} "
            f"breaks the log bound ({pass_cap:.0f})"
        )

    row = {
        "label": label,
        "depth": depth,
        "events": int(grid.e),
        "rounds": int(stats["rounds"]),
        "passes": int(stats["passes"]),
        "closure_passes": int(stats["closure_passes"]),
    }

    t = _best(lambda: run_doubling_passes(grid))
    observe_catchup(obs, stats, t)
    row["doubling_replay_s"] = round(t, 4)
    row["events_per_sec"] = round(grid.e / t, 1)
    from babble_tpu.tpu.doubling import _doubling_stage1

    row["doubling_walk_s"] = round(
        _best(lambda: _doubling_stage1(grid, jax.device_put, {})), 4
    )
    if ref is not None:
        row["levelscan_replay_s"] = round(_best(lambda: run_passes(grid)), 4)
        row["levelscan_walk_s"] = round(_best(_divide_rounds_timer(grid)), 4)
        row["walk_speedup"] = round(
            row["levelscan_walk_s"] / row["doubling_walk_s"], 2
        )
        row["replay_speedup"] = round(
            row["levelscan_replay_s"] / row["doubling_replay_s"], 2
        )
    if base:
        row["frontier_replay_s"] = round(
            _best(lambda: run_frontier_passes(grid)), 4
        )
        row["frontier_speedup"] = round(
            row["frontier_replay_s"] / row["doubling_replay_s"], 2
        )
    return row


def slo_gate(obs, max_replay_seconds: float):
    """Declare the replay-latency objective over the bench registry and
    evaluate it once; returns (ok, status_doc). Mirrors bench.slo_gate
    so drivers can gate catch-up latency the same way as throughput."""
    from babble_tpu.obs import SLOEngine

    slo = SLOEngine(obs)
    slo.objective(
        "catchup_replay",
        series="babble_catchup_replay_seconds",
        kind="mean_below", threshold=max_replay_seconds,
        description="cold-path section replay stays under the latency cap",
    )
    status = slo.evaluate()
    return not slo.breached(), status


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--slo", action="store_true",
                    help="Gate the run on the replay-latency SLO: exit 1 "
                         "when mean replay time breaches the cap")
    ap.add_argument("--slo-max-replay-seconds", type=float, default=30.0,
                    help="Replay latency cap for --slo (seconds)")
    ap.add_argument("--depths", type=str, default=None,
                    help="Comma-separated depth override (smoke runs)")
    args = ap.parse_args(argv)

    import jax

    from babble_tpu.tpu.runtime import enable_compile_cache

    enable_compile_cache()

    from babble_tpu.obs import Observability
    from babble_tpu.tpu.engine import run_frontier_passes
    from babble_tpu.tpu.grid import section_grid, synthetic_deep_grid

    depths = (
        tuple(int(d) for d in args.depths.split(","))
        if args.depths else DEPTHS
    )
    obs = Observability()
    rows = []
    for depth in depths:
        grid = synthetic_deep_grid(
            N_VALIDATORS, depth, seed=SEED, zipf_a=ZIPF_A
        )
        rows.append(bench_fixture(grid, obs, f"base@{depth}", base=True))
        print(json.dumps(rows[-1]), file=sys.stderr)
        # fast-sync / post-reset shape: the top half of the same dag with
        # the cut's parent metadata externalized, like a reset frame
        sec = section_grid(
            grid, run_frontier_passes(grid), grid.num_levels // 2
        )
        rows.append(bench_fixture(sec, obs, f"section@{depth}", base=False))
        print(json.dumps(rows[-1]), file=sys.stderr)

    deepest = rows[-1]
    obs.gauge(
        "babble_catchup_events_per_second",
        "Cold-ingest replay throughput at the deepest section fixture",
    ).set(deepest["events_per_sec"])

    print(
        json.dumps(
            {
                "metric": (
                    "events ordered/sec replaying the deepest post-reset "
                    f"section from cold, {N_VALIDATORS} validators, "
                    f"depth {deepest['depth']}, "
                    f"platform={jax.devices()[0].platform}"
                ),
                "value": deepest["events_per_sec"],
                "unit": "events/s",
                "sections": rows,
                "metrics": obs.registry.snapshot(),
            }
        )
    )

    if args.slo:
        ok, status = slo_gate(obs, args.slo_max_replay_seconds)
        print(
            "SLO gate:", json.dumps(status["objectives"], sort_keys=True),
            file=sys.stderr,
        )
        if not ok:
            print(
                "SLO BREACH: cold-path replay exceeded "
                f"{args.slo_max_replay_seconds:.1f}s mean",
                file=sys.stderr,
            )
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
