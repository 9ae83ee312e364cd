"""Append-mode benchmark: gossip-sized increments through the persistent
device pipelines.

Measures sustained end-to-end throughput of appending event trains to
device-resident DAG state — the live-node dispatch pattern — and checks
the final rounds/received bit-exactly against the one-shot pipeline on
the same DAG. Two engines:

- **frontier-live** (babble_tpu/tpu/frontier_live.py, the metric of
  record): INV/chain tables maintained incrementally per train (scatter +
  suffix-min re-closure), then the round-frontier walk + fame + received —
  sequential axis = round count, no per-event device work.
- **train** (babble_tpu/tpu/incremental.py, reported for comparison; set
  BENCH_INC_MODE=train to emit it as the JSON line): level-scan over the
  train's dependency-level table with one-hot MXU gathers.

Prints one JSON line like bench.py; this is the secondary metric
(BASELINE.md incremental target: >= 100k events/s).
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

N_VALIDATORS = 64
N_EVENTS = 32768
TRAIN = 8192  # events per device dispatch (gossip batches are staged
#               host-side in insert order; the train is the dispatch unit)
UPD_CAP = 524288
T_CAP = 832
# must cover the undetermined tail: fame decisions trail the frontier by
# ~6-8 rounds (~1.3k events/round at this config); the step's stale flag
# latches if this is ever undersized
E_WIN = 16384
SEED = 0
TARGET = 100_000.0


def _run_train_mode(grid, trains, e_cap, obs):
    """Level-scan incremental engine (incremental.py Train path)."""
    import jax.numpy as jnp
    import numpy as np

    from babble_tpu.obs import ledger_call
    from babble_tpu.tpu.incremental import init_state, train_step

    led = obs.devledger
    r_cap = 64
    state = init_state(grid.n, e_cap, r_cap)
    with led.activate("incremental"):
        for t in trains:
            state = ledger_call(
                "train_step", train_step, state, t, grid.super_majority,
                grid.n, e_win=E_WIN,
            )
    np.asarray(state.rounds)  # sync (compile + chip ramp)

    elapsed = float("inf")
    for _ in range(3):
        state = init_state(grid.n, e_cap, r_cap)
        start = time.perf_counter()
        with led.activate("incremental"):
            for t in trains:
                state = ledger_call(
                    "train_step", train_step, state, t,
                    grid.super_majority, grid.n, e_win=E_WIN,
                )
        acc = int(np.asarray(
            state.last_round + jnp.sum(state.rounds) + jnp.sum(state.received)
        ))
        elapsed = min(elapsed, time.perf_counter() - start)
    assert not bool(state.stale), "received window undersized (stale latch)"
    assert not bool(state.fame_lag), "fame unroll exceeded (fame_lag latch)"
    return state, elapsed, "train dispatch (level scan)"


def _run_frontier_mode(grid, trains, e_cap, obs):
    """Frontier-live engine: incrementally-maintained INV/chain tables +
    the round-frontier walk per train (frontier_live.py)."""
    import jax.numpy as jnp
    import numpy as np

    from babble_tpu.obs import ledger_call
    from babble_tpu.tpu.frontier_live import (
        frontier_train_step, init_frontier_state,
    )

    l_cap = 4096  # covers the hottest Zipf chain at this config (~1.5k);
    #               NB: 2048 measured SLOWER (lane-axis tiling pathology)
    r_cap = 128  # round axis; the r_over latch turns an undersizing into
    #              a visible failure
    sm, n = grid.super_majority, grid.n

    led = obs.devledger
    state = init_frontier_state(n, e_cap, l_cap, r_cap)
    with led.activate("frontier_live"):
        for t in trains:
            state = ledger_call(
                "frontier_train_step", frontier_train_step, state, t, sm, n,
            )
    np.asarray(state.rounds)  # sync (compile + chip ramp)

    elapsed = float("inf")
    for _ in range(3):
        state = init_frontier_state(n, e_cap, l_cap, r_cap)
        start = time.perf_counter()
        with led.activate("frontier_live"):
            for t in trains:
                state = ledger_call(
                    "frontier_train_step", frontier_train_step, state, t,
                    sm, n,
                )
        acc = int(np.asarray(
            state.last_round + jnp.sum(state.rounds) + jnp.sum(state.received)
        ))
        elapsed = min(elapsed, time.perf_counter() - start)
    assert not bool(state.l_over), "chain index axis exhausted (l_over)"
    assert not bool(state.r_over), "round window exhausted (r_over)"
    assert not bool(state.frozen_violation), "frozen-round violation latch"
    return state, elapsed, "frontier-live (incremental INV + frontier walk)"


def main():
    import jax
    import numpy as np

    from babble_tpu.tpu import synthetic_grid
    from babble_tpu.tpu.incremental import trains_from_grid
    from babble_tpu.tpu.runtime import enable_compile_cache

    enable_compile_cache()

    grid = synthetic_grid(
        N_VALIDATORS, N_EVENTS, seed=SEED, zipf_a=1.1, record_fd_updates=True
    )
    e_cap = N_EVENTS
    trains = [
        jax.device_put(t)
        for t in trains_from_grid(grid, TRAIN, UPD_CAP, e_cap, t_cap=T_CAP)
    ]

    # obs built before the timed run so the device-time ledger can seam
    # the per-train entry points (ISSUE 19)
    from babble_tpu.obs import Observability, log_buckets

    obs = Observability()

    mode = os.environ.get("BENCH_INC_MODE", "frontier")
    runner = _run_frontier_mode if mode == "frontier" else _run_train_mode
    state, elapsed, label = runner(grid, trains, e_cap, obs)
    events_per_sec = grid.e / elapsed

    # differential gate vs the one-shot pipeline
    from babble_tpu.tpu.engine import run_passes

    ref = run_passes(grid, adaptive_r=True)
    e = grid.e
    np.testing.assert_array_equal(np.asarray(state.rounds)[:e], ref.rounds)
    np.testing.assert_array_equal(np.asarray(state.lamport)[:e], ref.lamport)
    np.testing.assert_array_equal(np.asarray(state.witness)[:e], ref.witness)
    np.testing.assert_array_equal(np.asarray(state.received)[:e], ref.received)
    assert int(state.last_round) == ref.last_round

    # obs-layer registry view of the run, embedded in the headline
    obs.histogram(
        "babble_bench_iteration_seconds",
        "Per-train wall time of the append-mode benchmark",
        buckets=log_buckets(0.0001, 2.0, 20),
    ).observe(elapsed / max(len(trains), 1))
    obs.gauge(
        "babble_bench_events_per_second",
        "Benchmark throughput headline",
    ).set(events_per_sec)

    led_snap = obs.devledger.snapshot()
    print(
        json.dumps(
            {
                "metric": (
                    "events/sec appended through persistent device DAG "
                    f"state, {label}, {N_VALIDATORS} "
                    f"validators, platform={jax.devices()[0].platform}"
                ),
                "value": round(events_per_sec, 1),
                "unit": "events/s",
                "vs_baseline": round(events_per_sec / TARGET, 3),
                "ledger": {
                    "shares": led_snap["shares"],
                    "compiles": sum(
                        e["compiles"] for e in led_snap["entries"].values()
                    ),
                    "retraces": sum(
                        e["retraces"] for e in led_snap["entries"].values()
                    ),
                },
                "metrics": obs.registry.snapshot(),
            }
        )
    )


if __name__ == "__main__":
    main()
