"""Scale-point benchmark: the round-frontier pipeline at BASELINE's large
validator counts.

Two configs, selected by SCALE_CONFIG (default 5):
- SCALE_CONFIG=5 — 1024 validators, Zipf gossip (BASELINE.json configs[4],
  "streaming rounds with on-device DAG frontier").
- SCALE_CONFIG=4 — 256 validators with an adversarial 1/3-byzantine graph
  (withhold/flush cycles, Zipf fan-out; BASELINE.json configs[3]).

Complements bench.py (the 64-validator metric of record): same timed path,
same in-run bit-exactness gate vs the level-scan engine, at the configured
validator scale. The headline names the platform it ran on; the
multi-chip analog of this shape is exercised by the CPU-mesh differential
(tests/test_multichip.py::test_frontier_sharded_n256).

Prints one JSON line like bench.py.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

SCALE_CONFIG = int(os.environ.get("SCALE_CONFIG", "5"))
if SCALE_CONFIG == 4:
    N_VALIDATORS = 256
    N_EVENTS = 16384
    SEED = 11
    ZIPF = 1.05
    BYZ_FRAC = 1.0 / 3.0
    LABEL = "BASELINE config #4, 1/3-byzantine withhold/flush graph"
else:
    N_VALIDATORS = 1024
    N_EVENTS = 32768
    SEED = 7
    ZIPF = 1.02
    BYZ_FRAC = 0.0
    LABEL = "BASELINE config #5 scale"

CACHE = os.path.join(
    os.path.dirname(os.path.abspath(__file__)),
    "bench_cache",
    f"grid_{N_VALIDATORS}x{N_EVENTS}_seed{SEED}_b{int(BYZ_FRAC * 100)}.npz",
)


def load_grid():
    import numpy as np

    from babble_tpu.tpu.grid import DagGrid, MIN_INT32, build_levels, synthetic_grid

    if os.path.exists(CACHE):
        z = np.load(CACHE)
        e = N_EVENTS
        levels, num_levels = build_levels(
            N_VALIDATORS, z["self_parent"], z["other_parent"]
        )
        return DagGrid(
            n=N_VALIDATORS,
            e=e,
            super_majority=2 * N_VALIDATORS // 3 + 1,
            creator=z["creator"],
            index=z["index"],
            self_parent=z["self_parent"],
            other_parent=z["other_parent"],
            last_ancestors=z["la"],
            first_descendants=z["fd"],
            coin_bit=z["coin"],
            fixed_round=np.where(
                (z["self_parent"] < 0) & (z["other_parent"] < 0), 0, -1
            ).astype(np.int32),
            ext_sp_round=np.full(e, -1, dtype=np.int32),
            ext_op_round=np.full(e, -1, dtype=np.int32),
            ext_sp_lamport=np.full(e, -1, dtype=np.int32),
            ext_op_lamport=np.full(e, MIN_INT32, dtype=np.int32),
            fixed_lamport=np.full(e, MIN_INT32, dtype=np.int32),
            levels=levels,
            num_levels=num_levels,
        )

    grid = synthetic_grid(
        N_VALIDATORS, N_EVENTS, seed=SEED, zipf_a=ZIPF,
        byzantine_frac=BYZ_FRAC,
    )
    os.makedirs(os.path.dirname(CACHE), exist_ok=True)
    np.savez_compressed(
        CACHE,
        creator=grid.creator,
        index=grid.index,
        self_parent=grid.self_parent,
        other_parent=grid.other_parent,
        la=grid.last_ancestors,
        fd=grid.first_descendants,
        coin=grid.coin_bit,
    )
    return grid


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from babble_tpu.tpu.engine import run_passes
    from babble_tpu.tpu.runtime import enable_compile_cache

    enable_compile_cache()
    from babble_tpu.tpu.frontier import (
        build_inv, chain_table, frontier_pipeline, level_lamport, sp_index_of,
    )

    grid = load_grid()

    dev = {
        k: jax.device_put(getattr(grid, k))
        for k in (
            "creator", "index", "last_ancestors", "first_descendants",
            "coin_bit",
        )
    }
    rows_by = chain_table(grid)
    dev["rows_by"] = jax.device_put(rows_by)
    dev["sp_index"] = jax.device_put(sp_index_of(grid))
    dev["lamport"] = jax.device_put(level_lamport(grid))
    inv = build_inv(dev["rows_by"], dev["last_ancestors"])

    # the fame/received round axis: at 1024 validators real round counts
    # are tiny (few events per chain), so a small N-independent axis wins
    # (see engine._adaptive_r_loop's floor note)
    r_fame = 16

    def run_batch():
        return frontier_pipeline(
            inv, dev["rows_by"], dev["creator"], dev["index"],
            dev["sp_index"], dev["last_ancestors"], dev["first_descendants"],
            dev["lamport"], dev["coin_bit"],
            grid.super_majority, grid.n, r_fame,
        )

    out = run_batch()
    while int(np.asarray(out.last_round)) + 2 > r_fame:
        r_fame *= 2
        out = run_batch()

    warm = jnp.int32(0)
    for _ in range(15):
        warm = warm + run_batch().last_round
    int(np.asarray(warm))

    iters = 20
    start = time.perf_counter()
    acc = jnp.int32(0)
    for _ in range(iters):
        out = run_batch()
        acc = acc + out.last_round + jnp.sum(out.received) + jnp.sum(out.rounds)
    int(np.asarray(acc))
    elapsed = (time.perf_counter() - start) / iters

    # optional phase breakdown (VERDICT r4 #6): time the walk / fame /
    # received stages as separate programs, each timed over `iters`
    # back-to-back launches closed by one fetch of a scalar that depends
    # on every output
    if os.environ.get("SCALE_PHASES"):
        from babble_tpu.tpu.frontier import frontier_rounds
        from babble_tpu.tpu.kernels import _decide_fame, _decide_round_received

        fame_jit = jax.jit(
            _decide_fame,
            static_argnames=("super_majority", "n_participants", "d_cap"),
        )
        recv_jit = jax.jit(_decide_round_received)

        def walk():
            return frontier_rounds(
                inv, dev["rows_by"], dev["creator"], dev["index"],
                dev["sp_index"], dev["first_descendants"],
                super_majority=grid.super_majority, r_cap=r_fame,
                la=dev["last_ancestors"],
            )

        fr = walk()

        def fame():
            return fame_jit(
                fr.witness_table, dev["last_ancestors"],
                dev["first_descendants"], dev["index"], dev["coin_bit"],
                fr.last_round, super_majority=grid.super_majority,
                n_participants=grid.n, d_cap=r_fame + 2,
            )

        fm = fame()

        def received():
            return recv_jit(
                fr.witness_table, dev["last_ancestors"], dev["index"],
                dev["creator"], fr.rounds, fm.decided, fm.famous,
                fm.rounds_decided, fr.last_round,
            )

        phases = {
            "walk": lambda: walk().last_round,
            "fame": lambda: jnp.sum(fame().rounds_decided),
            "received": lambda: jnp.sum(received()),
        }
        report = {}
        for name, fn in phases.items():
            acc = jnp.int32(0)
            for _ in range(5):
                acc = acc + fn()
            int(np.asarray(acc))  # warm
            t0 = time.perf_counter()
            acc = jnp.int32(0)
            for _ in range(iters):
                acc = acc + fn()
            int(np.asarray(acc))
            report[name] = round((time.perf_counter() - t0) / iters * 1e3, 2)
        print(json.dumps({"phase_ms": report, "config": LABEL, "r_fame": r_fame}))

    # bit-exactness gate vs the level-scan engine path
    res = run_passes(grid, adaptive_r=True)
    np.testing.assert_array_equal(np.asarray(out.rounds), res.rounds)
    np.testing.assert_array_equal(np.asarray(out.received), res.received)

    events_per_sec = grid.e / elapsed

    # obs-layer registry view of the run, embedded in the headline
    from babble_tpu.obs import Observability, log_buckets

    obs = Observability()
    obs.histogram(
        "babble_bench_iteration_seconds",
        "Per-iteration wall time of the frontier pipeline at scale",
        buckets=log_buckets(0.0001, 2.0, 20),
    ).observe(elapsed)
    obs.gauge(
        "babble_bench_events_per_second",
        "Benchmark throughput headline",
    ).set(events_per_sec)

    print(
        json.dumps(
            {
                "metric": (
                    "events ordered/sec, frontier pipeline, "
                    f"{N_VALIDATORS} validators ({LABEL}), "
                    f"{N_EVENTS} events, platform={jax.devices()[0].platform}"
                ),
                "value": round(events_per_sec, 1),
                "unit": "events/s",
                "vs_baseline": round(events_per_sec / 1_000_000.0, 3),
                "metrics": obs.registry.snapshot(),
            }
        )
    )


if __name__ == "__main__":
    main()
