"""Differential tests for the persistent incremental device pipeline:
appending gossip-sized batches to device-resident state must reproduce the
one-shot pipeline bit-exactly — rounds, lamports, witness flags and
round-received — including when batches are applied through the fused
multi-batch dispatch (scan + one decide pass)."""

import numpy as np
import pytest

from babble_tpu.tpu import synthetic_grid
from babble_tpu.tpu.engine import run_passes
from babble_tpu.tpu.incremental import (
    batches_from_grid,
    init_state,
    multi_step,
    multi_train,
    stack_batches,
    stack_trains,
    step,
    train_step,
    trains_from_grid,
)


@pytest.mark.parametrize("zipf", [0.0, 1.1])
def test_incremental_matches_one_shot(zipf):
    n, e = 8, 768
    grid = synthetic_grid(n, e, seed=3, zipf_a=zipf, record_fd_updates=True)
    batches = batches_from_grid(grid, 32, 8192, e)

    st = init_state(n, e, 64)
    for b in batches:
        st = step(st, b, grid.super_majority, n, e_win=512)

    ref = run_passes(grid)
    assert not bool(st.stale)
    assert not bool(st.fame_lag)
    np.testing.assert_array_equal(np.asarray(st.rounds)[:e], ref.rounds)
    np.testing.assert_array_equal(np.asarray(st.lamport)[:e], ref.lamport)
    np.testing.assert_array_equal(np.asarray(st.witness)[:e], ref.witness)
    np.testing.assert_array_equal(np.asarray(st.received)[:e], ref.received)
    assert int(st.last_round) == ref.last_round


def test_multi_step_matches_per_batch():
    """The K-batches-per-dispatch path must equal the one-by-one path."""
    n, e = 8, 512
    grid = synthetic_grid(n, e, seed=5, zipf_a=1.1, record_fd_updates=True)
    batches = batches_from_grid(grid, 32, 8192, e)

    one = init_state(n, e, 64)
    for b in batches:
        one = step(one, b, grid.super_majority, n, e_win=512)

    k = 4
    many = init_state(n, e, 64)
    for i in range(0, len(batches), k):
        many = multi_step(
            many, stack_batches(batches[i : i + k]),
            grid.super_majority, n, e_win=512,
        )

    for f in ("rounds", "lamport", "witness", "received"):
        np.testing.assert_array_equal(
            np.asarray(getattr(one, f)), np.asarray(getattr(many, f)), f
        )
    assert not bool(many.stale) and not bool(many.fame_lag)


@pytest.mark.parametrize("zipf", [0.0, 1.1])
def test_train_matches_per_batch(zipf):
    """The flattened-train program (MXU one-hot gathers, bulk post-scan
    registration) must reproduce the per-batch path bit-exactly across
    every decision array."""
    n, e = 8, 768
    grid = synthetic_grid(n, e, seed=3, zipf_a=zipf, record_fd_updates=True)

    ref = init_state(n, e, 64)
    for b in batches_from_grid(grid, 32, 8192, e):
        ref = step(ref, b, grid.super_majority, n, e_win=512)

    tr = init_state(n, e, 64)
    for t in trains_from_grid(grid, 256, 8192, e, w_cap=16, t_cap=96):
        tr = train_step(tr, t, grid.super_majority, n, e_win=512)

    assert not bool(tr.stale) and not bool(tr.fame_lag)
    for f in ("rounds", "lamport", "witness", "received", "wtable",
              "fame_decided", "famous", "rounds_decided"):
        np.testing.assert_array_equal(
            np.asarray(getattr(ref, f)), np.asarray(getattr(tr, f)), f
        )
    assert int(tr.last_round) == int(ref.last_round)


def test_multi_train_matches_train():
    """K stacked trains per dispatch must equal per-train dispatch."""
    n, e = 8, 512
    grid = synthetic_grid(n, e, seed=5, zipf_a=1.1, record_fd_updates=True)
    trains = trains_from_grid(grid, 128, 8192, e, w_cap=16, t_cap=64)

    one = init_state(n, e, 64)
    for t in trains:
        one = train_step(one, t, grid.super_majority, n, e_win=512)

    k = 2
    many = init_state(n, e, 64)
    for i in range(0, len(trains), k):
        group = trains[i : i + k]
        if len(group) < k:
            for t in group:
                many = train_step(many, t, grid.super_majority, n, e_win=512)
        else:
            many = multi_train(
                many, stack_trains(group), grid.super_majority, n, e_win=512
            )

    for f in ("rounds", "lamport", "witness", "received"):
        np.testing.assert_array_equal(
            np.asarray(getattr(one, f)), np.asarray(getattr(many, f)), f
        )
    assert not bool(many.stale) and not bool(many.fame_lag)


def test_stale_latch_fires_on_undersized_window():
    """An undetermined row sliding below the received window must latch
    the stale flag instead of silently never deciding."""
    n, e = 8, 512
    grid = synthetic_grid(n, e, seed=7, zipf_a=1.1, record_fd_updates=True)
    batches = batches_from_grid(grid, 32, 8192, e)
    st = init_state(n, e, 64)
    for b in batches:
        st = step(st, b, grid.super_majority, n, e_win=64)  # far too small
    assert bool(st.stale)


# -- frontier-live engine (incremental INV + frontier walk) ------------------


def frontier_replay(grid, train_size, e_cap=4096, l_cap=256, r_cap=64):
    from babble_tpu.tpu.frontier_live import (
        frontier_train_step, init_frontier_state,
    )

    trains = trains_from_grid(grid, train_size, 16384, e_cap)
    state = init_frontier_state(grid.n, e_cap, l_cap, r_cap)
    for t in trains:
        state = frontier_train_step(state, t, grid.super_majority, grid.n)
    assert not bool(state.l_over) and not bool(state.r_over)
    assert not bool(state.frozen_violation)
    return state


@pytest.mark.parametrize("zipf", [0.0, 1.1])
def test_frontier_live_matches_one_shot(zipf):
    """The frontier-live engine's final state after train-sized appends
    must equal the one-shot pipeline on the same DAG — the claim that
    incrementally-maintained INV/chain tables reproduce build_inv."""
    grid = synthetic_grid(16, 2048, seed=3, zipf_a=zipf, record_fd_updates=True)
    state = frontier_replay(grid, 256)
    ref = run_passes(grid, adaptive_r=True)
    e = grid.e
    np.testing.assert_array_equal(np.asarray(state.rounds)[:e], ref.rounds)
    np.testing.assert_array_equal(np.asarray(state.witness)[:e], ref.witness)
    np.testing.assert_array_equal(np.asarray(state.lamport)[:e], ref.lamport)
    np.testing.assert_array_equal(np.asarray(state.received)[:e], ref.received)
    assert int(state.last_round) == ref.last_round


def test_frontier_live_small_trains_match_large():
    """Train-size independence: appending 32 events at a time must land in
    exactly the same state as 512 at a time (INV closure and frontier
    decisions are pure functions of the accumulated tables)."""
    grid = synthetic_grid(8, 1024, seed=9, zipf_a=1.1, record_fd_updates=True)
    a = frontier_replay(grid, 32)
    b = frontier_replay(grid, 512)
    for field in ("rounds", "witness", "received", "wtable",
                  "fame_decided", "famous"):
        np.testing.assert_array_equal(
            np.asarray(getattr(a, field)), np.asarray(getattr(b, field))
        )


def test_frontier_multi_train_matches_per_train():
    from babble_tpu.tpu.frontier_live import (
        frontier_multi_train, frontier_train_step, init_frontier_state,
    )
    from babble_tpu.tpu.incremental import stack_trains

    grid = synthetic_grid(8, 1024, seed=5, zipf_a=1.1, record_fd_updates=True)
    e_cap, l_cap, r_cap = 2048, 256, 64
    trains = trains_from_grid(grid, 128, 16384, e_cap)

    a = init_frontier_state(grid.n, e_cap, l_cap, r_cap)
    for t in trains:
        a = frontier_train_step(a, t, grid.super_majority, grid.n)

    b = init_frontier_state(grid.n, e_cap, l_cap, r_cap)
    b = frontier_multi_train(
        b, stack_trains(trains), grid.super_majority, grid.n
    )
    for field in ("rounds", "witness", "received", "last_round"):
        np.testing.assert_array_equal(
            np.asarray(getattr(a, field)), np.asarray(getattr(b, field))
        )


def test_frontier_live_l_over_latch():
    """A chain outgrowing the index axis must latch l_over, not corrupt."""
    from babble_tpu.tpu.frontier_live import (
        frontier_train_step, init_frontier_state,
    )

    grid = synthetic_grid(8, 512, seed=2, zipf_a=2.0, record_fd_updates=True)
    l_cap = 16  # far below the hottest chain's length
    trains = trains_from_grid(grid, 128, 16384, 1024)
    state = init_frontier_state(grid.n, 1024, l_cap, 64)
    for t in trains:
        state = frontier_train_step(state, t, grid.super_majority, grid.n)
    assert bool(state.l_over)


def test_batches_split_at_the_update_cap():
    """A batch whose first-descendant updates pass `upd_cap` is halved
    instead of refused, and the halves give the state the whole gave."""
    n, e = 8, 512
    grid = synthetic_grid(n, e, seed=5, zipf_a=1.1, record_fd_updates=True)
    whole = batches_from_grid(grid, 32, 8192, e)
    burst = max(int((np.asarray(b.upd_row) != e).sum()) for b in whole)
    cap = burst // 2
    halves = batches_from_grid(grid, 32, cap, e)
    assert len(halves) > len(whole)
    assert all(int((np.asarray(b.upd_row) != e).sum()) <= cap for b in halves)

    def run(batches):
        st = init_state(n, e, 64)
        for b in batches:
            st = step(st, b, grid.super_majority, n, e_win=512)
        return st

    one, two = run(whole), run(halves)
    for f in ("rounds", "lamport", "witness", "received", "fd"):
        np.testing.assert_array_equal(
            np.asarray(getattr(one, f)), np.asarray(getattr(two, f)), f)
    assert not bool(two.stale) and not bool(two.fame_lag)
