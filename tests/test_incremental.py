"""Differential tests for the live rung's device program
(babble_tpu/tpu/incremental.py): appending gossip-sized batches to
device-resident state must reproduce the one-shot pipeline bit-exactly —
rounds, lamports, witness flags and round-received — through `step` and
through `multi_step` trains of the shapes `tpu/live.py` launches (K=4 and
K=16, a short train padded with the engine's empty batches), whatever the
batch size, and on a stream whose adversarial third arrives late."""

import dataclasses

import numpy as np
import pytest

from babble_tpu.tpu import synthetic_grid
from babble_tpu.tpu.engine import run_passes
from babble_tpu.tpu.grid import build_levels
from babble_tpu.tpu.incremental import (
    BatchLayout,
    IncState,
    batches_from_grid,
    init_state,
    multi_step,
    step,
)

DECISIONS = ("rounds", "lamport", "witness", "received")
TABLES = ("wtable", "fame_decided", "famous", "rounds_decided")


def replay(grid, batches, e_cap, k=None, e_win=512, rows=32, upd_cap=8192):
    """`batches`, the buffers `batches_from_grid(grid, rows, upd_cap, ...)`
    wrote, into a fresh state: one `step` each, or `multi_step` trains of
    `k`, the last one padded to `k` with the empty batch, as the engine
    pads it."""
    n = grid.n
    layout = BatchLayout(rows, n, upd_cap)
    st = init_state(n, e_cap, 64)
    if k is None:
        for b in batches:
            st = step(st, b, layout, grid.super_majority, n, e_win=e_win)
        return st
    empty = layout.blank(e_cap)
    for i in range(0, len(batches), k):
        group = batches[i : i + k]
        group = group + [empty] * (k - len(group))
        st = multi_step(
            st, np.stack(group), layout, grid.super_majority, n, e_win=e_win)
    return st


def assert_same(a, b, fields):
    for f in fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(a, f)), np.asarray(getattr(b, f)), f)


def as_an_honest_validator_receives_it(grid, n_byz):
    """The grid with its rows in arrival order at an honest observer: an
    event of the adversarial validators (the first `n_byz`) arrives with
    the first honest event that descends from it, so a withheld chain
    lands all at once, rounds late. Events no honest event ever
    references are left out."""
    seen = np.zeros(grid.e, bool)
    order = []
    for i in np.flatnonzero(grid.creator >= n_byz):
        new, stack = [], [int(i)]
        while stack:
            r = stack.pop()
            if r >= 0 and not seen[r]:
                seen[r] = True
                new.append(r)
                stack += [int(grid.self_parent[r]), int(grid.other_parent[r])]
        order += sorted(new)
    order = np.array(order)
    new_row = np.full(grid.e, -1, np.int32)
    new_row[order] = np.arange(len(order), dtype=np.int32)

    def parents(p):
        return np.where(p >= 0, new_row[np.maximum(p, 0)], -1).astype(np.int32)

    sp, op = parents(grid.self_parent[order]), parents(grid.other_parent[order])
    levels, num_levels = build_levels(grid.n, sp, op)
    per_row = {
        f.name: getattr(grid, f.name)[order]
        for f in dataclasses.fields(grid)
        if isinstance(getattr(grid, f.name), np.ndarray)
        and f.name not in ("self_parent", "other_parent", "levels")
    }
    return dataclasses.replace(
        grid, e=len(order), self_parent=sp, other_parent=op,
        levels=levels, num_levels=num_levels,
        # a first-descendant write belongs to the descendant, whenever it
        # arrives: only the row numbers move
        fd_update_stream=[
            [int(new_row[r]) for r in grid.fd_update_stream[i]] for i in order
        ],
        **per_row,
    )


# zipf, withheld, (batch rows, K of multi_step or None for step)
STREAMS = [
    pytest.param(0.0, False, (32, None), id="0.0"),
    pytest.param(1.1, False, (32, None), id="1.1"),
    pytest.param(1.1, True, (32, None), id="withheld-step"),
    pytest.param(1.1, True, (8, 16), id="withheld-multi_step"),
]


@pytest.mark.parametrize("zipf,withheld,program", STREAMS)
def test_incremental_matches_one_shot(zipf, withheld, program):
    """The end state equals the one-shot pipeline's on the same DAG. On the
    withheld stream a third of the validators hide their chains and an
    honest event reveals them rounds later: their witnesses land in rounds
    whose fame is already decided, the device re-opens those rounds in
    place (`_reopen_rounds`), and the end state is still the one-shot
    pipeline's, which saw the whole DAG at once. Windows are sized so that
    nothing latches."""
    n, e = 8, 1024 if withheld else 768
    grid = synthetic_grid(n, e, seed=3, zipf_a=zipf, record_fd_updates=True,
                          byzantine_frac=1 / 3 if withheld else 0.0)
    if withheld:
        grid = as_an_honest_validator_receives_it(grid, n // 3)
        assert e - 64 < grid.e <= e
    rows, k = program
    st = replay(grid, batches_from_grid(grid, rows, 8192, e), e, k=k,
                e_win=e if withheld else 512, rows=rows)

    ref = run_passes(grid)
    assert not bool(st.stale)
    assert not bool(st.fame_lag)
    assert (int(np.asarray(st.reopened).sum()) > 0) == withheld
    for f in DECISIONS:
        np.testing.assert_array_equal(
            np.asarray(getattr(st, f))[: grid.e], getattr(ref, f), f)
    assert int(st.last_round) == ref.last_round


@pytest.mark.parametrize("zipf", [0.0, 1.1])
@pytest.mark.parametrize("k", [4, 16])
def test_multi_step_matches_per_batch(k, zipf):
    """A sync's batches as `multi_step` trains of K (one decide a train)
    must equal the one-by-one path (a decide a batch): K=4 and K=16 are
    the two train shapes the engine launches, and 1,024 events are two
    K=16 trains. The received window holds a whole train and more, as the
    engine's does: a train longer than the window latches `stale`."""
    n, e = 8, 1024
    grid = synthetic_grid(n, e, seed=5, zipf_a=zipf, record_fd_updates=True)
    batches = batches_from_grid(grid, 32, 8192, e)
    assert len(batches) >= 32

    one = replay(grid, batches, e, e_win=e)
    many = replay(grid, batches, e, k=k, e_win=e)
    assert_same(one, many, DECISIONS + TABLES)
    assert int(many.last_round) == int(one.last_round)
    assert not bool(many.stale) and not bool(many.fame_lag)


@pytest.mark.parametrize("rows", [8, 64])
def test_batch_size_does_not_move_the_end_state(rows):
    """Where a sync is cut into batches is the engine's choice
    (`LiveDeviceEngine._cut` ends one at the level table's height or at
    the update cap): 8-row and 64-row batches must land in the state
    32-row batches give."""
    n, e = 8, 768
    grid = synthetic_grid(n, e, seed=9, zipf_a=1.1, record_fd_updates=True)
    at_32 = replay(grid, batches_from_grid(grid, 32, 8192, e), e)
    other = replay(grid, batches_from_grid(grid, rows, 8192, e), e, rows=rows)
    assert_same(at_32, other, DECISIONS + ("fame_decided", "famous"))
    assert not bool(other.stale) and not bool(other.fame_lag)


def test_padded_train_equals_its_real_batches():
    """Five real batches and eleven of the engine's empty ones through one
    K=16 `multi_step` leave the state five `step` calls leave, field for
    field: padding a train to its compiled shape is a no-op."""
    n, e_cap = 8, 1024
    grid = synthetic_grid(n, 160, seed=4, zipf_a=1.1, record_fd_updates=True)
    batches = batches_from_grid(grid, 32, 8192, e_cap)
    assert len(batches) == 5
    assert_same(replay(grid, batches, e_cap, e_win=e_cap),
                replay(grid, batches, e_cap, k=16, e_win=e_cap),
                IncState._fields)


@pytest.mark.parametrize("k", [None, 16], ids=["step", "multi_step"])
def test_stale_latch_fires_on_undersized_window(k):
    """An undetermined row sliding below the received window must latch
    the stale flag instead of silently never deciding, under either
    program."""
    n, e = 8, 512
    grid = synthetic_grid(n, e, seed=7, zipf_a=1.1, record_fd_updates=True)
    batches = batches_from_grid(grid, 32, 8192, e)
    st = replay(grid, batches, e, k=k, e_win=64)  # far too small
    assert bool(st.stale)


def test_batches_split_at_the_update_cap():
    """A batch whose first-descendant updates pass `upd_cap` is halved
    instead of refused, and the halves give the state the whole gave."""
    n, e = 8, 512
    grid = synthetic_grid(n, e, seed=5, zipf_a=1.1, record_fd_updates=True)
    whole = batches_from_grid(grid, 32, 8192, e)
    staged = lambda b, cap: int((BatchLayout(32, n, cap).fields(b).upd_row
                                 != e).sum())
    burst = max(staged(b, 8192) for b in whole)
    cap = burst // 2
    halves = batches_from_grid(grid, 32, cap, e)
    assert len(halves) > len(whole)
    assert all(staged(b, cap) <= cap for b in halves)

    one, two = replay(grid, whole, e), replay(grid, halves, e, upd_cap=cap)
    for f in ("rounds", "lamport", "witness", "received", "fd"):
        np.testing.assert_array_equal(
            np.asarray(getattr(one, f)), np.asarray(getattr(two, f)), f)
    assert not bool(two.stale) and not bool(two.fame_lag)
