"""The live rung hands each launch its batch as one int32 buffer
(`incremental.BatchLayout`), and the programs slice it back into the
`Batch` the append body reads. The device must be handed the values it was
handed as fourteen arrays, and do the same work on them.

The yardstick is the fourteen-array form the programs had, kept here:
`fourteen_step` and `fourteen_multi_step` are `step` and `multi_step` as
they took a `Batch` pytree, over the same append and decide bodies, fed
the `Batch`es of the plain builders of `tests/test_stage_bulk.py` (which
know nothing of the layout). The state each leaves must be bit-identical,
field for field, to the state the buffer gives.
"""

import functools

import jax
import numpy as np
import pytest

from babble_tpu.hashgraph import Hashgraph, InmemStore
from babble_tpu.tpu import synthetic_grid
from babble_tpu.tpu.grid import MAX_INT32
from babble_tpu.tpu.incremental import (
    Batch,
    BatchLayout,
    IncState,
    L_MAX,
    _decide_body,
    _step_body,
    _unpack,
    batches_from_grid,
    init_state,
    multi_step,
    step,
)
from babble_tpu.tpu.live import LiveDeviceEngine
from benchmark.entries import replay

from test_stage_bulk import PlainStage, plain_batches_from_grid, triples_of

STATICS = ("super_majority", "n_participants", "r_win", "e_win", "packed")


@functools.partial(jax.jit, static_argnames=STATICS)
def fourteen_step(state, batch, super_majority, n_participants, r_win,
                  e_win, packed):
    return _decide_body(
        _step_body(state, batch, super_majority, n_participants,
                   packed=packed),
        super_majority, n_participants, r_win=r_win, e_win=e_win,
        packed=packed)


@functools.partial(jax.jit, static_argnames=STATICS)
def fourteen_multi_step(state, stacked, super_majority, n_participants,
                        r_win, e_win, packed):
    def body(st, b):
        return _step_body(st, b, super_majority, n_participants,
                          packed=packed), None

    out, _ = jax.lax.scan(body, state, stacked)
    return _decide_body(out, super_majority, n_participants,
                        r_win=r_win, e_win=e_win, packed=packed)


def stacked(batches):
    return Batch(*[np.stack([np.asarray(getattr(b, f)) for b in batches])
                   for f in Batch._fields])


def plain_empty(layout, e_cap):
    """The batch that appends nothing, field by field."""
    b, u = layout.batch_cap, layout.upd_cap
    minus = lambda *shape: np.full(shape, -1, np.int32)
    return Batch(
        rows=minus(b), creator=np.zeros(b, np.int32),
        index=np.full(b, MAX_INT32, np.int32), sp_row=minus(b),
        op_row=minus(b), la_rows=minus(b, layout.n),
        coin=np.zeros(b, bool), fixed_round=minus(b),
        upd_row=np.full(u, e_cap, np.int32), upd_col=np.zeros(u, np.int32),
        upd_val=np.zeros(u, np.int32), levels=minus(L_MAX, b),
        sp_lamport=minus(b), op_lamport=minus(b))


def assert_same_state(got, want):
    for f in IncState._fields:
        a, b = np.asarray(getattr(got, f)), np.asarray(getattr(want, f))
        assert a.dtype == b.dtype and a.shape == b.shape, f
        assert a.tobytes() == b.tobytes(), f


def both_forms(n, e_cap, bufs, plain, layout, sm, k=None, r_cap=64,
               r_win=32, e_win=512, packed=False):
    """The batches through the buffer programs and through the fourteen-
    array ones, from two fresh states: `step` a batch, or `multi_step`
    trains of `k`, the last padded with the empty batch. Returns both end
    states."""
    kw = dict(r_win=r_win, e_win=e_win, packed=packed)
    got, want = init_state(n, e_cap, r_cap), init_state(n, e_cap, r_cap)
    if k is None:
        for buf, b in zip(bufs, plain):
            got = step(got, buf, layout, sm, n, **kw)
            want = fourteen_step(want, b, sm, n, **kw)
        return got, want
    empty, empty_plain = layout.blank(e_cap), plain_empty(layout, e_cap)
    for i in range(0, len(bufs), k):
        pad = k - len(bufs[i : i + k])
        got = multi_step(got, np.stack(bufs[i : i + k] + [empty] * pad),
                         layout, sm, n, **kw)
        want = fourteen_multi_step(
            want, stacked(plain[i : i + k] + [empty_plain] * pad), sm, n,
            **kw)
    return got, want


# ---------------------------------------------------------------------------
# the layout: host pack, traced unpack
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,rows,upd_cap", [(4, 32, 8192), (16, 5, 100),
                                            (128, 8, 2048)])
def test_pack_then_traced_unpack_returns_every_field(n, rows, upd_cap):
    """A plain `Batch` packed on the host and sliced inside a program comes
    back field for field, in each field's shape and dtype: the padding of
    a short batch (-1, MAX_INT32, the update rows' e_cap) and `coin` (0/1
    in the buffer, bool again in the program). So does a batch of noise,
    whose fields all differ. A train's buffer does so row by row, and the
    empty batch is the plain one."""
    e_cap = 1024
    grid = synthetic_grid(n, 3 * rows - 1, seed=n, zipf_a=1.1,
                          record_fd_updates=True)
    plain = plain_batches_from_grid(
        grid, triples_of(grid, grid.fd_update_stream), rows, upd_cap, e_cap)
    layout = BatchLayout(rows, n, upd_cap)
    plain.append(plain_empty(layout, e_cap))
    assert np.array_equal(layout.pack(plain[-1]), layout.blank(e_cap))
    short = plain[-2]
    rng = np.random.default_rng(n)
    plain.append(Batch(*[
        rng.integers(0, 2, shape).astype(bool) if f == "coin"
        else rng.integers(-1, MAX_INT32, shape, dtype=np.int32)
        for f, shape in zip(Batch._fields, layout.shapes())]))
    assert (short.rows == -1).any() and (short.index == MAX_INT32).any()
    assert (short.upd_row == e_cap).any() and short.coin.any()

    unpack = jax.jit(_unpack, static_argnames="layout")
    train = np.stack([layout.pack(b) for b in plain])
    assert train.dtype == np.int32 and train.shape == (len(plain),
                                                       layout.width)
    rows_of_train = jax.jit(jax.vmap(_unpack, in_axes=(0, None)),
                            static_argnums=1)(train, layout)
    for k, b in enumerate(plain):
        for got in (unpack(layout.pack(b), layout), rows_of_train):
            for f in Batch._fields:
                want = np.asarray(getattr(b, f))
                a = np.asarray(getattr(got, f))
                a = a if a.shape == want.shape else a[k]
                assert a.dtype == (bool if f == "coin" else np.int32), f
                assert np.array_equal(a, want), (k, f)


# ---------------------------------------------------------------------------
# the programs: the buffer against the fourteen arrays
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,events,rows,k,packed", [
    (4, 400, 32, None, False),
    (16, 700, 32, None, False),
    (16, 700, 32, 4, False),
    (16, 160, 32, 16, False),  # five batches and eleven empty ones
    (16, 700, 32, 4, True),
], ids=["n4-step", "n16-step", "n16-k4", "n16-k16-padded",
        "n16-k4-packed-voting"])
def test_programs_fed_the_buffer_match_the_fourteen_arrays(n, events, rows,
                                                           k, packed):
    """The wide voting tables at 4 and 16 validators, through `step`, a
    K=4 train and a K=16 train padded with empty batches, and the packed
    voting tables (uint32 lanes) on the same stream."""
    e_cap = 1024
    grid = synthetic_grid(n, events, seed=7, zipf_a=1.1,
                          record_fd_updates=True)
    layout = BatchLayout(rows, n, 8192)
    bufs = batches_from_grid(grid, rows, 8192, e_cap)
    plain = plain_batches_from_grid(
        grid, triples_of(grid, grid.fd_update_stream), rows, 8192, e_cap)
    assert len(bufs) == len(plain) >= (5 if k == 16 else 10)
    got, want = both_forms(n, e_cap, bufs, plain, layout,
                           grid.super_majority, k=k, packed=packed)
    assert int(want.last_round) > 0 and int(want.count) == grid.e
    # past the short train's stream, fame was decided on real votes
    assert np.asarray(want.rounds_decided).any() == (events >= 400)
    assert_same_state(got, want)


def test_packed_voting_layout_at_128_matches_the_fourteen_arrays():
    """128 validators, the packed voting tables, at a toy capacity (a
    round axis of 16, a fame window of 8): a K=4 train, then the stream
    batch by batch, into round 1."""
    n, e_cap, rows = 128, 4096, 32
    grid = synthetic_grid(n, 3000, seed=5, record_fd_updates=True)
    layout = BatchLayout(rows, n, 8192)
    bufs = batches_from_grid(grid, rows, 8192, e_cap)
    plain = plain_batches_from_grid(
        grid, triples_of(grid, grid.fd_update_stream), rows, 8192, e_cap)
    sm = grid.super_majority
    kw = dict(r_cap=16, r_win=8, e_win=e_cap, packed=True)
    got, want = both_forms(n, e_cap, bufs[:4], plain[:4], layout, sm, k=4,
                           **kw)
    assert_same_state(got, want)
    got, want = both_forms(n, e_cap, bufs, plain, layout, sm, **kw)
    assert int(want.last_round) >= 1 and int(want.count) == grid.e
    assert_same_state(got, want)


def test_batches_cut_at_the_update_cap_match_the_fourteen_arrays():
    """The engine's own staging (`_cut`, `_build_batch`) at 16 validators
    over a staging of 128 updates, so most batches end where the next
    event would pass it: the buffers it writes, and the plain builder's
    `Batch`es of the same chunks, leave the same state."""
    stream = replay.Stream(16, 600, 5, 1.1, 1)
    hg = Hashgraph(stream.peers, InmemStore(stream.peers, 50000))
    eng = LiveDeviceEngine(hg, e_cap=1024, batch_cap=32, upd_cap=128)
    for signed in stream.signed:
        hg.insert_event(stream.copy(signed), True)
    drained, eng.pending = eng.pending, []
    position = hg.peer_position
    bufs, plain, pos = [], [], 0
    while pos < len(drained):
        chunk = eng._cut(drained[pos : pos + eng.batch_cap])
        pos += len(chunk)
        triples = [(ev, [(ah, position(ev.creator()), ev.index())
                         for ah in cells]) for ev, cells in chunk]
        plain.append(PlainStage(eng).build_batch(triples)[0])
        bufs.append(eng._build_batch(chunk)[0])
    assert eng.update_cuts > 20 and len(bufs) > 600 // 32 + 20
    got, want = both_forms(16, 1024, bufs, plain, eng.batch_layout,
                           hg.super_majority)
    assert int(want.count) == 600
    assert_same_state(got, want)
