"""The live rung's device program: persistent on-device DAG state advanced
by gossip-sized append batches (SURVEY §7 hard-part #2; the reference's
UndeterminedEvents + memo-cache discipline, src/hashgraph/hashgraph.go:36-40,
767-780, recast as device-resident buffers + delta scatters).

What the file holds, in order: the state (`IncState`), one append batch
(`Batch`), the append body and the fame + round-received body
(`_step_body`, `_decide_body`), the two jitted programs over them — `step`
(one batch, then decide) and `multi_step` (a scan of K batches, then one
decide) — and the host-side builder that slices a recorded grid into
batches (`batches_from_grid`). `tpu/live.py` is the caller: its
`LiveDeviceEngine` builds the same `Batch` from a node's inserts, launches
`step` for a sync of one or two batches and `multi_step` trains padded to
K=4 or K=16 beyond that, and fetches the decisions. It is the one append
engine of the package.

Per batch the host ships only O(batch) data:
- the new rows' coordinates (lastAncestors), identity and parent pointers;
- the first-descendant cell writes caused by those inserts (each (row, col)
  cell of the fd matrix is written at most once, ever — so the deltas are
  scatter-min ready);
- a within-batch level table (ancestors strictly earlier) + its depth.

TPU-first data layout: everything the strongly-see / fame / received math
touches per round is kept in dense per-witness buffers — la_w/fd_w/idx_w/
coin_w of shape (R_cap, N, ...) — populated by scatter when a witness is
registered and kept current by double-scattering the fd deltas through a
row->witness-slot map. This removes the per-step dynamic row gathers
(row-by-row DMA, the dominant cost of the naive formulation); the one
remaining index-domain lookup (creator -> column of min_la) is a one-hot
matmul on the MXU.

The jitted programs donate the state pytree, so XLA updates the buffers in
place: no reupload, no growth in host<->device traffic with DAG size.
Bit-exactness: tests/test_incremental.py holds the final rounds/lamport/
witness/received equal to the one-shot pipeline's on the same DAG, through
`step` and through `multi_step`; on the chip every benchmark cell's
`correct` holds them to the plain reference.
"""

from __future__ import annotations

import functools
import itertools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .kernels import MAX_INT32, received_core, suffix_min
from .grid import DagGrid, GridUnsupported
from .packed import pack_bits, pack_votes_t, packed_count, packed_tally, popcount_sum

class IncState(NamedTuple):
    """Device-resident DAG state (E_cap rows, R_cap rounds)."""

    la: jax.Array  # (E_cap, N) int32
    fd: jax.Array  # (E_cap, N) int32
    creator: jax.Array  # (E_cap,) int32
    index: jax.Array  # (E_cap,) int32 (MAX = empty row)
    rounds: jax.Array  # (E_cap,) int32 (-1 = unknown)
    lamport: jax.Array  # (E_cap,) int32
    witness: jax.Array  # (E_cap,) bool
    received: jax.Array  # (E_cap,) int32 (-1 = undetermined)
    w_of_row: jax.Array  # (E_cap,) int32 flat witness slot r*N+c (-1 = none)
    wtable: jax.Array  # (R_cap, N) int32 event rows (-1 = none)
    la_w: jax.Array  # (R_cap, N, N) int32 lastAnc of registered witnesses
    fd_w: jax.Array  # (R_cap, N, N) int32 firstDesc of registered witnesses
    idx_w: jax.Array  # (R_cap, N) int32
    coin_w: jax.Array  # (R_cap, N) bool
    fame_decided: jax.Array  # (R_cap, N) bool
    famous: jax.Array  # (R_cap, N) bool
    rounds_decided: jax.Array  # (R_cap,) bool
    # per round, witnesses that registered after the round's fame was
    # decided and re-opened it (cumulative; the host reads the difference
    # between two fetches)
    reopened: jax.Array  # (R_cap,) int32
    last_round: jax.Array  # () int32
    count: jax.Array  # () int32 rows in use
    # latched true if the windows could not hold the state and results
    # are unreliable: an undetermined row slid below the received window,
    # the round axis ran out, or a new row's round lies below the round
    # window's base (both parents below it)
    stale: jax.Array  # () bool
    # latched true if fame voting ever needed more offsets than the
    # static unroll (deep coin scenarios) — fall back to the full pipeline
    fame_lag: jax.Array  # () bool


def init_state(n: int, e_cap: int, r_cap: int) -> IncState:
    return IncState(
        la=jnp.full((e_cap, n), -1, jnp.int32),
        fd=jnp.full((e_cap, n), MAX_INT32, jnp.int32),
        creator=jnp.zeros((e_cap,), jnp.int32),
        index=jnp.full((e_cap,), MAX_INT32, jnp.int32),
        rounds=jnp.full((e_cap,), -1, jnp.int32),
        lamport=jnp.full((e_cap,), -1, jnp.int32),
        witness=jnp.zeros((e_cap,), bool),
        received=jnp.full((e_cap,), -1, jnp.int32),
        w_of_row=jnp.full((e_cap,), -1, jnp.int32),
        wtable=jnp.full((r_cap, n), -1, jnp.int32),
        la_w=jnp.full((r_cap, n, n), -1, jnp.int32),
        fd_w=jnp.full((r_cap, n, n), MAX_INT32, jnp.int32),
        idx_w=jnp.full((r_cap, n), MAX_INT32, jnp.int32),
        coin_w=jnp.zeros((r_cap, n), bool),
        fame_decided=jnp.zeros((r_cap, n), bool),
        famous=jnp.zeros((r_cap, n), bool),
        rounds_decided=jnp.zeros((r_cap,), bool),
        reopened=jnp.zeros((r_cap,), jnp.int32),
        last_round=jnp.int32(0),
        count=jnp.int32(0),
        stale=jnp.bool_(False),
        fame_lag=jnp.bool_(False),
    )


class Batch(NamedTuple):
    """One append batch, fixed static shapes (padded)."""

    rows: jax.Array  # (B,) int32 target rows, -1 padding
    creator: jax.Array  # (B,) int32
    index: jax.Array  # (B,) int32
    sp_row: jax.Array  # (B,) int32 (-1 = root-attached)
    op_row: jax.Array  # (B,) int32 (-1 = none)
    la_rows: jax.Array  # (B, N) int32
    coin: jax.Array  # (B,) bool
    fixed_round: jax.Array  # (B,) int32 (-1 = compute)
    upd_row: jax.Array  # (U,) int32 fd-update rows (E_cap = padding)
    upd_col: jax.Array  # (U,) int32
    upd_val: jax.Array  # (U,) int32
    levels: jax.Array  # (L_MAX, W) int32 positions into the batch, -1 padding
    # lamport timestamps of parents that are no rows of the state (a rebase
    # pruned them: their round lies below the base, which -1 already says,
    # but a child's lamport needs the value); -1 where the parent is a row
    sp_lamport: jax.Array  # (B,) int32
    op_lamport: jax.Array  # (B,) int32


# statically unrolled fame-voting depth: decisions normally land at d<=5;
# anything deeper latches the lag flag instead of looping dynamically
D_UNROLL = 8


@jax.named_scope("live.fame")
def _fame_window(w_valid, la_w, fd_w, idx_w, coin_w, last_round_rel,
                 super_majority: int, n_participants: int,
                 packed: bool = False):
    """DecideFame over a contiguous round window, all tables dense
    (the buffer-resident mirror of kernels._fame_setup + _decide_fame).
    With `packed` (tpu/packed.py) the strongly-see tensor and the carried
    vote matrix hold their voted-witness axis in uint32 lanes and the
    tallies are popcount reductions — integer-identical, so every
    decision is byte-equal to the wide window."""
    r_win, n = w_valid.shape

    fd_prev = jnp.roll(fd_w, 1, axis=0)
    cmp = la_w[:, :, None, :] >= fd_prev[:, None, :, :]
    counts = packed_count(cmp) if packed else jnp.sum(cmp, axis=-1)
    prev_valid = jnp.roll(w_valid, 1, axis=0).at[0].set(False)
    ss = (counts >= super_majority) & w_valid[:, :, None] & prev_valid[:, None, :]

    la_next = jnp.roll(la_w, -1, axis=0)
    see0 = la_next >= idx_w[:, None, :]
    valid_y0 = jnp.roll(w_valid, -1, axis=0).at[r_win - 1].set(False)
    votes0 = see0 & valid_y0[:, :, None]

    i_arr = jnp.arange(r_win)
    if packed:
        ss_p = pack_bits(ss)  # (r_win, N_y, W)
        total_p = popcount_sum(ss_p)

    # statically unrolled voting offsets: straight-line XLA, no dynamic
    # control flow. Decisions needing d > D_UNROLL+1 (e.g. contested coin
    # scenarios) are reported through the overflow flag; the caller falls
    # back to the full pipeline for those rare states.
    votes = pack_votes_t(votes0) if packed else votes0
    decided = jnp.zeros((r_win, n), bool)
    famous = jnp.zeros((r_win, n), bool)
    for d in range(2, 2 + D_UNROLL):
        j = i_arr + d
        # voters must be real window rows: beyond the window top the vote
        # simply waits (and the overflow flag below reports the state)
        j_ok = (j <= last_round_rel) & (j <= r_win - 1)
        jc = jnp.clip(j, 0, r_win - 1)

        vy = w_valid[jc] & j_ok[:, None]

        if packed:
            ss_d = jnp.where(j_ok[:, None, None], ss_p[jc], jnp.uint32(0))
            yays = packed_tally(ss_d, votes)
            total = jnp.where(j_ok[:, None], total_p[jc], 0)
        else:
            ss_d = ss[jc] & j_ok[:, None, None]
            yays = jnp.einsum(
                "ryw,rwx->ryx",
                ss_d.astype(jnp.float32),
                votes.astype(jnp.float32),
                preferred_element_type=jnp.float32,
            ).astype(jnp.int32)
            total = jnp.sum(ss_d, axis=-1, dtype=jnp.int32)
        nays = total[:, :, None] - yays
        v = yays >= nays
        t = jnp.where(v, yays, nays)

        strong = t >= super_majority

        if (d % n_participants) == 0:
            # coin round (static branch: d and n are compile-time)
            votes = jnp.where(strong, v, coin_w[jc][:, :, None])
        else:
            decide_now = (
                strong & vy[:, :, None]
                & w_valid[:, None, :] & (~decided[:, None, :])
            )
            any_decide = jnp.any(decide_now, axis=1)
            fame_val = jnp.any(decide_now & v, axis=1)
            famous = jnp.where(any_decide, fame_val, famous)
            decided = decided | any_decide
            votes = v
        if packed:
            # voters y of this step are the next step's voted witnesses
            votes = pack_votes_t(votes)

    rounds_decided = jnp.all(decided | ~w_valid, axis=1) & jnp.any(w_valid, axis=1)
    # undecided witnesses needing votes beyond the unroll OR the window top
    overflow = jnp.any(
        w_valid & ~decided
        & ((i_arr[:, None] + 2 + D_UNROLL) <= last_round_rel)
    ) | (last_round_rel >= r_win)
    return decided, famous, rounds_decided, overflow


@jax.named_scope("live.deltas")
def _apply_deltas_and_stage(state: IncState, b):
    """Front half of the append body (`_step_body`):

    1. min-scatter the whole batch's first-descendant deltas (each cell is
       written at most once, ever, so the scatter is order-free), mirrored
       into the dense witness buffer through the slot map;
    2. stage the new rows' static data (coordinates, identity, own fd
       cell) into the big arrays.
    """
    e_cap, n = state.la.shape
    r_cap = state.wtable.shape[0]

    fd = state.fd.at[b.upd_row, b.upd_col].min(b.upd_val, mode="drop")
    uslot = state.w_of_row.at[b.upd_row].get(mode="fill", fill_value=-1)
    fd_w_flat = state.fd_w.reshape(r_cap * n, n)
    fd_w_flat = fd_w_flat.at[
        jnp.where(uslot >= 0, uslot, r_cap * n), b.upd_col
    ].min(b.upd_val, mode="drop")
    fd_w = fd_w_flat.reshape(r_cap, n, n)

    valid = b.rows >= 0
    tgt = jnp.where(valid, b.rows, e_cap)
    la = state.la.at[tgt].set(b.la_rows, mode="drop")
    creator = state.creator.at[tgt].set(b.creator, mode="drop")
    index = state.index.at[tgt].set(b.index, mode="drop")
    fd = fd.at[tgt, b.creator].min(b.index, mode="drop")
    return fd, fd_w, la, creator, index, valid, tgt


def _reopen_rounds(state: IncState, rounds_b, witness_b, valid):
    """A witness that registers into a round whose fame is ALREADY DECIDED
    (a withheld chain revealed rounds later, a laggard's old events)
    re-opens that round: its decided flag is cleared, so the next
    `_decide_body` starts its fame window there and votes on the newcomer
    with the later rounds it holds, and receptions that cross the round
    wait until it is whole again. That is the decision the host engine
    takes too (`Hashgraph.queue_round` re-queues the round), and it cannot
    move a witness that was decided before: fame is a fact of the DAG
    (`_decide_body`). Returns (rounds_decided, reopened)."""
    r_cap = state.rounds_decided.shape[0]
    decided = state.rounds_decided.at[
        jnp.clip(rounds_b, 0, r_cap - 1)
    ].get(mode="fill", fill_value=False)
    late = witness_b & valid & decided & (rounds_b >= 0) & (rounds_b < r_cap)
    at = jnp.where(late, rounds_b, r_cap)
    return (
        state.rounds_decided.at[at].set(False, mode="drop"),
        state.reopened.at[at].add(1, mode="drop"),
    )


def _step_body(
    state: IncState,
    batch: Batch,
    super_majority: int,
    n_participants: int,
    packed: bool = False,
) -> IncState:
    """Append one batch: fd deltas, new rows, rounds/lamport/witness and
    witness-buffer registration. Fame/received live in _decide_body."""
    e_cap, n = state.la.shape
    r_cap = state.wtable.shape[0]

    fd, fd_w, la, creator, index, valid, tgt = _apply_deltas_and_stage(
        state, batch
    )

    # 3. rounds/lamport/witness for the new rows, one within-batch level at
    #    a time; witness registration scatters the dense per-witness
    #    buffers. Statically unrolled: level rows are -1-padded, so levels
    #    beyond the batch's real depth are pure no-ops (all scatters drop)
    def level_step(i, carry):
        (rounds, lamport, witness, wtable, w_of_row, la_w, fd_w, idx_w,
         coin_w, below_base) = carry
        pos = batch.levels[i]  # (W,) positions into the batch
        pvalid = pos >= 0
        p = jnp.maximum(pos, 0)
        rows = jnp.where(pvalid, batch.rows[p], e_cap)

        sp = batch.sp_row[p]
        op = batch.op_row[p]
        sp_round = jnp.where(sp >= 0, rounds[jnp.maximum(sp, 0)], -1)
        op_round = jnp.where(op >= 0, rounds[jnp.maximum(op, 0)], -1)
        parent_round = jnp.maximum(sp_round, op_round)

        pr = jnp.clip(parent_round, 0, r_cap - 1)
        wvalid = (wtable[pr] >= 0) & (parent_round[:, None] >= 0)  # (W, N)
        fd_ws = fd_w[pr]  # (W, N, N) — dense slice, no row gathers
        la_e = batch.la_rows[p]  # (W, N)
        if packed:
            counts = packed_count(la_e[:, None, :] >= fd_ws)
            ss = (counts >= super_majority) & wvalid
            c_seen = packed_count(ss)
        else:
            counts = jnp.sum(
                la_e[:, None, :] >= fd_ws, axis=-1, dtype=jnp.int32
            )
            ss = (counts >= super_majority) & wvalid
            c_seen = jnp.sum(ss, axis=-1, dtype=jnp.int32)

        new_round = parent_round + (c_seen >= super_majority).astype(jnp.int32)
        fixed = batch.fixed_round[p]
        new_round = jnp.where(fixed >= 0, fixed, new_round)
        new_witness = new_round > sp_round
        below_base = below_base | jnp.any(
            pvalid & (fixed < 0) & (parent_round < 0))

        sp_lt = jnp.where(
            sp >= 0, lamport[jnp.maximum(sp, 0)], batch.sp_lamport[p])
        op_lt = jnp.where(
            op >= 0, lamport[jnp.maximum(op, 0)], batch.op_lamport[p])
        new_lt = jnp.maximum(sp_lt, op_lt) + 1

        rounds = rounds.at[rows].set(new_round, mode="drop")
        lamport = lamport.at[rows].set(new_lt, mode="drop")
        witness = witness.at[rows].set(new_witness, mode="drop")

        w_mask = pvalid & new_witness
        c = batch.creator[p]
        wr = jnp.where(w_mask, jnp.clip(new_round, 0, r_cap - 1), r_cap)
        wtable = wtable.at[wr, c].set(rows, mode="drop")
        w_of_row = w_of_row.at[jnp.where(w_mask, rows, e_cap)].set(
            wr * n + c, mode="drop"
        )
        la_w = la_w.at[wr, c].set(la_e, mode="drop")
        # the witness's own fd row right now: every cell already written
        # (pre-loop batch deltas) is current; the rest are MAX
        fd_rows = fd[jnp.maximum(rows, 0)]
        fd_w = fd_w.at[wr, c].set(fd_rows, mode="drop")
        idx_w = idx_w.at[wr, c].set(batch.index[p], mode="drop")
        coin_w = coin_w.at[wr, c].set(batch.coin[p], mode="drop")
        return (rounds, lamport, witness, wtable, w_of_row, la_w, fd_w,
                idx_w, coin_w, below_base)

    carry = (state.rounds, state.lamport, state.witness, state.wtable,
             state.w_of_row, state.la_w, fd_w, state.idx_w, state.coin_w,
             jnp.bool_(False))
    with jax.named_scope("live.levels"):
        for i in range(batch.levels.shape[0]):
            carry = level_step(i, carry)
    (rounds, lamport, witness, wtable, w_of_row, la_w, fd_w, idx_w,
     coin_w, below_base) = carry
    last_round = jnp.maximum(state.last_round, jnp.max(rounds))
    count = state.count + jnp.sum(valid, dtype=jnp.int32)

    # round-capacity latch: registration clips rounds >= r_cap onto row
    # r_cap-1, which would silently corrupt that round's tables — a state
    # this deep needs rebasing (engine-level), so flag it as unreliable
    overflow = last_round >= r_cap - 1

    # base latch: a row whose parents both lie below the round window's
    # base (or are unknown to it) has a round the window cannot hold; its
    # computed round would be a guess, so the state is flagged unreliable
    overflow = overflow | below_base

    # a late witness re-opens its round instead (see _reopen_rounds)
    rounds_decided, reopened = _reopen_rounds(
        state,
        rounds.at[tgt].get(mode="fill", fill_value=-1),
        witness.at[tgt].get(mode="fill", fill_value=False),
        valid,
    )

    return state._replace(
        la=la, fd=fd, creator=creator, index=index,
        rounds=rounds, lamport=lamport, witness=witness,
        w_of_row=w_of_row, wtable=wtable,
        la_w=la_w, fd_w=fd_w, idx_w=idx_w, coin_w=coin_w,
        rounds_decided=rounds_decided, reopened=reopened,
        last_round=last_round, count=count,
        stale=state.stale | overflow,
    )


def _decide_body(
    state: IncState,
    super_majority: int,
    n_participants: int,
    r_win: int = 32,
    e_win: int = 8192,
    packed: bool = False,
) -> IncState:
    """Fame + round-received over the current state. Timing-independent:
    candidacy per fully-decided round is stable (its famous set is final
    and coordinates are immutable), so running this once per K appended
    batches yields the exact values per-batch evaluation would."""
    e_cap, n = state.la.shape
    r_cap = state.wtable.shape[0]
    wtable, la_w, fd_w, idx_w, coin_w = (
        state.wtable, state.la_w, state.fd_w, state.idx_w, state.coin_w
    )
    last_round = state.last_round
    index, creator, rounds = state.index, state.creator, state.rounds

    # fame over the active round window only: rounds below the first
    # undecided one keep their stored decisions and are not voted on
    # again. A decided round comes back into the window only when a late
    # witness re-opens it (_reopen_rounds): the window then starts at that
    # round, and every round from there up is decided again from the
    # tables. Fame is a fact of the DAG, so the witnesses decided before
    # come out as they were and the newcomer gets its own decision (not
    # famous: nobody that voted had seen it); until it has one, the round
    # is undecided and holds back the receptions that cross it, exactly
    # as the host engine's re-queued round does (Hashgraph.queue_round).
    r_idx = jnp.arange(r_cap)
    undecided = ~state.rounds_decided & (r_idx <= last_round)
    floor_true = jnp.min(jnp.where(undecided, r_idx, last_round))
    floor = jnp.clip(floor_true, 0, r_cap - r_win)

    sl = lambda a: jax.lax.dynamic_slice(a, (floor,) + (0,) * (a.ndim - 1),
                                         (r_win,) + a.shape[1:])
    dec_w, fam_w, rdec_w, fame_overflow = _fame_window(
        sl(wtable) >= 0, sl(la_w), sl(fd_w), sl(idx_w), sl(coin_w),
        last_round - floor, super_majority, n_participants, packed=packed,
    )
    # freeze mask: when the slice start was clipped below floor_true,
    # entries for already-settled rounds keep their stored values
    rel = jnp.arange(r_win)
    frozen = (floor + rel) < floor_true
    dec_w = jnp.where(frozen[:, None], sl(state.fame_decided), dec_w)
    fam_w = jnp.where(frozen[:, None], sl(state.famous), fam_w)
    rdec_w = jnp.where(frozen, sl(state.rounds_decided), rdec_w)
    fame_decided = jax.lax.dynamic_update_slice(state.fame_decided, dec_w, (floor, 0))
    famous = jax.lax.dynamic_update_slice(state.famous, fam_w, (floor, 0))
    rounds_decided = jax.lax.dynamic_update_slice(state.rounds_decided, rdec_w, (floor,))

    with jax.named_scope("live.received"):
        # round-received for the trailing row window (undetermined rows are
        # always among the most recent)
        is_famous = fame_decided & famous & (wtable >= 0)  # (R, N)
        famous_count = jnp.sum(is_famous, axis=1)
        # min over famous witnesses of lastAnc[w][c], from the dense buffer
        min_la = jnp.min(
            jnp.where(is_famous[:, :, None], la_w, MAX_INT32), axis=1
        )  # (R, N_c)
        i_ok = rounds_decided & (r_idx <= last_round)
        bad = jnp.where(~i_ok, r_idx, r_cap)
        horizon = suffix_min(bad, r_cap)

        lo = jnp.clip(state.count - e_win, 0, e_cap - e_win)
        idx_e = jax.lax.dynamic_slice(index, (lo,), (e_win,))
        cre_e = jax.lax.dynamic_slice(creator, (lo,), (e_win,))
        rnd_e = jax.lax.dynamic_slice(rounds, (lo,), (e_win,))

        # creator -> min_la column and rounds+1 -> horizon entry, as one-hot
        # MXU matmuls. Precision HIGHEST is load-bearing: TPU matmuls default
        # to bf16 inputs and min_la carries event indices (up to 2^24) that
        # bf16 cannot represent — a rounded threshold flips seen/not-seen
        onehot_c = (cre_e[:, None] == jnp.arange(n)[None, :]).astype(jnp.float32)
        seen_min = jnp.matmul(
            onehot_c,
            jnp.minimum(min_la, jnp.int32(1 << 24)).astype(jnp.float32).T,
            precision=jax.lax.Precision.HIGHEST,
        ).astype(jnp.int32)  # (e_win, R)
        start = jnp.clip(rnd_e + 1, 0, r_cap - 1)
        onehot_r = (start[:, None] == r_idx[None, :]).astype(jnp.float32)
        horizon_start = jnp.matmul(
            onehot_r,
            jnp.minimum(horizon, r_cap).astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
        ).astype(jnp.int32)  # (e_win,)

        rec_e = received_core(idx_e, rnd_e, seen_min, famous_count, i_ok, horizon_start)
        old_e = jax.lax.dynamic_slice(state.received, (lo,), (e_win,))
        occ_e = idx_e != MAX_INT32
        new_e = jnp.where((old_e < 0) & occ_e, rec_e, old_e)
        received = jax.lax.dynamic_update_slice(state.received, new_e, (lo,))

    # window-miss detector: an undetermined occupied row below the window
    # can never be decided again — latch it
    row_ids = jnp.arange(e_cap)
    stale = state.stale | jnp.any(
        (row_ids < lo) & (received < 0) & (index != MAX_INT32)
    )

    return state._replace(
        received=received, fame_decided=fame_decided, famous=famous,
        rounds_decided=rounds_decided, stale=stale,
        fame_lag=state.fame_lag | fame_overflow,
    )


# kernel-contract: _step_full
#   in: state:pytree batch:pytree
#   static: super_majority n_participants r_win e_win packed
#   donate: state
#   rung: incremental
#   out: IncState (in-place via donation)
def _step_full(state, batch, super_majority, n_participants,
               r_win: int = 32, e_win: int = 8192, packed: bool = False):
    return _decide_body(
        _step_body(state, batch, super_majority, n_participants,
                   packed=packed),
        super_majority, n_participants, r_win=r_win, e_win=e_win,
        packed=packed,
    )


step = functools.partial(
    jax.jit,
    static_argnames=(
        "super_majority", "n_participants", "r_win", "e_win", "packed",
    ),
    donate_argnames=("state",),
)(_step_full)


# kernel-contract: multi_step
#   in: state:pytree stacked:pytree
#   static: super_majority n_participants r_win e_win packed
#   donate: state
#   rung: incremental
#   out: IncState after K scanned batches + one decide
@functools.partial(
    jax.jit,
    static_argnames=(
        "super_majority", "n_participants", "r_win", "e_win", "packed",
    ),
    donate_argnames=("state",),
)
def multi_step(
    state: IncState,
    stacked: Batch,  # every field stacked along a leading K axis
    super_majority: int,
    n_participants: int,
    r_win: int = 32,
    e_win: int = 8192,
    packed: bool = False,
) -> IncState:
    """Apply K append batches in ONE device program (lax.scan over the
    append body) followed by one fame + round-received pass. Bit-identical
    results: decisions are timing-independent (see _decide_body), so
    deciding once per train equals deciding per batch. Amortizes both the
    per-execute overhead and the decide cost over K batches; the host
    dispatches one call per K syncs."""

    def body(st, b):
        return _step_body(st, b, super_majority, n_participants,
                          packed=packed), None

    out, _ = jax.lax.scan(body, state, stacked)
    return _decide_body(out, super_majority, n_participants,
                        r_win=r_win, e_win=e_win, packed=packed)


def stack_batches(batches):
    """Host-side: stack a list of equal-shape Batch pytrees along axis 0."""
    return Batch(*[
        np.stack([np.asarray(getattr(b, f)) for b in batches])
        for f in Batch._fields
    ])


def _pad1(a, pad, fill, dtype=np.int32):
    a = np.asarray(a, dtype=dtype)
    return np.concatenate([a, np.full(pad, fill, dtype=dtype)])


# a batch of this many first-descendant cells or fewer is packed cell by
# cell. numpy's fixed cost a call is a few microseconds alone and tens in a
# served node's process, whose other threads run between the calls: the
# bulk path's seven calls outweigh a loop over a served sync's 14-20 cells
# (4 validators), and the loop a 32-event batch's 500-2,000 (16 and 64)
FEW_CELLS = 64


def _pack_upd(cell_rows, n_cells, counts, creator, index, upd_cap, e_cap):
    """One batch's first-descendant scatter operands. `cell_rows` yields
    the rows of the `n_cells` ancestors whose cell the batch's events
    wrote, event after event, `counts[k]` of them for event k; a cell's
    column and value are its writer's own creator position and index,
    `creator[k]` and `index[k]`. A row of -1 is an ancestor that is no row
    of the state any more: its fd row is final and can never be read
    again, so the update is dropped, and the rest keep their order. Over
    FEW_CELLS the cells are taken in bulk, with no Python object a cell.
    Returns (urow, ucol, uval), padded to upd_cap with row e_cap (which the
    scatter drops), and the number of cells staged. What lets the GIL go
    costs a served node most, since its other threads take it: an
    allocation of upd_cap elements does (so column and value share one),
    and so does a mask over a second axis at any size (so everything
    indexed is one-dimensional); PERF.md section 6, PR 32."""
    if n_cells <= FEW_CELLS:
        writers = (
            w for w, count in zip(zip(creator, index), counts)
            for _ in range(count)
        )
        upd = [(row, *w) for row, w in zip(cell_rows, writers) if row >= 0]
        rows, cols, vals = zip(*upd) if upd else ((), (), ())
    else:
        cell_rows = np.fromiter(cell_rows, np.int32, n_cells)
        kept = cell_rows >= 0
        rows = cell_rows[kept]
        cols = np.repeat(creator, counts)[kept]
        vals = np.repeat(index, counts)[kept]
    m = len(rows)
    if m > upd_cap:
        raise GridUnsupported("fd update burst exceeds device staging")
    urow = np.full(upd_cap, e_cap, dtype=np.int32)
    ucol, uval = np.zeros((2, upd_cap), dtype=np.int32)
    urow[:m] = rows
    ucol[:m] = cols
    uval[:m] = vals
    return urow, ucol, uval, m


# static height of the within-batch level table; a gossip batch deeper
# than this (one creator chaining >L_MAX events) is split automatically
L_MAX = 16


def _dep_levels(sp_pos, op_pos):
    """Dependency depth of each slice member over slice-LOCAL parent
    positions (plain ints; negative = parent outside the slice): parents
    always land on strictly earlier levels."""
    lvl = []
    for s, o in zip(sp_pos, op_pos):
        d = lvl[s] + 1 if s >= 0 else 0
        if o >= 0 and lvl[o] >= d:
            d = lvl[o] + 1
        lvl.append(d)
    return lvl


def _level_table(lvl, batch_size):
    """(L_MAX, batch_size) table of the slice positions on each level, in
    slice order, -1 padding. The caller keeps every depth under L_MAX."""
    levels = np.full((L_MAX, batch_size), -1, dtype=np.int32)
    slot = [0] * L_MAX
    for k, l in enumerate(lvl):
        levels[l, slot[l]] = k
        slot[l] += 1
    return levels


def batches_from_grid(grid: DagGrid, batch_size: int, upd_cap: int, e_cap: int):
    """Slice a recorded synthetic DAG into fixed-shape append batches —
    the host-side work a live node would do during inserts (O(batch)).
    Batches whose within-batch dependency depth exceeds L_MAX, or whose
    first-descendant updates exceed upd_cap, are split."""
    assert grid.fd_update_stream is not None, "need record_fd_updates=True"
    spans = [
        (s, min(s + batch_size, grid.e))
        for s in range(0, grid.e, batch_size)
    ]
    out = []
    while spans:
        start, end = spans.pop(0)
        rows = np.arange(start, end)
        b = len(rows)
        pad = batch_size - b

        sp = grid.self_parent[rows]
        op = grid.other_parent[rows]

        # within-batch levels: level over batch-local dependency depth
        sp_loc = np.where((sp >= start) & (sp < end), sp - start, -1)
        op_loc = np.where((op >= start) & (op < end), op - start, -1)
        lvl = _dep_levels(sp_loc.tolist(), op_loc.tolist())
        l_b = max(lvl, default=-1) + 1
        cells = [grid.fd_update_stream[r] for r in range(start, end)]
        counts = [len(c) for c in cells]
        n_upd = sum(counts)
        if l_b > L_MAX or (n_upd > upd_cap and b > 1):
            mid = (start + end) // 2
            spans[:0] = [(start, mid), (mid, end)]
            continue
        urow, ucol, uval, _ = _pack_upd(
            itertools.chain.from_iterable(cells), n_upd,
            counts, grid.creator[rows], grid.index[rows], upd_cap, e_cap,
        )

        no_row = np.full(batch_size, -1, dtype=np.int32)  # a grid holds every parent
        out.append(Batch(
            rows=_pad1(rows, pad, -1),
            creator=_pad1(grid.creator[rows], pad, 0),
            index=_pad1(grid.index[rows], pad, MAX_INT32),
            sp_row=_pad1(sp, pad, -1),
            op_row=_pad1(op, pad, -1),
            la_rows=np.concatenate(
                [grid.last_ancestors[rows],
                 np.full((pad, grid.n), -1, dtype=np.int32)]
            ),
            coin=_pad1(grid.coin_bit[rows], pad, False, dtype=bool),
            fixed_round=_pad1(grid.fixed_round[rows], pad, -1),
            upd_row=urow, upd_col=ucol, upd_val=uval,
            levels=_level_table(lvl, batch_size),
            sp_lamport=no_row, op_lamport=no_row,
        ))
    return out
