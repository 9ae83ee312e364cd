"""JAX/XLA consensus kernels: the five-pass virtual-voting pipeline as dense
batched array programs.

Bit-exactness contract: every kernel reproduces the host engine's results
(rounds, witness flags, lamport timestamps, fame trileans, round-received)
on any fork-free DAG — verified by the differential tests in
tests/test_tpu_differential.py. The mapping from the reference algorithms
(reference: src/hashgraph/hashgraph.go:767-1036):

- stronglySee(x, y) = |{p : lastAnc[x][p] >= firstDesc[y][p]}| >= 2n/3+1
  (reference: hashgraph.go:184-190) -> batched compare + reduce over the
  trailing N axis.
- DivideRounds -> lax.scan over topological *levels* (<= N events each,
  ancestors strictly below), each step vectorized: parent-round max, then
  strongly-see counts against the parent round's witness row of the
  (R, N) witness table, then witness/lamport updates by scatter. External
  parents (roots, reset `others` entries) arrive as per-event host-resolved
  metadata (reference root cases: hashgraph.go:205-278).
- DecideFame -> a while_loop over the round-offset d, *batched over all
  rounds i simultaneously*: votes[i] is an (N, N) creator-indexed matrix;
  the vote count "yays(y,x) = sum_w stronglySee(y,w) * vote(w,x)"
  (reference: hashgraph.go:886-911) is a batched (R, N, N) float matmul —
  MXU work. Coin rounds substitute the precomputed event-hash middle bit
  (reference: hashgraph.go:922-928,1526-1535). The loop exits as soon as no
  undecided witness has voting rounds left (<= last_round) — extra
  iterations can never change a decided witness (first decision wins), and
  skipped iterations have no valid voters, so early exit is bit-exact.
- DecideRoundReceived -> per-round famous-witness column minima of
  lastAncestors: event e is seen by ALL famous witnesses of round i iff
  index[e] <= min over famous w of lastAnc[w][creator[e]] — an (R, N)
  table + an (E, R) masked argmin (reference: hashgraph.go:988-1001).

The full pipeline compiles as ONE XLA program (`consensus_pipeline`): no
host round-trips between passes; `last_round` is computed on device.

All shapes static; padding rows are -1/masked.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .packed import pack_bits, pack_votes_t, packed_count, packed_tally, popcount_sum

MAX_INT32 = 2**31 - 1
MIN_INT32 = -(2**31)

# NOTE: no module-level jnp array constants here. Creating one initializes
# the process's *default* JAX backend as a side effect of `import kernels`
# — on a TPU host that takes the chip, which belongs to one process, away
# from whoever was meant to hold it, and it breaks processes that pin the
# CPU after import. tests/test_multichip.py pins this with an
# import-purity subprocess test.


def suffix_min(x: jax.Array, fill, axis: int = -1) -> jax.Array:
    """Reverse cumulative minimum along `axis` via explicit log-step shift
    doubling. Used instead of jax.lax.associative_scan(min, reverse=True),
    which was observed to silently produce corrupt results on the TPU
    platform at large shapes (~2800-length axes).

    `fill` pads the shifted tail and MUST be >= every element of x (a min
    identity for the data range) — a smaller fill would propagate inward
    and corrupt the suffix minima. Callers pass the axis-domain sentinel
    (r_max / r_cap / chain length), which bounds all stored values."""
    axis = axis % x.ndim
    length = x.shape[axis]
    k = 1
    while k < length:
        lead = [slice(None)] * x.ndim
        lead[axis] = slice(k, None)
        pad_shape = list(x.shape)
        pad_shape[axis] = k
        shifted = jnp.concatenate(
            [x[tuple(lead)], jnp.full(pad_shape, fill, x.dtype)], axis=axis
        )
        x = jnp.minimum(x, shifted)
        k *= 2
    return x


class DivideRoundsResult(NamedTuple):
    rounds: jax.Array  # (E,) int32
    witness: jax.Array  # (E,) bool
    lamport: jax.Array  # (E,) int32
    witness_table: jax.Array  # (R, N) int32 event rows, -1 = none


class FameResult(NamedTuple):
    decided: jax.Array  # (R, N) bool — fame known for witness of (round, creator)
    famous: jax.Array  # (R, N) bool — fame value where decided
    rounds_decided: jax.Array  # (R,) bool — all witnesses of round decided


class PipelineResult(NamedTuple):
    rounds: jax.Array  # (E,) int32
    witness: jax.Array  # (E,) bool
    lamport: jax.Array  # (E,) int32
    witness_table: jax.Array  # (R, N) int32
    fame_decided: jax.Array  # (R, N) bool
    famous: jax.Array  # (R, N) bool
    rounds_decided: jax.Array  # (R,) bool
    received: jax.Array  # (E,) int32
    last_round: jax.Array  # () int32


# kernel-contract: _divide_rounds
#   in: levels:i32[2] creator:i32[1] index:i32[1] self_parent:i32[1]
#   in: other_parent:i32[1] la:i32[2] fd:i32[2] ext_sp_round:i32[1]
#   in: ext_op_round:i32[1] fixed_round:i32[1] ext_sp_lamport:i32[1]
#   in: ext_op_lamport:i32[1] fixed_lamport:i32[1]
#   static: super_majority r_max packed
#   rung: one-shot
#   out: rounds:i32[1] witness:bool[1] lamport:i32[1] wtable:i32[2]
def _divide_rounds(
    levels, creator, index, self_parent, other_parent, la, fd,
    ext_sp_round, ext_op_round, fixed_round, ext_sp_lamport, ext_op_lamport,
    fixed_lamport,
    super_majority: int, r_max: int, packed: bool = False,
) -> DivideRoundsResult:
    e_count, n = la.shape

    def step(carry, level_rows):
        rounds, lamport, witness, wtable = carry
        valid = level_rows >= 0
        rows = jnp.maximum(level_rows, 0)
        # scatter target: padding lanes go out of bounds and are dropped,
        # so they can never collide with row 0's real update
        scatter_rows = jnp.where(valid, rows, e_count)

        c = creator[rows]  # (N,)
        sp = self_parent[rows]
        op = other_parent[rows]

        sp_round = jnp.where(sp >= 0, rounds[jnp.maximum(sp, 0)], ext_sp_round[rows])
        op_round = jnp.where(op >= 0, rounds[jnp.maximum(op, 0)], ext_op_round[rows])
        parent_round = jnp.maximum(sp_round, op_round)

        # strongly-see counts against the parent round's witnesses
        wrows = wtable[jnp.clip(parent_round, 0, r_max - 1)]  # (N_lvl, N)
        wvalid = (wrows >= 0) & (parent_round[:, None] >= 0)
        fd_w = fd[jnp.maximum(wrows, 0)]  # (N_lvl, N, N)
        la_e = la[rows]  # (N_lvl, N)
        if packed:
            # packed ancestry-comparison tally: the (N_lvl, N, N) compare
            # mask packs into uint32 lanes and popcounts — same integers,
            # zero-filled padding lanes contribute nothing
            counts = packed_count(la_e[:, None, :] >= fd_w)
            ss = (counts >= super_majority) & wvalid
            c_seen = packed_count(ss)
        else:
            counts = jnp.sum(la_e[:, None, :] >= fd_w, axis=-1, dtype=jnp.int32)
            ss = (counts >= super_majority) & wvalid
            c_seen = jnp.sum(ss, axis=-1, dtype=jnp.int32)

        new_round = parent_round + (c_seen >= super_majority).astype(jnp.int32)
        # root-attached events have their round forced (reference root
        # cases: hashgraph.go:207-236)
        fixed = fixed_round[rows]
        new_round = jnp.where(fixed >= 0, fixed, new_round)

        new_witness = new_round > sp_round

        sp_lt = jnp.where(sp >= 0, lamport[jnp.maximum(sp, 0)], ext_sp_lamport[rows])
        op_lt = jnp.where(op >= 0, lamport[jnp.maximum(op, 0)], ext_op_lamport[rows])
        new_lt = jnp.maximum(sp_lt, op_lt) + 1
        # already-determined lamports are authoritative (host memo/stored
        # metadata, incl. donor section state after a fast-sync)
        fl = fixed_lamport[rows]
        new_lt = jnp.where(fl != MIN_INT32, fl, new_lt)

        rounds = rounds.at[scatter_rows].set(new_round, mode="drop")
        lamport = lamport.at[scatter_rows].set(new_lt, mode="drop")
        witness = witness.at[scatter_rows].set(new_witness, mode="drop")

        # scatter witnesses into the (R, N) table; non-witness lanes dropped
        w_mask = valid & new_witness
        wr = jnp.where(w_mask, jnp.clip(new_round, 0, r_max - 1), r_max)
        wtable = wtable.at[wr, c].set(level_rows, mode="drop")
        return (rounds, lamport, witness, wtable), None

    init = (
        jnp.full((e_count,), -1, dtype=jnp.int32),
        jnp.full((e_count,), -1, dtype=jnp.int32),
        jnp.zeros((e_count,), dtype=bool),
        jnp.full((r_max, n), -1, dtype=jnp.int32),
    )
    (rounds, lamport, witness, wtable), _ = jax.lax.scan(step, init, levels)
    return DivideRoundsResult(rounds, witness, lamport, wtable)


def _fame_setup_tables(wvalid, la_w, fd_w, idx_w, coin_w, super_majority: int,
                       packed: bool = False):
    """DecideFame preamble from prebuilt per-witness tables: the
    round-adjacent strongly-see tensor and the d=1 ancestry votes
    (reference: hashgraph.go:875-884); `_fame_setup` gathers the tables
    from the flat event arrays. With `packed` the ancestry-comparison tally
    runs as a popcount over uint32 lanes (tpu/packed.py) — integer-equal
    to the wide sum."""
    r_max, n = wvalid.shape

    # ss[j, y, w]: witness y of round j strongly sees witness w of round j-1
    fd_prev = jnp.roll(fd_w, 1, axis=0)
    cmp = la_w[:, :, None, :] >= fd_prev[:, None, :, :]
    counts = packed_count(cmp) if packed else jnp.sum(cmp, axis=-1)
    prev_valid = jnp.roll(wvalid, 1, axis=0).at[0].set(False)
    ss = (counts >= super_majority) & wvalid[:, :, None] & prev_valid[:, None, :]

    # votes at d=1: see(y of round i+1, x of round i) == ancestry
    # (reference: hashgraph.go:879-884)
    la_next = jnp.roll(la_w, -1, axis=0)  # (R, N_y, N_xc) la of round i+1
    see0 = la_next >= idx_w[:, None, :]
    valid_y0 = jnp.roll(wvalid, -1, axis=0).at[r_max - 1].set(False)
    votes0 = see0 & valid_y0[:, :, None]
    return ss, votes0, wvalid, coin_w


def _fame_setup(wtable, la, fd, index, coin_bit, super_majority: int,
                packed: bool = False):
    """Shared DecideFame preamble: gather per-witness tables, then the
    table math (_fame_setup_tables)."""
    wvalid = wtable >= 0
    wrows = jnp.maximum(wtable, 0)
    return _fame_setup_tables(
        wvalid, la[wrows], fd[wrows], index[wrows], coin_bit[wrows],
        super_majority, packed=packed,
    )


def _decide_fame_tables(
    ss, votes0, wvalid, coin_w, last_round,
    super_majority: int, n_participants: int, d_cap: int,
    packed: bool = False,
) -> FameResult:
    """Virtual voting from a prebuilt strongly-see tensor, batched over
    every round i at once; while_loop over the round offset d (j = i + d)
    with bit-exact early exit.

    With `packed` (tpu/packed.py) the loop-resident state shrinks 8x: the
    strongly-see tensor and the carried vote matrix pack their
    voted-witness axis into uint32 lanes, and the yay tally becomes
    sum-of-popcounts over ANDed words — integer-identical to the wide
    float32 einsum (0/1 products, sums far below f32's exact range), so
    every decision below is byte-equal to the wide program. The per-step
    vote verdict v is computed wide (it is the next step's vote input and
    the coin substitution reads wide coin bits) and re-packed transposed
    for the next tally; zero-filled padding lanes never contribute to a
    popcount."""
    r_max, n = wvalid.shape

    i_arr = jnp.arange(r_max)
    if packed:
        ss_p = pack_bits(ss)  # (R, N_y, W): witness axis in uint32 lanes
        total_p = popcount_sum(ss_p)  # (R, N_y), ss row tallies

    def cond(carry):
        votes, decided, famous, d = carry
        # a future voting round exists for some undecided witness
        active = wvalid & ~decided & ((i_arr[:, None] + d) <= last_round)
        return (d <= d_cap) & jnp.any(active)

    def body(carry):
        votes, decided, famous, d = carry
        j = i_arr + d  # per-i absolute round of the voters
        j_ok = j <= last_round
        jc = jnp.clip(j, 0, r_max - 1)

        vy = wvalid[jc] & j_ok[:, None]  # voter validity (R, N_y)

        if packed:
            # votes carries the TRANSPOSED-packed matrix (R, N_x, W):
            # both tally operands pack the voter axis, so AND + popcount
            # is the binary GEMM (packed.packed_tally)
            ss_d = jnp.where(j_ok[:, None, None], ss_p[jc], jnp.uint32(0))
            yays = packed_tally(ss_d, votes)  # (R, N_y, N_x) int32
            total = jnp.where(j_ok[:, None], total_p[jc], 0)
        else:
            ss_d = ss[jc] & j_ok[:, None, None]  # (R, N_y, N_w)
            yays = jnp.einsum(
                "ryw,rwx->ryx",
                ss_d.astype(jnp.float32),
                votes.astype(jnp.float32),
                preferred_element_type=jnp.float32,
            ).astype(jnp.int32)
            total = jnp.sum(ss_d, axis=-1, dtype=jnp.int32)  # (R, N_y)
        nays = total[:, :, None] - yays
        v = yays >= nays
        t = jnp.where(v, yays, nays)

        is_coin = (d % n_participants) == 0
        strong = t >= super_majority

        decide_now = (
            (~is_coin)
            & strong
            & vy[:, :, None]
            & wvalid[:, None, :]
            & (~decided[:, None, :])
        )
        any_decide = jnp.any(decide_now, axis=1)  # (R, N_x)
        fame_val = jnp.any(decide_now & v, axis=1)
        famous = jnp.where(any_decide, fame_val, famous)
        decided = decided | any_decide

        coin_votes = jnp.where(strong, v, coin_w[jc][:, :, None])
        votes_next = jnp.where(is_coin, coin_votes, v)
        if packed:
            # this step's voters y are the next step's voted witnesses w
            votes_next = pack_votes_t(votes_next)
        return (votes_next, decided, famous, d + 1)

    init = (
        pack_votes_t(votes0) if packed else votes0,
        jnp.zeros((r_max, n), dtype=bool),
        jnp.zeros((r_max, n), dtype=bool),
        jnp.int32(2),
    )
    votes, decided, famous, _ = jax.lax.while_loop(cond, body, init)

    # rounds with no witnesses at all don't exist; treat as not decided
    rounds_decided = jnp.all(decided | ~wvalid, axis=1) & jnp.any(wvalid, axis=1)
    return FameResult(decided, famous, rounds_decided)


# kernel-contract: _decide_fame
#   in: wtable:i32[2] la:i32[2] fd:i32[2] index:i32[1] coin_bit:bool[1]:wide
#   in: last_round:i32[0]
#   static: super_majority n_participants d_cap packed
#   rung: one-shot
#   out: FameResult (decided/famous bool[2] wide, rounds_decided bool[1])
def _decide_fame(
    wtable, la, fd, index, coin_bit, last_round,
    super_majority: int, n_participants: int, d_cap: int,
    packed: bool = False,
) -> FameResult:
    """Virtual voting with tables gathered from the flat event arrays."""
    ss, votes0, wvalid, coin_w = _fame_setup(
        wtable, la, fd, index, coin_bit, super_majority, packed=packed
    )
    return _decide_fame_tables(
        ss, votes0, wvalid, coin_w, last_round,
        super_majority, n_participants, d_cap, packed=packed,
    )


def _received_tables_from(wvalid, la_w, decided, famous, rounds_decided,
                          last_round):
    """Per-round received-search tables from prebuilt per-witness tables
    (`_received_tables` gathers them)."""
    r_max = wvalid.shape[0]
    is_famous = decided & famous & wvalid  # (R, N)
    famous_count = jnp.sum(is_famous, axis=1)  # (R,)

    # min over famous witnesses of lastAnc[w][c] per (round, creator-column)
    min_la = jnp.min(
        jnp.where(is_famous[:, :, None], la_w, MAX_INT32), axis=1
    )  # (R, N_c)

    idx = jnp.arange(r_max)
    i_ok = rounds_decided & (idx <= last_round)
    # first non-decided round at-or-after k, as a suffix-scan:
    # horizon[k] = min{ i >= k : not i_ok[i] }  (r_max if none)
    bad = jnp.where(~i_ok, idx, r_max)
    horizon = suffix_min(bad, r_max)  # (R,)
    return min_la, famous_count, i_ok, horizon


def _received_tables(wtable, la, decided, famous, rounds_decided, last_round):
    """Per-round tables consumed by the round-received search: famous-witness
    counts, column minima of famous witnesses' lastAncestors, eligibility,
    and the first-undecided-round suffix scan."""
    return _received_tables_from(
        wtable >= 0, la[jnp.maximum(wtable, 0)], decided, famous,
        rounds_decided, last_round,
    )


def received_core(index, rounds, seen_min, famous_count, i_ok, horizon_start):
    """Shared candidate selection given precomputed per-event tables:
    seen_min[e, i] = min over famous witnesses w of round i of
    lastAnc[w][creator(e)], and horizon_start[e] = first undecided round
    at-or-after rounds[e]+1. Callers differ only in how they build those
    (gathers in the one-shot pipeline, one-hot matmuls in the incremental
    engine where dynamic gathers are the bottleneck)."""
    r_dim = seen_min.shape[1]
    idx = jnp.arange(r_dim)
    cand = (
        (index[:, None] <= seen_min)
        & (famous_count[None, :] > 0)
        & i_ok[None, :]
        & (idx[None, :] > rounds[:, None])
        & (idx[None, :] < horizon_start[:, None])
    )
    received = jnp.min(jnp.where(cand, idx[None, :], r_dim), axis=1)
    return jnp.where(received == r_dim, -1, received).astype(jnp.int32)


def received_search(index, creator, rounds, min_la, famous_count, i_ok, horizon):
    """The per-event round-received candidate search, shared verbatim by the
    single-device pipeline and the events-sharded map (sharded.py):

    received(e) = min { i > round(e) : every round in (round(e), i] is
    fully fame-decided, round i has >= 1 famous witness, and all famous
    witnesses of i see e } (reference: hashgraph.go:951-1036).
    """
    r_dim = min_la.shape[0]
    seen_min = min_la[:, creator].T  # (E, R)
    start = jnp.clip(rounds + 1, 0, r_dim - 1)
    return received_core(
        index, rounds, seen_min, famous_count, i_ok, horizon[start]
    )


# kernel-contract: _decide_round_received
#   in: wtable:i32[2] la:i32[2] index:i32[1] creator:i32[1] rounds:i32[1]
#   in: decided:bool[2]:wide famous:bool[2]:wide rounds_decided:bool[1]
#   in: last_round:i32[0]
#   rung: one-shot
#   out: received:i32[1] (-1 while undetermined)
def _decide_round_received(
    wtable, la, index, creator, rounds, decided, famous, rounds_decided,
    last_round,
) -> jax.Array:
    """Round-received per event; -1 when still undetermined."""
    min_la, famous_count, i_ok, horizon = _received_tables(
        wtable, la, decided, famous, rounds_decided, last_round
    )
    return received_search(
        index, creator, rounds, min_la, famous_count, i_ok, horizon
    )


# kernel-contract: consensus_pipeline
#   in: levels:i32[2] creator:i32[1] index:i32[1] self_parent:i32[1]
#   in: other_parent:i32[1] la:i32[2] fd:i32[2] ext_sp_round:i32[1]
#   in: ext_op_round:i32[1] fixed_round:i32[1] ext_sp_lamport:i32[1]
#   in: ext_op_lamport:i32[1] fixed_lamport:i32[1] coin_bit:bool[1]:wide
#   static: super_majority n_participants r_max r_fame d_cap packed
#   rung: one-shot
#   out: PipelineResult
@functools.partial(
    jax.jit,
    static_argnames=(
        "super_majority", "n_participants", "r_max", "r_fame", "d_cap",
        "packed",
    ),
)
def consensus_pipeline(
    levels: jax.Array,  # (L, N) int32 event rows, -1 padded
    creator: jax.Array,  # (E,) int32
    index: jax.Array,  # (E,) int32
    self_parent: jax.Array,  # (E,) int32
    other_parent: jax.Array,  # (E,) int32
    la: jax.Array,  # (E, N) int32
    fd: jax.Array,  # (E, N) int32
    ext_sp_round: jax.Array,  # (E,) int32
    ext_op_round: jax.Array,  # (E,) int32
    fixed_round: jax.Array,  # (E,) int32
    ext_sp_lamport: jax.Array,  # (E,) int32
    ext_op_lamport: jax.Array,  # (E,) int32
    fixed_lamport: jax.Array,  # (E,) int32: != MIN forces the lamport
    coin_bit: jax.Array,  # (E,) bool
    super_majority: int,
    n_participants: int,
    r_max: int,
    r_fame: int,
    d_cap: int,
    packed: bool = False,
) -> PipelineResult:
    """DivideRounds + DecideFame + DecideRoundReceived as one XLA program.

    `r_max` bounds the witness-table scatter (cheap, so the loose
    levels-based bound is fine); `r_fame` bounds the round axis of the
    expensive fame/received tensors. The topological-level bound on rounds
    is often 50x looser than the real last_round (long chains advance
    rounds slowly), so callers pass a tight adaptive `r_fame` and check
    `last_round + 2 <= r_fame` on the result — if it overflowed, fame and
    received values are garbage and the caller re-runs with a bigger
    bucket (engine.run_passes does this)."""
    dr = _divide_rounds(
        levels, creator, index, self_parent, other_parent, la, fd,
        ext_sp_round, ext_op_round, fixed_round, ext_sp_lamport,
        ext_op_lamport, fixed_lamport, super_majority, r_max, packed=packed,
    )
    last_round = jnp.max(dr.rounds)
    wtable = dr.witness_table[:r_fame]
    fame = _decide_fame(
        wtable, la, fd, index, coin_bit, last_round,
        super_majority, n_participants, d_cap, packed=packed,
    )
    received = _decide_round_received(
        wtable, la, index, creator, dr.rounds,
        fame.decided, fame.famous, fame.rounds_decided, last_round,
    )
    return PipelineResult(
        rounds=dr.rounds,
        witness=dr.witness,
        lamport=dr.lamport,
        witness_table=wtable,
        fame_decided=fame.decided,
        famous=fame.famous,
        rounds_decided=fame.rounds_decided,
        received=received,
        last_round=last_round,
    )


# -- individually-jitted kernels (tests, sharded dryrun) ---------------------

divide_rounds = functools.partial(
    jax.jit, static_argnames=("super_majority", "r_max", "packed")
)(_divide_rounds)

decide_fame = functools.partial(
    jax.jit,
    static_argnames=("super_majority", "n_participants", "d_cap", "packed"),
)(_decide_fame)

decide_round_received = jax.jit(_decide_round_received)
