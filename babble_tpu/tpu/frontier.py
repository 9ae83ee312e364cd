"""Round-frontier DivideRounds: rounds assigned by walking ROUND frontiers
instead of topological levels.

The level scan (kernels._divide_rounds) costs one sequential step per DAG
level — for skewed gossip that is ~50x more steps than there are rounds
(a hot validator's self-chain adds depth without advancing rounds). This
kernel's sequential loop length is the ROUND count, and each step is MXU
work. Measured on the 64-validator 32k-event Zipf bench DAG: ~8 ms per
full pipeline vs ~44 ms for the level scan (~4M events/s).

It rests on three structural facts about hashgraph coordinates:

1. Monotonicity along chains: lastAncestors coordinates are non-decreasing
   along a creator's chain, so "first chain-c event whose p-coordinate
   reaches v" is a precomputable threshold table INV[c, p, v] (one scatter
   + suffix-min over the value axis), and strongly-seeing a fixed witness
   set is a suffix of every chain: the first index strongly seeing witness
   w is the super_majority-th smallest of the per-coordinate thresholds.
2. Transitivity of coordinates: la[e][c'] >= i means e inherits ALL
   ancestors of the c'-chain event at index i, so ONE cross-chain
   min-propagation pass closes "round >= r+1" reachability: every event of
   round >= r+1 has an increment-origin ancestor (the grounding of its
   round descends through exact rounds to an increment over the round-r
   witness set), and that origin is visible directly in la.
3. Jump-over candidates are harmless: if a chain's first event at-or-past
   round r actually has a higher round, counting it in the strongly-seen
   set still only certifies true "round >= r+1" facts — strongly seeing it
   implies having it as an ancestor, which alone forces round >= r+1.

Therefore each frontier step is exact:
    X(r+1)[c] = min( m0[c],  min_c' INV[c, c', m0[c']] ),  clamped >= X(r)
where m0[c] is the first chain-c index strongly seeing a supermajority of
the round-r frontier rows; a chain has a TRUE round-r witness iff
X(r+1) > X(r); and per-event rounds fall out of the frontier history:
round(e) = |{r : index(e) >= X(r)[creator(e)]}| - 1.

TPU mapping: INV lookups at data-dependent values would be scatter-pattern
gathers (row-by-row DMA, measured 17x slower end-to-end); instead the
value axis is contracted with a one-hot einsum on the MXU at HIGHEST
precision (INV values < 2^24, exact in f32).

Scope: fresh (non-reset) grids — the live engine keeps the level scan for
post-reset states. Lamport timestamps are pure DAG depth and are
maintained host-side at insert (level_lamport), like the coordinate
matrices themselves. Bit-exactness: tests/test_frontier.py differentials
against the level-scan kernel on every fixture; bench.py asserts equality
before timing.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .grid import DagGrid, MAX_INT32
from .kernels import (
    PipelineResult,
    _decide_fame,
    _decide_round_received,
    suffix_min,
)


# ---------------------------------------------------------------------------
# host-side staging
# ---------------------------------------------------------------------------


def chain_table(grid: DagGrid) -> np.ndarray:
    """(N, L) row table: rows_by[c, i] = grid row of creator c's event with
    per-creator index i (-1 = none). Host-side, O(E)."""
    n, e = grid.n, grid.e
    l_max = int(grid.index.max(initial=0)) + 1 if e else 1
    rows_by = np.full((n, max(l_max, 1)), -1, dtype=np.int32)
    if e:
        rows_by[grid.creator, grid.index] = np.arange(e, dtype=np.int32)
    return rows_by


def sp_index_of(grid: DagGrid) -> np.ndarray:
    """(E,) per-creator index of each event's self-parent (-1 = root)."""
    sp = grid.self_parent
    out = np.full(grid.e, -1, dtype=np.int32)
    mask = sp >= 0
    out[mask] = grid.index[sp[mask]]
    return out


def level_lamport(grid: DagGrid) -> np.ndarray:
    """(E,) lamport timestamps = DAG depth, from the grid's level layout
    (valid for base grids, whose external lamport seeds are all absent —
    the insert path maintains this incrementally in a live node)."""
    out = np.zeros(grid.e, dtype=np.int32)
    levels = grid.levels[: grid.num_levels]
    mask = levels >= 0
    out[levels[mask]] = np.broadcast_to(
        np.arange(grid.num_levels, dtype=np.int32)[:, None], levels.shape
    )[mask]
    return out


# ---------------------------------------------------------------------------
# device kernels
# ---------------------------------------------------------------------------


# kernel-contract: build_inv
#   in: rows_by:i32[2] la:i32[2]
#   rung: frontier
#   out: inv:f32[3] (threshold tables, MXU-ready)
@jax.jit
def build_inv(rows_by: jax.Array, la: jax.Array) -> jax.Array:
    """INV[c, p, v] = first chain-c index whose p-coordinate >= v
    (v in [0, L)); L = "never". One scatter-min into value slots + a
    reverse cumulative min. f32 so the lookup einsums hit the MXU
    directly (values <= L < 2^24: exact).

    INV is a pure function of the persistent coordinate state — a live
    engine maintains it incrementally alongside la/fd (appending an event
    updates one chain's slice), so precomputing it outside the timed
    pipeline mirrors production use."""
    # the chain axis and the coordinate axis are sized independently: under
    # shard_map (sharded.py) rows_by holds only this device's chain block
    # while la keeps the full N-wide coordinate vectors
    n_c, l = rows_by.shape
    n_p = la.shape[1]
    pad = rows_by < 0
    rb = jnp.maximum(rows_by, 0)
    la_chain = jnp.where(pad[:, :, None], -1, la[rb])  # (N_c, L, N_p)
    c_idx = jnp.broadcast_to(jnp.arange(n_c)[:, None, None], (n_c, l, n_p))
    i_idx = jnp.broadcast_to(jnp.arange(l)[None, :, None], (n_c, l, n_p))
    p_idx = jnp.broadcast_to(jnp.arange(n_p)[None, None, :], (n_c, l, n_p))
    v_slot = jnp.where(la_chain >= 0, jnp.minimum(la_chain, l - 1), l)
    inv0 = jnp.full((n_c, n_p, l + 1), l, jnp.int32)
    inv0 = inv0.at[c_idx, p_idx, v_slot].min(i_idx)
    inv = suffix_min(inv0[:, :, :l], l, axis=2)
    return inv.astype(jnp.float32)


class FrontierResult(NamedTuple):
    rounds: jax.Array  # (E,) int32
    witness: jax.Array  # (E,) bool
    witness_table: jax.Array  # (r_cap, N) int32 rows, -1 none
    last_round: jax.Array  # () int32


# chain-count threshold above which the m0 stage switches from the
# einsum+sort form (materializes a (N, N, N) tensor — 4.3 GB at N=1024)
# to the binary-search form (N^2-sized intermediates only)
M0_BINSEARCH_MIN_N = 512


def _m0_einsum_sort(fd_w, w_ok, inv_f32, super_majority: int, l: int):
    """m0 via INV lookups: u[w, c, p] = first chain-c index whose
    p-coordinate reaches fd_w[w, p] as a one-hot MXU contraction, then the
    supermajority-th smallest along p and along w. Materializes (N, N, N):
    the right form while N^3 stays cache-sized (the N=64 flagship config),
    catastrophic at N=1024."""
    sent = jnp.int32(l)
    vv = jnp.arange(l)
    oh = (
        jnp.clip(fd_w, 0, l - 1)[:, :, None] == vv[None, None, :]
    ).astype(jnp.float32)  # (w, p, v)
    u = jnp.einsum(
        "wpv,cpv->wcp", oh, inv_f32,
        precision=jax.lax.Precision.HIGHEST,
    ).astype(jnp.int32)
    u = jnp.where((fd_w < MAX_INT32)[:, None, :], u, sent)
    u = jnp.where(w_ok[:, None, None], u, sent)

    # t[w, c] = first chain-c index strongly seeing frontier row w;
    # m0[c] = first chain-c index strongly seeing a supermajority
    t = jnp.sort(u, axis=2)[:, :, super_majority - 1]
    return jnp.sort(t, axis=0)[super_majority - 1, :]  # (N_c,)


def _m0_binsearch(fd_w, w_ok, rb, chain_len, la, super_majority: int, l: int):
    """m0 via per-chain binary search over the chain index.

    "Event i of chain c strongly sees >= supermajority of the frontier
    rows" is monotone in i (lastAncestors are non-decreasing along a
    chain), so the first such index is found in ~log2(l) probes; each
    probe evaluates ONE event per chain against every frontier row — an
    (N_c, N_w, N_p) compare-reduce XLA fuses without materializing
    anything N^3-sized. Probes beyond the chain end are clamped to the
    last event (same predicate value), which keeps the search monotone;
    chains whose last event does not qualify resolve to the sentinel."""
    n = rb.shape[0]
    sent = jnp.int32(l)
    cc = jnp.arange(n)
    last = jnp.maximum(chain_len - 1, 0)

    lo = jnp.zeros((n,), jnp.int32)
    hi = jnp.full((n,), l, jnp.int32)
    steps = max(1, (l - 1).bit_length()) + 1
    for _ in range(steps):
        mid = jnp.minimum((lo + hi) // 2, l - 1)
        probe = jnp.minimum(mid, last)
        ev = rb[cc, probe]  # (N_c,) rows of the probed events
        la_mid = la[ev]  # (N_c, N_p)
        cnt_p = jnp.sum(
            la_mid[:, None, :] >= fd_w[None, :, :], axis=-1, dtype=jnp.int32
        )  # (N_c, N_w)
        sees = (cnt_p >= super_majority) & w_ok[None, :]
        pred = (
            (jnp.sum(sees, axis=1, dtype=jnp.int32) >= super_majority)
            & (chain_len > 0)
        )
        hi = jnp.where(pred, jnp.minimum(mid, hi), hi)
        lo = jnp.where(pred, lo, mid + 1)
    # hi is the first qualifying (clamped) probe; beyond-end probes only
    # repeat the last event's verdict, so a real result is always < len
    return jnp.where(hi < chain_len, hi, sent)


def make_walk_step(inv_f32, rows_by, fd, la, super_majority: int):
    """Build the one-round frontier transition X(r) -> X(r+1) over the
    given tables, for the walk of `_frontier_rounds`. The first chain
    index that strongly sees the frontier is found by binary search from
    M0_BINSEARCH_MIN_N validators up (it needs `la`), by the sort-based
    einsum below that."""
    n, l = rows_by.shape
    sent = jnp.int32(l)
    rb = jnp.maximum(rows_by, 0)
    cc = jnp.arange(n)
    vv = jnp.arange(l)
    use_binsearch = n >= M0_BINSEARCH_MIN_N and la is not None
    chain_len = jnp.sum(rows_by >= 0, axis=1).astype(jnp.int32)

    def step(x_cur):
        w_ok = x_cur < sent
        w_row = rb[cc, jnp.clip(x_cur, 0, l - 1)]  # (N,)
        fd_w = jnp.where(w_ok[:, None], fd[w_row], MAX_INT32)  # (N_w, N_p)

        if use_binsearch:
            m0 = _m0_binsearch(
                fd_w, w_ok, rb, chain_len, la, super_majority, l
            )
        else:
            m0 = _m0_einsum_sort(fd_w, w_ok, inv_f32, super_majority, l)

        # cross-chain closure, one pass (coordinate transitivity)
        oh2 = (
            jnp.clip(m0, 0, l - 1)[:, None] == vv[None, :]
        ).astype(jnp.float32)  # (c', v)
        reach = jnp.einsum(
            "xv,cxv->cx", oh2, inv_f32,
            precision=jax.lax.Precision.HIGHEST,
        ).astype(jnp.int32)
        reach = jnp.where((m0 < sent)[None, :], reach, sent)
        x_next = jnp.minimum(m0, jnp.min(reach, axis=1))
        x_next = jnp.minimum(jnp.maximum(x_next, x_cur), sent)
        return x_next

    return step


def frontier_x0(rows_by) -> jax.Array:
    """X(0): every non-empty chain's first event is root-attached with
    round 0 (base grids)."""
    l = rows_by.shape[1]
    return jnp.where(rows_by[:, 0] >= 0, 0, jnp.int32(l)).astype(jnp.int32)


# kernel-contract: _frontier_rounds
#   in: inv_f32:f32[3] rows_by:i32[2] creator:i32[1] index:i32[1]
#   in: sp_index:i32[1] fd:i32[2] la:i32[2]
#   static: super_majority r_cap
#   rung: frontier
#   out: FrontierResult
def _frontier_rounds(
    inv_f32, rows_by, creator, index, sp_index, fd, super_majority: int,
    r_cap: int, la=None,
) -> FrontierResult:
    step = make_walk_step(inv_f32, rows_by, fd, la, super_majority)

    def body(x_cur, _):
        return step(x_cur), x_cur

    _, x_hist = jax.lax.scan(
        body, frontier_x0(rows_by), None, length=r_cap
    )  # (r_cap, N)
    return frontier_post(x_hist, rows_by, creator, index, sp_index)


def frontier_post(x_hist, rows_by, creator, index, sp_index) -> FrontierResult:
    """Witness table + per-event rounds from the frontier history — shared
    verbatim by the single-device walk and the chains-sharded walk
    (sharded.py), so their outputs agree bit-for-bit by construction."""
    n, l = rows_by.shape
    r_cap = x_hist.shape[0]
    sent = jnp.int32(l)
    rb = jnp.maximum(rows_by, 0)
    cc = jnp.arange(n)
    x_next_hist = jnp.concatenate(
        [x_hist[1:], jnp.full((1, n), l, jnp.int32)], axis=0
    )

    # witness table: the frontier row, where the chain truly has an
    # exact-round-r event (the frontier moved past it at r+1)
    w_rows = rb[cc[None, :], jnp.clip(x_hist, 0, l - 1)]
    w_valid = (x_hist < sent) & (x_next_hist > x_hist)
    wtable = jnp.where(w_valid, w_rows, -1)

    # per-event rounds from the frontier history
    xh = jnp.where(x_hist < sent, x_hist, jnp.int32(l))  # (r_cap, N)
    ge = index[:, None] >= xh.T[creator]  # (E, r_cap)
    rounds = jnp.sum(ge, axis=1).astype(jnp.int32) - 1

    # sp_index already carries -1 for root-attached events, which can never
    # reach any frontier value
    sp_ge = sp_index[:, None] >= xh.T[creator]
    witness = rounds > (jnp.sum(sp_ge, axis=1).astype(jnp.int32) - 1)

    return FrontierResult(rounds, witness, wtable, jnp.max(rounds))


frontier_rounds = functools.partial(
    jax.jit, static_argnames=("super_majority", "r_cap")
)(_frontier_rounds)


# kernel-contract: frontier_pipeline
#   in: inv_f32:f32[3] rows_by:i32[2] creator:i32[1] index:i32[1]
#   in: sp_index:i32[1] la:i32[2] fd:i32[2] lamport:i32[1]
#   in: coin_bit:bool[1]:wide
#   static: super_majority n_participants r_cap d_cap packed
#   rung: frontier
#   out: PipelineResult
@functools.partial(
    jax.jit,
    static_argnames=(
        "super_majority", "n_participants", "r_cap", "d_cap", "packed",
    ),
)
def frontier_pipeline(
    inv_f32: jax.Array,  # (N, N, L) f32 from build_inv
    rows_by: jax.Array,  # (N, L) int32
    creator: jax.Array,  # (E,) int32
    index: jax.Array,  # (E,) int32
    sp_index: jax.Array,  # (E,) int32
    la: jax.Array,  # (E, N) int32
    fd: jax.Array,  # (E, N) int32
    lamport: jax.Array,  # (E,) int32 (host-maintained DAG depth)
    coin_bit: jax.Array,  # (E,) bool
    super_majority: int,
    n_participants: int,
    r_cap: int,
    d_cap: int = None,
    packed: bool = False,
) -> PipelineResult:
    """DivideRounds (frontier walk) + DecideFame + DecideRoundReceived as
    one XLA program; same output contract as kernels.consensus_pipeline.
    d_cap optionally caps the fame voting offset (the static safety net of
    the scan pipeline); default = r_cap + 2."""
    fr = _frontier_rounds(
        inv_f32, rows_by, creator, index, sp_index, fd, super_majority, r_cap,
        la=la,
    )
    fame = _decide_fame(
        fr.witness_table, la, fd, index, coin_bit, fr.last_round,
        super_majority, n_participants,
        r_cap + 2 if d_cap is None else d_cap,
        packed=packed,
    )
    received = _decide_round_received(
        fr.witness_table, la, index, creator, fr.rounds,
        fame.decided, fame.famous, fame.rounds_decided, fr.last_round,
    )
    return PipelineResult(
        rounds=fr.rounds,
        witness=fr.witness,
        lamport=lamport,
        witness_table=fr.witness_table,
        fame_decided=fame.decided,
        famous=fame.famous,
        rounds_decided=fame.rounds_decided,
        received=received,
        last_round=fr.last_round,
    )
