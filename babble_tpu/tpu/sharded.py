"""Multi-chip SPMD consensus: the virtual-voting pipeline partitioned over
a `jax.sharding.Mesh` (SURVEY.md §5 "events-dimension sharding";
BASELINE.json config #5).

Layout — who owns what:

- **DivideRounds** runs replicated (dp-style redundant compute): it is a
  sequential scan over topological levels whose state is the small (E,)
  round/lamport vectors — there is nothing worth sharding and everything
  downstream needs its outputs.
- **DecideFame** — the FLOPs — shards over the *rounds* axis. Each device
  owns R/ndev rounds' (N, N) vote matmuls. The voters of step d live at
  round j = i + d, i.e. d rows ahead of the decided round i, so the
  strongly-see tensor is kept aligned by ring-shifting one row per voting
  step with `lax.ppermute` over ICI — the same neighbor-exchange pattern as
  ring attention, applied to reachability matrices. Early exit is
  host-chunked: `chunk` voting steps per dispatch, stop when no undecided
  witness has voting rounds left (bit-exact: extra steps never overwrite a
  decision, skipped steps have no valid voters).
- **DecideRoundReceived** shards over the *events* axis: given the small
  replicated (R, N) fame tables it is a pure per-event map.

Differentially verified against the single-device pipeline in
tests/test_multichip.py on a virtual CPU mesh.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..obs.devledger import ledger_call
from . import kernels
from .engine import PassResults
from .frontier import frontier_post
from .grid import DagGrid, MAX_INT32
from .packed import (
    LANE, pack_bits, pack_votes_t, packed_tally, popcount_sum, resolve_packed,
)

# module-level jit so repeated pipeline runs reuse the compiled post-walk
_frontier_post_jit = jax.jit(frontier_post)


def _mesh_axes(mesh: Mesh):
    """(rounds_axis, validator_axis) of a consensus mesh. 1-D meshes
    shard rounds/events/chains over their single axis (validator_axis
    None); 2-D ``(validators, rounds)`` meshes — node/core.py
    ``mesh_validator_shards`` — additionally partition the fame working
    set's witness axis, so the per-device voting state shrinks by the
    validator-shard count (ISSUE 9: the MPC-style per-machine graph
    shard)."""
    names = mesh.axis_names
    if len(names) == 1:
        return names[0], None
    if len(names) == 2:
        return names[1], names[0]
    from .grid import GridUnsupported

    raise GridUnsupported(f"unsupported mesh rank: axes {names!r}")


def mesh_validator_shards(mesh: Mesh) -> int:
    """Validator-axis extent of the mesh (1 on 1-D meshes)."""
    _, v_axis = _mesh_axes(mesh)
    return int(mesh.shape[v_axis]) if v_axis is not None else 1


def sharded_engine_tag(mesh: Mesh, doubling: bool = False) -> str:
    """Engine label for decision-provenance capture: distinguishes the
    1-D event-sharded layout from the 2-D validator-sharded one (and the
    sharded doubling cold path), so a bisected divergence names the mesh
    discipline that produced the bad cell."""
    tag = "mesh2d" if mesh_validator_shards(mesh) > 1 else "mesh"
    return tag + "-doubling" if doubling else tag


def _pad_axis0(a: np.ndarray, size: int, fill) -> np.ndarray:
    out = np.full((size,) + a.shape[1:], fill, dtype=a.dtype)
    out[: a.shape[0]] = a
    return out


@functools.lru_cache(maxsize=16)
def _fame_loop_fn(mesh: Mesh, axis: str, chunk: int, n_participants: int,
                  super_majority: int, d_bound: int, v_axis=None,
                  packed: bool = False):
    """Build the shard_mapped fame voting pass for a mesh: the WHOLE
    voting loop runs in one dispatch, early-exiting ON DEVICE via a
    lax.while_loop whose continue-flag is a psum across the mesh
    (VERDICT r3 #4 — the previous per-chunk host `bool(active)` fetch
    serialized every voting chunk on host RTT; this matches the
    single-device discipline of kernels.consensus_pipeline). `d_bound`
    is the static safety cap on the voting offset (r_pad + 2), bucketed
    by the caller so the cache stays small.

    With `v_axis` (a 2-D (validators, rounds) mesh) the voted-witness
    axis is additionally partitioned: each device holds only its
    witness-column slice of the strongly-see tensor and vote matrix, the
    per-step tally is a LOCAL einsum over that slice closed by one psum
    of the (B, N_y, N_x) yay/total counts over the validator axis, and
    each shard slices its own witness rows back out of the replicated
    next-vote tensor — per-shard local voting plus one all-reduce per
    step, the MPC per-machine-shard discipline (ISSUE 9).

    With `packed` (tpu/packed.py) the two big boolean carries pack their
    voted-witness axis into uint32 lanes: ss_s is (B, N_y, W) and votes
    carries the TRANSPOSED-packed (B, N_x, W) matrix, BOTH sharding the
    word axis over v_axis — the caller lane-aligns the witness padding to
    32*ndev_v so every shard owns whole words. The local tally is AND +
    popcount over the local words; the SAME int32 psum closes it (packing
    changes what each device holds, not what crosses the interconnect),
    so the collective pattern — and every decision — is identical to the
    wide program. The per-step vote handoff re-packs the replicated wide
    next-vote tensor and slices the local words back out."""
    ndev_r = int(mesh.shape[axis])
    # send my first row to the previous device: a left ring-shift of the
    # globally R-sharded j-aligned tensors (along the rounds axis only —
    # every validator shard ring-shifts its own witness slice)
    perm = [(i, (i - 1) % ndev_r) for i in range(ndev_r)]

    # kernel-contract: local_fame
    #   in: last_round:i32[0] i_rows:i32[1] wvalid:bool[2]:wide
    #   in: votes:any[3]:dual decided:bool[2]:wide famous:bool[2]:wide
    #   in: ss_s:any[3]:dual wv_s:bool[2]:wide coin_s:bool[2]:wide
    #   donate: votes decided famous ss_s wv_s coin_s
    #   mesh: axis v_axis
    #   rung: sharded
    #   out: votes:any[3]:dual decided:bool[2]:wide famous:bool[2]:wide
    def local_fame(last_round, i_rows, wvalid, votes, decided, famous,
                   ss_s, wv_s, coin_s):
        def shift1(x):
            recv = jax.lax.ppermute(x[:1], axis, perm)
            return jnp.concatenate([x[1:], recv], axis=0)

        def step(carry, k):
            votes, decided, famous, ss_s, wv_s, coin_s, d0 = carry
            d = d0 + k
            j = i_rows + d  # absolute voter round per local row
            j_ok = j <= last_round

            vy = wv_s & j_ok[:, None]  # (B, N_y)

            if packed:
                # local AND + popcount over this shard's words; the psum
                # below closes the partial int32 tallies exactly as wide
                ss_d = jnp.where(
                    j_ok[:, None, None], ss_s, jnp.uint32(0)
                )  # (B, N_y, W_local)
                yays = packed_tally(ss_d, votes)
                total = popcount_sum(ss_d)
            else:
                ss_d = ss_s & j_ok[:, None, None]  # (B, N_y, N_w)
                yays = jnp.einsum(
                    "ryw,rwx->ryx",
                    ss_d.astype(jnp.float32),
                    votes.astype(jnp.float32),
                    preferred_element_type=jnp.float32,
                ).astype(jnp.int32)
                total = jnp.sum(ss_d, axis=-1, dtype=jnp.int32)
            if v_axis is not None:
                # close the witness-shard partial tallies: one psum per
                # voting step over the validator axis
                yays = jax.lax.psum(yays, v_axis)
                total = jax.lax.psum(total, v_axis)
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
            any_decide = jnp.any(decide_now, axis=1)
            fame_val = jnp.any(decide_now & v, axis=1)
            famous = jnp.where(any_decide, fame_val, famous)
            decided = decided | any_decide

            coin_votes = jnp.where(strong, v, coin_s[:, :, None])
            new_votes = jnp.where(is_coin, coin_votes, v)
            if packed:
                # voters y of this step are the voted witnesses w of the
                # next: repack transposed, then (on a 2-D mesh) keep only
                # this shard's whole-word slice of the packed voter axis
                new_votes = pack_votes_t(new_votes)  # (B, N_x, W)
                if v_axis is not None:
                    w_words = votes.shape[2]
                    off = jax.lax.axis_index(v_axis) * w_words
                    new_votes = jax.lax.dynamic_slice_in_dim(
                        new_votes, off, w_words, axis=2
                    )
            elif v_axis is not None:
                # each shard keeps only its witness-row slice
                w_local = votes.shape[1]
                off = jax.lax.axis_index(v_axis) * w_local
                new_votes = jax.lax.dynamic_slice_in_dim(
                    new_votes, off, w_local, axis=1
                )
            votes = new_votes
            return (votes, decided, famous, shift1(ss_s), shift1(wv_s),
                    shift1(coin_s), d0), None

        def chunk_body(carry):
            votes, decided, famous, ss_s, wv_s, coin_s, d0, _active = carry
            (votes, decided, famous, ss_s, wv_s, coin_s, _d), _ = (
                jax.lax.scan(
                    step,
                    (votes, decided, famous, ss_s, wv_s, coin_s, d0),
                    jnp.arange(chunk),
                )
            )
            d0 = d0 + chunk
            # does any undecided witness still have voting rounds left?
            # psum makes the flag identical on every device, so the
            # while_loop condition stays coherent across the mesh
            local_active = jnp.any(
                wvalid & ~decided & ((i_rows[:, None] + d0) <= last_round)
            )
            active = jax.lax.psum(local_active.astype(jnp.int32), axis) > 0
            return (votes, decided, famous, ss_s, wv_s, coin_s, d0, active)

        def cond(carry):
            d0, active = carry[-2], carry[-1]
            return active & (d0 <= d_bound)

        carry = (votes, decided, famous, ss_s, wv_s, coin_s,
                 jnp.int32(2), jnp.bool_(True))
        carry = chunk_body(carry)  # voting always runs at least one chunk
        carry = jax.lax.while_loop(cond, chunk_body, carry)
        votes, decided, famous, ss_s, wv_s, coin_s, _d0, _active = carry
        return votes, decided, famous

    shp2 = P(axis, None)
    rep = P()
    # wide: votes carry the voter axis in dim 1, the strongly-see tensor
    # carries the voted-witness axis in dim 2; packed: BOTH carry the
    # packed word axis in dim 2. On 1-D meshes v_axis is None and the P
    # entries collapse to the fully-replicated trailing dims
    votes_spec = P(axis, None, v_axis) if packed else P(axis, v_axis, None)
    ss_spec = P(axis, None, v_axis)
    # buffer donation (ISSUE 6): votes/decided/famous/ss_s/wv_s/coin_s
    # (positions 3-8) are freshly device_put per call by
    # _sharded_fame_received and never read after the dispatch, so XLA
    # may update them in place — the voting loop's working set stops
    # double-buffering. last_round/i_rows/wvalid_s stay undonated
    # (wvalid_s aliases setup state shared with the received tables).
    # Platforms without donation (CPU test mesh) fall back to copies.
    return jax.jit(
        jax.shard_map(
            local_fame,
            mesh=mesh,
            in_specs=(rep, P(axis), shp2, votes_spec, shp2, shp2,
                      ss_spec, shp2, shp2),
            out_specs=(votes_spec, shp2, shp2),
        ),
        donate_argnums=(3, 4, 5, 6, 7, 8),
    )


@functools.lru_cache(maxsize=8)
def _received_fn(mesh: Mesh, axis):
    """shard_mapped DecideRoundReceived: events sharded, fame tables
    replicated; pure local map (no collectives needed). `axis` may be a
    tuple of mesh axes — a 2-D mesh shards the event axis over every
    device. Every input is freshly staged (padded event columns,
    just-computed fame tables) and never read after this dispatch, so
    all seven are donated (ISSUE 9: the received stage stops
    double-buffering, same as the fame loop's carried set)."""

    # kernel-contract: local_received
    #   in: index:i32[1] creator:i32[1] rounds:i32[1] min_la:i32[2]
    #   in: famous_count:i32[1] i_ok:bool[1] horizon:i32[1]
    #   donate: index creator rounds min_la famous_count i_ok horizon
    #   mesh: axis
    #   rung: sharded
    #   out: received:i32[1]
    def local_received(index, creator, rounds, min_la, famous_count, i_ok,
                       horizon):
        # the exact single-device candidate search, applied to the local
        # event shard (fame tables replicated)
        return kernels.received_search(
            index, creator, rounds, min_la, famous_count, i_ok, horizon
        )

    shp = P(axis)
    rep = P()
    return jax.jit(
        jax.shard_map(
            local_received,
            mesh=mesh,
            in_specs=(shp, shp, shp, rep, rep, rep, rep),
            out_specs=shp,
        ),
        donate_argnums=(0, 1, 2, 3, 4, 5, 6),
    )


# kernel-contract: _fame_tables
#   in: wtable:i32[2] la:i32[2] decided:bool[2]:wide famous:bool[2]:wide
#   in: last_round:i32[0]
#   rung: sharded
#   out: min_la/famous_count/i_ok/horizon/rounds_decided
@jax.jit
def _fame_tables(wtable, la, decided, famous, last_round):
    """Replicated post-fame tables consumed by the received map (shared
    table math: kernels._received_tables)."""
    wvalid = wtable >= 0
    rounds_decided = jnp.all(decided | ~wvalid, axis=1) & jnp.any(wvalid, axis=1)
    min_la, famous_count, i_ok, horizon = kernels._received_tables(
        wtable, la, decided, famous, rounds_decided, last_round
    )
    return min_la, famous_count, i_ok, horizon, rounds_decided


# kernel-contract: _fame_setup_staged
#   in: wtable:i32[2] la:i32[2] fd:i32[2] index:i32[1] coin_bit:bool[1]:wide
#   static: super_majority
#   rung: sharded
#   out: ss/votes0/wvalid/coin_w (wide; the caller pads and packs)
@functools.partial(jax.jit, static_argnames=("super_majority",))
def _fame_setup_staged(wtable, la, fd, index, coin_bit, super_majority: int):
    """kernels._fame_setup as ONE program. Run op by op, its
    (R, N, N, N) ancestry compare is materialized before the count —
    66 GB at N=1024, R=64, refused by a 16 GB chip (chip_smoke.py mesh4,
    PR 21); staged, XLA fuses the compare into the reduction as it does
    inside the single-device pipelines."""
    return kernels._fame_setup(wtable, la, fd, index, coin_bit, super_majority)


def _sharded_fame_received(
    mesh, grid: DagGrid, wtable_np, la, fd, index, rounds_np, last_round,
    chunk: int, packed=None,
):
    """Passes 2+3 over the mesh, shared by the level-scan and frontier
    entry points: rounds-sharded fame voting with ring-shifted voters,
    then events-sharded round-received. On a 2-D (validators, rounds)
    mesh the voting working set (strongly-see tensor, vote matrix) is
    additionally partitioned over the witness axis, so per-device fame
    state is (R/dr, N, N/dv) instead of (R/dr, N, N) — the validator
    memory ceiling scales out with the mesh (ISSUE 9 tentpole leg 2).
    With `packed` the witness axis is additionally lane-packed into
    uint32 words and the witness padding is aligned to 32*ndev_v so
    every validator shard owns whole words (tpu/packed.py shard-boundary
    rule) — per-device fame state drops another 8x.
    Returns host numpy results."""
    pk = resolve_packed(packed, grid.n)
    axis, v_axis = _mesh_axes(mesh)
    ndev_r = int(mesh.shape[axis])
    ndev_v = int(mesh.shape[v_axis]) if v_axis is not None else 1
    ndev = ndev_r * ndev_v
    ev_axes = (v_axis, axis) if v_axis is not None else axis
    rep = NamedSharding(mesh, P())
    shard_r = NamedSharding(mesh, P(axis))
    shard_r2 = NamedSharding(mesh, P(axis, None))
    # witness-axis partitioning (None entries collapse on 1-D meshes);
    # packed layouts shard the word axis of both carries (dim 2)
    shard_ss = NamedSharding(mesh, P(axis, None, v_axis))
    shard_votes = NamedSharding(
        mesh, P(axis, None, v_axis) if pk else P(axis, v_axis, None)
    )
    shard_coin = NamedSharding(mesh, P(axis, None))

    r_rows = wtable_np.shape[0]
    r_pad = ((r_rows + ndev_r - 1) // ndev_r) * ndev_r
    e_pad = ((max(grid.e, 1) + ndev - 1) // ndev) * ndev
    # packed witness padding is lane-aligned per shard (32*ndev_v) so the
    # word axis splits evenly across validator shards; extra padded
    # columns/rows are vote-neutral (ss False, wv False), same as wide
    n_quant = LANE * ndev_v if pk else ndev_v
    n_pad_v = ((grid.n + n_quant - 1) // n_quant) * n_quant

    putr = lambda x: jax.device_put(np.asarray(x), rep)
    wtable = putr(_pad_axis0(wtable_np, r_pad, -1))
    ss, votes0, wvalid, coin_w = ledger_call(
        "_fame_setup_staged", _fame_setup_staged,
        wtable, la, fd, index, putr(grid.coin_bit), grid.super_majority,
    )
    # witness-axis padding for the validator shards: padded columns are
    # never strongly seen (ss False) so their garbage vote rows tally 0,
    # and padded voter rows are invalid (wv False) so they decide nothing
    padw = n_pad_v - grid.n
    ss_y = ss
    wv_y = wvalid
    coin_y = coin_w
    if padw:
        ss_y = jnp.pad(ss, ((0, 0), (0, padw), (0, padw)))
        votes0 = jnp.pad(votes0, ((0, 0), (0, padw), (0, 0)))
        wv_y = jnp.pad(wvalid, ((0, 0), (0, padw)))
        coin_y = jnp.pad(coin_w, ((0, 0), (0, padw)))
    # j-aligned buffers start at d0=2: a global left-shift by 2
    if pk:
        # pack once on host-side staging: ss packs its witness axis,
        # votes pack their voter axis transposed (packed_tally layout)
        ss_s = jax.device_put(pack_bits(jnp.roll(ss_y, -2, axis=0)), shard_ss)
        votes = jax.device_put(pack_votes_t(votes0), shard_votes)
    else:
        ss_s = jax.device_put(jnp.roll(ss_y, -2, axis=0), shard_ss)
        votes = jax.device_put(votes0, shard_votes)
    wv_s = jax.device_put(jnp.roll(wv_y, -2, axis=0), shard_r2)
    coin_s = jax.device_put(jnp.roll(coin_y, -2, axis=0), shard_coin)
    wvalid_s = jax.device_put(wvalid, shard_r2)
    decided = jax.device_put(np.zeros((r_pad, grid.n), bool), shard_r2)
    famous = jax.device_put(np.zeros((r_pad, grid.n), bool), shard_r2)
    i_rows = jax.device_put(np.arange(r_pad, dtype=np.int32), shard_r)

    # one dispatch for the whole fame pass: early exit happens on device
    # (d_bound bucketed to the padded round count so the compiled
    # executable is reused across similarly-sized batches)
    fame_loop = _fame_loop_fn(
        mesh, axis, chunk, grid.n, grid.super_majority, r_pad + 2, v_axis,
        packed=pk,
    )
    votes, decided, famous = ledger_call(
        "local_fame", fame_loop,
        last_round, i_rows, wvalid_s, votes, decided, famous,
        ss_s, wv_s, coin_s,
    )

    min_la, famous_count, i_ok, horizon, rounds_decided = ledger_call(
        "_fame_tables", _fame_tables, wtable, la, decided, famous, last_round
    )
    pute = lambda x, fill: jax.device_put(
        _pad_axis0(np.asarray(x), e_pad, fill), NamedSharding(mesh, P(ev_axes))
    )
    received = ledger_call(
        "local_received", _received_fn(mesh, ev_axes),
        pute(grid.index, 0), pute(grid.creator, 0),
        pute(rounds_np, -1),
        jax.device_put(min_la, rep), jax.device_put(famous_count, rep),
        jax.device_put(i_ok, rep), jax.device_put(horizon, rep),
    )
    return (
        np.asarray(decided)[:r_rows],
        np.asarray(famous)[:r_rows],
        np.asarray(rounds_decided)[:r_rows],
        np.asarray(received)[: grid.e],
    )


def sharded_run_passes(
    mesh: Mesh, grid: DagGrid, chunk: int = 8, packed=None,
) -> PassResults:
    """Full three-pass pipeline over a device mesh; results identical to
    the single-device `engine.run_passes` (differential-tested)."""
    pk = resolve_packed(packed, grid.n)
    rep = NamedSharding(mesh, P())
    r_max = grid.r_max

    # ---- pass 1: DivideRounds, replicated over the mesh ----
    # device_put straight from numpy: never touches the default backend, so
    # the pipeline runs entirely on the mesh's devices (the dryrun relies on
    # this to stay off the real TPU)
    putr = lambda x: jax.device_put(np.asarray(x), rep)
    la = putr(grid.last_ancestors)
    fd = putr(grid.first_descendants)
    index = putr(grid.index)
    dr = ledger_call(
        "_divide_rounds", kernels.divide_rounds,
        putr(grid.levels), putr(grid.creator), index,
        putr(grid.self_parent), putr(grid.other_parent), la, fd,
        putr(grid.ext_sp_round), putr(grid.ext_op_round),
        putr(grid.fixed_round), putr(grid.ext_sp_lamport),
        putr(grid.ext_op_lamport), putr(grid.fixed_lamport),
        grid.super_majority, r_max, packed=pk,
    )
    last_round = jnp.max(dr.rounds)

    # ---- passes 2+3: fame (rounds-sharded) + received (events-sharded) ----
    rounds_np = np.asarray(dr.rounds)
    decided, famous, rounds_decided, received = _sharded_fame_received(
        mesh, grid, np.asarray(dr.witness_table), la, fd, index,
        rounds_np, last_round, chunk, packed=pk,
    )

    return PassResults(
        rounds=rounds_np,
        witness=np.asarray(dr.witness),
        lamport=np.asarray(dr.lamport),
        witness_table=np.asarray(dr.witness_table),
        fame_decided=decided,
        famous=famous,
        rounds_decided=rounds_decided,
        received=received,
        last_round=int(last_round),
    )


# ---------------------------------------------------------------------------
# chains-sharded round-frontier pipeline (the flagship kernel, multi-chip)
# ---------------------------------------------------------------------------
#
# The frontier walk's big tensor is INV: (N, N, L) f32 — the per-chain
# threshold tables (frontier.py:build_inv). It is partitioned over axis 0
# (the owning chain), so each device holds and contracts only its N/ndev
# chains' tables; the frontier state X(r) is an (N,) vector kept globally
# consistent by two tiny all-gathers per round step (the per-chain
# strongly-see thresholds m0 and the closed frontier x_next). Witness-table
# assembly and per-event rounds reuse frontier.frontier_post verbatim, and
# fame/received ride the existing rounds-/events-sharded stages — so the
# whole flagship pipeline is mesh-partitioned end to end.


@functools.lru_cache(maxsize=8)
def _sharded_build_inv_fn(mesh: Mesh, axis):
    """shard_mapped build_inv: each device builds the INV slices of its
    own chains (pure local compute, no collectives). `axis` may be a
    tuple of mesh axes (2-D mesh: chains sharded over every device)."""
    from .frontier import build_inv

    return jax.jit(
        jax.shard_map(
            build_inv,
            mesh=mesh,
            in_specs=(P(axis, None), P()),
            out_specs=P(axis, None, None),
        )
    )


@functools.lru_cache(maxsize=8)
def _frontier_walk_fn(mesh: Mesh, axis, super_majority: int, r_cap: int,
                      l: int):
    """shard_mapped frontier walk: INV and the chain table sharded over
    chains (`axis` is a tuple of mesh axes on a 2-D mesh — the
    all-gathers then ride the full device set); fd/la replicated; the
    whole r_cap-step scan runs in ONE
    dispatch with two (N/ndev,)-sized all-gathers per step riding ICI.
    The m0 stage mirrors the single-device form switch (frontier.py):
    einsum+sort for small N, per-chain binary search for large N (the
    sort form materializes (N, N/ndev, N) per device — 500+ MB at
    N=1024 even sharded)."""
    from .frontier import M0_BINSEARCH_MIN_N, _m0_binsearch

    # kernel-contract: local_walk
    #   in: inv_local:f32[3] rb_local:i32[2] fd:i32[2] la:i32[2]
    #   in: x0_local:i32[1]
    #   mesh: axis
    #   rung: sharded
    #   out: x_hist_local:i32[2] (undonated: the r_cap retry re-reads inputs)
    def local_walk(inv_local, rb_local, fd, la, x0_local):
        # (B, N_p, L), (B, L), (E, N_p) replicated, (E, N_p) replicated, (B,)
        b = rb_local.shape[0]
        n_total = b * int(np.prod(mesh.devices.shape))
        sent = jnp.int32(l)
        rb = jnp.maximum(rb_local, 0)
        vv = jnp.arange(l)
        bb = jnp.arange(b)
        use_binsearch = n_total >= M0_BINSEARCH_MIN_N
        chain_len = jnp.sum(rb_local >= 0, axis=1).astype(jnp.int32)

        def step(x_local, _):
            # my chains' frontier rows -> their fd coordinate vectors
            w_row = rb[bb, jnp.clip(x_local, 0, l - 1)]  # (B,)
            w_ok = x_local < sent
            fd_w_local = jnp.where(w_ok[:, None], fd[w_row], MAX_INT32)

            # every device needs every frontier row's coordinates to test
            # its own chains against: gather the small (N, N_p) int table
            fd_w = jax.lax.all_gather(fd_w_local, axis, tiled=True)
            w_ok_all = jax.lax.all_gather(w_ok, axis, tiled=True)

            if use_binsearch:
                # first local-chain index strongly seeing a supermajority
                # of ALL frontier rows — same probe math as the
                # single-device walk, restricted to this device's chains
                m0_local = _m0_binsearch(
                    fd_w, w_ok_all, rb, chain_len, la, super_majority, l
                )
            else:
                # u[w, c_local, p] = first local-chain-c index whose
                # p-coordinate reaches fd_w[w, p] — one-hot MXU contraction
                # against the LOCAL INV shard only (1/ndev of the FLOPs)
                oh = (
                    jnp.clip(fd_w, 0, l - 1)[:, :, None] == vv[None, None, :]
                ).astype(jnp.float32)  # (N_w, N_p, L)
                u = jnp.einsum(
                    "wpv,cpv->wcp", oh, inv_local,
                    precision=jax.lax.Precision.HIGHEST,
                ).astype(jnp.int32)
                u = jnp.where((fd_w < MAX_INT32)[:, None, :], u, sent)

                # t[w, c_local] = first local-chain index strongly seeing
                # frontier row w; m0 = supermajority-th smallest over w
                t = jnp.sort(u, axis=2)[:, :, super_majority - 1]
                m0_local = jnp.sort(t, axis=0)[super_majority - 1, :]  # (B,)
            m0 = jax.lax.all_gather(m0_local, axis, tiled=True)  # (N,)

            # cross-chain closure, one pass (coordinate transitivity) —
            # the x axis is chains-as-coordinates, so slice the gathered m0
            # back to the real coordinate width (chain padding has no
            # coordinate column)
            n_p = fd.shape[1]
            oh2 = (
                jnp.clip(m0[:n_p], 0, l - 1)[:, None] == vv[None, :]
            ).astype(jnp.float32)  # (N_x, L)
            reach = jnp.einsum(
                "xv,cxv->cx", oh2, inv_local,
                precision=jax.lax.Precision.HIGHEST,
            ).astype(jnp.int32)  # (B, N_x)
            reach = jnp.where((m0[:n_p] < sent)[None, :], reach, sent)
            x_next = jnp.minimum(m0_local, jnp.min(reach, axis=1))
            x_next = jnp.minimum(jnp.maximum(x_next, x_local), sent)
            return x_next, x_local

        _, x_hist_local = jax.lax.scan(step, x0_local, None, length=r_cap)
        return x_hist_local  # (r_cap, B)

    return jax.jit(
        jax.shard_map(
            local_walk,
            mesh=mesh,
            in_specs=(P(axis, None, None), P(axis, None), P(), P(), P(axis)),
            out_specs=P(None, axis),
        )
    )


def sharded_frontier_passes(
    mesh: Mesh, grid: DagGrid, chunk: int = 8, r_cap: int = None,
    packed=None,
) -> PassResults:
    """The round-frontier pipeline over a device mesh: INV/chain tables
    sharded over chains, fame rounds-sharded, received events-sharded.
    Results identical to the single-device engine.run_frontier_passes
    (differential-tested in tests/test_multichip.py). Requires a
    frontier-safe (base-state) grid — see engine._frontier_safe."""
    from .engine import pad_grid, _bucket
    from .frontier import chain_table, level_lamport, sp_index_of

    r_axis, v_axis = _mesh_axes(mesh)
    axis = (v_axis, r_axis) if v_axis is not None else r_axis
    ndev = int(np.prod(mesh.devices.shape))
    rep = NamedSharding(mesh, P())

    e_real = grid.e
    rows_by = chain_table(grid)
    sp_index = sp_index_of(grid)
    lamport = level_lamport(grid)
    grid_p = pad_grid(grid)
    pad_e = grid_p.creator.shape[0] - e_real
    # same E-padding semantics as engine.run_frontier_passes: index -1
    # keeps padded rows below every frontier value
    index_np = np.concatenate([grid.index, np.full(pad_e, -1, np.int32)])
    sp_index = np.concatenate([sp_index, np.full(pad_e, -1, np.int32)])
    lamport = np.concatenate([lamport, np.full(pad_e, -1, np.int32)])

    l_b = _bucket(rows_by.shape[1], 64, factor=2)
    n_pad = ((grid.n + ndev - 1) // ndev) * ndev
    rb_pad = np.full((n_pad, l_b), -1, dtype=np.int32)
    rb_pad[: grid.n, : rows_by.shape[1]] = rows_by
    # l_b + 2 is the provable cap: a round advance moves every chain's
    # frontier index by >= 1, so last_round < L <= l_b (same bound as
    # engine._adaptive_r_loop's cap_bound)
    r_hard = l_b + 2
    if r_cap is None:
        r_cap = r_hard

    shard_c = NamedSharding(mesh, P(axis, None))
    putr = lambda x: jax.device_put(np.asarray(x), rep)
    la = putr(grid_p.last_ancestors)
    fd = putr(grid_p.first_descendants)
    index = putr(index_np)
    rb_dev = jax.device_put(rb_pad, shard_c)

    # ---- pass 1a: INV construction, chains-sharded ----
    inv = ledger_call("build_inv", _sharded_build_inv_fn(mesh, axis),
                      rb_dev, la)

    # ---- pass 1b: frontier walk, chains-sharded ----
    x0 = jax.device_put(
        np.where(rb_pad[:, 0] >= 0, 0, l_b).astype(np.int32),
        NamedSharding(mesh, P(axis)),
    )
    while True:
        x_hist = ledger_call(
            "local_walk",
            _frontier_walk_fn(mesh, axis, grid.super_majority, r_cap, l_b),
            inv, rb_dev, fd, la, x0,
        )

        # ---- pass 1c: witness table + per-event rounds (shared post-walk) --
        fr = _frontier_post_jit(
            jax.device_put(x_hist, rep), rb_dev, putr(grid_p.creator), index,
            putr(sp_index),
        )
        last_round = fr.last_round
        # an undersized caller-supplied r_cap truncates the walk and would
        # silently mis-round every event past it — detect via the same
        # last_round margin as the single-device adaptive loop and re-run
        # at the provable cap
        if int(last_round) + 2 <= r_cap or r_cap >= r_hard:
            break
        r_cap = r_hard
    wtable_np = np.asarray(fr.witness_table)[:, : grid.n]

    # ---- passes 2+3: fame (rounds-sharded) + received (events-sharded) ----
    # rounds from the padded walk are sliced back to real events; the
    # shared stage re-pads to its own mesh-divisible event bucket
    rounds_np = np.asarray(fr.rounds)[:e_real]
    decided, famous, rounds_decided, received = _sharded_fame_received(
        mesh, grid, wtable_np, la, fd, index, rounds_np, last_round, chunk,
        packed=packed,
    )

    return PassResults(
        rounds=rounds_np,
        witness=np.asarray(fr.witness)[:e_real],
        lamport=lamport[:e_real],
        witness_table=wtable_np,
        fame_decided=decided,
        famous=famous,
        rounds_decided=rounds_decided,
        received=received,
        last_round=int(last_round),
    )


# ---------------------------------------------------------------------------
# log-diameter cold path, mesh variant (tpu/doubling.py pass 1)
# ---------------------------------------------------------------------------


def sharded_doubling_passes(
    mesh: Mesh, grid: DagGrid, chunk: int = 8, stats=None, packed=None,
) -> PassResults:
    """Cold-path pipeline with pass 1 (pointer-doubling closure +
    contracted walk) running replicated on the mesh devices and passes
    2+3 riding the shared rounds-/events-sharded fame/received stages —
    so deep-section mesh catch-up uses the same queued-dispatch rung as
    the resident pipelines. Results identical to
    `doubling.run_doubling_passes` (differential-tested).

    Pass 1's device placement goes through a replicated device_put, never
    the default backend — the multichip dryrun relies on this to stay off
    the real TPU (same contract as sharded_run_passes)."""
    from .doubling import _doubling_stage1

    rep = NamedSharding(mesh, P())
    putr = lambda x: jax.device_put(np.asarray(x), rep)
    st = stats if stats is not None else {}

    (grid_rb, offset, rounds_np, witness_np, lamport_np, wtable_np,
     last_round) = _doubling_stage1(grid, putr, st)
    st["passes"] = st.get("closure_passes", 0) + st.get("walk_chunks", 0) + 1

    la = putr(grid.last_ancestors)
    fd = putr(grid.first_descendants)
    index = putr(grid.index)
    decided, famous, rounds_decided, received = _sharded_fame_received(
        mesh, grid, wtable_np, la, fd, index, rounds_np,
        putr(np.int32(last_round)), chunk, packed=packed,
    )

    rounds = rounds_np
    received = received.astype(np.int32)
    if offset:
        rounds = np.where(rounds >= 0, rounds + offset, rounds)
        received = np.where(received >= 0, received + offset, received)
    return PassResults(
        rounds=rounds.astype(np.int32),
        witness=witness_np,
        lamport=lamport_np,
        witness_table=wtable_np,
        fame_decided=decided,
        famous=famous,
        rounds_decided=rounds_decided,
        received=received,
        last_round=last_round + offset,
        round_offset=offset,
    )
