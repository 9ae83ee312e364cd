"""Bit-packed voting kernels (ISSUE 17, babble_tpu/tpu/packed.py).

The packed layout packs the voted-witness axis of the strongly-seen and
vote tables into uint32 lanes and re-derives every super-majority tally
as a popcount reduction. It is a LAYOUT, never an observable: every test
here is a byte-equality gate of packed against wide on a fixture rung —
one-shot, post-reset/amnesiac sections, the real consensus fixture, the
doubling cold path, the 2-D sharded mesh with non-lane-aligned validator
shards, and the incremental step/train paths — plus the seeded
single-bit-flip arm the PR 11 bisector must localize to its exact
(pass, table, round, witness) cell.
"""

import os
import random
from dataclasses import replace

import jax
import numpy as np
import pytest

from babble_tpu.obs import Observability, bisect_pass_results
from babble_tpu.tpu import synthetic_grid
from babble_tpu.tpu.engine import run_frontier_passes, run_passes
from babble_tpu.tpu.grid import section_grid, synthetic_deep_grid
from babble_tpu.tpu.packed import (
    LANE,
    PACKED_AUTO_MIN_N,
    observe_table_bytes,
    pack_bits,
    pack_votes_t,
    packed_count,
    packed_enabled,
    packed_mode,
    packed_tally,
    packed_words,
    popcount_sum,
    resolve_packed,
    set_packed_mode,
    unpack_bits,
    voting_table_bytes,
)

PASS_FIELDS = (
    "rounds", "witness", "lamport", "fame_decided", "rounds_decided",
    "received",
)


def assert_results_equal(a, b, fields=PASS_FIELDS):
    for f in fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(a, f)), np.asarray(getattr(b, f)), f
        )
    # famous is only defined where fame is decided
    np.testing.assert_array_equal(
        np.asarray(a.famous) & np.asarray(a.fame_decided),
        np.asarray(b.famous) & np.asarray(b.fame_decided),
    )
    assert int(a.last_round) == int(b.last_round)


# ---------------------------------------------------------------------------
# lane packing primitives
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [1, 7, 31, 32, 33, 64, 100])
def test_pack_unpack_round_trip(n):
    rng = np.random.default_rng(n)
    x = rng.integers(0, 2, size=(3, 5, n)).astype(bool)
    xp = np.asarray(pack_bits(x))
    assert xp.shape == (3, 5, packed_words(n))
    assert xp.dtype == np.uint32
    np.testing.assert_array_equal(np.asarray(unpack_bits(xp, n)), x)
    # popcount over words == the wide sum over lanes
    np.testing.assert_array_equal(
        np.asarray(popcount_sum(xp)), x.sum(axis=-1).astype(np.int32)
    )
    np.testing.assert_array_equal(
        np.asarray(packed_count(x)), x.sum(axis=-1).astype(np.int32)
    )


def test_padding_lanes_are_vote_neutral():
    """pack_bits zero-fills the trailing partial word, so padding lanes
    contribute nothing to any popcount tally — the all-ones row of a
    non-lane-aligned width must count exactly its width."""
    n = 7
    ones = np.ones((4, n), dtype=bool)
    xp = np.asarray(pack_bits(ones))
    assert xp.shape == (4, 1)
    assert (xp == (1 << n) - 1).all()  # top LANE-7 bits stay zero
    np.testing.assert_array_equal(
        np.asarray(popcount_sum(xp)), np.full(4, n, dtype=np.int32)
    )


def test_packed_tally_equals_wide_einsum():
    rng = np.random.default_rng(17)
    r_, ny, nx, w = 3, 9, 9, 70
    ss = rng.integers(0, 2, size=(r_, ny, w)).astype(bool)
    votes = rng.integers(0, 2, size=(r_, w, nx)).astype(bool)
    wide = np.einsum(
        "ryw,rwx->ryx", ss.astype(np.float32), votes.astype(np.float32)
    ).astype(np.int32)
    got = np.asarray(packed_tally(pack_bits(ss), pack_votes_t(votes)))
    np.testing.assert_array_equal(got, wide)


def test_pack_votes_t_packs_the_voter_axis():
    rng = np.random.default_rng(5)
    votes = rng.integers(0, 2, size=(2, 33, 6)).astype(bool)  # (R, W, X)
    vp = np.asarray(pack_votes_t(votes))
    assert vp.shape == (2, 6, packed_words(33))
    np.testing.assert_array_equal(
        np.asarray(unpack_bits(vp, 33)), np.swapaxes(votes, 1, 2)
    )


# ---------------------------------------------------------------------------
# mode knob
# ---------------------------------------------------------------------------


def test_mode_knob_env_and_resolution(monkeypatch):
    monkeypatch.delenv("BABBLE_PACKED_VOTING", raising=False)
    try:
        set_packed_mode("auto")
        assert packed_mode() == "auto"
        assert not packed_enabled(PACKED_AUTO_MIN_N - 1)
        assert packed_enabled(PACKED_AUTO_MIN_N)
        set_packed_mode("1")
        assert packed_enabled(4)
        set_packed_mode("0")
        assert not packed_enabled(4096)
        # the env var wins over the process-global mode at call time
        monkeypatch.setenv("BABBLE_PACKED_VOTING", "1")
        assert packed_mode() == "1" and packed_enabled(4)
        monkeypatch.setenv("BABBLE_PACKED_VOTING", "0")
        assert not packed_enabled(4096)
        # per-call override beats both
        assert resolve_packed(True, 4) is True
        assert resolve_packed(False, 4096) is False
        monkeypatch.delenv("BABBLE_PACKED_VOTING")
        with pytest.raises(ValueError):
            set_packed_mode("banana")
    finally:
        set_packed_mode("auto")


def test_engine_honors_env_knob(monkeypatch):
    """run_passes with packed=None resolves the layout from the env knob;
    both settings must agree byte-for-byte."""
    grid = synthetic_grid(7, 160, seed=9)
    monkeypatch.setenv("BABBLE_PACKED_VOTING", "0")
    wide = run_passes(grid)
    monkeypatch.setenv("BABBLE_PACKED_VOTING", "1")
    packed = run_passes(grid)
    assert_results_equal(wide, packed)


# ---------------------------------------------------------------------------
# differential matrix: packed must be byte-equal to wide on every rung
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "n,e,seed",
    [
        (7, 160, 9),    # non-lane-aligned: 25 padding lanes in play
        (33, 400, 4),   # crosses a word boundary (2 words, 31 pad lanes)
        (64, 512, 1),   # lane-aligned
    ],
)
def test_one_shot_packed_matches_wide(n, e, seed):
    grid = synthetic_grid(n, e, seed=seed)
    assert_results_equal(
        run_passes(grid, packed=False), run_passes(grid, packed=True)
    )
    assert_results_equal(
        run_frontier_passes(grid, packed=False),
        run_frontier_passes(grid, packed=True),
    )


def test_consensus_fixture_packed_matches_wide():
    """The real reference fixture (signed events through the host store),
    including the coin-branch topology the wide fame loop exercises."""
    from dsl import init_consensus_hashgraph
    from babble_tpu.tpu.grid import grid_from_hashgraph

    hg, _, _ = init_consensus_hashgraph()
    grid = grid_from_hashgraph(hg)
    assert_results_equal(
        run_passes(grid, packed=False), run_passes(grid, packed=True)
    )


@pytest.mark.parametrize("pin_cut", [True, False])
def test_section_grids_packed_matches_wide(pin_cut):
    """Post-reset (pin_cut=True) and amnesiac (pin_cut=False) sections:
    external parent metadata and pinned cut rounds must not disturb the
    packed tallies."""
    grid = synthetic_grid(7, 320, seed=6)
    full = run_passes(grid)
    sec = section_grid(grid, full, grid.num_levels // 2, pin_cut=pin_cut)
    assert_results_equal(
        run_passes(sec, packed=False), run_passes(sec, packed=True)
    )


def test_doubling_cold_path_packed_matches_wide():
    from babble_tpu.tpu.doubling import run_doubling_passes

    deep = synthetic_deep_grid(7, 2000, seed=11)
    assert_results_equal(
        run_doubling_passes(deep, packed=False),
        run_doubling_passes(deep, packed=True),
    )


@pytest.mark.parametrize("dv,dr", [(2, 2), (4, 2)])
def test_sharded_2d_mesh_packed_matches_wide(dv, dr):
    """2-D (validators, rounds) mesh with validator counts that do NOT
    divide into whole lanes per shard: the witness axis is padded to a
    multiple of LANE * dv so every shard owns whole words, and the psum
    of per-shard popcount tallies must equal the wide psum bit-exactly."""
    from jax.sharding import Mesh
    from babble_tpu.tpu.sharded import (
        sharded_frontier_passes, sharded_run_passes,
    )

    devices = jax.devices("cpu")
    if len(devices) < dv * dr:
        pytest.skip(f"need {dv * dr} CPU devices, have {len(devices)}")
    mesh = Mesh(
        np.array(devices[: dv * dr]).reshape(dv, dr),
        ("validators", "rounds"),
    )
    for n, e, seed in ((7, 160, 9), (33, 320, 4)):
        grid = synthetic_grid(n, e, seed=seed)
        assert_results_equal(
            sharded_run_passes(mesh, grid, packed=False),
            sharded_run_passes(mesh, grid, packed=True),
        )
        assert_results_equal(
            sharded_frontier_passes(mesh, grid, packed=False),
            sharded_frontier_passes(mesh, grid, packed=True),
        )


def test_incremental_step_and_multi_step_packed_match_wide():
    from babble_tpu.tpu.incremental import (
        BatchLayout, batches_from_grid, init_state, multi_step, step,
    )

    n, e = 7, 512
    grid = synthetic_grid(n, e, seed=3, zipf_a=1.1, record_fd_updates=True)
    sm = grid.super_majority
    batches = batches_from_grid(grid, 32, 8192, e)
    layout = BatchLayout(32, n, 8192)
    assert len(batches) >= 16

    def per_batch(st, packed):
        for b in batches:
            st = step(st, b, layout, sm, n, e_win=512, packed=packed)
        return st

    def per_train(st, packed):
        # whole K=16 trains; what is left of the stream goes batch by batch
        whole = len(batches) - len(batches) % 16
        for i in range(0, whole, 16):
            st = multi_step(st, np.stack(batches[i : i + 16]), layout, sm,
                            n, e_win=512, packed=packed)
        for b in batches[whole:]:
            st = step(st, b, layout, sm, n, e_win=512, packed=packed)
        return st

    for run in (per_batch, per_train):
        wide, packed = (run(init_state(n, e, 64), p) for p in (False, True))
        for f in ("rounds", "lamport", "witness", "received", "wtable",
                  "fame_decided", "famous", "rounds_decided"):
            np.testing.assert_array_equal(
                np.asarray(getattr(wide, f)), np.asarray(getattr(packed, f)),
                f"{run.__name__}: {f}",
            )
        assert int(packed.last_round) == int(wide.last_round)


# ---------------------------------------------------------------------------
# table-bytes accounting
# ---------------------------------------------------------------------------


def test_table_bytes_reduction_and_gauge():
    # lane-aligned N: exactly 8x (uint32 words of 32 lanes vs 32 bools);
    # the ISSUE 17 acceptance floor is 4x at N >= 128
    for n in (128, 1024):
        wide = voting_table_bytes(n, 16, False)
        packed = voting_table_bytes(n, 16, True)
        assert set(wide) == {"strongly_seen", "votes"}
        for t in wide:
            assert wide[t] == 16 * n * n
            assert packed[t] == 16 * n * 4 * packed_words(n)
            assert wide[t] / packed[t] >= 4.0
    obs = Observability()
    sizes = observe_table_bytes(obs, 128, 16, True)
    g = obs.registry.get("babble_device_table_bytes")
    assert g is not None
    for t, nbytes in sizes.items():
        assert g.value(table=t, layout="packed") == nbytes
    observe_table_bytes(obs, 128, 16, False)
    assert (
        g.value(table="votes", layout="wide")
        == 8.0 * g.value(table="votes", layout="packed")
    )


# ---------------------------------------------------------------------------
# seeded single-bit flip: the PR 11 bisector owns packed-vs-wide divergence
# ---------------------------------------------------------------------------


def test_seeded_bit_flip_localizes_to_exact_cell(tmp_path):
    """Flip exactly one decided famous bit in the PACKED arm: the
    divergence bisector must localize packed-vs-wide to that exact
    (pass, table, round, witness) cell — the triage path a real packed
    tally defect would take."""
    from babble_tpu.obs.provenance import grid_cell_keys

    grid = synthetic_grid(7, 160, seed=9)
    wide = run_passes(grid, packed=False)
    packed = run_passes(grid, packed=True)

    # clean arm: byte-equal, nothing to localize, no artifact
    loc, path = bisect_pass_results(
        grid, "wide", wide, "packed", packed,
        artifact_dir=str(tmp_path), label="packed-clean",
    )
    assert loc is None and path is None and not os.listdir(tmp_path)

    candidates = [
        (ti, c, int(packed.witness_table[ti, c]))
        for ti in range(packed.witness_table.shape[0])
        for c in range(packed.witness_table.shape[1])
        if int(packed.witness_table[ti, c]) >= 0
        and bool(packed.fame_decided[ti, c])
    ]
    assert candidates, "fixture decided no fame at all"
    ti, c, wrow = candidates[random.Random(17).randrange(len(candidates))]
    famous = np.array(packed.famous, copy=True)
    famous[ti, c] = not bool(famous[ti, c])
    broken = replace(packed, famous=famous)
    inj_round = ti + int(getattr(packed, "round_offset", 0))
    inj_hash = grid_cell_keys(grid)[wrow]

    loc, path = bisect_pass_results(
        grid, "wide", wide, "packed", broken,
        artifact_dir=str(tmp_path), label="packed-flip",
    )
    assert (loc["round"], loc["pass"], loc["table"], loc["cell"]) == (
        inj_round, "fame", "fame", inj_hash,
    )
    assert os.path.basename(path) == "bisect-packed-flip-wide-vs-packed.json"
