"""Differential oracle for the log-diameter cold path (tpu/doubling.py):
pointer-doubling closure + contracted frontier walk must match the
level-scan kernel bit-exactly — rounds, witness flags, lamports, fame and
round-received — on every DAG it accepts: the frontier test fixtures,
deep Zipf-skewed grids, and post-reset section grids (where the frontier
walk itself refuses). Device pass counts are asserted logarithmic in
depth; the CPU hashgraph stays the engine-selection oracle via the
forced-crossover integration test."""

import math
import os

import numpy as np
import pytest

from babble_tpu.tpu import synthetic_grid
from babble_tpu.tpu.doubling import (
    doubling_crossover,
    run_doubling_passes,
    use_doubling,
)
from babble_tpu.tpu.engine import run_frontier_passes, run_passes
from babble_tpu.tpu.grid import (
    GridUnsupported,
    section_grid,
    synthetic_deep_grid,
)


def assert_matches(res, ref, what=""):
    for f in ("rounds", "witness", "lamport", "received"):
        np.testing.assert_array_equal(
            np.asarray(getattr(res, f)), np.asarray(getattr(ref, f)),
            err_msg=f"{what}: {f}",
        )
    assert int(res.last_round) == int(ref.last_round), what
    # the (R, N) tables are indexed by round - round_offset (PassResults
    # contract; the doubling path rebases, the plain scan does not):
    # align both on the absolute round axis before comparing
    oa, ob = int(res.round_offset), int(ref.round_offset)
    lo = max(oa, ob)
    for f in ("fame_decided", "famous", "rounds_decided"):
        va = np.asarray(getattr(res, f))
        vb = np.asarray(getattr(ref, f))
        hi = min(oa + va.shape[0], ob + vb.shape[0])
        np.testing.assert_array_equal(
            va[lo - oa:hi - oa], vb[lo - ob:hi - ob], err_msg=f"{what}: {f}"
        )
        assert not va[:lo - oa].any() and not vb[:lo - ob].any(), (
            f"{what}: {f} head"
        )
        assert not va[hi - oa:].any() and not vb[hi - ob:].any(), (
            f"{what}: {f} tail"
        )


def assert_log_passes(stats, depth):
    cap = 3 * math.log2(max(depth, 2)) + 16
    assert stats["passes"] <= cap, (
        f"{stats['passes']} device passes at depth {depth} breaks the "
        f"log bound ({cap:.0f})"
    )


_slow = pytest.mark.slow


# the frontier suite's exact fixture matrix (tests/test_frontier.py);
# rows that exercise no new shape-bucket or topology class are
# slow-marked to keep tier-1 lean
@pytest.mark.parametrize("n,e,seed,zipf,byz", [
    (4, 64, 1, 0.0, 0.0),
    pytest.param(8, 256, 2, 0.0, 0.0, marks=_slow),
    (8, 512, 3, 1.1, 0.0),
    (16, 1024, 4, 1.1, 0.0),
    pytest.param(8, 300, 7, 2.0, 0.0, marks=_slow),
    pytest.param(32, 768, 9, 1.1, 0.0, marks=_slow),
    (32, 1024, 11, 1.05, 1.0 / 3.0),
    (64, 2048, 13, 1.05, 1.0 / 3.0),
])
def test_doubling_matches_scan(n, e, seed, zipf, byz):
    grid = synthetic_grid(n, e, seed=seed, zipf_a=zipf, byzantine_frac=byz)
    stats = {}
    res = run_doubling_passes(grid, stats=stats)
    ref = run_passes(grid)
    assert_matches(res, ref, f"n={n} e={e} seed={seed}")
    assert_log_passes(stats, grid.num_levels)


@pytest.mark.slow
def test_doubling_matches_frontier_deep():
    grid = synthetic_deep_grid(8, 1024, seed=0, zipf_a=1.2)
    stats = {}
    res = run_doubling_passes(grid, stats=stats)
    assert_matches(res, run_frontier_passes(grid), "deep base 1024")
    assert_log_passes(stats, grid.num_levels)


@pytest.mark.parametrize("cut_frac,pin", [
    (1.0 / 3.0, True),
    pytest.param(1.0 / 2.0, True, marks=pytest.mark.slow),
    (1.0 / 2.0, False),
])
def test_doubling_section_matches_scan(cut_frac, pin):
    """Post-reset / fast-sync frame shapes: the grid's top section with
    the cut's parent metadata externalized. pin=True mirrors a real reset
    (the frame pins boundary rounds); pin=False is the amnesiac variant
    whose chain-first rows are non-witness frontier rows — the sharpest
    exercise of the walk's first_nw witness mask."""
    grid = synthetic_deep_grid(6, 256, seed=2, zipf_a=1.0)
    full = run_passes(grid)
    cut = int(grid.num_levels * cut_frac)
    sec = section_grid(grid, full, cut, pin_cut=pin)
    ref = run_passes(sec)
    stats = {}
    res = run_doubling_passes(sec, stats=stats)
    assert_matches(res, ref, f"section cut={cut} pin={pin}")
    assert_log_passes(stats, sec.num_levels)


def test_doubling_rejects_empty_and_falls_back():
    import dataclasses

    grid = synthetic_grid(4, 16, seed=5)
    empty = dataclasses.replace(grid, e=0)
    with pytest.raises(GridUnsupported):
        run_doubling_passes(empty)
    assert not use_doubling(empty)


def test_crossover_env_override(monkeypatch):
    monkeypatch.setenv("BABBLE_DOUBLING_CROSSOVER", "7")
    assert doubling_crossover(False) == 7
    assert doubling_crossover(True) == 7
    grid = synthetic_deep_grid(8, 64, seed=1, zipf_a=1.2)
    assert use_doubling(grid)
    monkeypatch.delenv("BABBLE_DOUBLING_CROSSOVER")
    assert doubling_crossover(False) >= doubling_crossover(True)


def test_engine_selects_doubling_and_matches_cpu(monkeypatch):
    """End-to-end ladder check against the CPU hashgraph oracle: with the
    crossover forced to 1, run_consensus_device routes every deep-enough
    grid through the doubling kernels, and every stamped round / lamport /
    fame verdict / reception must still match the host engine verbatim."""
    from test_tpu_differential import assert_equivalent, build_hashgraph_from_grid

    monkeypatch.setenv("BABBLE_DOUBLING_CROSSOVER", "1")
    grid = synthetic_grid(4, 96, seed=11, zipf_a=1.1)
    assert use_doubling(grid)
    hg, _ = build_hashgraph_from_grid(grid)
    assert_equivalent(hg)


def test_sharded_doubling_matches():
    from test_multichip import make_mesh

    from babble_tpu.tpu.sharded import sharded_doubling_passes

    mesh = make_mesh(8)
    grid = synthetic_grid(8, 400, seed=1, zipf_a=1.2)
    stats = {}
    res = sharded_doubling_passes(mesh, grid, stats=stats)
    assert_matches(res, run_passes(grid), "sharded base")
    assert stats["passes"] > 0

    deep = synthetic_deep_grid(8, 128, seed=0, zipf_a=1.2)
    full = run_passes(deep)
    sec = section_grid(deep, full, deep.num_levels // 3)
    res = sharded_doubling_passes(mesh, sec)
    assert_matches(res, run_passes(sec), "sharded section")


def test_observe_catchup_emits_record_and_series():
    from babble_tpu.obs import Observability
    from babble_tpu.tpu.doubling import observe_catchup

    obs = Observability()
    observe_catchup(obs, {"depth": 123, "passes": 9}, 0.25)
    snap = obs.registry.snapshot()
    hist = snap["babble_catchup_replay_seconds"]["series"][""]
    assert hist["count"] == 1
    recs = [r for r in obs.flightrec.records() if r.name == "catchup.replay"]
    assert recs
    assert recs[-1].fields["depth"] == 123
    assert recs[-1].fields["passes"] == 9


@pytest.mark.slow
def test_doubling_deep_4096():
    grid = synthetic_deep_grid(8, 4096, seed=0, zipf_a=1.2)
    full = run_frontier_passes(grid)
    stats = {}
    res = run_doubling_passes(grid, stats=stats)
    assert_matches(res, full, "deep base 4096")
    assert_log_passes(stats, grid.num_levels)

    sec = section_grid(grid, full, grid.num_levels // 2)
    ref = run_passes(sec)
    stats = {}
    res = run_doubling_passes(sec, stats=stats)
    assert_matches(res, ref, "deep section 4096")
    assert_log_passes(stats, sec.num_levels)
