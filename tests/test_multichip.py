"""Multi-device CPU differential tests: the sharded SPMD pipeline
(babble_tpu/tpu/sharded.py) must produce exactly the single-device
pipeline's outputs on every topology (conftest pins JAX to a virtual
8-device CPU platform)."""

import numpy as np
import pytest

import jax
from jax.sharding import Mesh

from babble_tpu.tpu import grid_from_hashgraph, run_passes, synthetic_grid
from babble_tpu.tpu.sharded import sharded_run_passes

from dsl import init_consensus_hashgraph, init_simple_hashgraph


def make_mesh(n_devices):
    devices = jax.devices("cpu")
    if len(devices) < n_devices:
        pytest.skip(f"need {n_devices} CPU devices, have {len(devices)}")
    return Mesh(np.array(devices[:n_devices]), ("rounds",))


def assert_sharded_matches(grid, n_devices):
    mesh = make_mesh(n_devices)
    sharded = sharded_run_passes(mesh, grid)
    single = run_passes(grid)

    np.testing.assert_array_equal(sharded.rounds, single.rounds)
    np.testing.assert_array_equal(sharded.witness, single.witness)
    np.testing.assert_array_equal(sharded.lamport, single.lamport)
    np.testing.assert_array_equal(sharded.fame_decided, single.fame_decided)
    np.testing.assert_array_equal(
        sharded.famous & sharded.fame_decided,
        single.famous & single.fame_decided,
    )
    np.testing.assert_array_equal(sharded.rounds_decided, single.rounds_decided)
    np.testing.assert_array_equal(sharded.received, single.received)
    assert sharded.last_round == single.last_round


@pytest.mark.parametrize("n_devices", [2, 8])
def test_synthetic_sharded_differential(n_devices):
    grid = synthetic_grid(8, 192, seed=11)
    assert_sharded_matches(grid, n_devices)


def test_zipf_sharded_differential():
    grid = synthetic_grid(16, 384, seed=23, zipf_a=1.1)
    assert_sharded_matches(grid, 8)


def test_fixture_sharded_differential():
    """Named consensus fixture through the sharded pipeline."""
    hg, _, _ = init_consensus_hashgraph()
    grid = grid_from_hashgraph(hg)
    assert_sharded_matches(grid, 4)


def test_simple_fixture_sharded_differential():
    hg, _, _ = init_simple_hashgraph()
    grid = grid_from_hashgraph(hg)
    assert_sharded_matches(grid, 2)


# -- chains-sharded frontier pipeline (the flagship kernel) ------------------


def assert_frontier_sharded_matches(grid, n_devices, r_cap=None):
    from babble_tpu.tpu.engine import run_frontier_passes
    from babble_tpu.tpu.sharded import sharded_frontier_passes

    mesh = make_mesh(n_devices)
    sharded = sharded_frontier_passes(mesh, grid, r_cap=r_cap)
    single = run_frontier_passes(grid)

    np.testing.assert_array_equal(sharded.rounds, single.rounds)
    np.testing.assert_array_equal(sharded.witness, single.witness)
    np.testing.assert_array_equal(sharded.lamport, single.lamport)
    np.testing.assert_array_equal(sharded.received, single.received)
    assert sharded.last_round == single.last_round
    # fame tables may differ in round-axis length (adaptive single-device
    # bucketing); their real content must agree on the overlap
    r = min(sharded.fame_decided.shape[0], single.fame_decided.shape[0])
    np.testing.assert_array_equal(sharded.fame_decided[:r], single.fame_decided[:r])
    np.testing.assert_array_equal(
        (sharded.famous & sharded.fame_decided)[:r],
        (single.famous & single.fame_decided)[:r],
    )


@pytest.mark.parametrize("n_devices", [2, 8])
def test_frontier_sharded_differential(n_devices):
    grid = synthetic_grid(8, 192, seed=11)
    assert_frontier_sharded_matches(grid, n_devices)


def test_frontier_sharded_zipf():
    grid = synthetic_grid(16, 384, seed=23, zipf_a=1.1)
    assert_frontier_sharded_matches(grid, 8)


def test_frontier_sharded_chain_padding():
    """Validator count not divisible by the mesh: chain axis padded."""
    grid = synthetic_grid(12, 300, seed=7)
    assert_frontier_sharded_matches(grid, 8)


def test_frontier_sharded_fixture():
    hg, _, _ = init_consensus_hashgraph()
    grid = grid_from_hashgraph(hg)
    assert_frontier_sharded_matches(grid, 4)


def test_frontier_sharded_n256():
    """BASELINE config #4 scale on the CPU mesh: 256 validators, Zipf
    fan-out, chains-sharded INV (32 chains per device)."""
    grid = synthetic_grid(256, 1024, seed=41, zipf_a=1.05)
    assert_frontier_sharded_matches(grid, 8, r_cap=16)


def test_dryrun_multichip_entrypoint():
    """The driver's dryrun must pass end-to-end on the CPU mesh."""
    import __graft_entry__

    __graft_entry__.dryrun_multichip(8)


def test_mesh_backend_node_in_cluster_byte_identical():
    """VERDICT r3 #3: the sharded pipeline as a PRODUCT capability — a
    full Node configured with consensus_backend=tpu + mesh_devices=8
    participates in a live cluster over the in-memory transport and
    commits byte-identical blocks (check_gossip), with every consensus
    call routed through the mesh (no silent CPU fallback)."""
    from test_device_backend import build_mixed_cluster
    from test_node import (
        bombard_and_wait, check_gossip, run_nodes, shutdown_nodes,
    )

    nodes, proxies, *_ = build_mixed_cluster(
        ["cpu", "cpu", "cpu", "tpu"], sync_limit=2000, mesh_devices={3: 8},
    )
    try:
        run_nodes(nodes)
        bombard_and_wait(nodes, proxies, target_block=3, timeout_s=300)
        check_gossip(nodes, upto=3)
        assert nodes[3].core.device_consensus_runs > 0, (
            "mesh node never ran the sharded backend"
        )
        assert nodes[3].core.device_consensus_fallbacks == 0, (
            "mesh node silently fell back to the CPU engine"
        )
        assert nodes[3].core._mesh is not None
    finally:
        shutdown_nodes(nodes)


# -- driver-environment simulation (subprocess; conftest pins must NOT leak) --

import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_driver_like_subprocess(code, extra_env=None):
    """Run `code` in a subprocess whose environment mimics the driver:
    jax importable, JAX_PLATFORMS and XLA_FLAGS UNSET (conftest's pins
    scrubbed), jax pre-imported before __graft_entry__ — the setup under
    which an early multichip dry run died (module-level default-backend
    touch + env-var-only pin arriving too late)."""
    env = {
        k: v
        for k, v in os.environ.items()
        if k not in ("JAX_PLATFORMS", "XLA_FLAGS", "JAX_PLATFORM_NAME")
    }
    if extra_env:
        env.update(extra_env)
    return subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True,
        text=True,
        env=env,
        cwd=REPO,
        timeout=600,
    )


def test_tpu_import_initializes_no_backend():
    """Importing the kernel/engine modules must not create any JAX array —
    a module-level array constant initializes the process's DEFAULT backend
    at import time (the round-2 multichip killer: a dead `NEG` constant in
    kernels.py landed on the real TPU and died on a libtpu mismatch in the
    driver env). Regression-pinned by asserting the backend registry stays
    empty across import."""
    proc = run_driver_like_subprocess(
        """
        import jax  # a caller that imported jax first
        from jax._src import xla_bridge
        assert not xla_bridge.backends_are_initialized(), "pre-import dirty"
        import babble_tpu.tpu  # pulls grid, engine, kernels
        import babble_tpu.tpu.sharded
        import babble_tpu.tpu.frontier
        import babble_tpu.tpu.incremental
        import babble_tpu.tpu.live
        import babble_tpu.tpu.dispatch
        assert not xla_bridge.backends_are_initialized(), (
            "importing babble_tpu.tpu initialized a JAX backend"
        )
        print("IMPORT_PURE")
        """
    )
    assert proc.returncode == 0, proc.stderr
    assert "IMPORT_PURE" in proc.stdout


def test_dryrun_multichip_driver_env():
    """dryrun_multichip(8) must succeed when jax is pre-imported and
    JAX_PLATFORMS is unset — the entry point's own jax.config.update pin
    must do the work (env vars alone are too late once jax is imported,
    per conftest.py's note)."""
    proc = run_driver_like_subprocess(
        """
        import jax  # pre-import BEFORE __graft_entry__, like the driver
        import __graft_entry__
        __graft_entry__.dryrun_multichip(8)
        """
    )
    assert proc.returncode == 0, proc.stderr
    assert "dryrun_multichip OK" in proc.stdout


# -- 2-D (validators, rounds) mesh (ISSUE 9) ---------------------------------


def make_mesh2(dv, dr):
    devices = jax.devices("cpu")
    if len(devices) < dv * dr:
        pytest.skip(f"need {dv * dr} CPU devices, have {len(devices)}")
    return Mesh(
        np.array(devices[: dv * dr]).reshape(dv, dr), ("validators", "rounds")
    )


def assert_2d_matches(grid, dv=2, dr=2):
    """Every sharded pipeline on the 2-D mesh must be byte-equal to the
    single-device oracle — the validator-axis partition of the voting
    state (per-shard local tallies + one psum per fame step) is an
    implementation layout, never an observable."""
    from babble_tpu.tpu.engine import run_frontier_passes
    from babble_tpu.tpu.sharded import (
        mesh_validator_shards, sharded_frontier_passes, sharded_run_passes,
    )

    mesh = make_mesh2(dv, dr)
    assert mesh_validator_shards(mesh) == dv

    single = run_passes(grid)
    sharded = sharded_run_passes(mesh, grid)
    np.testing.assert_array_equal(sharded.rounds, single.rounds)
    np.testing.assert_array_equal(sharded.witness, single.witness)
    np.testing.assert_array_equal(sharded.lamport, single.lamport)
    np.testing.assert_array_equal(sharded.fame_decided, single.fame_decided)
    np.testing.assert_array_equal(
        sharded.famous & sharded.fame_decided,
        single.famous & single.fame_decided,
    )
    np.testing.assert_array_equal(sharded.rounds_decided, single.rounds_decided)
    np.testing.assert_array_equal(sharded.received, single.received)
    assert sharded.last_round == single.last_round

    single_f = run_frontier_passes(grid)
    sf = sharded_frontier_passes(mesh, grid)
    np.testing.assert_array_equal(sf.rounds, single_f.rounds)
    np.testing.assert_array_equal(sf.received, single_f.received)
    assert sf.last_round == single_f.last_round
    r = min(sf.fame_decided.shape[0], single_f.fame_decided.shape[0])
    np.testing.assert_array_equal(sf.fame_decided[:r], single_f.fame_decided[:r])
    np.testing.assert_array_equal(
        (sf.famous & sf.fame_decided)[:r],
        (single_f.famous & single_f.fame_decided)[:r],
    )


def test_2d_mesh_synthetic_differential():
    assert_2d_matches(synthetic_grid(8, 192, seed=11))


def test_2d_mesh_witness_padding():
    """Validator count not divisible by the validator shards: the
    witness axes pad to a multiple of dv (padded strongly-seen columns
    are False so padded vote rows tally zero)."""
    assert_2d_matches(synthetic_grid(7, 128, seed=9))


def test_2d_mesh_fixture_differential():
    hg, _, _ = init_consensus_hashgraph()
    assert_2d_matches(grid_from_hashgraph(hg))


def test_2d_mesh_post_reset_section():
    """Acceptance: 2-D outputs byte-equal on post-reset sections too."""
    from babble_tpu.tpu.grid import section_grid

    grid = synthetic_grid(8, 192, seed=11)
    res = run_passes(grid)
    sec = section_grid(grid, res, cut=4)
    assert_2d_matches(sec)


def test_2d_mesh_doubling_cold_path():
    """The sharded pointer-doubling pipeline (the round-batched rung's
    cold path) on the 2-D mesh, vs the frontier oracle."""
    from babble_tpu.tpu.engine import run_frontier_passes
    from babble_tpu.tpu.sharded import sharded_doubling_passes

    grid = synthetic_grid(8, 192, seed=11)
    mesh = make_mesh2(2, 2)
    sd = sharded_doubling_passes(mesh, grid)
    single = run_frontier_passes(grid)
    np.testing.assert_array_equal(sd.rounds, single.rounds)
    np.testing.assert_array_equal(sd.received, single.received)
    assert sd.last_round == single.last_round


# -- delta staging (GridStager, ISSUE 9) -------------------------------------


def test_grid_stager_incremental_matches_full_restage():
    """Replay the consensus fixture's event stream into a fresh
    hashgraph a few events at a time; after every chunk the persistent
    stager's grid must be byte-equal to a from-scratch
    grid_from_hashgraph on every column — delta staging is a pure
    restage eliminator, never an observable."""
    from babble_tpu.hashgraph import Hashgraph, InmemStore
    from babble_tpu.tpu.grid import GridStager

    from dsl import CACHE_SIZE

    src, _, ordered = init_consensus_hashgraph()
    hg = Hashgraph(
        src.participants, InmemStore(src.participants, CACHE_SIZE)
    )
    stager = GridStager(hg)
    CHUNK = 3
    for lo in range(0, len(ordered), CHUNK):
        for ev in ordered[lo : lo + CHUNK]:
            hg.insert_event(ev, True)
        got = stager.stage()
        want = grid_from_hashgraph(hg)
        assert got.e == want.e
        assert got.num_levels == want.num_levels
        for col in (
            "creator", "index", "self_parent", "other_parent",
            "last_ancestors", "first_descendants",
            "ext_sp_round", "ext_op_round", "fixed_round",
            "ext_sp_lamport", "ext_op_lamport", "fixed_lamport",
            "coin_bit",
        ):
            np.testing.assert_array_equal(
                getattr(got, col)[: got.e], getattr(want, col)[: want.e],
                err_msg=f"stager column {col} diverged at e={got.e}",
            )
        for lv in range(want.num_levels):
            np.testing.assert_array_equal(
                np.sort(got.levels[lv][got.levels[lv] >= 0]),
                np.sort(want.levels[lv][want.levels[lv] >= 0]),
                err_msg=f"stager level {lv} diverged at e={got.e}",
            )
        assert list(got.hashes) == list(want.hashes)
    assert stager.full_restages == 1, "delta path never took over"
    assert stager.delta_stages > 0
    last_chunk = len(ordered) - ((len(ordered) - 1) // CHUNK) * CHUNK
    assert stager.last_delta_rows == last_chunk


def test_grid_stager_snapshots_are_immutable():
    """A staged snapshot handed to an in-flight dispatch must not change
    under later inserts (first_descendants and levels mutate in the
    stager's resident buffers — snapshots copy them)."""
    from babble_tpu.hashgraph import Hashgraph, InmemStore
    from babble_tpu.tpu.grid import GridStager

    from dsl import CACHE_SIZE

    src, _, ordered = init_consensus_hashgraph()
    hg = Hashgraph(
        src.participants, InmemStore(src.participants, CACHE_SIZE)
    )
    stager = GridStager(hg)
    half = len(ordered) // 2
    for ev in ordered[:half]:
        hg.insert_event(ev, True)
    snap = stager.stage()
    fd_before = snap.first_descendants.copy()
    levels_before = snap.levels.copy()
    for ev in ordered[half:]:
        hg.insert_event(ev, True)
    stager.stage()
    np.testing.assert_array_equal(snap.first_descendants, fd_before)
    np.testing.assert_array_equal(snap.levels, levels_before)


def test_use_doubling_prefer_lowers_crossover():
    """The round-batched rung prefers the doubling cold path well below
    the per-sync crossover: one dispatch per batch amortizes the train."""
    from babble_tpu.tpu.doubling import use_doubling

    grid = synthetic_grid(8, 512, seed=3)
    assert grid.num_levels >= 64, "fixture too shallow for the assertion"
    assert not use_doubling(grid)
    assert use_doubling(grid, prefer=True)
