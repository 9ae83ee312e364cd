"""The controls of the withheld-stream cell (run by hand, like the rest of
benchmark/tests):

- the plain reference with the supermajority lowered by one, in the
  program's place, comes out as not correct on the withheld stream too
  (at a size a test can hold; `main` below prints the same reading at the
  cell's own size);
- a whole `--tiny` run of the cell is correct with nothing broken, and not
  correct with the re-opening of a late witness's round taken out of the
  device programs: the round then keeps a witness without fame, the
  queued round never decides and the commits behind it stall.

    python3 -m pytest benchmark/tests/test_adversarial_controls.py -q -p no:cacheprovider
    python3 benchmark/tests/test_adversarial_controls.py <events handed over> <seed> [<seed> ...]
"""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from benchmark import run as harness  # noqa: E402
from benchmark.entries import replay, replay_adversarial  # noqa: E402
from benchmark.tests.test_reference_control import control_mismatches  # noqa: E402

CELL = "v64-byz.sync500"
ARGS = ["--workload", CELL, "--seed", "2147483659", "--seconds", "4",
        "--trace", "0", "--tiny"]


def withheld_stream(cfg: dict, events: int, seed: int):
    """The configuration's stream, cut at `events` rows, signed from `seed`."""
    kept = replay.gen
    replay.gen = replay_adversarial.WithheldTraffic(cfg)
    try:
        return replay.Stream(int(cfg["validators"]), events, seed,
                             float(cfg["zipf_a"]), 1, cfg.get("topology_seed"))
    finally:
        replay.gen = kept


def cell_config() -> dict:
    manifest = harness.load("BENCHMARK.json")
    cell = harness.named(manifest["workloads"], CELL, "workload")
    return harness.load(
        harness.named(manifest["configs"], cell["config"], "config")["file"])


@pytest.mark.parametrize("seed", [1, 2_147_483_659])
def test_control_is_not_correct_on_the_withheld_stream(seed):
    cfg = {**cell_config(), "validators": 16, "byzantine": 5, "max_hidden": 2,
           "withhold_span": "12-48", "topology_seed": None}
    diff = control_mismatches(withheld_stream(cfg, 6000, seed), 6000)
    assert diff["events_mismatched"] > 0
    assert diff["blocks_mismatched"] > 0


def result_of(capsys):
    assert harness.main(ARGS) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_sound_run_is_correct(capsys):
    line = result_of(capsys)
    assert line["correct"] is True and line["failed"] == 0, line["compared"]
    assert line["counters"]["reopen_syncs"] > 0
    assert (line["counters"]["reopen_syncs_served"]
            == line["counters"]["reopen_syncs"])


def test_without_the_reopening_it_is_not_correct(monkeypatch, capsys):
    """The device programs leave a late witness's round decided (what they
    would do with the latch merely taken away)."""
    from babble_tpu.tpu import incremental

    monkeypatch.setattr(
        incremental, "_reopen_rounds",
        lambda state, *_: (state.rounds_decided, state.reopened))
    # other programs than the sound run's: keep them out of its cache's way
    incremental.step.clear_cache()
    incremental.multi_step.clear_cache()
    try:
        line = result_of(capsys)
    finally:
        incremental.step.clear_cache()
        incremental.multi_step.clear_cache()
    assert line["correct"] is False, line["compared"]
    assert any(c["value"] > c["limit"] for c in line["compared"].values())


def main(argv) -> int:
    consumed, seeds = int(argv[0]), [int(s) for s in argv[1:]]
    cfg = cell_config()
    for seed in seeds:
        diff = control_mismatches(withheld_stream(cfg, consumed, seed), consumed)
        print(json.dumps({"cell": CELL, "seed": seed, "events": consumed,
                          "control": diff}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
