"""The controls of `v128-byz.sync500` (run by hand, like the rest of
benchmark/tests; `test_adversarial_controls.py` holds `v64-byz.sync500`'s
and the helpers):

- the 128-wide DAG is what the configuration's `assumed` says it is: the
  share of rows that arrive late, how late, the cells an inserted event
  writes (the generator and numpy alone, over the rows a window can reach);
- the head of it through the program at the real width, on XLA:CPU: the
  syncs in which a decided round is re-opened are the ones the choice of
  `topology_seed` counted (`main` goes on to the whole count);
- the plain reference with the supermajority lowered by one, in the
  program's place, is not correct on it (`main` prints the reading at the
  cell's own size);
- a whole `--tiny` run of the cell is correct with nothing broken, and not
  correct with the re-opening of a late witness's round taken out.

    python3 -m pytest benchmark/tests/test_adversarial_controls_v128.py -q -p no:cacheprovider
    python3 benchmark/tests/test_adversarial_controls_v128.py control <events handed over> <seed> [<seed> ...]
    python3 benchmark/tests/test_adversarial_controls_v128.py reopen <events handed over>
"""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from benchmark import run as harness  # noqa: E402
from benchmark import traffic as gen  # noqa: E402
from benchmark import traffic_adversarial as adversarial  # noqa: E402
from benchmark.entries import replay_adversarial  # noqa: E402
from benchmark.reference import hashgraph as reference  # noqa: E402
from benchmark.tests import test_adversarial_controls as v64  # noqa: E402
from benchmark.tests.test_reference_control import control_mismatches  # noqa: E402

CELL = "v128-byz.sync500"
ARGS = ["--workload", CELL, "--seed", "2147483659", "--seconds", "4",
        "--trace", "0", "--tiny"]
ROWS = 110_000  # what the counts under `assumed` were taken over
# 500-event syncs of the stream's head (after the traffic file's lead-in of
# 2,048 + 1,500 events) that re-open a decided round, by their first row
REOPEN_HEAD = [13548, 14048, 15548]


@pytest.fixture(autouse=True)
def this_cell(monkeypatch):
    """`v64-byz.sync500`'s helpers and whole-run controls read their cell
    from their module: point them at this one."""
    monkeypatch.setattr(v64, "CELL", CELL)
    monkeypatch.setattr(v64, "ARGS", ARGS)


def test_the_dag_is_what_the_configuration_assumes():
    cfg = v64.cell_config()
    assert (cfg["validators"], cfg["byzantine"], cfg["max_hidden"]) == (128, 42, 16)
    assert cfg["byzantine"] == cfg["validators"] // 3
    assert cfg["max_hidden"] == cfg["validators"] // 8
    drawn = adversarial.from_config({**cfg, "events": ROWS}, cfg["topology_seed"])
    _, row_there = adversarial.creation_order(drawn)
    late_by = np.arange(ROWS) - row_there
    late = drawn.late
    # 11.6% of the rows were made while their creator withheld; the latest
    # arrives 17,638 rows after its place in creation order, half of them
    # over 4,300 rows late
    assert 0.11 < late.mean() < 0.125
    assert int(late_by[late].max()) == 17638
    assert 4000 < float(np.median(late_by[late])) < 4700
    # an honest row is never late: what is still hidden would have come first
    assert (late_by[~late] <= 0).all()
    # first-descendant cells an inserted event writes (every cell of the
    # final table but an event's own was written by a later insert): 124 an
    # event, twice v64-byz's 62
    d = drawn.dag
    la = reference.last_ancestors(
        d.n, d.creator, d.index, d.self_parent, d.other_parent)
    fd = reference.first_descendants(d.n, d.creator, d.index, la)
    assert 123 < ((fd != reference.NONE).sum() - d.e) / d.e < 125.5


def reopen_syncs(cfg: dict, events: int, seed: int = 7) -> list:
    """First rows of the 500-event syncs during which the program's
    `fame.reopen` total rose: the stream's first `events` rows through an
    observer Core on the live rung, synchronous fetch, lead-in and batch
    rows as the cell's."""
    stream = v64.withheld_stream(cfg, events, seed)
    core = stream.core("tpu", int(cfg["cache_size"]), **cfg["core"])
    note = replay_adversarial.ServedAndReopened(core)
    mix = harness.load("benchmark/traffic/sync500.json")
    spans, lo = [], 0
    for phase in mix["lead_in"]:
        spans += [(lo + a, lo + b) for a, b in
                  gen.syncs(int(phase["events"]), int(phase["sync_events"]))]
        lo += int(phase["events"])
    spans += [(lo + a, lo + b) for a, b in
              gen.syncs(events - lo, int(mix["sync_events"]))]
    for a, b in spans:
        for ev in stream.handed[a:b]:
            core.insert_event(ev, True)
        core.run_consensus()
        note.note()
    assert note.unserved == 0
    return [a for (a, b), (_, _, rounds) in zip(spans, note.log)
            if rounds > 0 and b - a == int(mix["sync_events"])]


def test_the_head_of_the_stream_reopens_where_it_was_counted(monkeypatch):
    from babble_tpu.tpu import live

    monkeypatch.setitem(live.ENGINE_DEFAULTS, "async_fetch", False)
    assert reopen_syncs(v64.cell_config(), 16048) == REOPEN_HEAD


@pytest.mark.parametrize("seed", [1, 2_147_483_659])
def test_control_is_not_correct_on_the_withheld_stream(seed):
    cfg = {**v64.cell_config(), "validators": 32, "byzantine": 10, "max_hidden": 4,
           "withhold_span": "16-64", "topology_seed": None}
    diff = control_mismatches(v64.withheld_stream(cfg, 8000, seed), 8000)
    assert diff["events_mismatched"] > 0
    assert diff["blocks_mismatched"] > 0


def test_sound_run_is_correct(capsys):
    v64.test_sound_run_is_correct(capsys)


def test_without_the_reopening_it_is_not_correct(monkeypatch, capsys):
    v64.test_without_the_reopening_it_is_not_correct(monkeypatch, capsys)


def main(argv) -> int:
    v64.CELL = CELL  # a script's one cell; the tests patch it per test
    cfg = v64.cell_config()
    if argv[0] == "reopen":
        from babble_tpu.tpu import live

        live.ENGINE_DEFAULTS["async_fetch"] = False
        rows = reopen_syncs(cfg, int(argv[1]))
        print(json.dumps({"cell": CELL, "events": int(argv[1]),
                          "reopen_syncs": len(rows), "first_rows": rows}))
        return 0
    consumed, seeds = int(argv[1]), [int(s) for s in argv[2:]]
    for seed in seeds:
        diff = control_mismatches(
            v64.withheld_stream(cfg, consumed, seed), consumed)
        print(json.dumps({"cell": CELL, "seed": seed, "events": consumed,
                          "control": diff}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
