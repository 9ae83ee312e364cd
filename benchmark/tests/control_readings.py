"""The control's readings at a cell's own size, for setting the limits
(run by hand, on the machine with the chip so that it is the cell's own
environment; it needs no device):

    python3 benchmark/tests/control_readings.py <cell> <events handed over> <seed> [<seed> ...]

For each seed: the cell's stream, the plain reference over its first
`events handed over` events, and the control in the program's place: the
same reference with the supermajority lowered by one, the nearest weaker
guarantee. Prints the control's mismatches against the reference, which
have to pass the limit (0) for the control to read as not correct.
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run as harness  # noqa: E402
from benchmark.entries import replay  # noqa: E402
from benchmark.tests.test_reference_control import control_mismatches  # noqa: E402


def main(argv) -> int:
    cell_name, consumed, seeds = argv[0], int(argv[1]), [int(s) for s in argv[2:]]
    manifest = harness.load("BENCHMARK.json")
    cell = harness.named(manifest["workloads"], cell_name, "workload")
    cfg = harness.load(harness.named(manifest["configs"], cell["config"], "config")["file"])
    for seed in seeds:
        stream = replay.Stream(int(cfg["validators"]), consumed, seed,
                               float(cfg["zipf_a"]), 1,
                               cfg.get("topology_seed"))
        diff = control_mismatches(stream, consumed)
        print(json.dumps({"cell": cell_name, "seed": seed, "events": consumed,
                          "control": diff}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
