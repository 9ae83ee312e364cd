"""The controls of the served cell (run by hand, like the rest of
benchmark/tests; each is a whole `--tiny` run of `demo4.served-open`: five
processes, node 0 on whatever platform JAX has):

- with nothing broken the run is correct;
- the plain reference with the supermajority lowered by one, in the
  reference's own place, no longer gives what node 0 stamped and committed;
- one block body of one other validator altered: `blocks_diverged`;
- one acknowledged transaction withheld from the blocks in the client's
  record: `acked_tx_lost`;
- node 0 left on the CPU rung: every consensus call is unserved.

    python3 -m pytest benchmark/tests/test_served_controls.py -q -p no:cacheprovider
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from benchmark import run as harness  # noqa: E402
from benchmark.entries import served  # noqa: E402
from benchmark.reference import hashgraph as reference  # noqa: E402

CELL = "demo4.served-open"
ARGS = ["--workload", CELL, "--seed", "2147483659", "--seconds", "3",
        "--trace", "0", "--tiny"]


def result_of(capsys) -> dict:
    assert harness.main(ARGS) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def over_limit(line: dict) -> set:
    return {name for name, c in line["compared"].items()
            if c["value"] > c["limit"]}


def test_sound_run_is_correct(capsys):
    line = result_of(capsys)
    assert line["correct"] is True and line["failed"] == 0, line["compared"]
    c = line["counters"]
    assert c["syncs"] > 0 and c["syncs_served"] == c["syncs"]
    assert c["tx_acked"] == c["tx_offered"] > 0
    assert c["blocks_compared"] > 0


def test_reference_with_a_lower_supermajority_disagrees(monkeypatch, capsys):
    order = reference.order

    def lowered(n, *rest):
        return order(n, *rest, super_majority=2 * n // 3)

    monkeypatch.setattr(reference, "order", lowered)
    line = result_of(capsys)
    assert line["correct"] is False
    assert {"events_mismatched", "blocks_mismatched"} <= over_limit(line)


def test_an_altered_peer_block_diverges(monkeypatch, capsys):
    read = served.peer_chain

    def altered(service, upto):
        chain = read(service, upto)
        block = next(b for b in chain if b.transactions())
        block.body.transactions[0] = b"altered"
        return chain

    monkeypatch.setattr(served, "peer_chain", altered)
    line = result_of(capsys)
    assert line["correct"] is False
    assert "blocks_diverged" in over_limit(line)


def test_a_withheld_acknowledged_transaction_is_lost(monkeypatch, capsys):
    read = served.client_record

    def withheld(path):
        record = read(path)
        acked = {tx for tx, _, verdict in record["submitted"]
                 if verdict in served.ACKED}
        block = next(b for b in record["blocks"] if acked & set(b[2]))
        block[2].remove(next(tx for tx in block[2] if tx in acked))
        return record

    monkeypatch.setattr(served, "client_record", withheld)
    line = result_of(capsys)
    assert line["correct"] is False and line["failed"] >= 1
    assert "acked_tx_lost" in over_limit(line)


def test_node0_on_the_cpu_rung_is_unserved(monkeypatch, capsys):
    monkeypatch.setattr(served, "BACKEND", "cpu")
    line = result_of(capsys)
    assert line["correct"] is False
    assert "unserved_syncs" in over_limit(line)
    assert line["counters"]["syncs_served"] == 0
