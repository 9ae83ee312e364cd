"""The client of entry `served`: one process that is node 0's application.

It holds the app side of the socket proxy (`SocketBabbleProxy`): its server
takes node 0's `State.CommitBlock` calls, its client submits with
`Babble.SubmitTxBatch`. The state behind it is the program's dummy app
(`proxy/dummy.py State`), so node 0's block bodies carry the state hash the
other validators' built-in dummy apps compute.

Load is `ingress/loadgen.py OpenLoopLoadGen.drive_tcp`: Poisson arrivals of
bursts at the rate it is given, never slowed by the answers. Every
transaction is stamped on `time.monotonic()` just before the submit call
that carries it and again when the block that holds it arrives in the commit
handler. The harness runs on the same host, so its window and these stamps
are one clock.

Protocol, on standard input and output, one line each:

    <- ready                 the commit server listens
    -> go                    start offering
    <- window <t0>           the lead-in has ended on a commit, at t0
    <- offered <t1>          offering has stopped, at t1 = t0 + seconds
    <- done                  the drain has ended; the record is in --out
    -> (end of input)        leave

Offering runs in slices of one `drive_tcp` call each (the lead-in, then short
ones until a block arrives, then the window). An exponential gap has no
memory, so arrivals drawn anew at a slice's start are the same Poisson
process. End of input at any time ends the process: a harness that dies takes
its client with it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

SLICE_S = 0.05  # offering goes on in slices this long until the lead-in ends on a commit
ACKED = ("accepted", "queued")


class StampedState:
    """The dummy app's state, with every block stamped on arrival."""

    def __init__(self) -> None:
        from babble_tpu.proxy.dummy import State

        self.state = State()
        self.lock = threading.Lock()
        self.blocks = []  # (index, arrived at, [transactions])
        self.committed = set()  # every transaction handed over so far

    def commit_handler(self, block) -> bytes:
        now = time.monotonic()
        txs = list(block.transactions())
        with self.lock:
            self.blocks.append((block.index(), now, txs))
            self.committed.update(txs)
        return self.state.commit_handler(block)

    def snapshot_handler(self, block_index: int) -> bytes:
        return self.state.snapshot_handler(block_index)

    def restore_handler(self, snapshot: bytes) -> bytes:
        return self.state.restore_handler(snapshot)

    def last_arrival(self) -> float:
        with self.lock:
            return self.blocks[-1][1] if self.blocks else 0.0


class StampingProxy:
    """What `drive_tcp` submits through: stamps each transaction before the
    call that carries it and keeps the verdict it was answered."""

    def __init__(self, proxy) -> None:
        self.proxy = proxy
        self.submitted = []  # [transaction, stamped at, verdict or None]

    def submit_tx_batch(self, txs, client_id=None):
        now = time.monotonic()
        rows = [[tx, now, None] for tx in txs]
        self.submitted.extend(rows)
        verdicts = self.proxy.submit_tx_batch(txs, client_id=client_id)
        for row, v in zip(rows, verdicts):
            row[2] = v.verdict
        return verdicts


def say(word: str, *values) -> None:
    print(" ".join([word, *(repr(v) for v in values)]), flush=True)


def leave_at_end_of_input() -> None:
    def watch() -> None:
        while sys.stdin.readline():
            pass
        os._exit(0)

    threading.Thread(target=watch, name="client-stdin", daemon=True).start()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--node", required=True, help="node 0's proxy address")
    ap.add_argument("--listen", required=True, help="where node 0 commits to")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rate", type=float, required=True, help="offered tx/s")
    ap.add_argument("--burst", type=int, required=True)
    ap.add_argument("--clients", type=int, required=True)
    ap.add_argument("--tx-bytes", type=int, required=True)
    ap.add_argument("--retry-every", type=int, default=0)
    ap.add_argument("--lead-in", type=float, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--drain", type=float, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    from babble_tpu.ingress.loadgen import OpenLoopLoadGen
    from babble_tpu.proxy import SocketBabbleProxy

    state = StampedState()
    proxy = SocketBabbleProxy(args.node, args.listen, state)
    say("ready")
    if sys.stdin.readline().strip() != "go":
        return 0
    leave_at_end_of_input()

    gen = OpenLoopLoadGen(
        rate=args.rate, clients=args.clients, burst=args.burst,
        tx_bytes=args.tx_bytes, retry_every=args.retry_every, seed=args.seed)
    through = StampingProxy(proxy)
    errors = 0
    began = time.monotonic()
    errors += gen.drive_tcp(through, args.lead_in)["errors"]
    lead_in_end = time.monotonic()
    while state.last_arrival() < lead_in_end:
        errors += gen.drive_tcp(through, SLICE_S)["errors"]
    t0 = state.last_arrival()
    say("window", t0)
    t1 = t0 + args.seconds
    errors += gen.drive_tcp(through, max(t1 - time.monotonic(), 0.0))["errors"]
    say("offered", t1)

    acked = {tx for tx, _, verdict in through.submitted if verdict in ACKED}
    deadline = time.monotonic() + args.drain
    while time.monotonic() < deadline:
        with state.lock:
            if acked <= state.committed:
                break
        time.sleep(0.05)
    drained = time.monotonic()

    with state.lock:
        blocks = list(state.blocks)
    record = {
        "began": began, "t0": t0, "t1": t1, "drained": drained,
        "errors": errors,
        "loadgen": gen.stats(),
        "submitted": [[tx.hex(), at, verdict]
                      for tx, at, verdict in through.submitted],
        "blocks": [[index, at, [tx.hex() for tx in txs]]
                   for index, at, txs in blocks],
    }
    with open(args.out + ".part", "w") as f:
        json.dump(record, f)
    os.replace(args.out + ".part", args.out)
    say("done")
    threading.Event().wait()  # the watcher of standard input ends the process
    return 0


if __name__ == "__main__":
    sys.exit(main())
