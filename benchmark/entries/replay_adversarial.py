"""Entry `replay_adversarial`: entry `replay` on a stream whose adversarial
minority withholds its chains and reveals them late
(`benchmark/traffic_adversarial.py`), handed over in arrival order.

Everything of `replay` is reused by import and runs unchanged: the signed
stream, the observer Core, the lead-in, the window that ends on a commit,
the flush, the commit stamps, the forged event and the comparison with the
plain reference (which reads a DAG in any topological order). Two names of
that module are exchanged for the length of the run, in a process that runs
one cell: its traffic generator, for one that draws the withheld DAG from
the configuration, and its `Served`, for one that also notes after every
sync whether the program's `fame.reopen` total rose during it.

A re-open sync is one during which that total rose: a witness was revealed
into a round whose fame the Core had already decided and dequeued, and the
Core queued the round again. `compared` gains `reopen_syncs_unserved`
(re-open syncs of the window that the live rung did not serve) and
`reopen_syncs_missing` (1 when the window held no re-open sync: a run in
which the mechanism never ran says nothing about it), both with limit 0.
"""

from __future__ import annotations

import time
from typing import List

from benchmark import traffic as gen
from benchmark import traffic_adversarial as adversarial
from benchmark.entries import replay

REOPEN = "fame.reopen"  # the program's tracer total


class WithheldTraffic:
    """`benchmark.traffic` with `gossip_dag` drawing the configuration's
    withheld DAG; `relabel` then permutes it like any other."""

    syncs = staticmethod(gen.syncs)
    payload = staticmethod(gen.payload)
    payload_event = staticmethod(gen.payload_event)
    relabel = staticmethod(gen.relabel)

    def __init__(self, cfg: dict):
        self.cfg = cfg
        self.drawn = None

    def gossip_dag(self, n: int, events: int, seed: int, zipf_a: float):
        cfg = {**self.cfg, "validators": n, "events": events, "zipf_a": zipf_a}
        self.drawn = adversarial.from_config(cfg, seed)
        return self.drawn.dag


class ServedAndReopened(replay.Served):
    """`Served`, and per sync: when it ended, whether the live rung served
    it, and whether the program re-opened a round during it."""

    def __init__(self, core) -> None:
        super().__init__(core)
        self.tracer = core.hg.obs.tracer
        self.reopened = self._reopened()
        self.log: List[tuple] = []  # (end, served, rounds re-opened)

    def _reopened(self) -> int:
        return self.tracer.totals().get(REOPEN, (0, 0.0))[0]

    def note(self) -> None:
        unserved = self.unserved
        super().note()
        now = self._reopened()
        self.log.append((time.monotonic(), self.unserved == unserved,
                         now - self.reopened))
        self.reopened = now


def run(ctx) -> dict:
    cfg = {**ctx.config, **ctx.config["tiny"]} if ctx.tiny else ctx.config
    traffic = WithheldTraffic(cfg)
    notes: List[ServedAndReopened] = []

    def served(core):
        notes.append(ServedAndReopened(core))
        return notes[-1]

    kept = replay.gen, replay.Served
    replay.gen, replay.Served = traffic, served
    try:
        result = replay.run(ctx)
    finally:
        replay.gen, replay.Served = kept

    t0, _ = result["window"]
    in_window = [(ok, rounds) for end, ok, rounds in notes[0].log
                 if end >= t0 and rounds > 0]
    reopen_syncs = len(in_window)
    reopen_served = sum(ok for ok, _ in in_window)
    counters = result["counters"]
    lo = counters["events_lead_in"]
    hi = lo + counters["events_inserted"]
    drawn = traffic.drawn
    result["compared"] += [
        ("reopen_syncs_unserved", reopen_syncs - reopen_served, 0),
        ("reopen_syncs_missing", int(reopen_syncs == 0), 0),
    ]
    counters.update({
        "reveals": int(((drawn.reveal_rows >= lo) & (drawn.reveal_rows < hi)).sum()),
        "late_events": int(drawn.late[lo:hi].sum()),
        "reopen_syncs": reopen_syncs,
        "reopen_syncs_served": reopen_served,
    })
    return result
