"""A whole run of the harness (run.main, `--tiny`, so the look for a chip
is skipped and XLA:CPU serves) with the timed path broken underneath:
`correct` has to come out false for each fault this cell can have, and
true with nothing broken.

- an answer altered where it is produced: the live rung's fetched results
  (`live._unpack_results`) with the newest event's lamport timestamp moved
  by one;
- a step that returns its state unchanged: the device programs `step` and
  `multi_step` as the live rung calls them hand back the state they got;
- half of the batch left out: the live engine stages every other event
  of each sync only.

There is no exchange between chips in a one-chip cell.
"""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from benchmark import run as harness  # noqa: E402

ARGS = ["--workload", "v64-zipf.sync500", "--seed", "2147483659",
        "--seconds", "4", "--trace", "0", "--tiny"]


def result_of(capsys):
    assert harness.main(ARGS) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def altered_answer(monkeypatch):
    from babble_tpu.tpu import live

    real = live._unpack_results

    def unpack(packed, e_win, r_cap, n):
        out = list(real(packed, e_win, r_cap, n))
        lamport = out[1].copy()
        hit = (lamport >= 0).nonzero()[0]
        if len(hit):
            lamport[hit[-1]] += 1
        out[1] = lamport
        return tuple(out)

    monkeypatch.setattr(live, "_unpack_results", unpack)


def unchanged_state(monkeypatch):
    from babble_tpu.tpu import live

    monkeypatch.setattr(live, "step", lambda state, *a, **kw: state)
    monkeypatch.setattr(live, "multi_step", lambda state, *a, **kw: state)


def half_batch(monkeypatch):
    from babble_tpu.tpu import live

    real = live.LiveDeviceEngine.advance

    def advance(self):
        self.pending = self.pending[::2]
        return real(self)

    monkeypatch.setattr(live.LiveDeviceEngine, "advance", advance)


def test_sound_run_is_correct(capsys):
    line = result_of(capsys)
    assert line["correct"] is True and line["failed"] == 0
    assert line["tiny"] is True and line["device"]["platform"] == "cpu"
    assert list(line)[-1] == "compared"
    assert all(c["value"] <= c["limit"] for c in line["compared"].values())


@pytest.mark.parametrize("fault", [altered_answer, unchanged_state, half_batch])
def test_fault_is_not_correct(fault, monkeypatch, capsys):
    # the fault is the window's, not set-up's: it goes in when the entry
    # first polls the tracer, which is the top of the window's first sync
    real_poll = harness.TraceSlice.poll
    armed = []

    def poll(self, elapsed):
        if not armed:
            armed.append(True)
            fault(monkeypatch)
        real_poll(self, elapsed)

    monkeypatch.setattr(harness.TraceSlice, "poll", poll)
    line = result_of(capsys)
    assert armed
    assert line["correct"] is False, line["compared"]
    assert any(c["value"] > c["limit"] for c in line["compared"].values())
