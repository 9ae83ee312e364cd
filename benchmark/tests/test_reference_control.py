"""The plain reference against the program's host engine, and the control
against the reference, at a size a test run can hold.

The control is the reference put in the program's place with one stated
guarantee broken: the supermajority 2n/3 + 1 lowered by one, the nearest
weaker threshold and the step a later change would be tempted by (fame and
rounds decided on one vote fewer). It has to come out as not correct.
"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from benchmark import traffic as gen  # noqa: E402
from benchmark.entries import replay  # noqa: E402
from benchmark.reference import hashgraph as reference  # noqa: E402

CASES = [(16, 6000, 0.0), (24, 6000, 1.1)]


def control_mismatches(stream, consumed):
    """mismatches of the control, in the program's place, against the
    reference as the configuration states it."""
    inputs = replay.reference_inputs(stream, consumed)
    n = inputs[0]
    want = reference.order(*inputs)
    control = reference.order(*inputs, super_majority=2 * n // 3)
    return replay.mismatches(replay.as_observed(control), want)


@pytest.mark.parametrize("n,events,zipf_a", CASES)
@pytest.mark.parametrize("seed", [1, 2_147_483_659, 3_000_000_019])
def test_control_is_not_correct(n, events, zipf_a, seed):
    stream = replay.Stream(n, events, seed, zipf_a, 1)
    diff = control_mismatches(stream, events)
    assert diff["events_mismatched"] > 0
    assert diff["blocks_mismatched"] > 0


@pytest.mark.parametrize("n,events,zipf_a", CASES)
def test_reference_agrees_with_the_host_engine(n, events, zipf_a):
    """A second witness for the reference: the program's CPU engine, fed
    the same syncs, stamps and commits exactly what the reference orders."""
    stream = replay.Stream(n, events, 7, zipf_a, 1)
    stamps = replay.CommitStamps()
    core = stream.core("cpu", 50000, commit_ch=stamps)
    for lo, hi in gen.syncs(events, 500):
        for ev in stream.handed[lo:hi]:
            core.insert_event(ev, True)
        core.run_consensus()
    assert len(stamps.blocks) > 10
    want = reference.order(*replay.reference_inputs(stream, events))
    diff = replay.mismatches(
        replay.observed(stream, events, stamps.blocks), want)
    assert diff == {"events_mismatched": 0, "blocks_mismatched": 0}
