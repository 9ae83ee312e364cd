"""The cycle collector's pauses, read from inside the program.

A collection stops the thread that set it off for as long as it lasts,
inside whatever span is open there: a generation-2 ("full") collection of
a process that holds a large DAG takes as long as a whole sync. One
`gc.callbacks` entry a process times every collection and hands the
reading to every watched tracer: totals `gc.young` (generations 0 and 1)
and `gc.full` (generation 2), for a full collection also a ring record
`gc.full` (attributes `collected`, `generation`) whose parent is the span
the pause landed in, and the registry counters
`babble_gc_pause_seconds_total` / `babble_gc_collections_total`
{generation="young"|"full"}.

Clock policy, as the device ledger's: only an `Observability` on the real
`SystemClock` is watched. Under a virtual clock nothing is installed,
declared or recorded, so the simulator's fingerprints never see the
collector.

The entry runs wherever an allocation set the collector off, possibly
inside a tracer or a metric on the same thread with that object's lock
held: it takes no lock and goes through `SpanTracer.defer`.
"""

from __future__ import annotations

import gc
import weakref

from ..common.clock import SYSTEM_CLOCK, SystemClock

FULL_GENERATION = 2

# tracer -> its (seconds, collections) counter children for "young" and
# for "full"; weak, so a node that is gone is no longer fed
_WATCHED: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
_began = [0.0]  # the running collection's start; collections do not nest


def watch(obs) -> None:
    """Feed `obs` (an Observability) the collector's pauses from now on;
    nothing for one on a virtual clock. The first call installs the
    process's one `gc.callbacks` entry."""
    if not isinstance(obs.clock, SystemClock):
        return
    seconds = obs.counter(
        "babble_gc_pause_seconds_total",
        "Seconds the cycle collector held a thread of this process",
        labels=("generation",),
    )
    collections = obs.counter(
        "babble_gc_collections_total",
        "Collections of the cycle collector in this process",
        labels=("generation",),
    )
    _WATCHED[obs.tracer] = tuple(
        (seconds.labels(generation=generation),
         collections.labels(generation=generation))
        for generation in ("young", "full")
    )
    if _on_collection not in gc.callbacks:
        gc.callbacks.append(_on_collection)


def _on_collection(phase: str, info: dict) -> None:
    if phase == "start":
        _began[0] = SYSTEM_CLOCK.monotonic()
        return
    began = _began[0]
    pause = SYSTEM_CLOCK.monotonic() - began
    full = info["generation"] >= FULL_GENERATION
    name = "gc.full" if full else "gc.young"
    for tracer, children in list(_WATCHED.items()):
        seconds, collections = children[full]
        # attributes make it a ring record: a full collection only
        attrs = {"collected": info["collected"],
                 "generation": info["generation"]} if full else None
        tracer.defer(name, began, pause, attrs,
                     ((seconds, pause), (collections, 1)))
