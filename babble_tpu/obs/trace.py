"""Bounded ring-buffer span tracer with Chrome trace-event export.

Spans are recorded into a fixed-capacity ring: constant memory, O(1)
record, oldest spans silently dropped once the ring wraps. The export
shape is the Chrome trace-event JSON format (complete "X" events), so
`GET /debug/trace` output loads directly in Perfetto / chrome://tracing.

Every span has an `id` (a per-tracer sequence number) and the `parent`
id of the span that was open on the same thread when it began, so the
ring is a tree. Beside the ring the tracer keeps cumulative
`[count, seconds]` totals per span name, which never wrap, and a bounded
list of timestamped copies of them (`checkpoint`), so that a reader who
did not stand at a window's start can still take a windowed reading
(`totals_between`).

Timestamps come exclusively from the injected Clock seam — the tracer
itself never touches wall time, and ids are sequence numbers, so it is
byte-deterministic under the simulator's SimClock.

One caller cannot take the tracer's lock: a `gc.callbacks` entry
(obs/gcpause.py) runs on whatever thread's allocation set the collector
off, and that thread may be inside this tracer. It hands its reading to
`defer`, which only appends to a queue; the next locked operation books
what is queued before its own work.
"""

from __future__ import annotations

import itertools
import threading
import weakref
from collections import deque
from contextlib import contextmanager
from typing import Callable, Dict, List, Optional, Tuple

from ..common.clock import Clock, SYSTEM_CLOCK

DEFAULT_SPAN_CAPACITY = 4096
CHECKPOINT_CAPACITY = 4096
# deferred readings a tracer nobody uses may hold before it drops new ones
DEFERRED_CAPACITY = 4096
ANNOTATION_PREFIX = "babble."

KEPT_TRACERS = 4

_LIVE: "weakref.WeakSet[SpanTracer]" = weakref.WeakSet()
# The newest tracers that have checkpointed (a node's: Core.run_consensus
# checkpoints) are held here, so that a windowed reading can still be taken
# once the node itself is gone: a benchmark's reader runs after the entry
# has dropped its Core, and the collector would take the tracer with it. A
# tracer holds its ring, its checkpoints and its clock, nothing of the node.
_KEPT: deque = deque(maxlen=KEPT_TRACERS)


def live_tracers() -> List["SpanTracer"]:
    """The process's tracers: those still referenced (a weak set) and the
    newest KEPT_TRACERS that have checkpointed. For in-process harnesses
    and tests that did not build the node and so hold no reference to its
    `Observability` (a benchmark reader, a script)."""
    return list(_LIVE)


class Span:
    __slots__ = ("name", "start", "duration", "attrs", "thread", "id",
                 "parent")

    def __init__(self, name: str, start: float, duration: float,
                 attrs: Optional[dict], thread: str, id: int = 0,
                 parent: Optional[int] = None):
        self.name = name
        self.start = start
        self.duration = duration
        self.attrs = attrs
        self.thread = thread
        self.id = id
        self.parent = parent


class SpanTracer:
    """Fixed-capacity span ring. Thread-safe; wraps by overwriting."""

    # One per process, like the profiler it feeds: a callable that takes
    # a name and returns a context manager. Where a device backend is
    # chosen (tpu/runtime.py) it is `jax.profiler.TraceAnnotation`, and a
    # span then also lies in a `jax.profiler` trace's host plane as
    # "babble.<name>", on the clock of the device's operations. None
    # elsewhere: obs/ and hashgraph/ never import jax.
    annotator: Optional[Callable[[str], object]] = None

    def __init__(self, clock: Optional[Clock] = None,
                 capacity: int = DEFAULT_SPAN_CAPACITY):
        if capacity < 1:
            raise ValueError("span capacity must be >= 1")
        self.clock = clock if clock is not None else SYSTEM_CLOCK
        self.capacity = capacity
        # a weak reference to the node's DeviceLedger, for spans that are
        # also a ledger cell (`span(..., ledger=...)`); set by Observability
        self.ledger: Optional[Callable[[], object]] = None
        self._lock = threading.Lock()
        self._ring: List[Optional[Span]] = [None] * capacity  # guarded-by: _lock
        self._next = 0  # guarded-by: _lock — total spans ever recorded
        self.dropped = 0  # guarded-by: _lock — overwritten by ring wrap
        self._ids = itertools.count(1)  # next() is atomic under the GIL
        # unguarded-ok: thread-local — .stack holds this thread's open span ids
        self._open = threading.local()
        self._totals: Dict[str, List[float]] = {}  # guarded-by: _lock — name -> [count, seconds]
        self._checkpoints: deque = deque(maxlen=CHECKPOINT_CAPACITY)  # guarded-by: _lock
        # unguarded-ok: deque append/popleft are atomic — readings handed
        # over by `defer`, not yet in the ring or the totals
        self._deferred: deque = deque()
        self.deferred_dropped = 0  # unguarded-ok: a diagnostic; a lost increment is harmless
        _LIVE.add(self)

    def _stack(self) -> List[int]:
        stack = getattr(self._open, "stack", None)
        if stack is None:
            stack = self._open.stack = []
        return stack

    def _store(self, sp: Span) -> None:
        if self._deferred:
            self._book_deferred()
        with self._lock:
            self._store_locked(sp)

    def _store_locked(self, sp: Span) -> None:  # requires-lock: _lock
        if self._next >= self.capacity and \
                self._ring[self._next % self.capacity] is not None:
            self.dropped += 1
        self._ring[self._next % self.capacity] = sp
        self._next += 1
        self._add_locked(sp.name, sp.duration)

    def _add_locked(self, name: str, seconds: float, count: int = 1) -> None:  # requires-lock: _lock
        total = self._totals.get(name)
        if total is None:
            total = self._totals[name] = [0, 0.0]
        total[0] += count
        total[1] += seconds

    def _copy_locked(self) -> Dict[str, Tuple[int, float]]:  # requires-lock: _lock
        return {k: (int(c), s) for k, (c, s) in self._totals.items()}

    def record(self, name: str, start: float, duration: float,
               attrs: Optional[dict] = None) -> None:
        """A span measured elsewhere (a stage mark, a wait that began on
        another call). Its parent is the span open on this thread now.
        Code that times a block uses `span`."""
        stack = self._stack()
        self._store(Span(name, start, duration, attrs,
                         threading.current_thread().name, next(self._ids),
                         stack[-1] if stack else None))

    def defer(self, name: str, start: float, duration: float,
              attrs: Optional[dict] = None, feeds=()) -> None:
        """`record` for a caller that may be running inside this tracer on
        its own thread (a `gc.callbacks` entry): takes no lock and calls
        nothing that does. The reading's parent is the span open on this
        thread now; it reaches the totals, and with `attrs` the ring, when
        the tracer is next used, before that use's own work (so before a
        checkpoint's copy). `feeds` are `(counter child, amount)` pairs
        incremented then, as `span(histogram=...)` feeds a histogram. A
        tracer that is never used again keeps DEFERRED_CAPACITY readings
        and drops the rest."""
        if len(self._deferred) >= DEFERRED_CAPACITY:
            self.deferred_dropped += 1
            return
        stack = self._stack()
        self._deferred.append(
            (name, start, duration, attrs, threading.current_thread().name,
             stack[-1] if stack else None, feeds))

    def _book_deferred(self) -> None:
        queue = self._deferred
        while queue:
            try:
                name, start, duration, attrs, thread, parent, feeds = \
                    queue.popleft()
            except IndexError:  # another thread booked it
                break
            with self._lock:
                if attrs is None:
                    self._add_locked(name, duration)
                else:
                    self._store_locked(Span(name, start, duration, attrs,
                                            thread, next(self._ids), parent))
            for child, amount in feeds:
                child.inc(amount)

    @contextmanager
    def span(self, name: str, histogram=None, ledger=None, **attrs):
        """Time a block: one clock-read pair records a span, adds to the
        totals and (if given) feeds the same duration into
        `histogram.observe` and into the device ledger's cell
        `ledger = (rung, component, layout)`. Yields the Span, so that the
        block can add attributes it only knows at its end
        (`sp.attrs["rows"] = n`) and the caller can read `sp.duration`
        afterwards."""
        stack = self._stack()
        sp = Span(name, 0.0, 0.0, attrs, threading.current_thread().name,
                  next(self._ids), stack[-1] if stack else None)
        annotator = SpanTracer.annotator
        note = annotator(ANNOTATION_PREFIX + name) if annotator else None
        if note is not None:
            note.__enter__()
        stack.append(sp.id)
        sp.start = self.clock.monotonic()
        try:
            yield sp
        finally:
            sp.duration = self.clock.monotonic() - sp.start
            if note is not None:
                note.__exit__(None, None, None)
            stack.pop()
            if not sp.attrs:
                sp.attrs = None
            self._store(sp)
            if histogram is not None:
                histogram.observe(sp.duration)
            book = self.ledger() if ledger and self.ledger else None
            if book is not None:
                rung, component, layout = ledger
                book.component(rung, component, sp.duration, layout=layout)

    # -- totals ------------------------------------------------------------

    def add(self, name: str, seconds: float, count: int = 1) -> None:
        """Add `count` occurrences that took `seconds` together to `name`'s
        totals and write no ring entry: for work done once per event,
        where a span each would wrap the ring within one sync, and for a
        count the program kept in a plain integer through a call."""
        if self._deferred:
            self._book_deferred()
        with self._lock:
            self._add_locked(name, seconds, count)

    def totals(self) -> Dict[str, Tuple[int, float]]:
        """Cumulative (count, seconds) per span name since the tracer was
        made: every span, every `record`, every `add`."""
        if self._deferred:
            self._book_deferred()
        with self._lock:
            return self._copy_locked()

    def checkpoint(self) -> None:
        """Keep a timestamped copy of the totals (the newest
        CHECKPOINT_CAPACITY are held). `Core.run_consensus` calls this on
        entry and on return."""
        if self._deferred:
            self._book_deferred()
        now = self.clock.monotonic()
        with self._lock:
            if not self._checkpoints:
                _KEPT.append(self)
            self._checkpoints.append((now, self._copy_locked()))

    def totals_between(self, t0: float, t1: float) -> Dict[str, Tuple[int, float]]:
        """Per name, (count, seconds) between the first and the last
        checkpoint taken inside [t0, t1] on the tracer's clock; empty
        when fewer than two checkpoints lie there."""
        with self._lock:
            inside = [c for c in self._checkpoints if t0 <= c[0] <= t1]
        if len(inside) < 2:
            return {}
        first, last = inside[0][1], inside[-1][1]
        out = {}
        for name, (count, seconds) in last.items():
            c0, s0 = first.get(name, (0, 0.0))
            out[name] = (count - c0, seconds - s0)
        return out

    # -- export ------------------------------------------------------------

    def spans(self) -> List[Span]:
        """Retained spans, oldest first."""
        if self._deferred:
            self._book_deferred()
        with self._lock:
            if self._next <= self.capacity:
                return [s for s in self._ring[: self._next] if s is not None]
            head = self._next % self.capacity
            return [s for s in self._ring[head:] + self._ring[:head]
                    if s is not None]

    def to_chrome_trace(self, pid: int = 0,
                        trace_id: Optional[str] = None) -> dict:
        """Chrome trace-event JSON: complete ("X") events, µs timestamps,
        plus thread_name metadata so Perfetto shows real thread names.
        A span's `args` are its attributes plus `id` and, where it has
        one, `parent_id` (`parent` is the causal-trace context's key).
        `trace_id` narrows the export to spans carrying that causal-trace
        id in their attrs (the /debug/trace?trace_id= filter)."""
        spans = self.spans()
        if trace_id is not None:
            spans = [sp for sp in spans
                     if sp.attrs and sp.attrs.get("trace") == trace_id]
        tids: Dict[str, int] = {}
        events: List[dict] = []
        for sp in spans:
            tid = tids.setdefault(sp.thread, len(tids))
            args = dict(sp.attrs) if sp.attrs else {}
            args["id"] = sp.id
            if sp.parent is not None:
                args["parent_id"] = sp.parent
            events.append({
                "name": sp.name,
                "ph": "X",
                "ts": round(sp.start * 1e6, 3),
                "dur": round(sp.duration * 1e6, 3),
                "pid": pid,
                "tid": tid,
                "args": args,
            })
        meta = [
            {"name": "thread_name", "ph": "M", "pid": pid, "tid": tid,
             "args": {"name": thread}}
            for thread, tid in sorted(tids.items(), key=lambda kv: kv[1])
        ]
        return {"traceEvents": meta + events, "displayTimeUnit": "ms"}
