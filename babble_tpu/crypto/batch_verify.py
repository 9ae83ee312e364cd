"""The signature checks of a list of stored events, in worker processes.

`Core.bootstrap` holds every stored event before it inserts the first, so
their checks need not wait for their turn. `PoolVerifier.start` spawns a
short-lived pool before the store is read; `Hashgraph.insert_stored` walks
`PoolVerifier.checked` in order and inserts each event as soon as its
chunk's verdict is back, while the workers check the chunks ahead of it.

The work is `Event.verify`'s, split where it is heavy. The parent computes
each event's digest from the body as it stands (`Event.fresh_hash`, which
leaves it cached on the event as `verify` does) and decodes the `r|s`
text; a worker parses each validator's key once, keeps it, and answers
`crypto.verify(key, digest, r, s)` with the backend the parent has. A
verdict is True, False, or None where the pool did not check the event:
its creator is no validator, its signature text does not decode, its key
does not parse, or its chunk's worker died or failed (after which no chunk
is sent). Only a True verdict
spares the event the one-by-one check at its turn; every other event goes
through `Hashgraph.insert_event`, which refuses it there as it always did.

There is no option. The pool's size follows the CPUs this process may run
on, and it starts only for lists long enough to pay for its start.
Workers are spawned, never forked (the parent holds a device client and
its threads), and this module imports nothing but `babble_tpu.crypto`.
"""

from __future__ import annotations

import logging
import multiprocessing
import os
from collections import deque
from concurrent.futures import ProcessPoolExecutor
from concurrent.futures.process import BrokenProcessPool
from typing import Callable, Container, Deque, Dict, Iterator, List, Optional, Sequence, Tuple

from .. import crypto

log = logging.getLogger(__name__)

# lists shorter than this are checked one by one: a pool's start (~0.2 s
# for six workers) is worth ~2,700 serial checks, and the stored count is
# known before the read, so the start overlaps it
MIN_EVENTS = 4096
MAX_WORKERS = 8
CHUNK = 256  # events a task: ~20 ms of a worker's checks
AHEAD = 2  # chunks sent ahead of the one being inserted, per worker

# a worker's parsed keys, under the creator's exact bytes: at most the
# validators', since the parent sends no other creator's events
_KEYS: Dict[bytes, object] = {}


def _ready() -> None:
    """Nothing: sent once to each worker, so that each starts now."""


def _check(jobs: List[Tuple[bytes, bytes, int, int]]) -> List[Optional[bool]]:
    """In a worker: each (creator bytes, digest, r, s) checked, None where
    the key does not parse."""
    out: List[Optional[bool]] = []
    for raw, digest, r, s in jobs:
        pub = _KEYS.get(raw)
        if pub is None:
            try:
                pub = crypto.pub_key_from_bytes(raw)
            except ValueError:
                out.append(None)
                continue
            _KEYS[raw] = pub
        out.append(crypto.verify(pub, digest, r, s))
    return out


Sent = Tuple[List[int], Optional[object]]  # the chunk's positions checked, the future


class PoolVerifier:
    """A pool of spawned workers for one list of events; `close` joins it.
    `verified` counts the events the pool checked, `blocked_s` the seconds
    the parent waited for their verdicts, on the caller's clock."""

    def __init__(self, executor: ProcessPoolExecutor, size: int,
                 now: Callable[[], float]):
        self._executor = executor
        self._now = now
        self.size = size
        self.verified = 0
        self.blocked_s = 0.0
        self._failed = False

    @classmethod
    def start(cls, events: int, now: Callable[[], float]) -> Optional["PoolVerifier"]:
        """A pool started for a list of `events`, or None where the list is
        too short, the process has one CPU, or no worker could start. Its
        size: the CPUs this process may run on less the parent's own, at
        most MAX_WORKERS."""
        if hasattr(os, "sched_getaffinity"):
            cpus = len(os.sched_getaffinity(0))
        else:
            cpus = os.cpu_count() or 1
        size = min(cpus - 1, MAX_WORKERS)
        if events < MIN_EVENTS or size < 1:
            return None
        executor = None
        try:
            executor = ProcessPoolExecutor(
                size, mp_context=multiprocessing.get_context("spawn"))
            for _ in range(size):  # each submit spawns a worker now
                executor.submit(_ready)
        except (OSError, BrokenProcessPool) as err:
            if executor is not None:
                executor.shutdown(wait=True, cancel_futures=True)
            log.warning("signature pool not started, checks run one by one: %s", err)
            return None
        return cls(executor, size, now)

    def close(self) -> None:
        """Cancel what is not running and join every worker."""
        self._executor.shutdown(wait=True, cancel_futures=True)

    def checked(self, events: Sequence, validators: Container[str]
                ) -> Iterator[Tuple[object, Optional[bool]]]:
        """(event, verdict) for each of `events`, in order; `validators`
        holds the creators (`Event.creator()`) whose events are sent. A
        chunk is sent AHEAD * size chunks before its turn."""
        starts = iter(range(0, len(events), CHUNK))
        ahead: Deque[Tuple[Sequence, Sent]] = deque()

        def send() -> None:
            lo = next(starts, None)
            if lo is not None:
                chunk = events[lo:lo + CHUNK]
                ahead.append((chunk, self._send(chunk, validators)))

        for _ in range(AHEAD * self.size):
            send()
        while ahead:
            chunk, sent = ahead.popleft()
            send()
            yield from zip(chunk, self._verdicts(len(chunk), sent))

    def _send(self, chunk: Sequence, validators: Container[str]) -> Sent:
        if self._failed:  # after a failure the rest is checked one by one
            return [], None
        at, jobs = [], []
        for i, ev in enumerate(chunk):
            if ev.creator() not in validators:
                continue
            try:
                r, s = crypto.decode_signature(ev.signature)
            except ValueError:
                continue
            at.append(i)
            jobs.append((ev.body.creator, ev.fresh_hash(), r, s))
        if not jobs:
            return at, None
        try:
            return at, self._executor.submit(_check, jobs)
        except BrokenProcessPool as err:
            self._note_failure(err)
            return at, None

    def _verdicts(self, n: int, sent: Sent) -> List[Optional[bool]]:
        verdicts: List[Optional[bool]] = [None] * n
        at, future = sent
        if future is None:
            return verdicts
        t = self._now()
        try:
            got = future.result()
        except Exception as err:  # a worker's death or fault: the chunk is
            # left to the one-by-one checks, never passed unchecked
            self._note_failure(err)
            return verdicts
        finally:
            self.blocked_s += self._now() - t
        for i, verdict in zip(at, got):
            verdicts[i] = verdict
        self.verified += sum(v is not None for v in got)
        return verdicts

    def _note_failure(self, err: BaseException) -> None:
        if not self._failed:
            self._failed = True
            log.warning("signature pool failed, its chunks are checked one by one",
                        exc_info=err)
