"""What a run records about itself: host spans of the benchmark's own,
XLA compilations in the process, peak device memory.

Spans are kept in memory (start and duration on `time.monotonic`) and, in
a traced run, also written into the profiler's trace as
`jax.profiler.TraceAnnotation`s named `bench.<name>`, so that the trace
reduction can say what the host was doing in a device idle gap.
"""

from __future__ import annotations

import contextlib
import time
from typing import Dict, List, Optional, Tuple

SPAN_PREFIX = "bench."


class Recorder:
    def __init__(self) -> None:
        self.spans: Dict[str, List[Tuple[float, float]]] = {}
        self.annotate = False  # set while the profiler is tracing

    @contextlib.contextmanager
    def span(self, name: str):
        note = None
        if self.annotate:
            import jax

            note = jax.profiler.TraceAnnotation(SPAN_PREFIX + name)
            note.__enter__()
        t0 = time.monotonic()
        try:
            yield
        finally:
            dt = time.monotonic() - t0
            if note is not None:
                note.__exit__(None, None, None)
            self.spans.setdefault(name, []).append((t0, dt))

    def span_seconds(self, name: str, since: float = 0.0,
                     until: float = float("inf")) -> Tuple[int, float]:
        """(count, summed seconds) of the spans that started in [since, until)."""
        hits = [d for t, d in self.spans.get(name, ()) if since <= t < until]
        return len(hits), sum(hits)


class CompileMeter:
    """Every XLA compilation in the process (copied from chip_smoke.py):
    count, wall seconds and the jitted function's name.
    `backend_compile_duration` wraps the persistent cache lookup too, so a
    warm cache shows as the same count with fewer seconds and
    `cache_hits` > 0."""

    def __init__(self) -> None:
        from jax import monitoring

        self.compiles = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.names: List[str] = []
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, name: str, secs: float, **kw) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += secs
            self.names.append(str(kw.get("fun_name", "?")))

    def _event(self, name: str, **_kw) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def peak_bytes() -> Optional[int]:
    """peak_bytes_in_use on the fullest device, where the backend reports
    it (copied from chip_smoke.py)."""
    import jax

    peaks = []
    for d in jax.devices():
        stats = d.memory_stats()
        if not stats or "peak_bytes_in_use" not in stats:
            return None
        peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks)
