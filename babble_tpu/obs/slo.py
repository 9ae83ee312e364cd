"""SLO engine: declared objectives over registry series, with
multi-window burn-rate evaluation on the injected Clock (ISSUE 7).

The metrics registry (metrics.py) records what happened; this module
turns a handful of those series into pass/fail *objectives* — the
ROADMAP's "p50/p99 submit->commit SLO gates" made executable. Each
objective reads one existing series (a histogram's buckets/sum/count or
a gauge/counter value), and ``evaluate()`` keeps a bounded Clock-pruned
sample history so burn rates are computed over deltas per window — the
SRE multi-window pattern: an objective only *breaches* when EVERY
configured window is burning past the threshold, so a transient spike
(short window hot, long window fine) pages nobody while a sustained
regression (all windows hot) does.

Evaluation is driven from the same seams as the liveness watchdog: the
threaded node's `_babble` tick and the sim's `_tick`, both on the
injected Clock — same-seed sim runs evaluate at identical virtual
times and produce byte-identical `babble_slo_*` gauges. Before the
first window has elapsed the baseline is the engine's start point, so a
one-shot evaluation (the `bench.py --slo` gate) degrades to cumulative
evaluation over the whole run — exactly what a bench wants.

A breach transition appends an `slo.breach` flight record and triggers
a flight-recorder dump (reason `slo-breach`), closing the observe →
triage loop.

Objective and series names are static string literals at call sites,
enforced by the `obs-slo-decl` lint rule (analysis/obs.py) — declare
objectives on a receiver *named* ``slo`` so the rule sees them.
"""

from __future__ import annotations

import logging
import threading
from collections import deque
from typing import Any, Callable, Deque, Dict, List, Optional, Sequence, Tuple

from .metrics import Counter, Gauge, Histogram

# default burn-rate evaluation windows, Clock seconds: a fast window
# that reacts within a sim run / soak and a slow one that filters noise
DEFAULT_WINDOWS: Tuple[float, ...] = (60.0, 300.0)

# burn >= this in EVERY window = breach (1.0 = consuming error budget
# exactly at the rate that exhausts it over the objective period)
DEFAULT_BURN_THRESHOLD = 1.0

# guard against division by zero in ratio math
_TINY = 1e-12


class SLObjective:
    """One declared objective over one registry series.

    kinds:
      - ``p_below``   histogram: the ``quantile`` of observations must
                      sit at or below ``threshold`` (good = obs <=
                      threshold; budget = 1 - quantile)
      - ``mean_below`` histogram: windowed mean must be <= threshold
      - ``mean_above`` histogram: windowed mean must be >= threshold
      - ``below``     gauge/counter: sampled value must be <= threshold
      - ``above``     gauge/counter: sampled value must be >= threshold
    """

    KINDS = ("p_below", "mean_below", "mean_above", "below", "above")

    __slots__ = ("name", "series", "kind", "threshold", "quantile",
                 "budget", "labels", "description")

    def __init__(self, name: str, series: str, kind: str, threshold: float,
                 quantile: Optional[float] = None,
                 budget: Optional[float] = None,
                 labels: Optional[Dict[str, str]] = None,
                 description: str = ""):
        if kind not in self.KINDS:
            raise ValueError(f"{name}: unknown objective kind {kind!r}")
        if kind == "p_below":
            if quantile is None:
                quantile = 0.99
            if budget is None:
                budget = max(1.0 - quantile, _TINY)
        self.name = name
        self.series = series
        self.kind = kind
        self.threshold = float(threshold)
        self.quantile = quantile
        self.budget = budget
        self.labels = dict(labels) if labels else {}
        self.description = description


class SLOEngine:
    """Evaluates declared objectives against the node's registry.

    ``evaluate()`` is cheap (a handful of dict reads) and must be
    called periodically from a Clock-driven tick; it samples every
    objective's underlying series, prunes history past the longest
    window, computes per-window burn rates, updates the
    ``babble_slo_*`` gauges and fires ``on_breach`` + a flight-recorder
    dump on the transition into breach."""

    def __init__(self, obs, windows: Sequence[float] = DEFAULT_WINDOWS,
                 burn_threshold: float = DEFAULT_BURN_THRESHOLD,
                 on_breach: Optional[Callable[[str, dict], None]] = None,
                 logger: Optional[logging.Logger] = None):
        self.obs = obs
        self.registry = obs.registry
        self.clock = obs.clock
        self.windows = tuple(sorted(windows))
        self.burn_threshold = burn_threshold
        self.on_breach = on_breach
        self.logger = logger if logger is not None else logging.getLogger(
            "babble.slo"
        )
        # unguarded-ok: objectives are declared during single-threaded
        # boot and the dict is read-only once the tick loop starts
        self._objectives: Dict[str, SLObjective] = {}
        # serializes evaluate() between the tick loop and /debug/slo
        self._lock = threading.Lock()
        # guarded-by: _lock — (t, {objective: reading}), pruned past the
        # longest window
        self._samples: Deque[Tuple[float, Dict[str, dict]]] = deque()
        self._t0 = self.clock.monotonic()
        self._last_tick = float("-inf")  # guarded-by: _lock
        self._breached: Dict[str, bool] = {}  # guarded-by: _lock
        self._g_burn = obs.gauge(
            "babble_slo_burn_rate",
            "Error-budget burn rate per objective and window (>= 1 in "
            "every window = breach)",
            labels=("objective", "window"),
        )
        self._g_breached = obs.gauge(
            "babble_slo_breached",
            "1 while the objective is burning past threshold in every "
            "window",
            labels=("objective",),
        )
        self._m_breaches = obs.counter(
            "babble_slo_breaches_total",
            "Breach transitions per objective since boot",
            labels=("objective",),
        )

    # ------------------------------------------------------------------
    # declaration
    # ------------------------------------------------------------------

    def objective(self, name: str, series: str, kind: str, threshold: float,
                  quantile: Optional[float] = None,
                  budget: Optional[float] = None,
                  labels: Optional[Dict[str, str]] = None,
                  description: str = "") -> SLObjective:
        """Declare one objective. ``name`` and ``series`` must be static
        string literals at the call site (obs-slo-decl lint rule)."""
        if name in self._objectives:
            raise ValueError(f"objective {name!r} already declared")
        obj = SLObjective(name, series, kind, threshold, quantile=quantile,
                          budget=budget, labels=labels,
                          description=description)
        self._objectives[name] = obj
        # unguarded-ok: declaration happens at boot, before the tick loop
        self._breached[name] = False
        self._g_breached.labels(objective=name).set(0.0)
        return obj

    def objectives(self) -> List[SLObjective]:
        return list(self._objectives.values())

    # ------------------------------------------------------------------
    # sampling
    # ------------------------------------------------------------------

    def _read(self, obj: SLObjective) -> dict:
        """Cumulative reading of the objective's series: histogram ->
        {count, sum, good}; gauge/counter -> {value}. Missing series
        read as zeros (an objective over a path the node never took
        simply has no data and cannot breach)."""
        metric = self.registry.get(obj.series)
        if metric is None:
            return {}
        if isinstance(metric, Histogram):
            key = ",".join(
                str(obj.labels.get(ln, "")) for ln in metric.label_names
            )
            snap = metric.snapshot()["series"].get(key)
            if snap is None:
                return {}
            good = snap["count"]
            if obj.kind == "p_below":
                # largest bucket upper bound at or below the threshold:
                # conservative (undercounts good, never bad)
                good = 0
                for le, cum in snap["buckets"]:
                    if float(le) <= obj.threshold * (1.0 + 1e-9):
                        good = cum
                    else:
                        break
            return {"count": snap["count"], "sum": snap["sum"],
                    "good": good}
        if isinstance(metric, (Gauge, Counter)):
            return {"value": metric.value(**obj.labels)}
        return {}

    @staticmethod
    def _delta(cur: dict, base: Optional[dict], field: str) -> float:
        if not cur:
            return 0.0
        b = base.get(field, 0.0) if base else 0.0
        return float(cur.get(field, 0.0)) - float(b)

    def _burn(self, obj: SLObjective, cur: dict, base: Optional[dict],
              gauge_samples: List[float]) -> Optional[float]:
        """Burn rate for one window; None = no data in the window."""
        if obj.kind in ("below", "above"):
            if not gauge_samples:
                return None
            mean = sum(gauge_samples) / len(gauge_samples)
            if obj.kind == "below":
                return mean / max(obj.threshold, _TINY)
            return obj.threshold / max(mean, _TINY)
        dc = self._delta(cur, base, "count")
        if dc <= 0:
            return None
        if obj.kind == "p_below":
            bad = dc - self._delta(cur, base, "good")
            return (bad / dc) / max(obj.budget or _TINY, _TINY)
        mean = self._delta(cur, base, "sum") / dc
        if obj.kind == "mean_below":
            return mean / max(obj.threshold, _TINY)
        return obj.threshold / max(mean, _TINY)

    # ------------------------------------------------------------------
    # evaluation
    # ------------------------------------------------------------------

    def evaluate(self) -> Dict[str, Any]:
        """One evaluation pass; returns the same document `status()`
        serves. Call from the sim tick or once for a bench gate."""
        with self._lock:
            return self._evaluate_locked()

    def tick(self) -> None:
        """`evaluate`, at most once a sixtieth of the shortest window (a
        second, by default): what a node's heartbeat calls. A pass keeps
        one sample and walks all it holds, so one a heartbeat (100 a
        second at the demo tuning) meant 30,000 samples at the longest
        window's end and a pass of tens of milliseconds on the gossip
        loop's thread; a burn rate is a mean over a minute or more and
        gains nothing from them."""
        with self._lock:
            now = self.clock.monotonic()
            if now - self._last_tick < (self.windows or (60.0,))[0] / 60.0:
                return
            self._last_tick = now
            self._evaluate_locked()

    def _evaluate_locked(self) -> Dict[str, Any]:  # requires-lock: _lock
        now = self.clock.monotonic()
        readings = {n: self._read(o) for n, o in self._objectives.items()}
        self._samples.append((now, readings))
        horizon = now - (self.windows[-1] if self.windows else 0.0)
        while len(self._samples) > 1 and self._samples[1][0] <= horizon:
            self._samples.popleft()

        results = []
        for name, obj in self._objectives.items():
            cur = readings[name]
            burns: Dict[str, Optional[float]] = {}
            any_data = False
            all_burning = True
            for w in self.windows:
                start = now - w
                # newest sample at or before the window start is the
                # baseline; before one exists, t0 (engine start) is —
                # so a young engine evaluates cumulatively
                base: Optional[dict] = None
                for t, r in self._samples:
                    if t <= start:
                        base = r.get(name)
                    else:
                        break
                gauge_samples = [
                    float(r[name]["value"])
                    for t, r in self._samples
                    if t > start and r.get(name) and "value" in r[name]
                ]
                burn = self._burn(obj, cur, base, gauge_samples)
                label = f"{int(w)}s"
                burns[label] = burn
                if burn is None:
                    all_burning = False
                else:
                    any_data = True
                    self._g_burn.labels(objective=name, window=label).set(
                        burn
                    )
                    if burn < self.burn_threshold:
                        all_burning = False
            breached = any_data and all_burning
            was = self._breached[name]
            self._breached[name] = breached
            self._g_breached.labels(objective=name).set(
                1.0 if breached else 0.0
            )
            doc = {
                "name": name,
                "series": obj.series,
                "kind": obj.kind,
                "threshold": obj.threshold,
                "quantile": obj.quantile,
                "description": obj.description,
                "burn": {
                    k: (round(v, 6) if v is not None else None)
                    for k, v in burns.items()
                },
                "breached": breached,
            }
            results.append(doc)
            if breached and not was:
                self._on_breach_transition(name, obj, doc)
        return {
            "t": round(now, 9),
            "burn_threshold": self.burn_threshold,
            "windows": [f"{int(w)}s" for w in self.windows],
            "objectives": results,
        }

    def _on_breach_transition(self, name: str, obj: SLObjective,
                              doc: dict) -> None:
        self._m_breaches.labels(objective=name).inc()
        self.logger.warning(
            "SLO breach: %s (%s %s vs threshold %g) burning in every "
            "window %s",
            name, obj.series, obj.kind, obj.threshold, doc["burn"],
        )
        flightrec = getattr(self.obs, "flightrec", None)
        if flightrec is not None:
            flightrec.record(
                "slo.breach", objective=name, series=obj.series,
                kind=obj.kind, threshold=obj.threshold,
            )
            flightrec.dump("slo-breach", objective=name)
        if self.on_breach is not None:
            try:
                self.on_breach(name, doc)
            except Exception:  # noqa: BLE001 — a broken callback must
                pass  # not take the evaluation tick down

    # ------------------------------------------------------------------
    # reporting
    # ------------------------------------------------------------------

    def status(self) -> Dict[str, Any]:
        """Document for ``GET /debug/slo`` — a fresh evaluation, so the
        endpoint always reflects the current registry state."""
        return self.evaluate()

    def breached(self) -> List[str]:
        """Names of currently-breached objectives (bench gates)."""
        # unguarded-ok: racy boolean snapshot; bench gates tolerate staleness
        return [n for n, b in self._breached.items() if b]
