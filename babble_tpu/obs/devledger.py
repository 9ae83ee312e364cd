"""Device-time ledger (ISSUE 19): per-pass kernel cost attribution,
compile/retrace accounting, and the unified host+device timeline.

The obs stack used to stop at the host boundary: the engine rungs
recorded one opaque `babble_device_run_seconds` per dispatch. This
module decomposes that wall time into a typed cost ledger — one cell
per (rung, pass, layout, component) with component one of

    stage     host restage work before the dispatch
    compile   trace+lower+backend-compile time attributed to a seam call
    run       device execution time of one staged kernel-contract entry
    fetch     blocked device->host result wait
    integrate host write-back of pass results

— by wrapping every host call into a staged callable in a *seam*
(`ledger_call` / `DeviceLedger.call`). The 19 `# kernel-contract:`
entry points (analysis/staged.py, PR 18) map onto seams via
`ENTRY_INFO`: entries whose trace lives inside another staged body
(e.g. `_divide_rounds` inside `consensus_pipeline`) carry a
`covered_by` pointer instead of their own seam, so ledger coverage of
the contract surface is total and testable (tests/test_devledger.py).

Determinism contract: every duration is read through the ledger's
clock policy — the REAL `SystemClock` is read directly; under any
injected virtual clock (the sim) the ledger records 0.0 durations and
never touches the clock object at all, so worker-thread seams
(tpu/dispatch.py's `mesh-dispatch` workers) cannot violate the
"virtual clock is serve-thread-only" discipline and same-seed sim runs
produce byte-identical ledger snapshots. `fingerprint()` joins the
SimCluster determinism contract alongside digest/trace/flightrec.

Compile/retrace accounting hooks `jax.monitoring`: the three
`/jax/core/compile/*` event-duration events fire per compilation and
are silent on executable-cache hits. A seam keeps a per-entry mirror
of the abstract call signature (shapes/dtypes/statics/layout); compile
events on a NEW signature are legitimate compiles
(`babble_kernel_compiles_total{entry}`), compile/trace events on a
signature already seen are silent retraces
(`babble_kernel_retraces_total{entry}`) — the dynamic truth backing
the static `kernel-retrace-hazard` lint rule. Seconds attributed to
compilation come from the injected-clock delta around the call (0.0 in
the sim), never from the monitoring payload, preserving determinism.

The static cost-model sidecar estimates bytes moved per entry exactly
from the abstract signature (deterministic, in the snapshot) and
lazily probes XLA's `lower().compile().cost_analysis()` for FLOPs on
the real clock only (`efficiency()`; excluded from the fingerprint).

Entry/rung/pass names on ledger receivers are static string literals,
enforced by the `obs-ledger-static-name` lint rule (analysis/obs.py).
"""

from __future__ import annotations

import hashlib
import json
import threading
from collections import deque
from contextlib import contextmanager
from typing import Any, Dict, List, Optional, Tuple

from ..common.clock import SystemClock

# lifecycle components a dispatch's wall time decomposes into
COMPONENTS = ("stage", "compile", "run", "fetch", "integrate", "sync")

# bounded ring of recent seam events feeding the /debug/timeline device
# lanes (the cells above are cumulative; the ring is the time-ordered view)
TIMELINE_CAPACITY = 2048

# ---------------------------------------------------------------------------
# kernel-contract entry registry
# ---------------------------------------------------------------------------
#
# Every `# kernel-contract:` entry point (analysis/staged.py) maps to
# (default rung, pass name, covered_by). `covered_by` names the seam
# whose traced body contains this entry — those entries execute inside
# another staged callable and cannot carry their own host-side timing
# seam; their cost is attributed to the covering entry's pass.
# tests/test_devledger.py asserts this table matches the parsed
# contract surface exactly, so a new contract without a ledger decision
# fails tests, not silently drops out of attribution.
ENTRY_INFO: Dict[str, Tuple[str, str, Optional[str]]] = {
    # tpu/kernels.py — fused level-scan pipeline (one-shot rung)
    "consensus_pipeline": ("oneshot", "pipeline", None),
    "_divide_rounds": ("oneshot", "rounds", "consensus_pipeline"),
    "_decide_fame": ("oneshot", "fame", "consensus_pipeline"),
    "_decide_round_received": ("oneshot", "received", "consensus_pipeline"),
    # tpu/frontier.py — round-frontier pipeline
    "build_inv": ("frontier", "inv", None),
    "_frontier_rounds": ("frontier", "walk", "frontier_pipeline"),
    "frontier_pipeline": ("frontier", "pipeline", None),
    # tpu/incremental.py — resident live-engine steps
    "_step_full": ("incremental", "step", None),
    "multi_step": ("incremental", "multi_step", None),
    # tpu/doubling.py — log-diameter cold path
    "_closure_la": ("doubling", "closure", None),
    "_walk_chunk": ("doubling", "walk", None),
    "_fame_received": ("doubling", "fame_received", None),
    "_lamport_levels_scan": ("doubling", "levels", None),
    # tpu/live.py — packed result fetch program
    "_pack_results": ("live", "pack", None),
    # tpu/sharded.py — mesh-partitioned stages
    "_fame_setup_staged": ("sharded", "fame_setup", None),
    "local_fame": ("sharded", "fame", None),
    "local_received": ("sharded", "received", None),
    "_fame_tables": ("sharded", "fame_tables", None),
    "local_walk": ("sharded", "walk", None),
}


def seam_entries() -> List[str]:
    """Entries that carry their own host-side timing seam."""
    return sorted(e for e, (_, _, cov) in ENTRY_INFO.items() if cov is None)


def covered_entries() -> Dict[str, str]:
    """{covered entry: covering seam} for contract entries whose trace
    lives inside another staged body."""
    return {
        e: cov for e, (_, _, cov) in ENTRY_INFO.items() if cov is not None
    }


# ---------------------------------------------------------------------------
# jax.monitoring hook — process-wide, armed only inside seams
# ---------------------------------------------------------------------------

# thread-local stack of per-seam accumulators; the listener is a no-op
# on threads with an empty stack (and before the first ledger exists)
_MON = threading.local()

_LISTENER_LOCK = threading.Lock()
_LISTENER_REGISTERED = False

_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _on_jax_event(name: str, secs: float, **_kw) -> None:
    stack = getattr(_MON, "stack", None)
    if not stack:
        return
    acc = stack[-1]
    if name == _TRACE_EVENT:
        acc["traces"] += 1
    elif name == _COMPILE_EVENT:
        acc["compiles"] += 1


def _ensure_listener() -> None:
    """Register the compile/trace listener, once per process, at the
    first seam call — so a cpu-backend node, whose ledger never sees a
    seam, never imports jax. A failed registration raises: a silent one
    would make "retraces must stay 0" pass vacuously."""
    global _LISTENER_REGISTERED
    if _LISTENER_REGISTERED:
        return
    with _LISTENER_LOCK:
        if _LISTENER_REGISTERED:
            return
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(_on_jax_event)
        _LISTENER_REGISTERED = True


def _monitor_begin() -> dict:
    stack = getattr(_MON, "stack", None)
    if stack is None:
        stack = _MON.stack = []
    acc = {"traces": 0, "compiles": 0}
    stack.append(acc)
    return acc


def _monitor_end(acc: dict) -> Tuple[int, int]:
    stack = getattr(_MON, "stack", None)
    if stack and stack[-1] is acc:
        stack.pop()
        # nested seams: bubble the inner events up so the outer seam's
        # view of "did anything compile under me" stays complete
        if stack:
            stack[-1]["traces"] += acc["traces"]
            stack[-1]["compiles"] += acc["compiles"]
    return acc["compiles"], acc["traces"]


# ---------------------------------------------------------------------------
# ambient activation context (rung + layout, per thread)
# ---------------------------------------------------------------------------

_TL = threading.local()


class _Ctx:
    __slots__ = ("ledger", "rung", "layout", "seam_seconds")

    def __init__(self, ledger: "DeviceLedger", rung: str, layout: str):
        self.ledger = ledger
        self.rung = rung
        self.layout = layout
        # wall seconds the seams below this activation already accounted
        # for; activate(measure_sync=True) subtracts it from the block's
        # total wall time to expose the host-sync residual
        self.seam_seconds = 0.0


def active_ledger() -> Optional["DeviceLedger"]:
    ctx = getattr(_TL, "ctx", None)
    return ctx.ledger if ctx is not None else None


def ledger_call(entry: str, fn, *args, **kwargs):
    """Module-level seam for call sites without an obs handle (deep in
    tpu/): times `fn(*args, **kwargs)` into the thread's active ledger,
    or passes straight through when none is active. `entry` must be a
    static literal (obs-ledger-static-name)."""
    ctx = getattr(_TL, "ctx", None)
    if ctx is None:
        return fn(*args, **kwargs)
    return ctx.ledger.call(entry, fn, *args, **kwargs)  # obs-ok: delegate, entry checked at ledger_call sites


def _sig_of(value) -> Any:
    shape = getattr(value, "shape", None)
    if shape is not None:
        return ("a", tuple(shape), str(getattr(value, "dtype", "?")))
    if isinstance(value, (int, float, bool, str, bytes, type(None))):
        return value
    if isinstance(value, tuple):
        # pytree arguments (IncState, Batch): their leaves' shapes ARE the
        # signature — multi_step at K=4 and at K=16 differ only there, and
        # naming both "Batch" booked the second compile as a retrace
        return (type(value).__name__,) + tuple(_sig_of(v) for v in value)
    return type(value).__name__


def _abstract_sig(args, kwargs) -> Tuple:
    return (
        tuple(_sig_of(a) for a in args),
        tuple(sorted((k, _sig_of(v)) for k, v in kwargs.items())),
    )


def _nbytes(value) -> int:
    shape = getattr(value, "shape", None)
    dtype = getattr(value, "dtype", None)
    if shape is None or dtype is None:
        return 0
    n = 1
    for d in shape:
        n *= int(d)
    return n * int(getattr(dtype, "itemsize", 4))


def _tree_bytes(value) -> int:
    if isinstance(value, (tuple, list)):
        return sum(_tree_bytes(v) for v in value)
    if hasattr(value, "_fields"):  # NamedTuple results (PassResults etc.)
        return sum(_tree_bytes(getattr(value, f)) for f in value._fields)
    return _nbytes(value)


# ---------------------------------------------------------------------------
# the ledger
# ---------------------------------------------------------------------------


class DeviceLedger:
    """Per-node device-time cost ledger.

    Cells are cumulative [calls, seconds] keyed by
    (rung, pass, layout, component); per-entry stats carry the
    compile/retrace accounting and the byte-exact cost sidecar. All
    mutation happens under one small lock — seams run on the serve
    thread AND on dispatch workers."""

    def __init__(self, obs):
        self.obs = obs
        self.clock = obs.clock
        # clock policy: only the real wall clock is ever read. Any
        # injected virtual clock (sim) yields 0.0 durations WITHOUT a
        # clock read, keeping worker-thread seams off the SimClock and
        # same-seed snapshots byte-identical.
        self._real = isinstance(self.clock, SystemClock)
        self._lock = threading.Lock()
        self._cells: Dict[Tuple[str, str, str, str], List[float]] = {}  # guarded-by: _lock
        self._entries: Dict[str, Dict[str, float]] = {}  # guarded-by: _lock
        self._seen: Dict[str, set] = {}  # guarded-by: _lock
        # unguarded-ok: write-once memo keyed by entry; a racing double
        # probe writes the same deterministic cost doc twice
        self._cost: Dict[str, Optional[dict]] = {}
        self._ring: deque = deque(maxlen=TIMELINE_CAPACITY)  # guarded-by: _lock
        self._m_pass = obs.histogram(
            "babble_kernel_pass_seconds",
            "Device-time ledger: seconds per kernel pass / lifecycle "
            "component, by engine rung and voting-table layout",
            labels=("rung", "pass", "layout"),
        )
        self._c_compiles = obs.counter(
            "babble_kernel_compiles_total",
            "Seam calls that compiled a new executable for a new abstract "
            "signature, per kernel-contract entry point",
            labels=("entry",),
        )
        self._c_retraces = obs.counter(
            "babble_kernel_retraces_total",
            "Seam calls that re-traced an abstract signature already "
            "seen (a silent retrace — the dynamic kernel-retrace-hazard)",
            labels=("entry",),
        )
        self._h_compile = obs.histogram(
            "babble_kernel_compile_seconds",
            "Wall seconds of seam calls that compiled, per entry point",
            labels=("entry",),
        )

    # -- clock policy ------------------------------------------------------

    def now(self) -> float:
        return self.clock.monotonic() if self._real else 0.0

    # -- activation --------------------------------------------------------

    @contextmanager
    def activate(self, rung: str, layout: str = "wide",
                 measure_sync: bool = False):
        """Bind this ledger + (rung, layout) to the current thread so
        `ledger_call` seams below this frame attribute to it. The rung
        name must be a static literal (obs-ledger-static-name).

        With `measure_sync=True` the activation also times the whole
        block and books the residual — wall seconds NOT accounted for by
        the seams inside it — under the `sync` component. On an async
        dispatch rung that residual is where the device compute actually
        completes: each seam returns at dispatch, and the deferred work
        is paid at the unseamed host syncs (np.asarray fetches) between
        passes, so per-pass run cells alone under-count the blocked wall
        time. run + compile + sync covers it."""
        prev = getattr(_TL, "ctx", None)
        ctx = _Ctx(self, rung, layout)
        _TL.ctx = ctx
        t0 = self.now() if measure_sync else 0.0
        try:
            yield self
        finally:
            _TL.ctx = prev
            if measure_sync:
                residual = max(0.0, self.now() - t0 - ctx.seam_seconds)
                self.component(rung, "sync", residual, layout=layout)

    # -- the seam ----------------------------------------------------------

    def call(self, entry: str, fn, *args, **kwargs):
        """Time one host call into a staged callable and attribute it.

        Duration goes to the entry's (rung, pass, layout) cell — under
        the `compile` component when jax compiled during the call, else
        under `run`. Compile events on a signature this ledger has seen
        before count as a retrace, not a compile."""
        info = ENTRY_INFO.get(entry)
        pass_name = info[1] if info else entry
        ctx = getattr(_TL, "ctx", None)
        if ctx is not None and ctx.ledger is self:
            rung, layout = ctx.rung, ctx.layout
        else:
            rung = info[0] if info else "unknown"
            layout = "wide"
        sig = (layout,) + _abstract_sig(args, kwargs)
        _ensure_listener()
        acc = _monitor_begin()
        t0 = self.now()
        try:
            out = fn(*args, **kwargs)
        finally:
            compiles, traces = _monitor_end(acc)
        dt = self.now() - t0
        if ctx is not None and ctx.ledger is self:
            ctx.seam_seconds += dt  # thread-local; no lock needed
        bytes_in = sum(_nbytes(a) for a in args)
        bytes_out = _tree_bytes(out)
        with self._lock:
            seen = self._seen.setdefault(entry, set())
            fresh = sig not in seen
            seen.add(sig)
            est = self._entries.setdefault(entry, {
                "calls": 0, "seconds": 0.0, "compiles": 0, "retraces": 0,
                "compile_seconds": 0.0, "bytes_in": 0, "bytes_out": 0,
            })
            est["calls"] += 1
            est["seconds"] += dt
            est["bytes_in"] += bytes_in
            est["bytes_out"] += bytes_out
            compiled = compiles > 0 and fresh
            retraced = (compiles > 0 or traces > 0) and not fresh
            if compiled:
                est["compiles"] += 1
            if retraced:
                est["retraces"] += 1
            # the compile COMPONENT is "time spent compiling", which a
            # silent retrace also pays — the counters above keep legit
            # compiles (new signature) and retraces (seen one) apart
            comp = "compile" if compiles > 0 else "run"
            if compiles > 0:
                est["compile_seconds"] += dt
            cell = self._cells.setdefault(
                (rung, pass_name, layout, comp), [0, 0.0]
            )
            cell[0] += 1
            cell[1] += dt
            self._ring.append({
                "entry": entry, "rung": rung, "pass": pass_name,
                "layout": layout, "component": comp, "t0": t0, "dt": dt,
                "compiles": compiles, "traces": traces,
            })
        if compiled:
            self._c_compiles.labels(entry=entry).inc()
            self._h_compile.labels(entry=entry).observe(dt)
        if retraced:
            self._c_retraces.labels(entry=entry).inc()
        self._m_pass.labels(
            rung=rung, layout=layout, **{"pass": pass_name}
        ).observe(dt)
        return out

    # -- lifecycle components ----------------------------------------------

    def component(self, rung: str, component: str, seconds: float,
                  layout: str = "wide", calls: int = 1) -> None:
        """Record host-side lifecycle time (stage/fetch/integrate) for a
        dispatch on `rung`. `rung` and `component` must be static
        literals (obs-ledger-static-name). Callers measure `seconds`
        with the ledger's own clock policy (`now()`), so the sim records
        deterministic zeros."""
        if component not in COMPONENTS:
            raise ValueError(f"unknown ledger component {component!r}")
        with self._lock:
            cell = self._cells.setdefault(
                (rung, "dispatch", layout, component), [0, 0.0]
            )
            cell[0] += calls
            cell[1] += seconds
        self._m_pass.labels(
            rung=rung, **{"pass": component}, layout=layout
        ).observe(seconds)

    # -- export ------------------------------------------------------------

    def snapshot(self) -> Dict[str, Any]:
        """Canonical ledger document: cumulative cells, per-entry
        compile/retrace stats, and per-(rung, pass) shares of total
        attributed seconds (the trend-attribution input). Deterministic
        under the sim clock policy; feeds `fingerprint()`."""
        with self._lock:
            cells = {
                "/".join(k): [c[0], round(c[1], 9)]
                for k, c in sorted(self._cells.items())
            }
            entries = {
                e: {
                    "calls": st["calls"],
                    "seconds": round(st["seconds"], 9),
                    "compiles": st["compiles"],
                    "retraces": st["retraces"],
                    "compile_seconds": round(st["compile_seconds"], 9),
                    "bytes_in": st["bytes_in"],
                    "bytes_out": st["bytes_out"],
                }
                for e, st in sorted(self._entries.items())
            }
            total = sum(c[1] for c in self._cells.values())
            shares = {}
            for (rung, pass_name, layout, _comp), c in self._cells.items():
                key = f"{rung}/{pass_name}/{layout}"
                shares[key] = shares.get(key, 0.0) + c[1]
            shares = {
                k: round(v / total, 6) if total > 0 else 0.0
                for k, v in sorted(shares.items())
            }
        return {
            "cells": cells,
            "entries": entries,
            "total_seconds": round(total, 9),
            "shares": shares,
        }

    def fingerprint(self) -> str:
        """sha256 of the canonical snapshot — joins the SimCluster
        determinism contract (digest/trace/flightrec/provenance)."""
        doc = json.dumps(self.snapshot(), sort_keys=True,
                         separators=(",", ":"))
        return hashlib.sha256(doc.encode()).hexdigest()

    def entry_stats(self, entry: str) -> Optional[Dict[str, float]]:
        with self._lock:
            st = self._entries.get(entry)
            return dict(st) if st is not None else None

    def recent(self) -> List[dict]:
        with self._lock:
            return list(self._ring)

    # -- cost-model sidecar -------------------------------------------------

    def probe_cost(self, entry: str, fn, *args, **kwargs) -> Optional[dict]:
        """One-shot XLA cost-analysis probe for `entry` (FLOPs / bytes
        accessed). Runs OUTSIDE the monitoring seam (its trace events
        must not count as retraces) and only on the real clock — probe
        results never enter the fingerprint."""
        if entry in self._cost:
            return self._cost[entry]
        cost: Optional[dict] = None
        if self._real and hasattr(fn, "lower"):
            try:
                ca = fn.lower(*args, **kwargs).compile().cost_analysis()
                if isinstance(ca, (list, tuple)):
                    ca = ca[0] if ca else None
                if isinstance(ca, dict):
                    cost = {
                        "flops": float(ca.get("flops", 0.0)),
                        "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
                    }
            except Exception:  # noqa: BLE001 — backend without cost model
                cost = None
        self._cost[entry] = cost
        return cost

    def efficiency(self) -> Dict[str, Any]:
        """Measured time next to the static cost model, per entry: bytes
        moved per second (exact, from abstract signatures) and FLOPs per
        second where an XLA cost probe ran. The efficiency ratio the
        mesh-scaling work reads before trusting a rung's headline."""
        out: Dict[str, Any] = {}
        with self._lock:
            items = [(e, dict(st)) for e, st in sorted(self._entries.items())]
        for entry, st in items:
            run_s = st["seconds"] - st["compile_seconds"]
            moved = st["bytes_in"] + st["bytes_out"]
            doc: Dict[str, Any] = {
                "calls": st["calls"],
                "run_seconds": round(run_s, 9),
                "bytes_moved": moved,
                "gbytes_per_sec": (
                    round(moved / run_s / 1e9, 3) if run_s > 0 else None
                ),
            }
            cost = self._cost.get(entry)
            if cost:
                doc["flops_est"] = cost["flops"] * st["calls"]
                doc["gflops_per_sec"] = (
                    round(cost["flops"] * st["calls"] / run_s / 1e9, 3)
                    if run_s > 0 else None
                )
            out[entry] = doc
        return out


# ---------------------------------------------------------------------------
# retrace budget gate (queued-mesh benches)
# ---------------------------------------------------------------------------


def retrace_baseline(obs) -> Dict[str, float]:
    """Per-entry retrace counts at warmup time — subtract from a later
    reading to get the steady-state delta the budget gate asserts on."""
    return _retrace_values(obs)


def _retrace_values(obs) -> Dict[str, float]:
    out: Dict[str, float] = {}
    counter = obs.registry.get("babble_kernel_retraces_total")
    if counter is None:
        return out
    for entry in ENTRY_INFO:
        try:
            v = counter.value(entry=entry)
        except Exception:  # noqa: BLE001 — series not materialized yet
            v = 0.0
        if v:
            out[entry] = v
    return out


def retrace_delta(obs, baseline: Dict[str, float]) -> Dict[str, float]:
    """Entries whose retrace counter moved past the warmup baseline.
    Non-empty = the steady-state retrace budget (zero) is blown; the
    caller names the offenders and dumps the flight ring."""
    now = _retrace_values(obs)
    out = {}
    for entry, v in now.items():
        d = v - baseline.get(entry, 0.0)
        if d > 0:
            out[entry] = d
    return out


# ---------------------------------------------------------------------------
# unified host+device timeline (GET /debug/timeline)
# ---------------------------------------------------------------------------

# device lanes start above any real host thread id the span tracer used
_DEVICE_TID_BASE = 1 << 20
_QUEUE_TID = _DEVICE_TID_BASE - 1


def build_timeline(obs, trace_id: Optional[str] = None) -> dict:
    """One Chrome-trace/Perfetto document merging three sources:

    - host lanes: the SpanTracer ring (gossip/serve/integrate spans),
      exactly as `GET /debug/trace` renders them;
    - device pass lanes: the ledger's seam ring, one lane per
      (rung, pass) with compile/retrace annotations per slice;
    - queue lane: `dispatch.enqueue`/`dispatch.integrate` flight
      records as instant events plus a queue-occupancy counter track.

    All timestamps share the node's monotonic clock, so host blocking
    and device execution line up on one axis."""
    doc = obs.tracer.to_chrome_trace(
        pid=getattr(obs, "node_id", 0), trace_id=trace_id,
    )
    events = doc.setdefault("traceEvents", [])
    pid = getattr(obs, "node_id", 0)

    ledger = getattr(obs, "devledger", None)
    if ledger is not None:
        lanes: Dict[Tuple[str, str], int] = {}
        for ev in ledger.recent():
            lane_key = (ev["rung"], ev["pass"])
            tid = lanes.get(lane_key)
            if tid is None:
                tid = _DEVICE_TID_BASE + len(lanes)
                lanes[lane_key] = tid
                events.append({
                    "ph": "M", "name": "thread_name", "pid": pid,
                    "tid": tid,
                    "args": {"name": f"device:{lane_key[0]}/{lane_key[1]}"},
                })
            events.append({
                "name": f"{ev['entry']}[{ev['layout']}]",
                "cat": "device," + ev["component"],
                "ph": "X",
                "ts": round(ev["t0"] * 1e6, 3),
                "dur": round(ev["dt"] * 1e6, 3),
                "pid": pid, "tid": tid,
                "args": {
                    "component": ev["component"],
                    "compiles": ev["compiles"],
                    "traces": ev["traces"],
                },
            })

    flightrec = getattr(obs, "flightrec", None)
    if flightrec is not None:
        queue_named = False
        for rec in flightrec.records():
            if rec.name not in ("dispatch.enqueue", "dispatch.integrate"):
                continue
            if not queue_named:
                events.append({
                    "ph": "M", "name": "thread_name", "pid": pid,
                    "tid": _QUEUE_TID, "args": {"name": "dispatch-queue"},
                })
                queue_named = True
            ts = round(rec.t * 1e6, 3)
            events.append({
                "name": rec.name, "cat": "dispatch", "ph": "i", "s": "t",
                "ts": ts, "pid": pid, "tid": _QUEUE_TID,
                "args": dict(rec.fields),
            })
            depth = rec.fields.get("depth")
            if depth is not None:
                events.append({
                    "name": "queue_depth", "cat": "dispatch", "ph": "C",
                    "ts": ts, "pid": pid,
                    "args": {"depth": depth},
                })
    return doc
