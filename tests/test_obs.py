"""Observability-layer tests (babble_tpu/obs/, docs/observability.md):
bucket math, Prometheus exposition format, bounded label cardinality,
registry get-or-create semantics, span-ring truncation, Chrome trace
export shape, and the headline determinism property — two same-seed
simulator runs produce byte-identical commit-latency histograms.
"""

import json

import pytest

from babble_tpu.obs import (
    DEFAULT_LATENCY_BUCKETS,
    MAX_LABEL_SETS,
    Observability,
    SpanTracer,
    log_buckets,
)
from babble_tpu.obs.metrics import MetricsRegistry
from babble_tpu.sim import SimClock, run_one


# ----------------------------------------------------------------------
# bucket math
# ----------------------------------------------------------------------

def test_log_buckets_geometric():
    assert log_buckets(1, 2.0, 4) == (1.0, 2.0, 4.0, 8.0)
    bs = log_buckets(0.001, 2.0, 17)
    assert bs == DEFAULT_LATENCY_BUCKETS
    assert bs[0] == 0.001 and bs[-1] == pytest.approx(65.536)
    with pytest.raises(ValueError):
        log_buckets(0, 2.0, 4)
    with pytest.raises(ValueError):
        log_buckets(1, 1.0, 4)


def test_histogram_bucket_placement_and_render():
    reg = MetricsRegistry()
    h = reg.histogram("h_seconds", "x", buckets=(0.1, 1.0, 10.0))
    # boundary values land in the bucket whose bound they equal (le is
    # inclusive, as in Prometheus)
    for v in (0.05, 0.1, 0.5, 1.0, 10.0, 99.0):
        h.observe(v)
    assert h.stats() == (6, pytest.approx(110.65))
    text = reg.expose()
    assert '# TYPE h_seconds histogram' in text
    assert 'h_seconds_bucket{le="0.1"} 2' in text  # cumulative
    assert 'h_seconds_bucket{le="1"} 4' in text
    assert 'h_seconds_bucket{le="10"} 5' in text
    assert 'h_seconds_bucket{le="+Inf"} 6' in text
    assert 'h_seconds_count 6' in text
    assert text.endswith("\n")


def test_histogram_rejects_unsorted_buckets():
    reg = MetricsRegistry()
    with pytest.raises(ValueError):
        reg.histogram("bad_h", "x", buckets=(1.0, 0.5))


# ----------------------------------------------------------------------
# exposition format + labels
# ----------------------------------------------------------------------

def test_counter_gauge_exposition():
    reg = MetricsRegistry()
    c = reg.counter("c_total", "counted things", labels=("result",))
    c.labels(result="ok").inc()
    c.labels(result="ok").inc(2)
    c.labels(result="error").inc()
    g = reg.gauge("g_now", "a level")
    g.set(2.5)
    text = reg.expose()
    assert "# HELP c_total counted things" in text
    assert "# TYPE c_total counter" in text
    assert 'c_total{result="error"} 1' in text
    assert 'c_total{result="ok"} 3' in text
    assert "# TYPE g_now gauge" in text
    assert "g_now 2.5" in text
    # integral floats render without the dot
    g.set(4.0)
    assert "g_now 4\n" in reg.expose()
    with pytest.raises(ValueError):
        c.labels(result="ok").inc(-1)  # counters only go up
    with pytest.raises(ValueError):
        c.inc()  # unlabeled use of a labeled metric


def test_gauge_set_function_is_read_at_render():
    reg = MetricsRegistry()
    box = {"v": 1.0}
    reg.gauge("live_g", "x").set_function(lambda: box["v"])
    assert "live_g 1" in reg.expose()
    box["v"] = 7.0
    assert "live_g 7" in reg.expose()
    # a broken callback degrades to 0, never breaks the scrape
    reg.gauge("live_g", "x").set_function(lambda: 1 / 0)
    assert "live_g 0" in reg.expose()


def test_label_overflow_collapses_to_other():
    reg = MetricsRegistry()
    c = reg.counter("many_total", "x", labels=("peer",))
    for i in range(MAX_LABEL_SETS + 10):
        c.labels(peer=f"p{i}").inc()
    assert c.value(peer="p0") == 1.0
    assert c.value(peer="other") == 10.0  # overflow series absorbs the rest
    text = reg.expose()
    assert text.count("many_total{") == MAX_LABEL_SETS + 1


def test_registry_get_or_create_and_mismatch():
    reg = MetricsRegistry()
    c1 = reg.counter("same_total", "x")
    assert reg.counter("same_total") is c1
    assert reg.get("same_total") is c1
    assert reg.get("nope") is None
    with pytest.raises(ValueError):
        reg.gauge("same_total")  # kind mismatch
    with pytest.raises(ValueError):
        reg.counter("same_total", labels=("a",))  # label-set mismatch
    with pytest.raises(ValueError):
        reg.counter("bad name")
    with pytest.raises(ValueError):
        reg.counter("ok_total", labels=("bad-label",))


def test_snapshot_flat_shapes():
    reg = MetricsRegistry()
    reg.counter("c_total", "x", labels=("k",)).labels(k="a").inc(3)
    reg.histogram("h_s", "x", buckets=(1.0,)).observe(0.5)
    flat = reg.snapshot_flat()
    assert flat["c_total{a}"] == 3
    assert flat["h_s_count"] == 1
    assert flat["h_s_sum"] == 0.5


# ----------------------------------------------------------------------
# span tracer
# ----------------------------------------------------------------------

def test_span_ring_truncates_oldest():
    tracer = SpanTracer(capacity=8)
    for i in range(20):
        tracer.record(f"s{i}", float(i), 0.5)
    spans = tracer.spans()
    assert [s.name for s in spans] == [f"s{i}" for i in range(12, 20)]
    assert tracer.dropped == 12


def test_span_context_manager_times_through_clock():
    clock = SimClock()
    obs = Observability(clock=clock)
    h = obs.histogram("span_h_seconds", "x")
    with obs.span("work", histogram=h, phase="p1"):
        clock.now += 0.25
    [sp] = obs.tracer.spans()
    assert sp.name == "work"
    assert sp.duration == 0.25
    assert sp.attrs == {"phase": "p1"}
    assert h.stats() == (1, 0.25)


def test_chrome_trace_export_shape():
    tracer = SpanTracer(capacity=8)
    tracer.record("a", 1.0, 0.5, {"k": "v"})
    tracer.record("b", 2.0, 0.25)
    doc = tracer.to_chrome_trace(pid=3)
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    spans = [e for e in evs if e["ph"] == "X"]
    assert len(meta) == 1 and meta[0]["name"] == "thread_name"
    assert [e["name"] for e in spans] == ["a", "b"]
    assert spans[0]["ts"] == 1e6 and spans[0]["dur"] == 5e5  # microseconds
    assert spans[0]["args"] == {"k": "v", "id": 1}  # attrs plus the span's id
    assert spans[1]["args"] == {"id": 2}
    assert all(e["pid"] == 3 for e in evs)
    json.dumps(doc)  # must be directly serializable


# ----------------------------------------------------------------------
# the span tree (ISSUE 25): id and parent per thread, totals that never
# wrap, checkpoints for windowed readings, the profiler annotator, the
# weak set of live tracers
# ----------------------------------------------------------------------

def _tree_on_two_threads():
    """root(a(b), c) on the main thread and, while `a` is open there,
    other(x) on a second thread: {name: span}."""
    import threading

    tracer = SpanTracer(capacity=32)

    def worker():
        with tracer.span("other"):
            with tracer.span("x"):
                pass

    with tracer.span("root"):
        with tracer.span("a"):
            t = threading.Thread(target=worker, name="second")
            t.start()
            t.join(10)
            assert not t.is_alive()
            with tracer.span("b"):
                pass
            tracer.record("marked", 0.0, 0.0)
        with tracer.span("c"):
            pass
    tracer.record("loose", 0.0, 0.0)
    return {s.name: s for s in tracer.spans()}


@pytest.mark.parametrize("child,parent", [
    ("root", None), ("a", "root"), ("b", "a"), ("c", "root"),  # nested, sibling
    ("marked", "a"), ("loose", None),  # record(): whatever is open now
    ("other", None), ("x", "other"),  # a thread has a stack of its own
])
def test_span_parent_is_the_span_open_on_its_thread(child, parent):
    by_name = _tree_on_two_threads()
    ids = [s.id for s in by_name.values()]
    assert sorted(ids) == list(range(1, len(ids) + 1))  # a sequence, no gaps
    got = by_name[child].parent
    assert got == (by_name[parent].id if parent else None)
    assert by_name["x"].thread == "second" != by_name["b"].thread
    doc = SpanTracer(capacity=4)
    with doc.span("p"):
        with doc.span("q", k=1):
            pass
    q, p = [e["args"] for e in doc.to_chrome_trace()["traceEvents"] if e["ph"] == "X"]
    assert p == {"id": 1} and q == {"k": 1, "id": 2, "parent_id": 1}


def _timed(clock, tracer, name, seconds, how):
    if how == "span":
        with tracer.span(name):
            clock.now += seconds
    elif how == "record":
        tracer.record(name, clock.now, seconds)
    else:
        tracer.add(name, seconds)


@pytest.mark.parametrize("how,ring_entries", [("span", 3), ("record", 3), ("add", 0)])
def test_totals_count_every_span_record_and_add(how, ring_entries):
    clock = SimClock()
    tracer = SpanTracer(clock=clock, capacity=2)  # the ring wraps, totals do not
    for seconds in (0.25, 0.5, 1.0):
        _timed(clock, tracer, "work", seconds, how)
    _timed(clock, tracer, "other", 2.0, "add")
    assert tracer.totals() == {"work": (3, 1.75), "other": (1, 2.0)}
    assert len(tracer.spans()) == min(ring_entries, 2)
    assert tracer.dropped == max(ring_entries - 2, 0)


@pytest.mark.parametrize("t0,t1,want", [
    # checkpoints at 0 (empty), 1, 3, 6: between the first and last inside
    (0.0, 10.0, {"w": (3, 6.0), "late": (1, 0.5)}),
    (0.5, 6.0, {"w": (2, 5.0), "late": (1, 0.5)}),
    (1.0, 3.0, {"w": (1, 2.0)}),
    (2.0, 5.0, {}),   # one checkpoint inside: nothing to take a difference of
    (7.0, 9.0, {}),   # none
])
def test_totals_between_checkpoints(t0, t1, want):
    clock = SimClock()
    tracer = SpanTracer(clock=clock)
    tracer.checkpoint()
    for seconds in (1.0, 2.0, 3.0):
        with tracer.span("w"):
            clock.now += seconds
        if seconds == 3.0:
            tracer.add("late", 0.5)
        tracer.checkpoint()
    assert tracer.totals_between(t0, t1) == want


def test_checkpoints_are_bounded():
    from babble_tpu.obs.trace import CHECKPOINT_CAPACITY

    clock = SimClock()
    tracer = SpanTracer(clock=clock)
    for i in range(CHECKPOINT_CAPACITY + 10):
        clock.now = float(i)
        tracer.add("tick", 1.0)
        tracer.checkpoint()
    got = tracer.totals_between(0.0, float("inf"))
    assert got == {"tick": (CHECKPOINT_CAPACITY - 1, CHECKPOINT_CAPACITY - 1.0)}


class _Note:
    log = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        _Note.log.append(("open", self.name))

    def __exit__(self, *exc):
        _Note.log.append(("close", self.name))


@pytest.mark.parametrize("raises", [False, True])
def test_annotator_wraps_every_span_and_closes_on_error(monkeypatch, raises):
    monkeypatch.setattr(SpanTracer, "annotator", _Note)
    monkeypatch.setattr(_Note, "log", [])
    obs = Observability(clock=SimClock())
    try:
        with obs.span("outer"):
            with obs.span("inner"):
                if raises:
                    raise KeyError("boom")
    except KeyError:
        assert raises
    obs.tracer.add("quiet", 1.0)  # totals only: no annotation
    assert _Note.log == [("open", "babble.outer"), ("open", "babble.inner"),
                         ("close", "babble.inner"), ("close", "babble.outer")]
    assert [s.name for s in obs.tracer.spans()] == ["inner", "outer"]
    assert obs.tracer._stack() == []  # nothing left open after the error


def test_a_cpu_node_imports_no_jax_and_annotates_nothing():
    """The annotator is None until a device backend is chosen, and a
    cpu-backend Core's consensus call, spans and all, never imports jax."""
    import subprocess
    import sys

    code = (
        "import sys\n"
        "from babble_tpu.crypto import generate_key, pub_key_bytes\n"
        "from babble_tpu.hashgraph import InmemStore\n"
        "from babble_tpu.node import Core\n"
        "from babble_tpu.obs import SpanTracer\n"
        "from babble_tpu.peers import Peer, Peers\n"
        "key = generate_key()\n"
        "peers = Peers.from_slice([Peer(net_addr='', pub_key_hex='0x' + "
        "pub_key_bytes(key).hex().upper())])\n"
        "core = Core(0, key, peers, InmemStore(peers, 10))\n"
        "core.run_consensus()\n"
        "totals = core.hg.obs.tracer.totals()\n"
        "assert totals['core.run_consensus'][0] == 1, totals\n"
        "assert totals['consensus.process_decided_rounds'][0] == 1, totals\n"
        "assert SpanTracer.annotator is None\n"
        "assert 'jax' not in sys.modules\n"
    )
    done = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=120)
    assert done.returncode == 0, done.stderr


def test_span_feeds_histogram_and_ledger_from_one_reading():
    clock = SimClock()
    obs = Observability(clock=clock)
    h = obs.histogram("one_reading_seconds", "x")
    with obs.span("live.stage", histogram=h, ledger=("live", "stage", "wide")) as sp:
        clock.now += 0.125
        sp.attrs["events"] = 7
    assert sp.duration == 0.125 and sp.attrs == {"events": 7}
    assert h.stats() == (1, 0.125)
    assert obs.tracer.totals()["live.stage"] == (1, 0.125)
    assert obs.devledger.snapshot()["cells"]["live/dispatch/wide/stage"] == [1, 0.125]


def test_live_tracers_drops_a_collected_tracer():
    import gc

    from babble_tpu.obs import live_tracers

    kept = Observability()
    gone = Observability()
    assert {kept.tracer, gone.tracer} <= set(live_tracers())
    gone_id = id(gone.tracer)
    del gone
    gc.collect()
    alive = live_tracers()
    assert kept.tracer in alive
    assert gone_id not in {id(t) for t in alive}


def test_a_checkpointed_tracer_outlives_its_node_for_later_readers():
    """A benchmark's reader runs after the entry has dropped its Core: the
    newest tracers that have checkpointed are held, and only they."""
    import gc
    import weakref

    from babble_tpu.obs import live_tracers
    from babble_tpu.obs.trace import KEPT_TRACERS

    def node(clock):
        obs = Observability(clock=clock)
        obs.tracer.checkpoint()
        with obs.span("w", ledger=("live", "stage", "wide")):
            clock.now += 2.0
        obs.tracer.checkpoint()
        return id(obs.tracer), weakref.ref(obs.devledger)

    ident, ledger = node(SimClock(start=1000.0))
    gc.collect()
    assert ledger() is None  # the tracer holds nothing of the node
    [kept] = [t for t in live_tracers() if id(t) == ident]
    assert kept.totals_between(999.0, 1003.0) == {"w": (1, 2.0)}
    with kept.span("late", ledger=("live", "stage", "wide")):
        pass  # a span after the ledger is gone books nowhere, and is no error
    del kept
    for _ in range(KEPT_TRACERS):
        node(SimClock())
    gc.collect()
    assert ident not in {id(t) for t in live_tracers()}


def test_sim_span_tree_export_is_byte_identical():
    """Two same-seed simulator runs: every node's whole Chrome export
    (ids and parent ids included) and its totals are byte-identical."""
    from babble_tpu.sim import SimCluster, preset_plan

    def export(seed):
        cluster = SimCluster(n=4, seed=seed, plan=preset_plan("lossy", 4))
        cluster.run(until=None, target_block=3)
        doc = cluster.cluster_trace()
        totals = [sorted(sn.node.obs.tracer.totals().items()) for sn in cluster.sns]
        return json.dumps([doc, totals], sort_keys=True)

    a, b = export(5), export(5)
    assert a == b
    spans = [e for e in json.loads(a)[0]["traceEvents"] if e["ph"] == "X"]
    assert any(e["name"] == "core.run_consensus" for e in spans)
    assert any(e["args"].get("parent_id") for e in spans)
    assert a != export(6)


# ----------------------------------------------------------------------
# headline determinism: same-seed sim runs give byte-identical
# commit-latency histograms (ISSUE 4 acceptance)
# ----------------------------------------------------------------------

def test_sim_commit_latency_histogram_deterministic():
    a = run_one(5, plan="lossy", n=4, until=None, target_block=3)
    b = run_one(5, plan="lossy", n=4, until=None, target_block=3)
    assert a["ok"] and b["ok"]
    # the histograms actually measured something: every live node saw
    # commits for transactions it submitted itself
    counts = [
        series["count"]
        for snap in a["commit_latency"].values()
        for series in snap["series"].values()
    ]
    assert counts and all(c > 0 for c in counts)
    # and the whole snapshot — counts, sums, bucket assignment — is
    # byte-identical across the two runs
    assert (
        json.dumps(a["commit_latency"], sort_keys=True)
        == json.dumps(b["commit_latency"], sort_keys=True)
    )


# ----------------------------------------------------------------------
# cross-node causal tracing (ISSUE 5): TraceStore lifecycle, bounded
# memory, wire absorption, filtered export, cluster assembly, watchdog
# ----------------------------------------------------------------------

import logging
import urllib.request

from babble_tpu.obs import (
    TraceStore,
    assemble_cluster_trace,
    span_id_for,
    trace_id_for,
)
from babble_tpu.node.watchdog import LivenessWatchdog
from babble_tpu.service import Service


def _get(url):
    with urllib.request.urlopen(url, timeout=5) as resp:
        return json.loads(resp.read())


class _Ev:
    """Minimal stand-in for a hashgraph event: just its payload."""

    def __init__(self, *txs):
        self._txs = list(txs)

    def transactions(self):
        return self._txs


def _stage_count(obs, name):
    snap = obs.registry.snapshot()
    return snap[name]["series"][""]["count"]


def test_trace_store_stage_flow_and_completion():
    clock = SimClock()
    obs = Observability(clock=clock, node_id=7)
    st = obs.traces
    tx = b"tx-bytes"
    tid = trace_id_for(tx)

    st.begin(tx)
    st.begin(tx)  # idempotent re-submit
    assert len(st) == 1
    ctx = st.get(tid)
    assert ctx.span_id == span_id_for(tid, 7)
    assert ctx.parent == "" and ctx.origin == 7

    clock.advance_to(1.0)
    st.mark_event([tx])
    st.mark_event([tx])  # idempotent per stage
    assert _stage_count(obs, "babble_trace_stage_submit_to_event_seconds") == 1
    clock.advance_to(1.5)
    st.mark_round([tx])
    clock.advance_to(2.0)
    st.mark_famous([tx])
    clock.advance_to(3.0)
    st.mark_commit([tx])
    # commit completes and removes the context — not a drop
    assert len(st) == 0 and st.get(tid) is None
    snap = obs.registry.snapshot()
    assert snap["obs_traces_dropped_total"]["series"].get("", 0.0) == 0.0
    assert snap["babble_trace_stage_famous_to_commit_seconds"]["series"][""]["sum"] == pytest.approx(1.0)
    # post-commit relays carry nothing (clean truncation downstream)
    assert st.contexts_for([_Ev(tx)]) == []
    # every stage span is tagged with the trace and chains to the base span
    spans = [s for s in obs.tracer.spans() if s.attrs and s.attrs.get("trace") == tid]
    assert [s.name for s in spans] == [
        "trace.submit", "trace.event", "trace.round",
        "trace.famous", "trace.commit",
    ]
    assert all(s.attrs["parent"] == ctx.span_id for s in spans if ":" in s.attrs["span"])


def test_trace_store_absorb_and_piggyback():
    clock = SimClock()
    sender = Observability(clock=clock, node_id=0)
    receiver = Observability(clock=clock, node_id=1)
    tx = b"cross-node"
    tid = trace_id_for(tx)
    sender.traces.begin(tx)

    wire = sender.traces.contexts_for([_Ev(tx, b"untraced-tx")])
    assert wire == [{"Id": tid, "Origin": 0, "Span": span_id_for(tid, 0)}]

    clock.advance_to(0.5)
    receiver.traces.absorb(wire)
    receiver.traces.absorb(wire)  # duplicate delivery is harmless
    ctx = receiver.traces.get(tid)
    assert ctx.parent == span_id_for(tid, 0)  # the cross-node causal edge
    assert ctx.span_id == span_id_for(tid, 1)
    assert ctx.marks == {"receive": 0.5}
    # malformed piggyback entries are ignored, not fatal
    receiver.traces.absorb([{"bogus": 1}, "junk", {"Id": ""}])
    assert len(receiver.traces) == 1


def test_trace_store_lru_bound_and_disabled_mode():
    clock = SimClock()
    obs = Observability(clock=clock, node_id=0, trace_capacity=2)
    st = obs.traces
    for i in range(4):
        st.begin(b"tx%d" % i)
    assert len(st) == 2
    snap = obs.registry.snapshot()
    assert snap["obs_traces_dropped_total"]["series"][""] == 2.0
    assert snap["obs_traces_live"]["series"][""] == 2.0
    # eviction is LRU: the two newest survive
    assert st.get(trace_id_for(b"tx3")) is not None
    assert st.get(trace_id_for(b"tx0")) is None

    off = Observability(clock=clock, node_id=0, tracing=False)
    off.traces.begin(b"tx")
    off.traces.absorb([{"Id": "ab", "Origin": 0, "Span": "cd"}])
    assert len(off.traces) == 0
    assert off.traces.contexts_for([_Ev(b"tx")]) == []


def test_chrome_trace_trace_id_filter():
    tracer = SpanTracer(capacity=8)
    tracer.record("trace.event", 1.0, 0.5, {"trace": "t1", "span": "a"})
    tracer.record("trace.event", 2.0, 0.5, {"trace": "t2", "span": "b"})
    tracer.record("gossip", 3.0, 0.5)
    doc = tracer.to_chrome_trace(pid=0, trace_id="t1")
    spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert [e["args"]["trace"] for e in spans] == ["t1"]


def test_assemble_cluster_trace_reroots_unresolved_parents():
    doc_a = {"traceEvents": [
        {"ph": "X", "name": "trace.submit", "pid": 0, "ts": 0, "dur": 0,
         "args": {"trace": "t", "span": "s0", "parent": ""}},
    ]}
    doc_b = {"traceEvents": [
        {"ph": "X", "name": "trace.receive", "pid": 9, "ts": 1, "dur": 0,
         "args": {"trace": "t", "span": "s1", "parent": "s0"}},
        {"ph": "X", "name": "trace.receive", "pid": 9, "ts": 2, "dur": 0,
         "args": {"trace": "t", "span": "s2", "parent": "gone"}},
    ]}
    merged = assemble_cluster_trace([(0, doc_a), (3, doc_b)])
    evs = merged["traceEvents"]
    assert [e["pid"] for e in evs] == [0, 3, 3]  # sim path re-stamps pids
    by_span = {e["args"]["span"]: e["args"] for e in evs}
    assert by_span["s1"]["parent"] == "s0"  # resolvable edge kept
    assert by_span["s2"]["parent"] == "" and by_span["s2"]["truncated"]
    # the source documents were not mutated
    assert doc_b["traceEvents"][1]["args"]["parent"] == "gone"
    # None keeps the exporter's pid (the HTTP federation path)
    kept = assemble_cluster_trace([(None, doc_b)])
    assert [e["pid"] for e in kept["traceEvents"]] == [9, 9]


def test_watchdog_peer_labels_ride_registry_overflow():
    clock = SimClock()
    obs = Observability(clock=clock, node_id=0)
    wd = LivenessWatchdog(
        clock=clock, obs=obs, logger=logging.getLogger("test.wd"),
        deadline=5.0, round_fn=lambda: 1, pending_fn=lambda: 0,
    )
    for i in range(MAX_LABEL_SETS + 10):
        wd.note_sync(f"10.0.0.{i}:1337", ok=True)
    wd.check()
    snap = obs.registry.snapshot()
    for name in ("babble_peer_health", "babble_peer_sync_staleness_seconds"):
        series = snap[name]["series"]
        # novel peers past the cap collapse into the "other" series
        assert len(series) == MAX_LABEL_SETS + 1
        assert "other" in series
    assert snap["babble_peer_health"]["series"]["10.0.0.0:1337"] == 1.0


class _FakeNode:
    def __init__(self, node_id, obs):
        self.id = node_id
        self.obs = obs

    def get_stats(self):
        return {"id": str(self.id)}


def test_service_trace_filter_and_cluster_federation():
    tid = "ab" * 8
    obs0 = Observability(node_id=0)
    obs1 = Observability(node_id=1)
    s0 = span_id_for(tid, 0)
    s1 = span_id_for(tid, 1)
    obs0.tracer.record("trace.submit", 0.0, 0.0,
                       {"trace": tid, "span": s0, "parent": "", "node": 0})
    obs0.tracer.record("gossip", 0.0, 1.0)  # untraced noise
    obs1.tracer.record("trace.receive", 1.0, 0.0,
                       {"trace": tid, "span": s1, "parent": s0, "node": 1})
    obs1.tracer.record("trace.event", 1.0, 0.5,
                       {"trace": "ffff", "span": "x", "parent": ""})

    svc0 = Service("127.0.0.1:0", _FakeNode(0, obs0))
    svc1 = Service("127.0.0.1:0", _FakeNode(1, obs1))
    try:
        svc0.serve()
        svc1.serve()
        base = f"http://{svc0.local_addr()}"

        doc = _get(f"{base}/debug/trace?trace_id={tid}")
        spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert [e["name"] for e in spans] == ["trace.submit"]

        url = (f"{base}/debug/trace/cluster?trace_id={tid}"
               f"&peers={svc1.local_addr()},127.0.0.1:1")
        merged = _get(url)
        spans = [e for e in merged["traceEvents"] if e["ph"] == "X"]
        assert sorted(e["name"] for e in spans) == [
            "trace.receive", "trace.submit",
        ]
        assert {e["pid"] for e in spans} == {0, 1}
        # the cross-node parent edge survived federation
        recv = next(e for e in spans if e["name"] == "trace.receive")
        assert recv["args"]["parent"] == s0
        assert merged["failed_peers"] == ["127.0.0.1:1"]
        assert merged["trace_id"] == tid
    finally:
        svc0.shutdown()
        svc1.shutdown()
