"""PR-9 observability: request-scoped span trees (`repro.ann.trace`),
tail-based sampling + flight recorder, Perfetto export invariants, the
Prometheus exposition (`repro.ann.metrics`), and trace correctness under
the async queue's thread hops and live-index compaction.

Well-formedness here means: every kept tree has exactly one root, every
span is closed (`t1` set), children lie inside their parent's bounds on
the shared monotonic clock, and the stage spans the pipeline promises
(enqueue_wait -> batch_assembly -> route -> execute) are all present —
even when route and execute ran on different worker threads.
"""

import glob
import json
import os
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

from repro.ann import engine, trace
from repro.ann.index import QueryBatch
from repro.ann.live import LiveFilteredIndex
from repro.ann.metrics import MetricsServer, metrics_text
from repro.ann.predicates import Predicate
from repro.ann.service import AsyncBatchQueue, RouterService
from repro.ann.telemetry import TelemetrySink
from repro.ann.trace import (BUCKET_BOUNDS_US, LatencyHistogram, Span,
                             Tracer, bucket_index, perfetto_json)


def _assert_well_formed(root):
    """One closed tree: every span finished, non-negative duration,
    children inside the parent's [t0, t1] on the monotonic clock."""
    for s in root.walk():
        assert s.t1 is not None, f"span {s.name!r} left open"
        assert s.t1 >= s.t0, f"span {s.name!r} negative duration"
        for c in s.children:
            assert c.t0 >= s.t0 - 1e-9, \
                f"{c.name!r} starts before parent {s.name!r}"
            assert c.t1 <= s.t1 + 1e-9, \
                f"{c.name!r} ends after parent {s.name!r}"


# ---------------------------------------------------------------- spans


def test_span_is_noop_without_active_trace():
    with trace.span("anything", x=1) as s:
        assert s is None
        trace.annotate(y=2)         # all silently ignored
        trace.count("n")
    assert trace.current() is None


def test_span_tree_nesting_attrs_and_annotate():
    tr = Tracer(sample=1.0)
    with tr.trace("root", q=4) as root:
        with trace.span("a") as a:
            trace.annotate(k=10)
            with trace.span("a1"):
                trace.count("rows", 3)
                trace.count("rows", 2)
        with trace.span("b"):
            pass
    assert [c.name for c in root.children] == ["a", "b"]
    assert a.attrs["k"] == 10
    assert root.find("a1").attrs["rows"] == 5
    assert root.attrs["q"] == 4
    _assert_well_formed(root)
    assert tr.stats()["traces"] == 1 and tr.stats()["kept"] == 1


def test_span_records_exception_and_trace_is_kept():
    tr = Tracer(sample=0.0)          # head sampling would drop it...
    with pytest.raises(RuntimeError):
        with tr.trace("root"):
            with trace.span("inner"):
                raise RuntimeError("boom")
    assert tr.stats()["errors"] == 1
    flight = tr.flight()             # ...but errors are always kept
    assert len(flight) == 1 and flight[0]["reason"] == "error"
    assert "boom" in flight[0]["error"]
    _assert_well_formed(flight[0]["root"])


def test_attach_propagates_across_threads_and_none_is_inert():
    tr = Tracer(sample=1.0)
    root = tr.start("request")

    def worker():
        with trace.attach(root):
            with trace.span("work", thread=True):
                pass

    t = threading.Thread(target=worker)
    t.start()
    t.join()
    tr.finish(root)
    assert [c.name for c in root.children] == ["work"]
    _assert_well_formed(root)
    with trace.attach(None) as s:    # optional-root call sites
        assert s is None


def test_maybe_trace_nests_instead_of_double_rooting():
    tr = Tracer(sample=1.0)
    with trace.maybe_trace(tr, "outer"):
        with trace.maybe_trace(tr, "inner"):   # ambient active: nests
            pass
    assert tr.stats()["traces"] == 1           # one root, not two
    root = tr.recent()[-1]
    assert root.name == "outer"
    assert [c.name for c in root.children] == ["inner"]
    with trace.maybe_trace(None, "off") as s:  # no tracer, no ambient
        assert s is None


class _RecordingAnnotation:
    """Stands in for `jax.profiler.TraceAnnotation`: records each build
    and checks that every entered annotation is left on its thread."""

    built: list = []
    open_: dict = {}

    def __init__(self, name, **meta):
        self.name, self.meta = name, meta
        _RecordingAnnotation.built.append((name, meta))

    def __enter__(self):
        key = threading.get_ident()
        _RecordingAnnotation.open_.setdefault(key, []).append(self.name)
        return self

    def __exit__(self, *exc):
        stack = _RecordingAnnotation.open_[threading.get_ident()]
        assert stack.pop() == self.name
        return False


@pytest.fixture
def recorded_annotations(monkeypatch):
    import jax.profiler

    _RecordingAnnotation.built = []
    _RecordingAnnotation.open_ = {}
    monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                        _RecordingAnnotation)
    yield _RecordingAnnotation
    assert not any(_RecordingAnnotation.open_.values())


def test_profiler_annotation_per_span_only_under_a_trace(
        recorded_annotations):
    with trace.span("orphan", q=1):           # no trace: nothing built
        trace.count("launches")
    assert recorded_annotations.built == []
    tr = Tracer(sample=1.0)
    with tr.trace("search", q=4, pred=1) as root:
        with trace.span("group", method="sieve", ps="b1", q=2,
                        rows=np.int64(3), bits=[1, 2]):
            with trace.launch(64, 8):
                pass
        root.child("enqueue_wait", t0=root.t0, t1=root.t0)  # after the fact
    tr.finish(tr.start("detached"))
    names = [n for n, _ in recorded_annotations.built]
    assert names == ["repro.search", "repro.group", "repro.launch"]
    meta = dict(recorded_annotations.built)
    assert meta["repro.search"] == {"q": 4, "pred": 1,
                                    "trace_id": root.trace_id}
    # scalar attributes only: numpy scalars and lists stay in the tracer
    assert meta["repro.group"] == {"method": "sieve", "ps": "b1", "q": 2}
    assert meta["repro.launch"] == {"launches": 1, "slots": 64,
                                    "pad_slots": 8}
    h = tr.histograms()
    assert h["search"]["counters"] == {"launches": 1, "slots": 64,
                                       "pad_slots": 8}


def _hand_span(parent, name, t0, t1, **counts):
    s = parent.child(name, t0=t0, t1=t1)
    with trace.attach(s):
        for k, v in counts.items():
            trace.count(k, v)
    return s


def test_host_time_and_counters_on_a_hand_built_tree():
    """search [0, 10]: route [0, 4] holds launches [1, 2] and [1.5, 3]
    (overlapping: their union is 2); execute [4, 10] holds a group
    [4, 9] with a launch [5, 8], and a distances span [9, 10] with no
    launch."""
    tr = Tracer(sample=1.0)
    root = tr.start("search")
    root.t0 = 0.0
    with trace.attach(root):
        trace.count("queries", 8)
    route = _hand_span(root, "route", 0.0, 4.0)
    _hand_span(route, trace.LAUNCH, 1.0, 2.0, launches=1, slots=8,
               pad_slots=0)
    _hand_span(route, trace.LAUNCH, 1.5, 3.0, launches=1, slots=8,
               pad_slots=0)
    execute = _hand_span(root, "execute", 4.0, 10.0)
    group = _hand_span(execute, "group", 4.0, 9.0, cand_rows=96)
    _hand_span(group, trace.LAUNCH, 5.0, 8.0, launches=1, slots=16,
               pad_slots=8)
    _hand_span(execute, "distances", 9.0, 10.0)
    root.t1 = 10.0
    tr.finish(root)
    h = tr.histograms()
    us = {n: h[n]["host_us"] for n in h}
    assert us["route"] == pytest.approx(2e6)         # 4 − union 2
    assert us["group"] == pytest.approx(2e6)         # 5 − 3
    assert us["execute"] == pytest.approx(3e6)       # 6 − 3
    assert us["distances"] == pytest.approx(1e6)
    assert us["search"] == pytest.approx(5e6)        # 10 − 2 − 3
    assert us[trace.LAUNCH] == pytest.approx(1e6 + 1.5e6 + 3e6)
    assert h["search"]["counters"] == {"queries": 8, "launches": 3,
                                       "slots": 32, "pad_slots": 8,
                                       "cand_rows": 96}
    assert h["route"]["counters"] == {"launches": 2, "slots": 16,
                                      "pad_slots": 0}
    assert h["group"]["counters"] == {"cand_rows": 96, "launches": 1,
                                      "slots": 16, "pad_slots": 8}
    assert h[trace.LAUNCH]["counters"] == {"launches": 3, "slots": 32,
                                           "pad_slots": 8}
    assert h["distances"]["counters"] == {}
    assert root.find("group").attrs["cand_rows"] == 96   # still attrs
    tr.clear()
    assert tr.histograms() == {}


@pytest.mark.parametrize("n, launches, pad", [(256, 4, 0), (100, 2, 28)])
def test_run_chunked_counts_launches_and_padding(n, launches, pad):
    tr = Tracer(sample=1.0)
    q = np.arange(n * 3, dtype=np.float32).reshape(n, 3)
    with tr.trace("search"):
        out = engine.run_chunked(lambda qv: qv * 2, n, q)
    np.testing.assert_array_equal(out, q * 2)
    h = tr.histograms()
    assert h["search"]["counters"] == {"launches": launches,
                                       "slots": launches * 64,
                                       "pad_slots": pad}
    assert h[trace.LAUNCH]["count"] == launches
    # untraced: the same answer and no tracer work
    np.testing.assert_array_equal(
        engine.run_chunked(lambda qv: qv * 2, n, q), q * 2)


# ----------------------------------------------------- sampling policy


def test_tail_sampling_keeps_slow_drops_fast_deterministically():
    tr = Tracer(slow_ms=5.0, sample=0.0, seed=0)
    with tr.trace("fast"):
        pass
    with tr.trace("slow"):
        time.sleep(0.01)
    s = tr.stats()
    assert s["traces"] == 2 and s["slow"] == 1
    assert s["kept"] == 1 and s["dropped"] == 1
    flight = tr.flight()
    assert len(flight) == 1 and flight[0]["root"].name == "slow"
    assert flight[0]["reason"] == "slow"


def test_histograms_update_even_for_dropped_traces():
    tr = Tracer(sample=0.0)
    for _ in range(4):
        with tr.trace("search"):
            with trace.span("route"):
                pass
    assert tr.stats()["dropped"] == 4
    h = tr.histograms()
    assert h["search"]["count"] == 4 and h["route"]["count"] == 4
    assert sum(h["route"]["counts"]) == 4


def test_flight_recorder_bounded_ring():
    tr = Tracer(slow_ms=0.0, flight_capacity=3)
    for i in range(7):
        with tr.trace("r", i=i):
            pass
    flight = tr.flight()
    assert len(flight) == 3
    assert [f["root"].attrs["i"] for f in flight] == [4, 5, 6]
    assert [f["seq"] for f in flight] == sorted(f["seq"] for f in flight)


def test_flight_dump_json_roundtrips():
    tr = Tracer(slow_ms=0.0)
    with tr.trace("req", q=2):
        with trace.span("route"):
            trace.annotate(decisions=["m/ps"], table_version=7)
    doc = json.loads(tr.dump_flight_json())
    assert len(doc["flight"]) == 1
    rec = doc["flight"][0]
    assert rec["annotations"] == {"decisions": ["m/ps"],
                                  "table_version": 7}
    assert rec["trace"]["name"] == "req"
    assert rec["trace"]["children"][0]["name"] == "route"


# -------------------------------------------------- histogram buckets


def test_bucket_index_fixed_log2_bounds():
    assert BUCKET_BOUNDS_US[0] == 1.0 and BUCKET_BOUNDS_US[-1] == float("inf")
    assert len(BUCKET_BOUNDS_US) == 26
    assert bucket_index(0.0) == 0
    assert bucket_index(1.0) == 0
    assert bucket_index(1.5) == 1       # first bound strictly above
    assert bucket_index(2.0) == 1
    assert bucket_index(2.1) == 2
    assert bucket_index(1 << 24) == 24
    assert bucket_index(1e18) == 25     # +Inf bucket, never out of range
    # every observation lands in the first bucket whose bound covers it
    for us in (0.5, 1, 3, 7, 100, 1e6):
        i = bucket_index(us)
        assert us <= BUCKET_BOUNDS_US[i]
        if i:
            assert us > BUCKET_BOUNDS_US[i - 1]


def test_latency_histogram_observe_and_quantile():
    h = LatencyHistogram()
    for us in (1, 2, 4, 8, 1000):
        h.observe(us)
    assert h.count == 5 and h.sum_us == 1015
    assert sum(h.counts) == 5
    assert h.quantile_us(0.5) == 4.0    # bucket upper bound
    assert h.quantile_us(1.0) == 1024.0
    assert LatencyHistogram().quantile_us(0.5) == 0.0


# ------------------------------------------------------ perfetto export


def _stack_discipline_ok(events, eps=0.01):
    """Per tid, 'X' intervals sorted by ts must nest or be disjoint."""
    by_tid: dict = {}
    for ev in events:
        by_tid.setdefault(ev["tid"], []).append(ev)
    for tid, evs in by_tid.items():
        evs.sort(key=lambda e: (e["ts"], -e["dur"]))
        stack = []
        for ev in evs:
            end = ev["ts"] + ev["dur"]
            while stack and ev["ts"] >= stack[-1] - eps:
                stack.pop()
            if stack:
                assert end <= stack[-1] + eps, \
                    f"tid {tid}: event {ev['name']} overlaps its parent"
            stack.append(end)
    return True


def test_perfetto_export_parses_and_nests():
    tr = Tracer(slow_ms=0.0)
    with tr.trace("req"):
        with trace.span("route"):
            time.sleep(0.001)
        with trace.span("execute"):
            with trace.span("group"):
                time.sleep(0.001)
    doc = json.loads(tr.perfetto_json())
    evs = doc["traceEvents"]
    assert {e["name"] for e in evs} == {"req", "route", "execute", "group"}
    for e in evs:
        assert e["ph"] == "X" and e["ts"] >= 0 and e["dur"] >= 0
    assert _stack_discipline_ok(evs)
    # parent bounds contain (clamped) children
    req = next(e for e in evs if e["name"] == "req")
    for e in evs:
        assert e["ts"] >= req["ts"] - 0.01
        assert e["ts"] + e["dur"] <= req["ts"] + req["dur"] + 0.01


def test_perfetto_overlapping_siblings_get_own_lanes():
    """Parallel fan-out produces overlapping sibling spans; the export
    must move them to fresh tids so each lane still nests."""
    root = Span("parent")
    t0 = root.t0
    a = root.child("shard0", t0=t0 + 0.001)
    a.t1 = t0 + 0.005
    b = root.child("shard1", t0=t0 + 0.002)   # overlaps shard0
    b.t1 = t0 + 0.006
    root.finish(t0 + 0.01)
    evs = json.loads(perfetto_json(root))["traceEvents"]
    tids = {e["name"]: e["tid"] for e in evs}
    assert tids["shard0"] != tids["shard1"]
    assert _stack_discipline_ok(evs)


def test_perfetto_empty_and_attrs_serialised():
    assert json.loads(perfetto_json([]))["traceEvents"] == []
    root = Span("r", {"arr": np.int32(3), "s": {1, 2}})
    root.finish()
    ev = json.loads(perfetto_json(root))["traceEvents"][0]
    assert ev["args"]["arr"] == 3 and sorted(ev["args"]["s"]) == [1, 2]


# -------------------------------------------- service + queue end-to-end


def _routed(tiny_index, toy_router, tracer, sink=None):
    return RouterService(tiny_index, toy_router, t=0.9, telemetry=sink,
                         tracer=tracer)


def test_service_search_traces_route_and_execute(tiny_ds, tiny_index,
                                                 toy_router, tiny_queries):
    tracer = Tracer(slow_ms=0.0)     # force: every query is "slow"
    svc = _routed(tiny_index, toy_router, tracer)
    qs = tiny_queries[Predicate.AND]
    batch = QueryBatch(qs.vectors[:6], qs.bitmaps[:6], Predicate.AND, 5)
    svc.search(batch)
    flight = tracer.flight()
    assert flight, "forced-slow query missing from the flight recorder"
    rec = flight[-1]
    root = rec["root"]
    assert root.name == "search"
    route, execute = root.find("route"), root.find("execute")
    assert route is not None and execute is not None
    assert route.t1 <= execute.t0 + 1e-9     # route precedes execute
    assert execute.find("group").attrs["method"]
    _assert_well_formed(root)
    # RoutingDecision + table/generation provenance on the record
    assert rec["annotations"]["decisions"]
    assert "generation" in rec["annotations"]


def test_profile_nests_program_spans_inside_the_harness_span(
        tmp_path, tiny_ds, tiny_index, toy_router, tiny_queries):
    """A JAX profile (CPU) of one traced search holds the program's spans
    as `repro.*` host annotations, nested on the profiler's one clock:
    bench.search ⊃ repro.search ⊃ repro.route ⊃ repro.launch and
    repro.search ⊃ repro.execute ⊃ repro.group ⊃ repro.launch."""
    import jax
    from jax.profiler import ProfileData

    root_dir = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root_dir not in sys.path:
        sys.path.insert(0, root_dir)
    from bench import trace_reduce

    tracer = Tracer(sample=1.0)
    svc = _routed(tiny_index, toy_router, tracer)
    qs = tiny_queries[Predicate.OR]
    batch = QueryBatch(qs.vectors[:6], qs.bitmaps[:6], Predicate.OR, 5)
    svc.search(batch)                # compile outside the profile
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.search"):
            svc.search(batch)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    planes = trace_reduce.flatten(ProfileData.from_file(path))
    evs = [(name, s, s + d) for p in planes
           if not p["name"].startswith(trace_reduce.DEVICE_PREFIX)
           for ln in p["lines"] for name, s, d in ln["events"]
           if name.startswith(("bench.", "repro."))]

    def inside(name, outer):
        return [e for e in evs if e[0] == name
                and outer[1] <= e[1] and e[2] <= outer[2]]

    bench_search, = [e for e in evs if e[0] == "bench.search"]
    search, = inside("repro.search", bench_search)
    route, = inside("repro.route", search)
    execute, = inside("repro.execute", search)
    assert route[2] <= execute[1]
    assert inside("repro.route.features", route)
    mlp, = inside("repro.route.mlp", route)
    assert inside("repro.launch", mlp)
    assert inside("repro.route.decide", route)
    groups = inside("repro.group", execute)
    assert groups and all(inside("repro.launch", g) for g in groups)
    assert inside("repro.distances", execute)
    # every program span of the search, and nothing more, is annotated
    h = tracer.histograms()
    assert sum(h[n]["count"] for n in h) == 2 * len(
        [e for e in evs if e[0].startswith("repro.")])


def test_queue_traces_well_formed_across_thread_hops(tiny_ds, tiny_index,
                                                     toy_router,
                                                     tiny_queries):
    """Concurrent submitters -> route worker -> exec worker: every kept
    tree must be one well-formed root with the full stage ladder, and
    the per-root q attributes must account for every request exactly."""
    tracer = Tracer(slow_ms=0.0, flight_capacity=256)
    svc = _routed(tiny_index, toy_router, tracer)
    preds = (Predicate.AND, Predicate.OR)
    n_threads, per_thread = 4, 6
    results = []
    lock = threading.Lock()

    with AsyncBatchQueue(svc, max_batch=8, max_wait_ms=5.0) as queue:
        def submitter(tid):
            qs = tiny_queries[preds[tid % len(preds)]]
            futs = [queue.submit(qs.vectors[i], qs.bitmaps[i],
                                 preds[tid % len(preds)], k=5)
                    for i in range(per_thread)]
            got = [f.result(timeout=120) for f in futs]
            with lock:
                results.extend(got)

        threads = [threading.Thread(target=submitter, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

    assert len(results) == n_threads * per_thread
    assert all(r.ids.shape == (5,) for r in results)
    roots = [f["root"] for f in tracer.flight()]
    assert roots and all(r.name == "request" for r in roots)
    for root in roots:
        _assert_well_formed(root)
        names = [c.name for c in root.children]
        assert "enqueue_wait" in names
        assert "batch_assembly" in names
        assert "route" in names
        assert "execute" in names
        # the retroactive enqueue_wait child still nests in the root
        ew = root.find("enqueue_wait")
        assert ew.t0 >= root.t0 - 1e-9 and ew.t1 <= root.t1 + 1e-9
    assert sum(r.attrs["q"] for r in roots) == n_threads * per_thread
    assert tracer.stats()["errors"] == 0


def test_queue_traces_survive_compaction_mid_batch(tiny_ds, toy_router,
                                                   tiny_queries):
    """A writer thread upserts + compacts while the queue serves: trees
    stay well-formed, carry the live stage spans, and record the pinned
    generation."""
    tracer = Tracer(slow_ms=0.0, flight_capacity=256)
    with LiveFilteredIndex(tiny_ds) as live:
        svc = RouterService(live, toy_router, t=0.9, tracer=tracer)
        qs = tiny_queries[Predicate.AND]
        stop = threading.Event()
        rng = np.random.default_rng(0)

        def churn():
            rounds = 0
            while not stop.is_set():
                pick = rng.integers(0, tiny_ds.n, 16)
                live.upsert(tiny_ds.vectors[pick], tiny_ds.bitmaps[pick])
                if rounds % 3 == 0:  # compaction must race some batch
                    live.compact()
                rounds += 1
                stop.wait(0.01)      # yield: queries must make progress

        w = threading.Thread(target=churn)
        w.start()
        try:
            with AsyncBatchQueue(svc, max_batch=4,
                                 max_wait_ms=5.0) as queue:
                for round_ in range(3):
                    futs = [queue.submit(qs.vectors[i], qs.bitmaps[i],
                                         Predicate.AND, k=5)
                            for i in range(8)]
                    for f in futs:
                        assert f.result(timeout=120).ids.shape == (5,)
        finally:
            stop.set()
            w.join()
    roots = [f["root"] for f in tracer.flight()]
    assert roots
    pinned = 0
    for root in roots:
        _assert_well_formed(root)
        assert root.find("execute") is not None
        if root.find("snapshot_pin") is not None:
            pinned += 1
    assert pinned == len(roots)      # live handle: every batch pins
    assert tracer.stats()["errors"] == 0
    # perfetto export of real concurrent trees stays viewer-valid
    evs = json.loads(tracer.perfetto_json())["traceEvents"]
    assert _stack_discipline_ok(evs)


def test_cache_facade_produces_single_tree(tiny_ds, tiny_index,
                                           toy_router, tiny_queries):
    from repro.ann.cache import SemanticResultCache

    tracer = Tracer(slow_ms=0.0)
    svc = _routed(tiny_index, toy_router, tracer)
    cache = SemanticResultCache(svc, threshold=None)
    qs = tiny_queries[Predicate.AND]
    batch = QueryBatch(qs.vectors[:4], qs.bitmaps[:4], Predicate.AND, 5)
    cache.search(batch)              # miss -> routed fill
    roots = [f["root"] for f in tracer.flight()]
    fill = roots[-1]
    assert fill.name == "cache_search"
    assert fill.find("cache.probe") is not None
    assert fill.find("search") is not None      # nested, not a 2nd root
    assert fill.find("route") is not None
    assert fill.find("cache.admit") is not None
    _assert_well_formed(fill)
    n_before = tracer.stats()["traces"]
    cache.search(batch)              # exact hit: no search subtree
    hits = [f["root"] for f in tracer.flight()][-1]
    assert tracer.stats()["traces"] == n_before + 1
    assert hits.find("route") is None
    cache.close()


# ------------------------------------------------------------- metrics


def test_metrics_text_exposition_format():
    sink = TelemetrySink(capacity=16, reservoir=0)
    bm = np.zeros((3, 1), np.uint32)
    batch = QueryBatch(np.zeros((3, 4), np.float32), bm, Predicate.OR, 3)
    sink.record_batch(batch, ("m", "p"), search_s=3e-3, shard=1)
    sink.note_shard(1, "exec", 2e-3, 3)
    tracer = Tracer(slow_ms=0.0)
    with tracer.trace("search"):
        pass
    text = metrics_text(sink=sink, tracer=tracer)
    lines = text.splitlines()
    assert "# TYPE ann_queries_total counter" in lines
    assert "ann_queries_total 3" in lines
    assert 'ann_cell_queries_total{method="m",ps="p",pred="OR"} 3' in lines
    assert 'ann_shard_stage_seconds_total{shard="1",stage="exec"} 0.002' \
        in lines
    assert 'ann_traces_total{outcome="traces"} 1' in lines
    # histogram: cumulative buckets, +Inf bucket equals _count
    buckets = [ln for ln in lines
               if ln.startswith('ann_span_latency_us_bucket{span="search"')]
    assert len(buckets) == len(BUCKET_BOUNDS_US)
    counts = [float(ln.rsplit(" ", 1)[1]) for ln in buckets]
    assert counts == sorted(counts)            # cumulative
    inf_line = next(ln for ln in buckets if 'le="+Inf"' in ln)
    total = next(ln for ln in lines
                 if ln.startswith('ann_span_latency_us_count'))
    assert inf_line.rsplit(" ", 1)[1] == total.rsplit(" ", 1)[1]
    # every non-comment line is "name{labels} value" or "name value"
    for ln in lines:
        if ln.startswith("#"):
            continue
        name, val = ln.rsplit(" ", 1)
        float(val)
        assert name and " " not in name.split("{")[0]


def test_metrics_label_escaping():
    sink = TelemetrySink(capacity=8, reservoir=0)
    sink.note('we"ird\\stage_s', 1.0)
    text = metrics_text(sink=sink)
    assert 'name="we\\"ird\\\\stage_s"' in text


def test_metrics_text_empty_exporter_is_up():
    text = metrics_text()
    assert "ann_up 1" in text


def test_metrics_server_endpoints():
    tracer = Tracer(slow_ms=0.0)
    with tracer.trace("search"):
        pass
    srv = MetricsServer(lambda: metrics_text(tracer=tracer),
                        health=lambda: {"traces": tracer.stats()["traces"]})
    try:
        r = urllib.request.urlopen(srv.url + "/metrics", timeout=10)
        assert r.status == 200
        assert r.headers["Content-Type"].startswith(
            "text/plain; version=0.0.4")
        body = r.read().decode()
        assert 'ann_traces_total{outcome="traces"} 1' in body
        h = urllib.request.urlopen(srv.url + "/healthz", timeout=10)
        payload = json.loads(h.read())
        assert payload == {"status": "ok", "traces": 1}
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(srv.url + "/nope", timeout=10)
        assert ei.value.code == 404
    finally:
        srv.close()


def test_metrics_server_render_error_surfaces_as_500():
    srv = MetricsServer(lambda: 1 / 0)
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(srv.url + "/metrics", timeout=10)
        assert ei.value.code == 500
    finally:
        srv.close()
