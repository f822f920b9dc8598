"""Unified observability (DESIGN.md §4): span tracer correctness under
concurrency, the disabled fast path, metrics-registry-backed stats,
Chrome-trace export round-trips, and critical-path attribution.

The concurrency tests pin the load-bearing propagation claims from
``repro.obs.spans``: the parent link must survive asyncio task switches
(sibling tasks must not adopt each other's spans), offload worker
threads (``ctx.run`` in ``Runtime.run_sync``), and the sync-client
bridge loop (context adoption in ``_BridgeLoop.run``).  The disabled
path must allocate *zero* spans — ``SPAN_ALLOCS`` exists for exactly
this assertion.
"""

import asyncio
import json
import time

from helpers_core import ExternalWorld
from repro import obs
from repro.core import poppy, sequential_mode, unordered
from repro.core.ai import SimulatedBackend, llm_sync, use_backend, \
    use_dispatcher
from repro.dispatch import Dispatcher
from repro.dispatch.batcher import BatchStats
from repro.dispatch.stats import DispatchStats, LatencyDigest, PrefixStats
from repro.obs import spans as spans_mod
from repro.obs.metrics import Histogram, InstrumentAttr, MetricsRegistry
from repro.obs.spans import PHASE_MIN_S, Span, Tracer, maybe_span


# ---------------------------------------------------------------------------
# tracer basics


def test_span_nesting_parents_and_tracks():
    trz = Tracer()
    with trz.span("outer", cat="engine", track="lane") as outer:
        assert obs.current_span() is outer
        with trz.span("inner", cat="phase") as inner:
            assert inner.parent_id == outer.span_id
            # "main" (the default) inherits the parent's display lane
            assert inner.track == "lane"
        assert obs.current_span() is outer
    assert obs.current_span() is None
    spans = trz.closed_spans()
    assert [s.name for s in spans] == ["outer", "inner"]
    assert outer.parent_id == 0 and not outer.open
    assert outer.t0 <= inner.t0 and inner.t1 <= outer.t1


def test_span_records_error_attr():
    trz = Tracer()
    try:
        with trz.span("boom"):
            raise ValueError("x")
    except ValueError:
        pass
    (sp,) = trz.closed_spans()
    assert sp.attrs["error"] == "ValueError"


def test_record_retroactive_phase_spans():
    trz = Tracer()
    with trz.span("ext", cat="external") as ext:
        # the instrumentation pattern: note now(), do the phase, record
        # only when the elapsed time clears PHASE_MIN_S
        t0 = trz.now()
        if trz.now() - t0 >= PHASE_MIN_S:  # no-wait path: nothing recorded
            trz.record("lock.wait", t0, cat="external.lock")
        t0 = trz.now()
        time.sleep(0.002)
        sp = trz.record("lock.wait", t0, cat="external.lock", locks="rw")
    assert sp.t0 == t0 and not sp.open and sp.dur >= 0.002
    assert sp.parent_id == ext.span_id  # parent from context
    assert sp.track == ext.track        # lane inherited like begin()
    names = [s.name for s in trz.closed_spans()]
    assert names.count("lock.wait") == 1


def test_times_are_perf_counter_and_exports_start_at_zero():
    # spans sit on the host clock a profiler trace is anchored to; an
    # export subtracts the tracer's origin, so Perfetto still starts at 0
    trz = Tracer()
    t = time.perf_counter()
    with trz.span("a") as sp:
        pass
    ev = trz.event("mark")
    assert abs(sp.t0 - t) < 1e-3 and abs(ev.t0 - time.perf_counter()) < 1e-3
    assert trz.origin <= sp.t0 and abs(trz.now() - time.perf_counter()) < 1e-3
    ts = [e["ts"] for e in obs.chrome_trace(trz)["traceEvents"]
          if e["ph"] in ("X", "i")]
    assert len(ts) == 2 and 0 <= min(ts) < 1e3       # microseconds
    assert obs.render_timeline([sp]).startswith("timeline:")


def test_event_instants_are_separate_from_spans():
    trz = Tracer()
    with trz.span("outer") as outer:
        ev = trz.event("mark", cat="serving.admit", slot=3)
    assert ev.t0 == ev.t1 and ev.parent_id == outer.span_id
    assert trz.instants == [ev]
    assert [s.name for s in trz.closed_spans()] == ["outer"]


# ---------------------------------------------------------------------------
# the disabled fast path


def test_disabled_path_allocates_nothing():
    assert obs.current_tracer() is None
    # shared null context manager: no per-call allocation
    assert maybe_span("a") is maybe_span("b")
    world = ExternalWorld(latency=0.0)

    @poppy
    def prog():
        a = world.compute(1)
        b = world.compute(2)
        world.emit(a)
        world.emit(b)
        return (a, b)

    before = spans_mod.SPAN_ALLOCS
    assert prog() == ("c(1)", "c(2)")
    assert spans_mod.SPAN_ALLOCS == before, \
        "untraced run allocated spans — the disabled path regressed"


# ---------------------------------------------------------------------------
# context propagation under concurrency


def test_parent_survives_asyncio_task_switches():
    trz = Tracer()

    async def child(i):
        with trz.span(f"c{i}") as sp:
            # interleave: siblings run during this sleep; after resuming,
            # the current span must still be ours, not a sibling's
            await asyncio.sleep(0.002 * ((i + 1) % 3))
            assert obs.current_span() is sp
            trz.event(f"e{i}")
        return sp

    async def go():
        with trz.span("root") as root:
            sps = await asyncio.gather(
                *[asyncio.ensure_future(child(i)) for i in range(8)])
        return root, sps

    root, sps = asyncio.run(go())
    # every task's span parents under root (context copied at create_task),
    # never under a sibling that happened to be running at switch time
    assert {sp.parent_id for sp in sps} == {root.span_id}
    ev_parent = {e.name: e.parent_id for e in trz.instants}
    for i, sp in enumerate(sps):
        assert ev_parent[f"e{i}"] == sp.span_id


def test_offload_thread_parents_under_external_call():
    @unordered
    def blocking(x):
        time.sleep(0.01)
        return x * 10

    @poppy
    def prog():
        return (blocking(1), blocking(2), blocking(3))

    with obs.tracing() as trz:
        assert prog() == (10, 20, 30)
    spans = {s.span_id: s for s in trz.closed_spans()}
    offloads = [s for s in spans.values() if s.cat == "offload"]
    assert len(offloads) == 3
    for s in offloads:
        assert s.track.startswith("offload:")
        call = spans[s.parent_id]
        assert call.cat == "external.call"
        ext = spans[call.parent_id]
        assert ext.cat == "external" and ext.name.endswith("blocking")


def test_bridge_loop_adopts_caller_span():
    # llm_sync blocks an offload worker and drives the async dispatcher on
    # the bridge loop; the dispatch spans recorded *there* must still
    # parent back through the worker's offload span to the external
    @poppy
    def ask(topics):
        out = tuple()
        for t in topics:
            out += (llm_sync(f"about {t}"),)
        return out

    be = SimulatedBackend(base_s=0.01)
    d = Dispatcher()   # routes to the ambient use_backend backend
    with obs.tracing() as trz, use_backend(be), use_dispatcher(d):
        r = ask(("a", "b"))
    assert len(r) == 2
    spans = {s.span_id: s for s in trz.closed_spans()}
    dispatches = [s for s in spans.values() if s.cat == "dispatch"]
    assert dispatches, "no dispatch spans recorded on the bridge loop"
    for s in dispatches:
        cats = set()
        p = s
        while p.parent_id:
            p = spans[p.parent_id]
            cats.add(p.cat)
        assert "offload" in cats and "external" in cats, (
            f"bridge-loop span {s.name!r} lost its caller chain: "
            f"ancestors {cats}")


def test_traced_and_untraced_runs_agree():
    world = ExternalWorld(latency=0.002)

    @poppy
    def prog():
        a = world.compute("a")
        b = world.compute("b")
        world.store(a)
        p = world.peek()
        world.emit(b)
        return (a, b, p)

    with sequential_mode():
        r_plain = prog()
        out_plain = list(world.out)
    world.reset()
    with obs.tracing() as trz:
        r_traced = prog()
    assert r_traced == r_plain and world.out == out_plain
    exts = [s for s in trz.closed_spans() if s.cat == "external"]
    # span names are qualnames; intrinsics (py_getattr for world.compute
    # attribute loads) are externals too
    leaf = {s.name.rsplit(".", 1)[-1] for s in exts}
    assert {"compute", "store", "peek", "emit"} <= leaf
    for s in exts:
        assert s.attrs["cls"] in ("unordered", "readonly", "sequential")
        assert s.track.startswith("domain:")
    rep = obs.report(trz)
    assert rep.n_externals >= 5
    # every instant of the run is attributed exactly once
    assert abs(sum(seg.dur for seg in rep.path) - rep.wall_s) < 1e-9


# ---------------------------------------------------------------------------
# export round-trip + CLI


def test_chrome_trace_roundtrip(tmp_path):
    trz = Tracer(name="t")
    with trz.span("outer", cat="engine", track="lane", k=1):
        with trz.span("inner", cat="external", cls="unordered"):
            time.sleep(0.001)
        trz.event("mark", cat="serving.admit")
    path = str(tmp_path / "trace.json")
    obs.write_chrome_trace(path, trz)
    doc = json.loads(open(path).read())
    names = {e["name"] for e in doc["traceEvents"] if e["ph"] == "X"}
    assert names == {"outer", "inner"}
    lanes = {e["args"]["name"] for e in doc["traceEvents"]
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert "lane" in lanes
    assert any(e["ph"] == "i" and e["name"] == "mark"
               for e in doc["traceEvents"])

    back = obs.load_spans(path)
    orig = trz.closed_spans()
    assert [(s.name, s.cat, s.track, s.span_id, s.parent_id)
            for s in back] == \
        [(s.name, s.cat, s.track, s.span_id, s.parent_id) for s in orig]
    for a, b in zip(back, orig):
        assert abs(a.t0 - b.t0) < 1e-5 and abs(a.t1 - b.t1) < 1e-5
    assert back[0].attrs["k"] == 1
    # a report over loaded spans matches one over the live tracer
    assert obs.report(back).n_spans == obs.report(trz).n_spans


def test_cli_reports_over_exported_trace(tmp_path, capsys):
    from repro.obs.__main__ import main

    trz = Tracer()
    with trz.span("run", cat="engine"):
        with trz.span("call", cat="external.call"):
            time.sleep(0.002)
    path = str(tmp_path / "t.json")
    obs.write_chrome_trace(path, trz)
    assert main([path, "--timeline"]) == 0
    out = capsys.readouterr().out
    assert "critical path" in out and "timeline:" in out

    # a trace with no complete spans reports failure, not a crash
    empty = str(tmp_path / "empty.json")
    obs.write_chrome_trace(empty, Tracer())
    assert main([empty]) == 1


# ---------------------------------------------------------------------------
# critical-path attribution on synthetic spans


def _span(name, cat, t0, t1, sid, parent=0, track="main", **attrs):
    return Span(name=name, cat=cat, t0=t0, t1=t1, span_id=sid,
                parent_id=parent, track=track, attrs=attrs)


def test_critical_path_synthetic_sequential_chain():
    spans = [
        _span("run", "engine", 0.0, 8.5, 1),
        _span("a", "external", 0.0, 4.0, 2, parent=1,
              cls="sequential", effects=["m"], seq=0),
        _span("b", "external", 4.0, 8.0, 3, parent=1,
              cls="sequential", effects=["m"], seq=1),
    ]
    rep = obs.report(spans)
    assert rep.wall_s == 8.5
    assert abs(sum(seg.dur for seg in rep.path) - 8.5) < 1e-9
    # 0-8 attributed to the externals, 8-8.5 to the enclosing run span
    assert abs(rep.attributed_external_s - 8.0) < 1e-9
    assert rep.idle_s == 0.0
    # both sequential calls share domain "m": the ideal makespan is their
    # serialized sum — this run is close to optimal (the 0.5s engine tail
    # is the only loss: achieved 8/8.5 vs ideal 8/8)
    assert abs(rep.ideal_makespan_s - 8.0) < 1e-9
    assert abs(rep.busy_external_s - 8.0) < 1e-9
    assert abs(rep.parallel_efficiency - 8.0 / 8.5) < 1e-9
    comp = rep.components[("external", "a")]
    assert comp.count == 1 and abs(comp.critical_s - 4.0) < 1e-9


def test_critical_path_unordered_fanout_and_idle():
    # two unordered calls overlap; a gap nothing covers is idle
    spans = [
        _span("a", "external", 0.0, 3.0, 1, cls="unordered", effects=[]),
        _span("b", "external", 0.0, 4.0, 2, cls="unordered", effects=[]),
        _span("c", "external", 6.0, 7.0, 3, cls="unordered", effects=[]),
    ]
    rep = obs.report(spans)
    assert rep.wall_s == 7.0
    assert abs(rep.idle_s - 2.0) < 1e-9          # the 4-6 gap
    assert abs(rep.busy_external_s - 8.0) < 1e-9
    # no ordering constraints: ideal makespan = the longest single call
    assert abs(rep.ideal_makespan_s - 4.0) < 1e-9
    assert rep.achieved_parallelism < rep.ideal_parallelism
    blockers = {(c.cat, c.name) for c in rep.top_blockers()}
    assert ("", "idle") in blockers


def test_critical_path_attributes_innermost_span():
    # a call child inside an external: the covered instants go to the
    # innermost span; the external keeps only its exclusive margin
    spans = [
        _span("ext", "external", 0.0, 5.0, 1, cls="unordered", effects=[]),
        _span("call", "external.call", 1.0, 4.0, 2, parent=1),
    ]
    rep = obs.report(spans)
    comp_call = rep.components[("external.call", "call")]
    comp_ext = rep.components[("external", "ext")]
    assert abs(comp_call.critical_s - 3.0) < 1e-9
    assert abs(comp_ext.critical_s - 2.0) < 1e-9
    assert abs(comp_ext.exclusive_s - 2.0) < 1e-9
    assert abs(comp_ext.inclusive_s - 5.0) < 1e-9
    # busy time counts the *call* duration, not the external's extent
    # (waiting inside the external is not work)
    assert abs(rep.busy_external_s - 3.0) < 1e-9


def test_report_render_and_empty():
    empty = obs.report([])
    assert empty.wall_s == 0.0 and empty.path == []
    spans = [_span("x", "external", 0.0, 1.0, 1, cls="unordered",
                   effects=[])]
    text = obs.report(spans).render()
    assert "critical path" in text and "external:x" in text
    tl = obs.render_timeline(spans)
    assert "1000.00ms" in tl or "x" in tl


# ---------------------------------------------------------------------------
# metrics registry + stats views


def test_registry_identity_labels_and_types():
    reg = MetricsRegistry()
    c = reg.counter("reqs")
    c.inc()
    c.inc(2)
    assert c.value == 3
    assert reg.counter("reqs") is c
    assert reg.counter("reqs", domain="a") is not c
    g = reg.gauge("depth")
    g.inc()
    g.inc()
    g.dec()
    assert g.value == 1 and g.peak == 2
    h = reg.histogram("lat")
    h.observe(0.5)
    h.add(1.5)
    assert h.count == 2 and h.mean == 1.0
    try:
        reg.gauge("reqs")
        raise AssertionError("type conflict not detected")
    except TypeError:
        pass
    snap = reg.snapshot()
    assert snap["reqs"] == 3 and snap["reqs{domain=a}"] == 0
    assert snap["depth"] == {"value": 1, "peak": 2}
    assert snap["lat"]["count"] == 2
    assert "depth: 1 (peak 2)" in reg.render()


def test_instrument_attr_descriptor():
    reg = MetricsRegistry()

    class View:
        hits = InstrumentAttr()

        def __init__(self):
            self._i_hits = reg.counter("hits")

    v = View()
    v.hits += 1
    v.hits += 2
    assert v.hits == 3
    assert reg.counter("hits").value == 3  # same storage
    w = View()
    assert w.hits == 3                     # shared series, not per-instance


def test_dispatch_stats_are_registry_views():
    assert LatencyDigest is Histogram
    st = DispatchStats()
    st.requests += 3
    st.dispatched += 2
    st.cache_hits += 1
    st.cache_misses += 1
    st.enqueue()
    st.enqueue()
    st.dequeue()
    st.note_domains(["http:a", "http:b"])
    st.note_domains(["http:a"])
    st.observe("b0", 0.012)
    st.observe("b0", 0.020, error=True)
    st.note_prefix_batch(elements=4, shared_tokens=100, computed_tokens=0)

    assert st.queue_depth == 1 and st.queue_peak == 2
    assert st.per_domain == {"http:a": 2, "http:b": 1}
    snap = st.snapshot()
    assert snap["requests"] == 3 and snap["hit_rate"] == 0.5
    assert snap["backends"]["b0"]["requests"] == 2
    assert snap["backends"]["b0"]["errors"] == 1
    assert snap["prefix"]["warm_cached"] == 1
    # the same numbers through the registry surface
    rsnap = st.registry.snapshot()
    assert rsnap["dispatch_requests"] == 3
    assert rsnap["domain_requests{domain=http:a}"] == 2
    assert rsnap["backend_requests{backend=b0}"] == 2
    assert rsnap["prefix_warm_cached"] == 1
    assert rsnap["dispatch_queue_depth"] == {"value": 1, "peak": 2}
    assert "dispatch" in st.report()


def test_batch_stats_view():
    reg = MetricsRegistry()
    bst = BatchStats(max_batch=8, registry=reg)
    bst.record_batch(5)
    bst.record_batch(5)
    bst.record_batch(3)
    bst.record_wait(0.001)
    snap = bst.snapshot()
    assert snap["batches"] == 3 and snap["elements"] == 13
    assert bst.size_hist == {5: 2, 3: 1}
    assert abs(snap["fill_ratio"] - 13 / 24) < 1e-9
    assert reg.snapshot()["batch_elements"] == 13
    assert reg.counter("batch_size", size=5).value == 2


def test_prefix_stats_standalone():
    ps = PrefixStats()
    ps.note_batch(elements=3, shared_tokens=50, computed_tokens=50)
    ps.note_batch(elements=2, shared_tokens=50, computed_tokens=0)
    assert ps.snapshot() == {"batches": 2, "elements": 5,
                             "shared_tokens": 100, "computed_tokens": 50,
                             "warm_cached": 1}
