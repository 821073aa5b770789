"""Unified observability (repro.obs, DESIGN.md §6.10).

Pins the subsystem's contracts:

* metrics registry — counter/gauge/histogram semantics, type conflicts,
  legacy-name aliases, snapshot schema (``validate_metrics``);
* legacy stats-dict shapes — ``CycleService.stats`` and the continuous
  scheduler's session stats are VIEWS over the registry: dict == registry
  equality is regression-pinned, including the divergent legacy names
  (``cache_hits`` vs ``hits``) resolving to one canonical metric;
* request spans — every recycled request decomposes into
  queue_wait → seed → superstep… → recycle/retire → drain slices whose
  root reconciles with the session's reported e2e latency;
* Perfetto export — recycled serve_stream renders a schema-valid
  trace_event JSON with per-lane tracks, counter tracks, guard instants,
  and per-request span tracks (``validate_perfetto`` as the gate);
* the overhead contract — observability disabled retains NO TraceEvent /
  Span objects per dispatch while aggregate counters match an enabled run
  exactly;
* host phases — ``SpanLog.phase`` annotations nest inside
  ``repro.enumerate`` in a profiler trace and cover it, every device read
  of the wave driver falls in ``readback`` or ``drain``, and a span agrees
  with its annotation on the profiler's host clock;
* device stages and counters — lowered plans carry their
  ``repro.round.*`` scope, the round path a plan records is the VMEM
  rule's, and ``n_d2h_arrays`` matches a hand count;
* boundary accounting — seed/recycle events carry ``wall_ms`` and
  ``boundary_ms_total`` accumulates them;
* FlightRecorder — bounded ring, guard-storm / warm-retrace /
  occupancy-collapse triggers, dump rate limiting.
"""
import numpy as np
import pytest

from repro.core import CycleService, EngineConfig, build_graph
from repro.core.graphs import grid_graph, random_gnp
from repro.obs import (FlightRecorder, MetricsRegistry, SpanLog,
                       collect_events, new_request_id, to_perfetto,
                       validate_metrics, validate_perfetto)
from repro.sched.traffic import imbalanced_queue
from repro.tune.telemetry import TraceEvent

# span-vs-stats reconciliation slack (clock reads on both sides of a
# boundary + host jitter); generous because CI machines are noisy
SLACK_MS = 50.0


def _event(**kw):
    base = dict(kind="batch", bucket=64, cyc_cap=1, budget=4, rounds=2,
                status="RUN", t_sizes=(8, 4), c_counts=(1, 0),
                enter_count=8, exit_count=4, pending_new=0, pending_cyc=0,
                cyc_fill=0, t_ms=0.5)
    base.update(kw)
    return TraceEvent(**base)


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------

def test_counter_monotone_and_labeled():
    reg = MetricsRegistry()
    c = reg.counter("requests_total")
    c.inc()
    c.inc(3, backend="pallas")
    assert c.value() == 1
    assert c.value(backend="pallas") == 3
    assert c.total() == 4
    with pytest.raises(ValueError):
        c.inc(-1)


def test_gauge_set_and_pull():
    reg = MetricsRegistry()
    g = reg.gauge("live_lanes")
    g.set(3)
    assert g.value() == 3
    state = {"n": 7}
    g2 = reg.gauge("programs")
    g2.set_fn(lambda: state["n"])
    assert g2.value() == 7
    state["n"] = 9
    assert reg.snapshot()["gauges"]["programs"][""] == 9


def test_histogram_percentiles_and_counts():
    reg = MetricsRegistry()
    h = reg.histogram("lat_ms", buckets=(1.0, 10.0, 100.0))
    for v in (0.5, 2.0, 3.0, 50.0):
        h.observe(v)
    assert h.count() == 4
    assert h.sum() == pytest.approx(55.5)
    snap = h.snapshot()[""]
    assert snap["count"] == sum(snap["counts"])
    assert snap["min"] == 0.5 and snap["max"] == 50.0
    assert 0.5 <= h.percentile(50) <= 10.0
    assert h.percentile(100) == 50.0


def test_registry_type_conflict_raises():
    reg = MetricsRegistry()
    reg.counter("x")
    with pytest.raises(TypeError):
        reg.gauge("x")


def test_aliases_resolve_to_canonical():
    reg = MetricsRegistry()
    reg.counter("plan_cache_hits_total").inc(5)
    reg.alias("cache_hits", "plan_cache_hits_total")
    reg.alias("hits", "plan_cache_hits_total")
    view = reg.legacy_view(["cache_hits", "hits"])
    assert view == {"cache_hits": 5, "hits": 5}
    snap = reg.snapshot()
    assert snap["aliases"]["cache_hits"] == 5 == snap["aliases"]["hits"]


def test_metrics_snapshot_schema_valid_and_gate_catches_rot():
    reg = MetricsRegistry()
    reg.counter("a").inc()
    reg.histogram("h").observe(1.0)
    snap = reg.snapshot()
    assert validate_metrics(snap) == []
    bad = dict(snap)
    bad["schema"] = "nope"
    assert validate_metrics(bad)
    broken = reg.snapshot()
    broken["histograms"]["h"][""]["counts"][0] += 1   # count != sum(counts)
    assert any("count != sum" in e for e in validate_metrics(broken))


# ---------------------------------------------------------------------------
# Legacy stats-dict shapes as registry views (the normalization satellite)
# ---------------------------------------------------------------------------

def test_service_stats_is_a_view_over_the_registry():
    svc = CycleService(EngineConfig(store=False))
    g = build_graph(*grid_graph(3, 4))
    svc.enumerate(g)
    svc.enumerate(g)
    s = svc.stats
    # the legacy key set, pinned
    for key in ("programs", "cache_hits", "cache_misses", "n_traces",
                "evictions", "requests", "graphs", "batches", "streams",
                "sessions", "traces_recorded", "tuned_requests"):
        assert key in s, key
    # dict == registry: every legacy key resolves through the alias table
    view = svc.metrics.legacy_view(
        ["cache_hits", "cache_misses", "evictions", "programs", "n_traces",
         "requests", "graphs", "batches", "streams", "sessions"])
    for key, val in view.items():
        assert s[key] == val, key
    # divergent legacy names hit the SAME canonical metric
    assert svc.metrics.value("plan_cache_hits_total") == s["cache_hits"]
    assert (svc.metrics.legacy_view(["hits"])["hits"]
            == svc.metrics.legacy_view(["cache_hits"])["cache_hits"])
    assert s["requests"] == 2 and s["graphs"] == 2


def test_session_stats_mirror_registry():
    svc = CycleService(EngineConfig(store=False, superstep_rounds=3))
    queue = imbalanced_queue(n_long=2, shorts_per_long=2)
    list(svc.serve_stream(queue, slots=2))
    sess = svc.last_session
    m = svc.metrics
    for name in ("requests", "completed", "supersteps", "boundaries",
                 "admissions", "retirements", "pools"):
        assert sess.stats[name] == m.value(f"sched_{name}_total"), name
    h = m.get("e2e_ms")
    assert h.count(sched="recycle") == len(sess.stats["e2e_ms"])
    assert (m.get("queue_wait_ms").count(sched="recycle")
            == len(sess.stats["queue_wait_ms"]))


def test_serve_wave_scheduler_mirrors_registry():
    from repro.launch.serve import serve
    svc = CycleService(EngineConfig(store=False))
    queue = [build_graph(*grid_graph(3, 3)) for _ in range(4)]
    queue.append(build_graph(*random_gnp(8, 0.4, 3)))
    stats = serve(svc, queue, slots=2, verbose=False)
    m = svc.metrics
    assert stats["requests"] == m.value("serve_requests_total") == 5
    assert stats["waves"] == m.value("serve_waves_total")
    assert stats["coalesced_lanes"] == m.value("serve_coalesced_lanes_total")
    assert stats["solo_requests"] == m.value("serve_solo_requests_total")
    assert (m.get("e2e_ms").count(sched="wave")
            == len(stats["e2e_ms"]) == 5)


# ---------------------------------------------------------------------------
# Request spans: decomposition + reconciliation
# ---------------------------------------------------------------------------

def test_request_ids_are_unique_and_monotone():
    a, b = new_request_id(), new_request_id()
    assert a != b and a < b and a.startswith("r")


def test_recycled_spans_reconcile_with_session_latency():
    svc = CycleService(EngineConfig(store=True, superstep_rounds=3),
                       trace=True)
    queue = imbalanced_queue(n_long=2, shorts_per_long=3)
    done = list(svc.serve_stream(queue, slots=2))
    assert len(done) == len(queue)
    sess = svc.last_session
    roots = svc.spans.roots()
    assert len(roots) == len(queue)
    # each root's duration IS the session's reported e2e for that request
    e2e_sorted = sorted(sess.stats["e2e_ms"])
    root_sorted = sorted(sp.dur_ms for sp in roots.values())
    for a, b in zip(root_sorted, e2e_sorted):
        assert a == pytest.approx(b, abs=SLACK_MS)
    for rid, root in roots.items():
        spans = [sp for sp in svc.spans.spans if sp.rid == rid]
        names = {sp.name for sp in spans}
        assert {"request", "queue_wait", "seed", "retire"} <= names, names
        # every request rode at least one superstep dispatch
        assert "superstep" in names
        # slices nest inside the root (the export validator re-checks this
        # on the rendered trace; here we pin the raw spans)
        for sp in spans:
            assert sp.t_start_ms >= root.t_start_ms - SLACK_MS
            assert sp.t_end_ms <= root.t_end_ms + SLACK_MS
        # accounted time never exceeds e2e by more than boundary slack:
        # supersteps are shared dispatch slices, so Σ is bounded by the
        # wall the lane actually lived plus measurement jitter
        roll = svc.spans.rollup(rid)
        assert roll["e2e_ms"] == root.dur_ms
        assert roll["slices_ms"]["queue_wait"] <= root.dur_ms + SLACK_MS


def test_single_graph_request_gets_spans_too():
    svc = CycleService(EngineConfig(store=False), trace=True)
    g = build_graph(*grid_graph(3, 4))
    svc.enumerate(g)
    roots = svc.spans.roots()
    assert len(roots) == 1
    (rid,) = roots
    names = [sp.name for sp in svc.spans.spans if sp.rid == rid]
    assert "superstep" in names and "request" in names


# ---------------------------------------------------------------------------
# Perfetto export
# ---------------------------------------------------------------------------

def _traced_recycled_service(**cfg_kw):
    cfg = EngineConfig(store=True, superstep_rounds=3, **cfg_kw)
    svc = CycleService(cfg, trace=True)
    queue = imbalanced_queue(n_long=2, shorts_per_long=3)
    list(svc.serve_stream(queue, slots=2))
    return svc, queue


def test_perfetto_export_schema_and_tracks():
    svc, queue = _traced_recycled_service()
    doc = to_perfetto(collect_events(svc), svc.spans.spans,
                      meta=dict(test=True))
    assert validate_perfetto(doc) == []
    evs = doc["traceEvents"]
    lane_tids = {e["tid"] for e in evs
                 if e.get("ph") == "X" and e["pid"] == 1}
    assert len(lane_tids) == 2          # slots=2 → one track per lane
    roots = [e for e in evs if e.get("ph") == "X" and e["pid"] == 2
             and e["name"] == "request"]
    assert len(roots) == len(queue)
    counters = {e["name"] for e in evs if e.get("ph") == "C"}
    assert {"frontier_rows", "ring_fill", "live_lanes"} <= counters
    # lane slices carry the rid riding them
    lane_rids = {e["args"]["rid"] for e in evs
                 if e.get("ph") == "X" and e["pid"] == 1
                 and e["args"].get("rid")}
    span_rids = {e["args"]["rid"] for e in roots}
    assert lane_rids and lane_rids <= span_rids


def test_perfetto_guard_instants_on_forced_drain():
    # a tiny ring forces DRAIN guard trips → instant events in the export
    svc = CycleService(EngineConfig(store=True, cycle_buffer_rows=1,
                                    superstep_rounds=3), trace=True)
    g = build_graph(*grid_graph(4, 4))
    svc.enumerate(g)
    doc = to_perfetto(collect_events(svc), svc.spans.spans)
    assert validate_perfetto(doc) == []
    instants = [e for e in doc["traceEvents"] if e.get("ph") == "i"]
    assert any(e["name"] == "guard:DRAIN" for e in instants)


def test_validate_perfetto_catches_bad_documents():
    assert validate_perfetto({}) != []
    assert validate_perfetto({"traceEvents": "nope"})
    base = {"otherData": {"schema": "repro.obs/perfetto/v1"}}
    # missing dur on an X event
    doc = dict(base, traceEvents=[
        {"ph": "X", "pid": 1, "tid": 0, "ts": 0}])
    assert any("dur" in e for e in validate_perfetto(doc))
    # non-monotonic ts on one track
    doc = dict(base, traceEvents=[
        {"ph": "X", "pid": 1, "tid": 0, "ts": 100, "dur": 1},
        {"ph": "X", "pid": 1, "tid": 0, "ts": 50, "dur": 1}])
    assert any("non-monotonic" in e for e in validate_perfetto(doc))
    # span escaping its request root
    doc = dict(base, traceEvents=[
        {"ph": "X", "pid": 2, "tid": 0, "ts": 0, "dur": 10,
         "name": "request", "args": {"rid": "r1"}},
        {"ph": "X", "pid": 2, "tid": 0, "ts": 900000, "dur": 10,
         "name": "superstep", "args": {"rid": "r1"}}])
    assert any("escapes root" in e for e in validate_perfetto(doc))
    # spans without a root
    doc = dict(base, traceEvents=[
        {"ph": "X", "pid": 2, "tid": 0, "ts": 0, "dur": 10,
         "name": "superstep", "args": {"rid": "r1"}}])
    assert any("without a 'request' root" in e
               for e in validate_perfetto(doc))


# ---------------------------------------------------------------------------
# Overhead contract: disabled observability allocates nothing per dispatch
# ---------------------------------------------------------------------------

def test_disabled_path_retains_nothing_but_counts_match():
    queue = imbalanced_queue(n_long=2, shorts_per_long=2)
    cfg = EngineConfig(store=True, superstep_rounds=3)
    svc_off = CycleService(cfg)                 # trace off (default)
    svc_on = CycleService(cfg, trace=True)
    res_off = dict(svc_off.serve_stream(queue, slots=2))
    res_on = dict(svc_on.serve_stream(queue, slots=2))
    # the single-graph driver opens its host phases on the same log
    g = build_graph(*grid_graph(4, 5))
    one_off, one_on = svc_off.enumerate(g), svc_on.enumerate(g)

    # nothing retained per dispatch on the disabled path
    assert list(svc_off.trace_log) == []
    assert svc_off.spans.spans == []
    assert svc_off.last_trace is None
    assert not svc_off.spans.enabled
    assert one_off.trace is None
    assert {sp.name for sp in svc_on.spans.spans} >= {
        "enumerate", "seed", "superstep", "readback", "drain"}
    for name in ("n_host_syncs", "n_d2h_arrays", "n_dispatches",
                 "fused_rounds", "split_rounds"):
        assert one_off.stats[name] == one_on.stats[name], name

    # identical results and aggregate accounting either way
    for i in res_off:
        assert res_off[i].n_cycles == res_on[i].n_cycles
        assert res_off[i].history == res_on[i].history
        a = np.asarray(res_off[i].cycle_masks)
        b = np.asarray(res_on[i].cycle_masks)
        assert a.shape == b.shape and (a == b).all()
    for name in ("requests", "completed", "supersteps", "boundaries",
                 "admissions", "retirements", "pools", "n_host_syncs",
                 "n_d2h_arrays", "split_rounds"):
        assert (svc_off.last_session.stats[name]
                == svc_on.last_session.stats[name]), name
    for name in ("sched_requests_total", "sched_supersteps_total",
                 "sched_admissions_total", "boundary_ms_total"):
        off, on = svc_off.metrics.value(name), svc_on.metrics.value(name)
        if name.endswith("_ms_total"):
            assert (off > 0) == (on > 0)
        else:
            assert off == on, name


def test_disabled_enumerate_retains_no_events():
    svc = CycleService(EngineConfig(store=False))
    res = svc.enumerate(build_graph(*grid_graph(3, 4)))
    assert res.trace is None
    assert svc.spans.spans == [] and list(svc.trace_log) == []
    assert svc.stats["traces_recorded"] == 0


# ---------------------------------------------------------------------------
# Boundary wall-time accounting (the wall_ms satellite)
# ---------------------------------------------------------------------------

def test_boundary_events_carry_wall_ms_and_total_accumulates():
    svc, _ = _traced_recycled_service()
    events = collect_events(svc)
    seeds = [e for e in events if e.kind == "seed"]
    merges = [e for e in events if e.kind == "recycle" and e.admitted]
    assert seeds and merges
    assert all(e.wall_ms > 0 for e in seeds)
    assert all(e.wall_ms > 0 for e in merges)
    # wall_ms covers the whole boundary, so it dominates the device t_ms
    assert all(e.wall_ms >= e.t_ms * 0.5 for e in seeds)
    total = svc.metrics.value("boundary_ms_total")
    acc = sum(e.wall_ms for e in events if e.kind in ("seed", "recycle"))
    assert total == pytest.approx(acc, rel=1e-6)
    assert svc.last_session.stats["boundary_ms"] == pytest.approx(total)


# ---------------------------------------------------------------------------
# FlightRecorder
# ---------------------------------------------------------------------------

def test_flight_recorder_ring_is_bounded():
    fr = FlightRecorder(capacity=8)
    for _ in range(50):
        fr.record(_event())
    assert len(fr.ring) == 8 and fr.n_seen == 50


def test_flight_recorder_guard_storm_trips_and_rate_limits():
    fr = FlightRecorder(capacity=64, storm_window=4, storm_trips=3,
                        cooldown=100)
    for _ in range(8):
        fr.record(_event(status="DRAIN"))
    assert fr.trips.get("guard_storm", 0) >= 1
    assert len(fr.dumps) == 1            # cooldown suppressed repeats
    assert fr.dumps[0]["reason"] == "guard_storm"


def test_flight_recorder_warm_retrace_trigger():
    fr = FlightRecorder()
    fr.record(_event(fresh=False, plan_key="wave/a"))   # program ran warm
    # a cold compile of a NEVER-SEEN key is not a retrace
    fr.record(_event(fresh=True, plan_key="wave/b"))
    assert "warm_retrace" not in fr.trips
    fr.record(_event(fresh=True, plan_key="wave/a"))    # …that key again
    assert fr.trips.get("warm_retrace") == 1
    # events without a plan_key degrade to (kind, bucket) identity
    fr2 = FlightRecorder()
    fr2.record(_event(fresh=False))
    fr2.record(_event(fresh=True, bucket=128))
    assert "warm_retrace" not in fr2.trips
    fr2.record(_event(fresh=True))
    assert fr2.trips.get("warm_retrace") == 1


def test_flight_recorder_occupancy_collapse(tmp_path):
    fr = FlightRecorder(dump_dir=str(tmp_path), min_events=4,
                        occupancy_floor=0.5)
    for _ in range(5):
        fr.record(_event(lanes=4, live_lanes=4))
    fr.record(_event(lanes=4, live_lanes=1))
    assert fr.trips.get("occupancy_collapse") == 1
    dumped = list(tmp_path.glob("flight-*-occupancy_collapse.json"))
    assert len(dumped) == 1


def test_flight_recorder_rides_disabled_service():
    fr = FlightRecorder()
    svc = CycleService(EngineConfig(store=False), recorder=fr)
    svc.enumerate(build_graph(*grid_graph(3, 4)))
    assert fr.n_seen > 0                 # observer saw events…
    assert list(svc.trace_log) == []     # …but nothing was retained
    assert svc.spans.spans == []


# ---------------------------------------------------------------------------
# Host phases on the profiler's clock, device-stage scopes, d2h counts
# ---------------------------------------------------------------------------

def _profile(tmp_path, fn):
    """Run ``fn`` under the JAX profiler (host annotations only) and return
    its ProfileData."""
    import glob
    import os
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(str(tmp_path), "**",
                                         "*.xplane.pb"), recursive=True),
                  key=os.path.getmtime)[-1]
    with open(path, "rb") as f:
        return jax.profiler.ProfileData.from_serialized_xspace(f.read())


def _repro_events(pd):
    """(start ns, end ns, name) of every ``repro.*`` host event."""
    return sorted((e.start_ns, e.end_ns, e.name)
                  for p in pd.planes if p.name == "/host:CPU"
                  for line in p.lines for e in line.events
                  if e.name.startswith("repro."))


def _store_service():
    # a 16-row ring and 2-round supersteps on Grid_4x5: the run GROWs,
    # SHRINKs and drains the ring mid-wave
    return CycleService(EngineConfig(store=True, cycle_buffer_rows=16,
                                     superstep_rounds=2))


def test_phases_nest_inside_enumerate_and_cover_it(tmp_path):
    svc = _store_service()
    g = build_graph(*grid_graph(4, 5))
    svc.enumerate(g)                             # compile outside the trace
    res = {}
    pd = _profile(tmp_path, lambda: res.update(r=svc.enumerate(g)))
    stats = res["r"].stats
    assert stats["exit_causes"].get("GROW") and stats["n_drains"] >= 2
    assert stats["n_bucket_transitions"] > stats["exit_causes"]["GROW"]

    evs = _repro_events(pd)
    (root,) = [e for e in evs if e[2] == "repro.enumerate"]
    kids = [e for e in evs if e is not root]
    names = {e[2] for e in kids}
    assert {"repro.seed", "repro.superstep", "repro.readback",
            "repro.drain", "repro.rebucket"} <= names
    # every phase nests inside the request, and phases never overlap
    assert all(root[0] <= s and e <= root[1] for s, e, _ in kids)
    assert all(a[1] <= b[0] for a, b in zip(kids, kids[1:]))
    covered = sum(e - s for s, e, _ in kids)
    assert covered >= 0.95 * (root[1] - root[0])


def test_wave_driver_reads_the_device_only_in_readback_or_drain(
        monkeypatch):
    import sys
    import jax
    svc = CycleService(EngineConfig(store=True, cycle_buffer_rows=16,
                                    superstep_rounds=2), trace=True)
    g = build_graph(*grid_graph(4, 5))
    svc.enumerate(g)
    reads = []
    real_get, real_asarray = jax.device_get, np.asarray

    def note():
        if sys._getframe(2).f_code.co_name == "_wave_events":
            reads.append(svc.spans.now_ms())

    def device_get(x):
        note()
        return real_get(x)

    def asarray(x, *a, **k):
        if isinstance(x, jax.Array):
            note()
        return real_asarray(x, *a, **k)

    monkeypatch.setattr(jax, "device_get", device_get)
    monkeypatch.setattr(np, "asarray", asarray)
    svc.spans.clear()
    res = svc.enumerate(g)
    monkeypatch.undo()
    assert len(reads) >= res.stats["n_drains"] + 2
    phases = [sp for sp in svc.spans.spans
              if sp.name not in ("request", "enumerate")]
    for t in reads:
        inner = min((sp for sp in phases
                     if sp.t_start_ms <= t <= sp.t_end_ms),
                    key=lambda sp: sp.dur_ms)
        assert inner.name in ("readback", "drain"), inner


def test_span_and_its_annotation_agree_on_the_host_clock(tmp_path):
    import time
    for log in (SpanLog(), SpanLog(origin=time.perf_counter() - 3.0)):
        def run():
            with log.phase("readback", "r1"):
                time.sleep(0.005)
        pd = _profile(tmp_path / str(id(log)), run)
        (sp,) = log.spans
        (ev,) = [e for e in _repro_events(pd) if e[2] == "repro.readback"]
        (env,) = [p for p in pd.planes if p.name == "Task Environment"]
        start_ns = dict(env.stats)["profile_start_time"]
        span_ns = log.origin_unix_ns + sp.t_start_ms * 1e6
        assert abs(start_ns + ev[0] - span_ns) < 1e6
        assert abs((ev[1] - ev[0]) - sp.dur_ms * 1e6) < 1e6


def test_phase_without_a_request_or_log_records_no_span():
    on, off = SpanLog(), SpanLog(enabled=False)
    with on.phase("drain"):
        pass
    with off.phase("drain", "r1"):
        pass
    with on.phase("drain", "r1", lane=2, bucket=64):
        pass
    assert off.spans == []
    assert [(sp.name, sp.rid, sp.lane) for sp in on.spans] == [
        ("drain", "r1", 2)]
    assert on.spans[0].attrs == {"bucket": 64}
    with pytest.raises(KeyError):
        with on.phase("no_such_phase"):
            pass


def _abstract_plan_args(cap, nw, n, m, cyc_cap):
    import jax
    import jax.numpy as jnp
    from repro.core.bitset_graph import BitsetGraph
    from repro.core.frontier import CycleBuffer, Frontier
    S = jax.ShapeDtypeStruct
    i32, u32 = jnp.int32, jnp.uint32
    g = BitsetGraph(offsets=S((n + 1,), i32), neighbors=S((2 * m,), i32),
                    labels=S((n,), i32), adj_bits=S((n, nw), u32),
                    labelgt_bits=S((n, nw), u32), degrees=S((n,), i32),
                    n=n, m=m, max_degree=4)
    f = Frontier(path=S((cap, nw), u32), blocked=S((cap, nw), u32),
                 v1=S((cap,), i32), l2=S((cap,), i32),
                 vlast=S((cap,), i32), count=S((), i32))
    buf = CycleBuffer(masks=S((cyc_cap, nw), u32), count=S((), i32))
    return g, f, buf, S((), i32)


def _plan(cap, *, backend="pallas", rpl=1, store=False, nw=2, n=60,
          m=110, cyc_cap=1):
    from repro.core.plan import PlanKey, WavePlan
    key = PlanKey(kind="wave", bucket=cap, nw=nw, cyc_rows=cyc_cap,
                  delta=4, store=store, formulation="bitword",
                  backend=backend, k_max=4, rpl=rpl,
                  extra=(n, m))
    plan = WavePlan(key)
    lowered = plan.lower(*_abstract_plan_args(cap, nw, n, m, cyc_cap))
    return plan, lowered


def _fused_limit(nw=2, n=60, m=110, cyc_cap=1, store=False):
    """Largest power-of-two bucket whose single-round kernel fits VMEM."""
    from repro.kernels.fused_round import fits_vmem
    cap = 16
    while fits_vmem(cap=2 * cap, nw=nw, n=n, n_neighbors=2 * m,
                    cyc_cap=cyc_cap, formulation="bitword", store=store,
                    persistent=False):
        cap *= 2
    return cap


def test_lowered_plans_name_their_round_stage():
    top = _fused_limit()
    fused, lo_fused = _plan(top)
    split, lo_split = _plan(2 * top)
    text_fused = lo_fused.as_text(debug_info=True)
    text_split = lo_split.as_text(debug_info=True)
    assert "repro.round.fused" in text_fused
    assert "repro.round.compact" not in text_fused
    assert "repro.round.compact" in text_split
    assert "repro.round.flags" in text_split
    assert "repro.round.fused" not in text_split
    # stage 1 is its own pair of programs
    from repro.core import triplets as T
    g = build_graph(*grid_graph(3, 4))
    flags = T._flags_counts_program(g.max_degree, "pallas", False)
    assert "repro.seed" in flags.lower(g).as_text(debug_info=True)


@pytest.mark.parametrize("rpl", [1, 2])
def test_plan_records_the_round_path_of_the_vmem_rule(rpl):
    """The path a traced plan records is the one the VMEM rule gives: a
    fused pallas round while one round's blocks fit the budget, the split
    path past it; the jnp backend always splits."""
    from repro.kernels.fused_round import fits_vmem
    top = _fused_limit()
    for cap in (top // 2, top, 2 * top, 4 * top):
        fits = fits_vmem(cap=cap, nw=2, n=60, n_neighbors=220, cyc_cap=1,
                         formulation="bitword", store=False,
                         persistent=False)
        plan, _ = _plan(cap, rpl=rpl)
        assert plan.round_path == ("fused" if fits else "split"), cap
        assert fits == (cap <= top)
    plan, _ = _plan(top, backend="jnp", rpl=rpl)
    assert plan.round_path == "split"


def test_d2h_arrays_match_a_hand_count():
    g = build_graph(*grid_graph(4, 5))
    for store in (False, True):
        svc = CycleService(EngineConfig(store=store, cycle_buffer_rows=16,
                                        superstep_rounds=2), trace=True)
        res = svc.enumerate(g)
        steps = sum(e.kind == "superstep" for e in res.trace.events)
        # stage 1: two counts, the triangle masks, the live count; each
        # superstep reads status, rounds, two histories, two pending sizes
        # and two counts; a stored run reads the ring count at the end and
        # the masks of every drain
        want = 4 + 8 * steps
        if store:
            want += 1 + res.stats["n_drains"]
        assert res.stats["n_d2h_arrays"] == want, store


def test_sharded_driver_records_its_phases_and_reads(tmp_path):
    """The sharded driver opens the wave driver's host phases: a span each
    for the deal (``seed``), every superstep, every readback and every
    resize of the shards (``rebucket``), nested in the request, and its
    sync and read counters match the readbacks."""
    import jax
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    svc = CycleService(EngineConfig(store=False, mesh=mesh,
                                    formulation="bitword", backend="pallas",
                                    local_capacity=1 << 10, balance_block=8,
                                    superstep_rounds=3), trace=True)
    g = build_graph(*grid_graph(4, 5))
    svc.enumerate(g)                             # compile outside the trace
    svc.spans.clear()
    res = {}
    pd = _profile(tmp_path, lambda: res.update(r=svc.enumerate(g)))
    res = res["r"]
    (rid,) = svc.spans.roots()
    names = [sp.name for sp in svc.spans.spans if sp.rid == rid]
    steps = sum(e.kind == "dist" for e in res.trace.events)
    assert steps > 1
    assert names.count("seed") == 1 and names.count("superstep") == steps
    # one readback after the deal, one per superstep, one at the end, and
    # no dispatch slices added a second time by the request's decomposition
    assert names.count("readback") == steps + 2
    # one resize of the shards per bucket transition
    assert res.stats["bucket_transitions"] > 0
    assert names.count("rebucket") == res.stats["bucket_transitions"]
    assert not {"deal", "dist"} & set(names)
    assert res.stats["n_host_syncs"] == steps + 2
    # the deal's meta; per superstep the rounds, status, three histories
    # and the counters; at the end the counters and the live counts
    assert res.stats["n_d2h_arrays"] == 1 + 6 * steps + 2

    evs = _repro_events(pd)
    (root,) = [e for e in evs if e[2] == "repro.enumerate"]
    kids = [e for e in evs if e is not root]
    assert {e[2] for e in kids} == {"repro.seed", "repro.superstep",
                                    "repro.readback", "repro.rebucket"}
    assert all(root[0] <= s and e <= root[1] for s, e, _ in kids)
    assert all(a[1] <= b[0] for a, b in zip(kids, kids[1:]))
    covered = sum(e - s for s, e, _ in kids)
    assert covered >= 0.9 * (root[1] - root[0])
