"""repro.tune subsystem: telemetry schema, replay-twin fidelity, cost-model
fitting, the persistent store (versioning + LRU), the autotuner search, and
the CycleService(auto_tune=...) integration — including the acceptance
property: any tuner-emitted EngineConfig is bit-identical to the default
config across the slot/bitword × wave/host matrix, and the warm-hit path
runs with no search and no re-trace."""
import json
import os

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (CycleService, EngineConfig, build_graph,
                        sequential_chordless_cycles)
from repro.core.graphs import grid_graph, random_gnp
from repro.tune import (AutoTuner, CostModel, TuneKey, TuneSpace, TuneStore,
                        TUNED_KNOBS, SCHEMA_VERSION, STATUSES, WaveProfile,
                        WaveTrace, disabled_trace, replay, shape_class)


# ---------------------------------------------------------------------------
# Telemetry: recorder schema + near-zero disabled path
# ---------------------------------------------------------------------------

def test_disabled_trace_counts_without_retaining_events():
    tr = disabled_trace()
    tr.sync()
    tr.dispatch(kind="superstep", bucket=64, cyc_cap=1, budget=8, rounds=3,
                status="GROW", t_sizes=(1, 2, 3), c_counts=(0, 0, 1))
    tr.transition()
    assert tr.events == []                       # nothing retained
    s = tr.finalize(rounds=3)
    assert s["n_dispatches"] == 1 and s["n_host_syncs"] == 1
    assert s["n_bucket_transitions"] == 1
    assert s["exit_causes"] == {"GROW": 1}


def test_service_trace_records_structured_events():
    svc = CycleService(EngineConfig(store=False, formulation="bitword"),
                       trace=True)
    g = build_graph(*grid_graph(4, 4))
    res = svc.enumerate(g)
    assert res.trace is not None and res.trace is svc.last_trace
    evs = res.trace.events
    assert len(evs) == res.stats["n_dispatches"]
    assert sum(e.rounds for e in evs) == res.iterations
    for e in evs:
        assert e.kind == "superstep"
        assert e.status in STATUSES
        assert len(e.t_sizes) == e.rounds == len(e.c_counts)
        assert e.bucket >= 1 and e.t_ms > 0
    # the recorded per-round sizes ARE the history (same wave shape)
    flat = [t for e in evs for t in e.t_sizes]
    assert flat == [h["T"] for h in res.history[1:]]
    # the first dispatch of a cold service compiled a fresh program
    assert evs[0].fresh and not evs[-1].fresh
    # measured row-work/waste accounting agrees with the replay twin's
    nw = g.adj_bits.shape[1]
    rep = replay(WaveProfile.from_history(res.history, n=g.n, nw=nw),
                 svc.cfg)
    assert res.trace.row_work(nw) == rep.row_work
    assert res.trace.padded_waste(nw) == rep.padded_waste


def test_untraced_service_attaches_no_trace():
    svc = CycleService(EngineConfig(store=False, formulation="bitword"))
    res = svc.enumerate(build_graph(*grid_graph(3, 4)))
    assert res.trace is None
    assert svc.stats["traces_recorded"] == 0
    assert res.stats["n_dispatches"] > 0      # counters still maintained


# ---------------------------------------------------------------------------
# WaveProfile: extraction + roundtrip
# ---------------------------------------------------------------------------

def test_profile_from_history_and_json_roundtrip():
    g = build_graph(*grid_graph(4, 4))
    res = CycleService(EngineConfig(store=True)).enumerate(g)
    prof = WaveProfile.from_history(res.history, n=g.n,
                                    nw=g.adj_bits.shape[1])
    assert prof.n0 == res.history[0]["T"]
    assert len(prof.t_sizes) == res.iterations
    assert sum(prof.c_counts) == res.n_cycles - res.n_triangles
    assert prof.limit == g.n - 3
    assert prof.peak == max(prof.n0, *prof.t_sizes)
    again = WaveProfile.from_json(json.loads(json.dumps(prof.to_json())))
    assert again == prof


# ---------------------------------------------------------------------------
# Replay: the digital twin must reproduce the real driver's accounting
# ---------------------------------------------------------------------------

REPLAY_CONFIGS = [
    dict(),                                              # defaults
    dict(superstep_rounds=2),                            # budget-bound
    dict(superstep_rounds=32),                           # one big dispatch
    dict(growth_bits=2, grow_headroom=0),                # coarse buckets
    dict(cycle_buffer_rows=16, superstep_rounds=4),      # forced drains
    dict(store=False, grow_headroom=2),                  # count-only
]


@pytest.mark.parametrize("knobs", REPLAY_CONFIGS)
def test_replay_matches_real_driver(knobs):
    n, edges = grid_graph(4, 5)
    g = build_graph(n, edges)
    base = CycleService(EngineConfig(store=True)).enumerate(g)
    prof = WaveProfile.from_history(base.history, n=g.n,
                                    nw=g.adj_bits.shape[1])
    cfg = EngineConfig(**dict(dict(store=True), **knobs))
    real = CycleService(cfg).enumerate(g)
    rep = replay(prof, cfg)
    s = real.stats
    assert rep.n_dispatches == s["n_dispatches"]
    assert rep.n_host_syncs == s["n_host_syncs"]
    assert rep.n_bucket_transitions == s["n_bucket_transitions"]
    assert rep.n_drains == s["n_drains"]
    assert rep.rounds == s["rounds"]
    assert rep.by_cause == s.get("exit_causes", {})
    assert rep.n_programs >= 1 and rep.row_work > rep.padded_waste >= 0


def test_replay_scales_dispatches_with_round_budget():
    g = build_graph(*grid_graph(4, 5))
    res = CycleService(EngineConfig(store=False)).enumerate(g)
    prof = WaveProfile.from_history(res.history, n=g.n,
                                    nw=g.adj_bits.shape[1])
    disp = [replay(prof, EngineConfig(store=False, superstep_rounds=k)
                   ).n_dispatches for k in (1, 4, 32)]
    assert disp[0] >= disp[1] >= disp[2] >= 1


# ---------------------------------------------------------------------------
# Cost model: fitting + scoring
# ---------------------------------------------------------------------------

def _synthetic_trace(a=0.5, b=20.0, compile_ms=100.0):
    tr = WaveTrace(enabled=True)
    for i, rows in enumerate([1 << 8, 1 << 10, 1 << 12, 1 << 14, 1 << 9]):
        warm = a + b * rows / 1e6
        tr.dispatch(kind="superstep", bucket=rows, cyc_cap=1, budget=8,
                    rounds=1, status="RUN", t_sizes=(rows,), c_counts=(0,),
                    t_ms=warm + (compile_ms if i == 0 else 0.0),
                    fresh=(i == 0))
    return tr


def test_replay_dist_feasibility_guard():
    """The sharded twin must keep knob candidates that could drop rows out
    of the running: sparser balance cadence scales the per-device peak
    estimate, and even base-capacity candidates must re-pass the headroom
    check when their cadence is sparser than the profiled run's."""
    from repro.tune import DistProfile, replay_dist

    prof = DistProfile(n=64, nw=2, ndev=4, n0=400,
                       t_sizes=(8000, 30000, 12000, 0),
                       c_counts=(10, 20, 30, 5),
                       peak_device_live=8000, base_local_capacity=8192,
                       base_balance_every=1, balance_block=256)
    base = EngineConfig(store=False, local_capacity=8192, balance_every=1)
    assert replay_dist(prof, base).feasible          # the run that happened
    # same capacity, sparser cadence: peaks can grow between balance steps
    sparser = EngineConfig(store=False, local_capacity=8192, balance_every=4)
    assert not replay_dist(prof, sparser).feasible
    # sparser cadence IS feasible with enough headroom for the scaled peak
    roomy = EngineConfig(store=False, local_capacity=1 << 16,
                         balance_every=4)
    assert replay_dist(prof, roomy).feasible
    # capacity below the initial deal's per-device share can never run
    tiny = EngineConfig(store=False, local_capacity=64, balance_block=32,
                        balance_every=1)
    assert not replay_dist(prof, tiny).feasible
    # infeasible candidates score infinite — never picked over the base
    assert CostModel().score(prof, sparser) == float("inf")
    assert CostModel().score(prof, base) < float("inf")


def test_apply_drops_stored_capacity_conflicting_with_balance_block():
    """TuneKey carries no balance_block, so a stored local_capacity below
    THIS base config's block must be dropped on lookup, not applied (it
    would raise in EngineConfig validation and crash a warm hit)."""
    cfg = EngineConfig(store=False, balance_block=8192,
                       local_capacity=1 << 16)
    out = AutoTuner.apply(dict(local_capacity=4096, superstep_rounds=16),
                          cfg)
    assert out.local_capacity == 1 << 16
    assert out.superstep_rounds == 16


def test_dist_measured_pool_excludes_infeasible():
    """Measured trials rank by wall time alone, and a row-dropping config
    does less work — infeasible candidates must never enter the pool."""
    import jax
    from jax.sharding import Mesh
    from repro.tune import DistProfile, replay_dist

    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    prof = DistProfile(n=64, nw=2, ndev=4, n0=400, t_sizes=(60000, 0),
                       c_counts=(5, 1), peak_device_live=30000,
                       base_local_capacity=1 << 16, base_balance_every=1,
                       balance_block=256)
    base = EngineConfig(store=False, mesh=mesh, local_capacity=1 << 16)
    seen = []

    def measure(c):
        assert replay_dist(prof, c).feasible, "timed an infeasible config"
        seen.append(c)
        return 1.0

    AutoTuner(trials=4).tune(prof, base, measure=measure)
    assert seen, "no trials ran"


def test_cost_model_fit_recovers_coefficients():
    m = CostModel().fit([_synthetic_trace(a=0.5, b=20.0, compile_ms=100.0)])
    assert m.n_fit_events == 4
    assert m.dispatch_ms == pytest.approx(0.5, rel=0.05)
    assert m.ms_per_mrow == pytest.approx(20.0, rel=0.05)
    assert m.compile_ms == pytest.approx(100.0, rel=0.1)


def test_cost_model_unfittable_traces_keep_defaults():
    m = CostModel()
    d0 = (m.dispatch_ms, m.ms_per_mrow)
    m.fit([disabled_trace()])                 # no events at all
    assert (m.dispatch_ms, m.ms_per_mrow) == d0 and m.n_fit_events == 0


def test_cost_model_scoring_prefers_fewer_dispatches_when_rows_equal():
    prof = WaveProfile(n=40, nw=2, n0=64,
                       t_sizes=tuple([64] * 20), c_counts=tuple([0] * 20))
    m = CostModel(dispatch_ms=1.0, ms_per_mrow=0.0, sync_ms=0.0)
    slow = m.score(prof, EngineConfig(store=False, superstep_rounds=1))
    fast = m.score(prof, EngineConfig(store=False, superstep_rounds=32))
    assert fast < slow
    # cold objective charges compiles on top
    assert (m.score(prof, EngineConfig(store=False), objective="cold")
            > m.score(prof, EngineConfig(store=False)))


# ---------------------------------------------------------------------------
# TuneStore: persistence, versioning, LRU bound
# ---------------------------------------------------------------------------

def _key(i=0):
    return TuneKey(shape=f"n{1 << (4 + i)}-m64-d4", store=False,
                   formulation="bitword", backend="jnp", engine="wave",
                   device_kind="cpu")


def test_store_roundtrip_and_key_string():
    k = _key()
    assert TuneKey.from_str(k.as_str()) == k
    s = TuneStore()
    assert s.get(k) is None and s.misses == 1
    s.put(k, dict(superstep_rounds=16), meta=dict(source="model"))
    assert s.get(k) == dict(superstep_rounds=16) and s.hits == 1
    assert k in s and len(s) == 1


def test_store_persists_atomically(tmp_path):
    path = str(tmp_path / "cache" / "tune.json")
    s = TuneStore(path=path)
    s.put(_key(), dict(superstep_rounds=32, growth_bits=2))
    assert os.path.exists(path) and not os.path.exists(path + ".tmp")
    warm = TuneStore(path=path)                  # fresh process re-loads
    assert warm.get(_key()) == dict(superstep_rounds=32, growth_bits=2)
    doc = json.load(open(path))
    assert doc["version"] == SCHEMA_VERSION


def test_store_version_mismatch_drops_stale_entries(tmp_path):
    path = str(tmp_path / "tune.json")
    s = TuneStore(path=path)
    s.put(_key(), dict(superstep_rounds=32))
    doc = json.load(open(path))
    doc["version"] = SCHEMA_VERSION + 1
    json.dump(doc, open(path, "w"))
    fresh = TuneStore(path=path)
    assert len(fresh) == 0 and fresh.stale_drops == 1
    assert fresh.get(_key()) is None


def test_store_save_merges_concurrent_writers(tmp_path):
    """Two processes sharing one store path must not clobber each other's
    entries: save() merges the on-disk state (ours win on conflict)."""
    path = str(tmp_path / "tune.json")
    a = TuneStore(path=path)
    b = TuneStore(path=path)          # loaded before a wrote anything
    a.put(_key(0), dict(superstep_rounds=4))
    b.put(_key(1), dict(superstep_rounds=32))   # must not drop a's entry
    merged = TuneStore(path=path)
    assert merged.get(_key(0)) == dict(superstep_rounds=4)
    assert merged.get(_key(1)) == dict(superstep_rounds=32)


def test_store_locked_save_survives_racing_writers(tmp_path):
    """The fcntl lock serializes the read→merge→replace window: many
    threads hammering one path through separate TuneStore instances must
    not lose a single update (the pre-lock race could drop one)."""
    import threading

    path = str(tmp_path / "tune.json")
    n_writers, n_keys = 8, 6
    errs = []

    def writer(w):
        try:
            s = TuneStore(path=path)
            for i in range(n_keys):
                s.put(_key(w * n_keys + i), dict(superstep_rounds=4 + w))
        except Exception as e:  # pragma: no cover - surfaced via errs
            errs.append(e)

    threads = [threading.Thread(target=writer, args=(w,))
               for w in range(n_writers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    merged = TuneStore(path=path)
    assert len(merged) == n_writers * n_keys
    for w in range(n_writers):
        for i in range(n_keys):
            assert merged.get(_key(w * n_keys + i)) == \
                dict(superstep_rounds=4 + w)


def test_tune_key_ndev_roundtrip_and_legacy_format():
    """Mesh-routed keys carry the device count; unsharded keys keep the
    pre-dist string format (old persisted stores parse unchanged)."""
    k = TuneKey(shape="n32-m64-d4", store=False, formulation="slot",
                backend="jnp", engine="dist", device_kind="cpu", ndev=4)
    assert k.as_str().endswith("|x4")
    assert TuneKey.from_str(k.as_str()) == k
    legacy = "n32-m64-d4|count|slot|jnp|wave|cpu"
    parsed = TuneKey.from_str(legacy)
    assert parsed.ndev == 0 and parsed.as_str() == legacy


def test_store_lru_eviction_and_recency_refresh():
    s = TuneStore(max_entries=2)
    s.put(_key(0), dict(a=0))
    s.put(_key(1), dict(a=1))
    assert s.get(_key(0)) is not None            # refresh 0 → 1 is LRU
    s.put(_key(2), dict(a=2))
    assert s.evictions == 1
    assert s.get(_key(1)) is None                # 1 was evicted, not 0
    assert s.get(_key(0)) is not None
    assert s.stats()["max_entries"] == 2


# ---------------------------------------------------------------------------
# AutoTuner: search mechanics
# ---------------------------------------------------------------------------

def test_space_candidates_lead_with_base_config():
    cfg = EngineConfig(store=True, superstep_rounds=8)
    sets = TuneSpace().knob_sets(cfg)
    assert sets[0] == {k: getattr(cfg, k) for k in TUNED_KNOBS}
    assert len(sets) == len({tuple(sorted(d.items())) for d in sets})
    count_only = TuneSpace().knob_sets(EngineConfig(store=False))
    assert all("cycle_buffer_rows" not in d for d in count_only)


def test_tuner_preserves_correctness_fields_and_persists():
    g = build_graph(*grid_graph(4, 4))
    res = CycleService(EngineConfig(store=True)).enumerate(g)
    prof = WaveProfile.from_history(res.history, n=g.n,
                                    nw=g.adj_bits.shape[1])
    cfg = EngineConfig(store=True, formulation="slot", backend="jnp",
                       max_iters=7, donate=False)
    tuner = AutoTuner(device_kind="cpu")
    key = tuner.key_for(g.n, g.m, g.max_degree, cfg)
    tuned = tuner.tune(prof, cfg, key=key)
    for field in ("store", "formulation", "backend", "max_iters",
                  "donate", "mesh"):
        assert getattr(tuned, field) == getattr(cfg, field)
    assert tuner.lookup(key, cfg) == tuned       # stored → warm path
    assert tuner.stats()["searches"] == 1


def test_tuner_measured_trials_pick_argmin_including_base():
    prof = WaveProfile(n=20, nw=1, n0=32, t_sizes=(64, 128, 40, 8, 0),
                       c_counts=(0, 1, 2, 1, 0))
    cfg = EngineConfig(store=False)
    fake_ms = {4: 9.0, 8: 5.0, 16: 1.0, 32: 7.0}

    def measure(c):
        return fake_ms[c.superstep_rounds]

    tuner = AutoTuner(trials=len(TuneSpace().knob_sets(cfg)),
                      device_kind="cpu")
    tuned = tuner.tune(prof, cfg, measure=measure)
    assert tuned.superstep_rounds == 16
    assert tuner.stats()["trials_run"] > 0


def test_shape_class_buckets_similar_graphs_together():
    assert shape_class(30, 49, 4) == shape_class(32, 64, 3)
    assert shape_class(30, 49, 4) != shape_class(70, 49, 4)


# ---------------------------------------------------------------------------
# Service integration: the acceptance property + the warm-hit path
# ---------------------------------------------------------------------------

@settings(max_examples=6, deadline=None)
@given(formulation=st.sampled_from(["slot", "bitword"]),
       seed=st.integers(0, 4))
def test_tuned_config_bit_identical_masks(formulation, seed):
    """Acceptance: any tuner-emitted EngineConfig yields bit-identical
    cycle_masks to the default config (slot/bitword), through the
    service's own auto-tuning."""
    n, edges = random_gnp(11, 0.35, seed)
    g = build_graph(n, edges)
    cfg = EngineConfig(store=True, formulation=formulation)
    ref = CycleService(cfg).enumerate(g)

    svc = CycleService(cfg, auto_tune=True)
    first = svc.enumerate(g)    # observes: runs base cfg, then tunes
    tuned = svc.enumerate(g)    # executes the tuner-emitted config
    assert svc.stats["tune"]["searches"] == 1
    assert svc.stats["tuned_requests"] == 1
    for res in (first, tuned):
        assert res.n_cycles == ref.n_cycles
        assert res.n_triangles == ref.n_triangles
        assert np.array_equal(res.cycle_masks, ref.cycle_masks)
    cnt_seq, _ = sequential_chordless_cycles(n, edges)
    assert ref.n_cycles == cnt_seq


def test_warm_hit_skips_search_and_trace():
    """A service joining a warm store executes tuned configs immediately:
    no search, no profiling re-trace."""
    store = TuneStore()
    cfg = EngineConfig(store=False, formulation="bitword")
    g = build_graph(*grid_graph(4, 4))
    a = CycleService(cfg, tuner=AutoTuner(store=store, device_kind="cpu"))
    r1 = a.enumerate(g)
    assert a.stats["tune"]["searches"] == 1
    assert a.stats["traces_recorded"] == 1

    b = CycleService(cfg, tuner=AutoTuner(store=store, device_kind="cpu"))
    r2 = b.enumerate(g)
    bs = b.stats
    assert r2.n_cycles == r1.n_cycles
    assert bs["tune"]["searches"] == 0           # no search
    assert bs["tune"]["warm_hits"] == 1
    assert bs["traces_recorded"] == 0            # no re-trace
    assert bs["tuned_requests"] == 1
    assert r2.trace is None


def test_stream_and_batch_flow_through_tuner():
    cfg = EngineConfig(store=True, formulation="bitword")
    g = build_graph(*grid_graph(4, 4))
    svc = CycleService(cfg, auto_tune=True)
    plain = CycleService(cfg).enumerate(g)

    # stream observes like enumerate does
    chunks = []
    gen = svc.stream(g)
    while True:
        try:
            chunks.append(next(gen))
        except StopIteration:
            break
    assert np.array_equal(np.concatenate(chunks, axis=0), plain.cycle_masks)
    assert svc.stats["tune"]["observations"] == 1

    # batch observes its own (padded shape × batch-size) class on first
    # visit — the lane-aware profile feeds the tuner like enumerate does —
    # and executes the stored knobs warm on the second
    results = svc.enumerate_batch([g, build_graph(*grid_graph(4, 4))])
    for res in results:
        assert res.n_cycles == plain.n_cycles
    assert svc.stats["tune"]["observations"] == 2
    again = svc.enumerate_batch([g, build_graph(*grid_graph(4, 4))])
    assert [r.n_cycles for r in again] == [r.n_cycles for r in results]
    assert svc.stats["tune"]["observations"] == 2   # warm hit: no re-observe
    assert svc.stats["tuned_requests"] >= 1


def test_explicit_per_request_config_bypasses_tuner():
    """A caller-pinned config= must not be overridden by a stored tuned
    entry (e.g. a memory-bounding cycle_buffer_rows)."""
    g = build_graph(*grid_graph(4, 4))
    svc = CycleService(EngineConfig(store=True), auto_tune=True, trace=True)
    svc.enumerate(g)                      # tunes the service-default class
    assert svc.stats["tune"]["searches"] == 1
    pinned = EngineConfig(store=True, cycle_buffer_rows=256)
    res = svc.enumerate(g, config=pinned)
    assert res.trace.events[0].cyc_cap == 256    # pinned ring size held
    s = svc.stats
    assert s["tune"]["searches"] == 1            # no second search either
    assert s["tuned_requests"] == 0


def test_tune_store_alone_implies_auto_tune():
    """A persistence path must never be silently ignored: passing
    tune_store without auto_tune=True still wires up the tuner, and
    combining it with an injected tuner (which carries its own store)
    raises."""
    store = TuneStore()
    svc = CycleService(EngineConfig(store=False, formulation="bitword"),
                       tune_store=store)
    svc.enumerate(build_graph(*grid_graph(4, 4)))
    assert svc.stats["tune"]["searches"] == 1 and len(store) == 1
    with pytest.raises(ValueError, match="tune_store"):
        CycleService(tuner=AutoTuner(device_kind="cpu"), tune_store=store)


def test_default_service_unaffected_by_tuning_flags():
    from repro.core import enumerate_chordless_cycles
    g = build_graph(*grid_graph(3, 4))
    res = enumerate_chordless_cycles(g, store=False)
    cnt, _ = sequential_chordless_cycles(*grid_graph(3, 4))
    assert res.n_cycles == cnt
