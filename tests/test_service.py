"""CycleService session API: program cache, batch path, streaming, buffer
donation, eager config validation — and oracle equivalence through the new
surface (slot/bitword × store/count vs ref_sequential)."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import (CycleService, EngineConfig, build_graph,
                        enumerate_chordless_cycles,
                        sequential_chordless_cycles)
from repro.core.frontier import empty_cycle_buffer
from repro.core.graphs import grid_graph, random_gnp
from repro.core.plan import PlanKey, WavePlan, batch_graphs, pad_graph
from repro.core import triplets as T


def _ref_sets(n, edges):
    cnt, cycles = sequential_chordless_cycles(n, edges)
    return cnt, set(frozenset(c) for c in cycles)


# ---------------------------------------------------------------------------
# Eager EngineConfig validation
# ---------------------------------------------------------------------------

def test_config_unknown_values_raise_eagerly():
    with pytest.raises(ValueError, match="slot.*bitword"):
        EngineConfig(formulation="bitplane")
    with pytest.raises(ValueError, match="jnp.*pallas"):
        EngineConfig(backend="cuda")
    with pytest.raises(ValueError, match="superstep_rounds"):
        EngineConfig(superstep_rounds=0)
    with pytest.raises(ValueError, match="grow_headroom"):
        EngineConfig(grow_headroom=-1)


def test_config_mesh_mismatches_raise_eagerly():
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    # the sharded path runs any ExpandOp but counts only
    with pytest.raises(ValueError, match="store=True"):
        EngineConfig(store=True, mesh=mesh)
    with pytest.raises(ValueError, match="store=True"):
        EngineConfig(store=True, formulation="bitword", backend="pallas",
                     mesh=mesh)
    # and every count-only combination constructs fine, the fast path too
    for formulation in ("slot", "bitword"):
        for backend in ("jnp", "pallas"):
            EngineConfig(store=False, formulation=formulation,
                         backend=backend, mesh=mesh)


def test_compat_wrapper_validates_before_tracing():
    g = build_graph(*grid_graph(3, 3))
    with pytest.raises(ValueError, match="formulation"):
        enumerate_chordless_cycles(g, formulation="warp")


# ---------------------------------------------------------------------------
# Program cache: hit/miss counters + zero retraces on the warm path
# ---------------------------------------------------------------------------

def test_cache_warm_path_zero_retraces():
    svc = CycleService(EngineConfig(store=False, formulation="bitword"))
    n, edges = grid_graph(4, 4)
    r1 = svc.enumerate(build_graph(n, edges))
    s1 = dict(svc.stats)
    assert s1["cache_misses"] > 0 and s1["n_traces"] == s1["cache_misses"]
    # second-and-later same-bucket graphs: hits only, ZERO retraces
    r2 = svc.enumerate(build_graph(n, edges))
    s2 = dict(svc.stats)
    assert r1.n_cycles == r2.n_cycles
    assert s2["n_traces"] == s1["n_traces"]
    assert s2["cache_misses"] == s1["cache_misses"]
    assert s2["cache_hits"] > s1["cache_hits"]
    assert s2["programs"] == s1["programs"]


def test_plan_precompiles_first_bucket():
    svc = CycleService(EngineConfig(store=False, formulation="bitword"))
    g = build_graph(*grid_graph(4, 4))
    svc.plan(g)
    traces_after_plan = svc.stats["n_traces"]
    assert traces_after_plan >= 1
    res = svc.enumerate(g)
    assert res.n_cycles > 0
    # the first dispatch reused the planned program (no retrace for it);
    # only later (shrunk) buckets may add programs
    assert svc.stats["cache_hits"] >= 1


def test_distinct_services_do_not_share_programs():
    cfg = EngineConfig(store=False, formulation="bitword")
    g = build_graph(*grid_graph(3, 4))
    a, b = CycleService(cfg), CycleService(cfg)
    a.enumerate(g)
    assert b.stats["programs"] == 0 and b.stats["cache_hits"] == 0


# ---------------------------------------------------------------------------
# Donation: no-copy aliasing of the superstep's frontier/CycleBuffer args
# ---------------------------------------------------------------------------

def test_superstep_buffers_are_donated():
    """--log-donation style check: the aliasing must be in the lowered
    program, and on this backend the donated inputs must actually be
    consumed (no defensive copy)."""
    cfg = EngineConfig(store=False, formulation="bitword")
    g = build_graph(*grid_graph(4, 4))
    key = PlanKey(kind="wave", bucket=64, nw=g.adj_bits.shape[1],
                  cyc_rows=1, delta=max(g.max_degree, 1), store=False,
                  formulation="bitword", backend="jnp",
                  k_max=cfg.superstep_rounds, extra=(g.n, g.m))
    plan = WavePlan(key, donate=True)
    f, _, _ = T.initial_frontier(g, bucket=lambda c: 64)
    buf = empty_cycle_buffer(1, g.adj_bits.shape[1])
    txt = plan.lower(g, f, buf, jnp.int32(1)).as_text()
    assert "tf.aliasing_output" in txt, "donation not recorded in lowering"
    plan(g, f, buf, jnp.int32(1))
    assert f.path.is_deleted() and f.blocked.is_deleted(), \
        "donated frontier was copied, not aliased"
    assert buf.masks.is_deleted(), "donated CycleBuffer was copied"


def test_donation_off_keeps_inputs_alive():
    cfg = EngineConfig(store=False, formulation="bitword", donate=False)
    svc = CycleService(cfg)
    g = build_graph(*grid_graph(3, 4))
    cnt_ref, _ = _ref_sets(*grid_graph(3, 4))
    assert svc.enumerate(g).n_cycles == cnt_ref


def test_donate_flag_is_part_of_program_identity():
    """A donating plan must never be served to a donate=False request."""
    svc = CycleService(EngineConfig(store=False, formulation="bitword"))
    g = build_graph(*grid_graph(3, 4))
    svc.enumerate(g)  # populates donating plans
    programs_before = svc.stats["programs"]
    off = EngineConfig(store=False, formulation="bitword", donate=False)
    svc.enumerate(g, config=off)
    assert svc.stats["programs"] > programs_before
    plans = {k: p for k, p in svc._cache._plans.items() if k.kind == "wave"}
    assert {k.donate for k in plans} == {True, False}
    for k, p in plans.items():
        assert p.donated == k.donate


def test_plan_rejects_non_wave_configs():
    from jax.sharding import Mesh
    g = build_graph(*grid_graph(3, 3))
    svc = CycleService()
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    with pytest.raises(ValueError, match="wave"):
        svc.plan(g, config=EngineConfig(store=False, mesh=mesh))


# ---------------------------------------------------------------------------
# Batch path: equivalence vs per-graph loops on mixed-size graphs
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("formulation", ["slot", "bitword"])
def test_batch_matches_per_graph_mixed_sizes(formulation):
    specs = [grid_graph(3, 4), grid_graph(4, 5), random_gnp(12, 0.3, 3),
             random_gnp(9, 0.45, 5)]
    graphs = [build_graph(n, e) for n, e in specs]
    svc = CycleService(EngineConfig(store=True, formulation=formulation))
    batch = svc.enumerate_batch(graphs)
    assert svc.stats["batches"] == 1
    for (n, edges), res in zip(specs, batch):
        cnt_ref, sets_ref = _ref_sets(n, edges)
        assert res.n_cycles == cnt_ref
        assert set(res.cycles_as_sets(n)) == sets_ref
    singles = [svc.enumerate(g) for g in graphs]
    for b, s in zip(batch, singles):
        assert (b.n_cycles, b.n_triangles, b.iterations) == \
            (s.n_cycles, s.n_triangles, s.iterations)
        assert b.history == s.history


def test_batch_count_only_and_empty():
    svc = CycleService(EngineConfig(store=False, formulation="bitword"))
    assert svc.enumerate_batch([]) == []
    specs = [grid_graph(4, 4), random_gnp(10, 0.4, 1), grid_graph(2, 3)]
    graphs = [build_graph(n, e) for n, e in specs]
    for (n, edges), res in zip(specs, svc.enumerate_batch(graphs)):
        cnt_ref, _ = _ref_sets(n, edges)
        assert res.n_cycles == cnt_ref
        assert res.cycle_masks is None


def test_batch_padding_preserves_labels_and_adjacency():
    n, edges = grid_graph(3, 4)
    g = build_graph(n, edges)
    pg = pad_graph(g, n + 7, g.m + 5, g.max_degree + 2)
    assert pg.n == n + 7 and sorted(np.asarray(pg.labels).tolist()) == \
        list(range(n + 7))
    assert (np.asarray(pg.labels[:n]) == np.asarray(g.labels)).all()
    assert (np.asarray(pg.degrees[n:]) == 0).all()
    gb = batch_graphs([g, build_graph(*grid_graph(2, 2))])
    assert gb.adj_bits.shape[0] == 2  # stacked batch axis


# ---------------------------------------------------------------------------
# Streaming
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("formulation", ["slot", "bitword"])
def test_stream_chunks_bit_identical(formulation):
    n, edges = grid_graph(4, 5)
    g = build_graph(n, edges)
    # tiny ring forces multiple mid-run drains → multiple chunks
    cfg = EngineConfig(store=True, formulation=formulation,
                       cycle_buffer_rows=16, superstep_rounds=4)
    svc = CycleService(cfg)
    full = svc.enumerate(g)
    chunks = []
    gen = svc.stream(g)
    while True:
        try:
            chunks.append(next(gen))
        except StopIteration as stop:
            summary = stop.value
            break
    assert len(chunks) > 1
    assert np.array_equal(np.concatenate(chunks, axis=0), full.cycle_masks)
    assert summary.n_cycles == full.n_cycles
    assert summary.cycle_masks is None  # the chunks ARE the masks


def test_stream_requires_store_mode():
    svc = CycleService(EngineConfig(store=False))
    g = build_graph(*grid_graph(3, 3))
    with pytest.raises(ValueError, match="store=True"):
        list(svc.stream(g))


def test_stream_mesh_routed_raises_not_implemented():
    """A mesh-routed config must fail stream() with a clear
    NotImplementedError at call time — not the misleading store=True
    error (mesh configs are count-only by construction), and never the
    silent single-device path."""
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    svc = CycleService()
    g = build_graph(*grid_graph(3, 3))
    with pytest.raises(NotImplementedError, match="shard_map"):
        svc.stream(g, config=EngineConfig(store=False, mesh=mesh))


# ---------------------------------------------------------------------------
# ProgramCache LRU eviction (max_plans)
# ---------------------------------------------------------------------------

def test_program_cache_lru_evicts_and_counts():
    from repro.core.plan import ProgramCache
    cache = ProgramCache(max_plans=2)
    keys = [PlanKey(kind="wave", bucket=1 << (4 + i), nw=1, cyc_rows=1,
                    delta=2, store=False, formulation="bitword",
                    backend="jnp", k_max=8) for i in range(3)]
    sentinels = [object() for _ in keys]
    cache.get_or_build(keys[0], lambda: sentinels[0])
    cache.get_or_build(keys[1], lambda: sentinels[1])
    assert cache.get_or_build(keys[0], lambda: None) is sentinels[0]
    cache.get_or_build(keys[2], lambda: sentinels[2])   # evicts LRU = keys[1]
    assert cache.evictions == 1 and len(cache) == 2
    assert keys[1] not in cache and keys[0] in cache
    rebuilt = object()
    assert cache.get_or_build(keys[1], lambda: rebuilt) is rebuilt
    s = cache.stats()
    assert s["evictions"] == 2 and s["max_plans"] == 2
    with pytest.raises(ValueError, match="max_plans"):
        ProgramCache(max_plans=0)


def test_service_max_plans_bounds_cache_without_breaking_results():
    cfg = EngineConfig(store=False, formulation="bitword")
    bounded = CycleService(cfg, max_plans=1)
    unbounded = CycleService(cfg)
    for spec in [grid_graph(4, 4), grid_graph(3, 5), grid_graph(4, 4)]:
        g = build_graph(*spec)
        assert (bounded.enumerate(g).n_cycles
                == unbounded.enumerate(g).n_cycles)
    s = bounded.stats
    assert s["programs"] <= 1 and s["evictions"] > 0
    # trace accounting stays monotonic across evictions
    assert s["n_traces"] == s["cache_misses"]


# ---------------------------------------------------------------------------
# Oracle equivalence through the new API (acceptance matrix)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("formulation", ["slot", "bitword"])
@pytest.mark.parametrize("store", [True, False])
def test_service_matches_ref_sequential(formulation, store):
    for n, edges in [grid_graph(3, 4), random_gnp(11, 0.35, 17)]:
        g = build_graph(n, edges)
        cnt_ref, sets_ref = _ref_sets(n, edges)
        svc = CycleService(EngineConfig(store=store, formulation=formulation))
        res = svc.enumerate(g)
        assert res.n_cycles == cnt_ref
        if store:
            assert set(res.cycles_as_sets(n)) == sets_ref
        else:
            assert res.cycle_masks is None


def test_per_call_config_override_shares_cache():
    svc = CycleService(EngineConfig(store=True))
    g = build_graph(*grid_graph(3, 4))
    a = svc.enumerate(g)
    b = svc.enumerate(g, config=EngineConfig(store=False))
    assert a.n_cycles == b.n_cycles and b.cycle_masks is None
