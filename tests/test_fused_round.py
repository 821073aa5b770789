"""Fused single-kernel round (DESIGN.md §6.8).

The acceptance surface of the two-phase-scatter fusion:

* the round each op runs — the pallas kernel where the bucket fits its
  VMEM budget, the split round (flags, then gather compaction) elsewhere —
  is bit-identical to the split round of the same formulation on the jnp
  op: every frontier leaf, the cycle-ring masks, the raw n_cyc/n_new
  totals, and BOTH guard flags, round by round, including guard-tripped
  (overflowing) rounds where the round must not be applied;
* the same identity holds through the batched lanes path (custom_vmap →
  lane-gridded kernel) and end-to-end through ``CycleService`` between
  the pallas and jnp backends, in ``cycle_masks`` and |T| histories;
* mesh-routed enumeration matches the reference count on 1/2/4-device
  meshes;
* the traced fused-round program is ONE ``pallas_call`` with zero
  scatter/cumsum/sort passes outside it (the split program demonstrably
  leaks them) — asserted on the jaxpr, plus the trace-time build counters;
* every round a run attempts is counted on exactly one path;
* stored tuner entries from before the knob was removed — carrying a
  ``fused_round`` value or an ``engine='host'`` key — still load and apply.
"""
import os
import subprocess
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import (CycleService, EngineConfig, build_graph,
                        sequential_chordless_cycles)
from repro.core import expand as E
from repro.core.frontier import empty_cycle_buffer, stack_frontiers
from repro.core.graphs import grid_graph, random_gnp
from repro.core.plan import batch_graphs
from repro.core.triplets import initial_frontier
from repro.analysis.dispatch import (assert_fused_round_program,
                                     compaction_prims_outside_kernel,
                                     primitive_counts)

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _graph(r=4, c=4):
    n, edges = grid_graph(r, c)
    return build_graph(n, edges)


def _leaves(f):
    return [("path", f.path), ("blocked", f.blocked), ("v1", f.v1),
            ("l2", f.l2), ("vlast", f.vlast), ("count", f.count)]


# ---------------------------------------------------------------------------
# Round-level bit-identity: each op's round == the jnp split round
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("formulation", ["slot", "bitword"])
@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("store", [True, False])
def test_fused_round_bit_identical(formulation, backend, store):
    g = _graph()
    delta = int(g.max_degree)
    f0, _, _ = initial_frontier(g, bucket=lambda c: 64)
    buf0 = empty_cycle_buffer(256, g.adj_bits.shape[1])
    op_ref = E.expand_op(formulation, "jnp")
    op_fus = E.expand_op(formulation, backend)
    f, buf, fp, bp = f0, buf0, f0, buf0
    for rnd in range(6):
        f, buf, nc, nn, okf, okc = E.expand_count_compact(
            g, f, buf, delta=delta, store=store, op=op_ref)
        fp, bp, ncp, nnp, okfp, okcp = E.expand_count_compact(
            g, fp, bp, delta=delta, store=store, op=op_fus)
        assert int(nc) == int(ncp) and int(nn) == int(nnp), (rnd, nn, nnp)
        assert bool(okf) == bool(okfp) and bool(okc) == bool(okcp), rnd
        for name, leaf in _leaves(f):
            got = dict(_leaves(fp))[name]
            assert np.array_equal(np.asarray(leaf), np.asarray(got)), \
                (rnd, name)
        if store:
            assert np.array_equal(np.asarray(buf.masks),
                                  np.asarray(bp.masks)), rnd
            assert int(buf.count) == int(bp.count)


@pytest.mark.parametrize("formulation", ["slot", "bitword"])
def test_fused_round_guard_trip_not_applied(formulation):
    """An overflowing round must leave the state untouched in BOTH paths —
    the fused kernel evaluates the guard inside and copies the input
    through (identity) instead of scattering a truncated frontier."""
    g = _graph()
    delta = int(g.max_degree)
    f0, _, _ = initial_frontier(g, bucket=lambda c: 16)  # forces overflow
    buf0 = empty_cycle_buffer(256, g.adj_bits.shape[1])
    op_ref = E.expand_op(formulation, "jnp")
    op_pal = E.expand_op(formulation, "pallas")
    f, buf, fp, bp = f0, buf0, f0, buf0
    tripped = False
    for rnd in range(4):
        f, buf, nc, nn, okf, _ = E.expand_count_compact(
            g, f, buf, delta=delta, store=True, op=op_ref)
        fp, bp, _, _, okfp, _ = E.expand_count_compact(
            g, fp, bp, delta=delta, store=True, op=op_pal)
        assert bool(okf) == bool(okfp), rnd
        tripped = tripped or not bool(okf)
        assert np.array_equal(np.asarray(f.path), np.asarray(fp.path)), rnd
        assert int(f.count) == int(fp.count)
        assert np.array_equal(np.asarray(buf.masks), np.asarray(bp.masks))
    assert tripped  # the bucket was sized to overflow — prove it did


@pytest.mark.parametrize("formulation", ["slot", "bitword"])
def test_fused_round_batched_lanes_bit_identical(formulation):
    """vmapped fused round (custom_vmap → lane-gridded kernel) == vmapped
    jnp split round, per lane, on a mixed-size batch."""
    specs = [grid_graph(3, 4), grid_graph(4, 4)]
    gs = [build_graph(n, e) for n, e in specs]
    gb = batch_graphs(gs)
    delta = int(max(g.max_degree for g in gs))
    fb = stack_frontiers([initial_frontier(g, bucket=lambda c: 64)[0]
                          for g in gs])
    bb = empty_cycle_buffer(256, gb.adj_bits.shape[2], batch=2)
    op_ref = E.expand_op(formulation, "jnp")
    op_pal = E.expand_op(formulation, "pallas")
    step_ref = jax.vmap(lambda gg, ff, uu: E.expand_count_compact(
        gg, ff, uu, delta=delta, store=True, op=op_ref))
    step_pal = jax.vmap(lambda gg, ff, uu: E.expand_count_compact(
        gg, ff, uu, delta=delta, store=True, op=op_pal))
    f, buf, fp, bp = fb, bb, fb, bb
    for rnd in range(5):
        f, buf, nc, nn, *_ = step_ref(gb, f, buf)
        fp, bp, ncp, nnp, *_ = step_pal(gb, fp, bp)
        assert np.array_equal(np.asarray(nn), np.asarray(nnp)), rnd
        assert np.array_equal(np.asarray(nc), np.asarray(ncp)), rnd
        assert np.array_equal(np.asarray(f.path), np.asarray(fp.path)), rnd
        assert np.array_equal(np.asarray(f.count), np.asarray(fp.count))
        assert np.array_equal(np.asarray(buf.masks), np.asarray(bp.masks))
    assert np.array_equal(np.asarray(buf.count), np.asarray(bp.count))


# ---------------------------------------------------------------------------
# End-to-end: CycleService pallas (fused) == jnp (split) in masks + histories
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("formulation", ["slot", "bitword"])
@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_service_fused_matches_split_end_to_end(formulation, backend):
    """``backend`` against the jnp backend (every round split): the pallas
    case runs its fused kernel, the jnp case pins the split round's
    determinism across two services."""
    for n, edges in [grid_graph(4, 4), random_gnp(14, 0.35, 7)]:
        g = build_graph(n, edges)
        ref, _ = sequential_chordless_cycles(n, edges)
        res = {}
        for be in (backend, "jnp"):
            svc = CycleService(EngineConfig(
                store=True, formulation=formulation, backend=be))
            res[be] = svc.enumerate(g)
        got, split = res[backend], res["jnp"]
        if backend == "pallas":
            assert got.stats["fused_rounds"] > 0
        assert split.stats["fused_rounds"] == 0
        assert got.n_cycles == split.n_cycles == ref
        assert got.history == split.history
        assert np.array_equal(got.cycle_masks, split.cycle_masks)


def test_service_fused_batched_matches_split():
    specs = [grid_graph(3, 4), grid_graph(4, 5), random_gnp(12, 0.3, 3)]
    gs = [build_graph(n, e) for n, e in specs]
    out = {}
    for backend in ("pallas", "jnp"):
        svc = CycleService(EngineConfig(store=True, formulation="bitword",
                                        backend=backend))
        out[backend] = svc.enumerate_batch(gs)
    assert out["pallas"][0].stats["fused_rounds"] > 0
    for a, b, (n, edges) in zip(out["pallas"], out["jnp"], specs):
        ref, _ = sequential_chordless_cycles(n, edges)
        assert a.n_cycles == b.n_cycles == ref
        assert a.history == b.history
        assert np.array_equal(a.cycle_masks, b.cycle_masks)


def test_mesh_fused_matches_reference_1_2_4_devices():
    """Sharded local step (flags, then gather compaction) == reference
    counts on 1/2/4-device meshes, on both backends (subprocess: forces
    multiple host devices)."""
    code = """
import jax, numpy as np
from jax.sharding import Mesh
from repro.core import (CycleService, EngineConfig, build_graph,
                        sequential_chordless_cycles)
from repro.core.graphs import grid_graph, random_gnp

for n, edges in [grid_graph(4, 6), random_gnp(24, 0.3, 5)]:
    g = build_graph(n, edges)
    ref, _ = sequential_chordless_cycles(n, edges)
    for ndev in (1, 2, 4):
        mesh = Mesh(np.array(jax.devices())[:ndev].reshape(ndev,), ('data',))
        for backend in ('jnp', 'pallas'):
            cfg = EngineConfig(store=False, mesh=mesh, local_capacity=1<<13,
                               balance_block=64, backend=backend)
            res = CycleService(cfg).enumerate(g)
            assert res.n_cycles == ref, (ndev, backend, res.n_cycles, ref)
            assert res.stats['dropped'] == 0 and res.stats['lost'] == 0
print('OK')
"""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout


# ---------------------------------------------------------------------------
# Dispatch contract: one pallas_call, zero compaction passes outside it
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("formulation", ["slot", "bitword"])
@pytest.mark.parametrize("store", [True, False])
def test_fused_round_is_one_kernel_dispatch(formulation, store):
    g = _graph()
    delta = int(g.max_degree)
    f, _, _ = initial_frontier(g, bucket=lambda c: 64)
    buf = empty_cycle_buffer(256, g.adj_bits.shape[1])
    op = E.expand_op(formulation, "pallas")

    def fused_body(g, f, buf):
        return E.expand_count_compact(g, f, buf, delta=delta, store=store,
                                      op=op)

    counts = assert_fused_round_program(fused_body, g, f, buf)
    assert counts.get("pallas_call", 0) == 1

    # the contrast: the split round leaks compaction passes into XLA
    split_op = E.expand_op(formulation, "jnp")

    def split_body(g, f, buf):
        return E.expand_count_compact(g, f, buf, delta=delta, store=store,
                                      op=split_op)

    leak = compaction_prims_outside_kernel(
        primitive_counts(jax.make_jaxpr(split_body)(g, f, buf)))
    assert leak, "split round should still issue compaction primitives"


def test_fused_kernel_build_counters_increment():
    from repro.kernels import ops as kops
    g = _graph()
    delta = int(g.max_degree)
    f, _, _ = initial_frontier(g, bucket=lambda c: 64)
    buf = empty_cycle_buffer(256, g.adj_bits.shape[1])
    op = E.expand_op("bitword", "pallas")
    before = dict(kops.FUSED_KERNEL_BUILDS)
    jax.make_jaxpr(lambda g, f, buf: E.expand_count_compact(
        g, f, buf, delta=delta, store=False, op=op))(g, f, buf)
    assert kops.FUSED_KERNEL_BUILDS["single"] > before["single"]




# ---------------------------------------------------------------------------
# Round-path accounting: every attempted round is counted on one path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("formulation", ["slot", "bitword"])
@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_round_path_counters_cover_every_round(formulation, backend):
    """``fused_rounds + split_rounds`` is every round the run applied plus
    the one each GROW or DRAIN exit aborted (and re-ran after the host
    resized); the jnp backend has no fused kernel to take."""
    g = _graph(4, 5)
    res = CycleService(EngineConfig(formulation=formulation,
                                    backend=backend)).enumerate(g)
    st = res.stats
    aborted = sum(st["exit_causes"].get(s, 0) for s in ("GROW", "DRAIN"))
    assert aborted > 0          # the default buckets do grow on Grid_4x5
    assert st["rounds"] == res.iterations == len(res.history) - 1 > 0
    assert st["fused_rounds"] + st["split_rounds"] == st["rounds"] + aborted
    if backend == "jnp":
        assert st["fused_rounds"] == 0
    else:
        assert st["fused_rounds"] > 0


# ---------------------------------------------------------------------------
# Stored tuner entries from before the knob went
# ---------------------------------------------------------------------------

def test_legacy_tune_entries_parse_and_apply():
    """Key strings written before the key gained tokens round-trip, and a
    stored knob dict applies onto the base config."""
    from repro.tune import AutoTuner, TuneKey
    legacy = "n32-m64-d8|count|bitword|pallas|wave|cpu"
    key = TuneKey.from_str(legacy)
    assert key.as_str() == legacy
    tuned = AutoTuner.apply({"superstep_rounds": 8}, EngineConfig())
    assert tuned.superstep_rounds == 8


@pytest.mark.parametrize("legacy", [True, False])
def test_legacy_store_with_fused_round_and_host_entries_loads(tmp_path,
                                                             legacy):
    """A store file in today's format whose entries carry the removed
    ``fused_round`` knob, plus an entry keyed ``engine='host'``: it loads,
    the wave entry applies without the knob, and the host entry matches no
    request."""
    import dataclasses
    import json
    from repro.tune import SCHEMA_VERSION, AutoTuner, TuneKey, TuneStore
    g = _graph(4, 4)
    cfg = EngineConfig(store=False, formulation="bitword")
    key = AutoTuner(device_kind=jax.devices()[0].platform).key_for(
        g.n, g.m, g.max_degree, cfg)
    host = dataclasses.replace(key, engine="host")
    path = tmp_path / "tune.json"
    path.write_text(json.dumps(dict(version=SCHEMA_VERSION, entries={
        key.as_str(): dict(knobs={"superstep_rounds": 4,
                                  "fused_round": legacy},
                           meta={}, hits=0),
        host.as_str(): dict(knobs={"superstep_rounds": 2,
                                   "fused_round": legacy},
                            meta={}, hits=0)})))
    store = TuneStore(path=str(path))
    assert len(store) == 2 and store.stale_drops == 0
    assert TuneKey.from_str(host.as_str()) == host

    svc = CycleService(cfg, tune_store=store)
    res = svc.enumerate(g)
    ref, _ = sequential_chordless_cycles(*grid_graph(4, 4))
    assert res.n_cycles == ref
    st = svc.stats
    assert st["tuned_requests"] == 1 and st["tune"]["searches"] == 0
    # the stored round budget is the one the compiled supersteps run
    assert {k.k_max for k in svc._cache._plans if k.kind == "wave"} == {4}
    entries = json.loads(path.read_text())["entries"]
    assert store._entries[host.as_str()]["hits"] == 0
    assert host.as_str() in entries
    for knobs in (store.get(key), store.get(host)):
        tuned = AutoTuner.apply(knobs, cfg)
        assert not hasattr(tuned, "fused_round")
        assert tuned.formulation == "bitword" and tuned.store is False
