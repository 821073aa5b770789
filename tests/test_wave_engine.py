"""Fused wave engine: oracle equivalence matrix + dispatch accounting.

The wave engine must agree with the sequential baseline (Dias et al.) on
every formulation × backend × mode — same cycle count AND the same exact set
of cycle bitmaps where stored — while issuing O(bucket transitions)
dispatches/host syncs instead of O(iterations)."""
import math

import numpy as np
import pytest
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st

from repro.core import (build_graph, enumerate_chordless_cycles,
                        sequential_chordless_cycles)
from repro.core.engine import EngineConfig
from repro.core.graphs import grid_graph, random_gnp


def _ref_sets(n, edges):
    cnt, cycles = sequential_chordless_cycles(n, edges)
    return cnt, set(frozenset(c) for c in cycles)


def _stored_sets(res, n):
    return set(res.cycles_as_sets(n))


@settings(max_examples=8, deadline=None)
@given(n=st.integers(6, 13), p=st.floats(0.2, 0.5), seed=st.integers(0, 10**6))
def test_property_all_formulations_match_ref_er(n, p, seed):
    """slot / bitword × store / count-only on G(n, p)."""
    n, edges = random_gnp(n, p, seed)
    g = build_graph(n, edges)
    cnt_ref, sets_ref = _ref_sets(n, edges)
    for formulation in ("slot", "bitword"):
        r = enumerate_chordless_cycles(g, formulation=formulation, store=True)
        assert r.n_cycles == cnt_ref, formulation
        assert _stored_sets(r, n) == sets_ref, formulation
        rc = enumerate_chordless_cycles(g, formulation=formulation,
                                        store=False)
        assert rc.n_cycles == cnt_ref, (formulation, "count")
        assert rc.cycle_masks is None
        assert rc.history == r.history, formulation


@pytest.mark.parametrize("rows,cols", [(3, 4), (4, 4), (4, 5)])
def test_mesh_graphs_all_formulations(rows, cols):
    """Structured meshes (the paper's grid family) across the full matrix."""
    n, edges = grid_graph(rows, cols)
    g = build_graph(n, edges)
    cnt_ref, sets_ref = _ref_sets(n, edges)
    results = {}
    for formulation in ("slot", "bitword"):
        r = enumerate_chordless_cycles(g, formulation=formulation, store=True)
        assert r.n_cycles == cnt_ref
        assert _stored_sets(r, n) == sets_ref
        results[formulation] = r
    # history (the Fig. 4 wave) must agree exactly across formulations
    assert results["slot"].history == results["bitword"].history


def test_pallas_backend_matrix():
    """The Pallas path (incl. the fused-popcount kernel inside the wave's
    lax.while_loop) must match the reference on every formulation × mode.
    Interpret mode is slow — one small graph covers the routing."""
    n, edges = grid_graph(3, 4)
    g = build_graph(n, edges)
    cnt_ref, sets_ref = _ref_sets(n, edges)
    for formulation in ("slot", "bitword"):
        r = enumerate_chordless_cycles(g, formulation=formulation,
                                       backend="pallas", store=True)
        assert r.n_cycles == cnt_ref, formulation
        assert _stored_sets(r, n) == sets_ref, formulation
        rc = enumerate_chordless_cycles(g, formulation=formulation,
                                        backend="pallas", store=False)
        assert rc.n_cycles == cnt_ref, (formulation, "count")


def test_wave_reduces_dispatches_and_syncs():
    """The tentpole claim: one dispatch per K rounds plus one per bucket
    transition (and the last, partial superstep), not one per round."""
    n, edges = grid_graph(5, 6)
    g = build_graph(n, edges)
    wave = enumerate_chordless_cycles(g, store=False, formulation="bitword")
    cnt_ref, _ = _ref_sets(n, edges)
    assert wave.n_cycles == cnt_ref
    st = wave.stats
    rounds, k = st["rounds"], EngineConfig().superstep_rounds
    assert rounds == wave.iterations > k
    full = math.ceil(rounds / k)
    assert full <= st["n_dispatches"] <= (
        full + st["n_bucket_transitions"] + 1)
    assert st["n_dispatches"] < rounds
    # device-resident loop: syncs scale with bucket transitions, not rounds
    assert st["n_host_syncs"] <= 2 * (st["n_dispatches"] + 2)


def test_wave_tiny_cycle_buffer_drains():
    """Cycle ring smaller than one round's yield: host must drain + regrow
    without losing or duplicating any cycle."""
    n, edges = grid_graph(4, 5)
    g = build_graph(n, edges)
    cnt_ref, sets_ref = _ref_sets(n, edges)
    cfg = EngineConfig(store=True, formulation="bitword",
                       cycle_buffer_rows=16, superstep_rounds=4)
    r = enumerate_chordless_cycles(g, config=cfg)
    assert r.n_cycles == cnt_ref
    assert _stored_sets(r, n) == sets_ref
    assert r.stats["n_drains"] >= 1


def test_wave_max_iters_parity():
    """A ``max_iters`` run stops after exactly that many rounds, on the
    full run's own wave."""
    n, edges = grid_graph(5, 6)
    g = build_graph(n, edges)
    full = enumerate_chordless_cycles(g, store=False)
    cut = enumerate_chordless_cycles(g, store=False, max_iters=5)
    assert full.iterations > 5
    assert cut.iterations == 5
    assert cut.history == full.history[:len(cut.history)]
    assert len(cut.history) == 6
    assert cut.n_cycles == cut.history[-1]["C"]


def test_wave_superstep_rounds_knob():
    """Any K must give identical results (it only changes dispatch batching)."""
    n, edges = grid_graph(4, 6)
    g = build_graph(n, edges)
    base = None
    for k in (1, 3, 32):
        cfg = EngineConfig(store=False, formulation="bitword",
                           superstep_rounds=k)
        r = enumerate_chordless_cycles(g, config=cfg)
        if base is None:
            base = (r.n_cycles, r.iterations, [h["T"] for h in r.history])
        assert base == (r.n_cycles, r.iterations,
                        [h["T"] for h in r.history]), k


def test_engine_config_roundtrip():
    cfg = EngineConfig(store=False, formulation="bitword",
                       growth_bits=2, superstep_rounds=8)
    assert cfg.bucket(3) == 16       # floor bucket
    assert cfg.bucket(17) == 64      # ×4 growth buckets (bits ceil to even)
    n, edges = grid_graph(3, 4)
    g = build_graph(n, edges)
    r = enumerate_chordless_cycles(g, config=cfg)
    cnt_ref, _ = _ref_sets(n, edges)
    assert r.n_cycles == cnt_ref
