"""Pallas kernels vs pure-jnp oracles (ref.py) across shape/density sweeps.

All kernels run in the Pallas interpreter on CPU; outputs are exact-integer /
boolean so comparisons are exact (np.array_equal), which is stronger than
allclose."""
import numpy as np
import pytest
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st

from repro.core import build_graph
from repro.core.frontier import Frontier
from repro.core.graphs import complete_bipartite, grid_graph, random_gnp, wheel_graph
from repro.core.triplets import initial_frontier
from repro.kernels import ops, ref


def _mk(n, edges):
    g = build_graph(n, edges)
    f, _, _ = initial_frontier(g)
    return g, f


GRAPHS = [
    ("grid3x4", grid_graph(3, 4)),
    ("grid5x5", grid_graph(5, 5)),
    ("K55", complete_bipartite(5, 5)),
    ("K2_9", complete_bipartite(2, 9)),
    ("wheel12", wheel_graph(12)),
    ("gnp30", random_gnp(30, 0.2, 0)),
    ("gnp64", random_gnp(64, 0.1, 1)),
    ("gnp100_dense", random_gnp(100, 0.35, 2)),   # nw > 3, Δ large
    ("gnp9", random_gnp(9, 0.5, 3)),
]


@pytest.mark.parametrize("name,graph", GRAPHS, ids=[g[0] for g in GRAPHS])
def test_triplet_kernel_matches_ref(name, graph):
    n, edges = graph
    g = build_graph(n, edges)
    d = max(g.max_degree, 1)
    tri_k, trip_k = ops.triplet_flags(g, d)
    tri_r, trip_r = ref.triplet_flags_ref(g, d)
    assert np.array_equal(np.asarray(tri_k), np.asarray(tri_r))
    assert np.array_equal(np.asarray(trip_k), np.asarray(trip_r))


@pytest.mark.parametrize("name,graph", GRAPHS, ids=[g[0] for g in GRAPHS])
def test_expand_kernel_matches_ref(name, graph):
    n, edges = graph
    g, f = _mk(n, edges)
    if int(f.count) == 0:
        pytest.skip("no triplets")
    d = max(g.max_degree, 1)
    cand_k, cyc_k, ext_k = ops.expand_flags_slot(g, f, d)
    cand_r, cyc_r, ext_r = ref.expand_flags_slot_ref(g, f, d)
    # candidate ids only meaningful where some flag is set
    flag = np.asarray(cyc_r | ext_r)
    assert np.array_equal(np.asarray(cyc_k), np.asarray(cyc_r))
    assert np.array_equal(np.asarray(ext_k), np.asarray(ext_r))
    assert np.array_equal(np.asarray(cand_k)[flag], np.asarray(cand_r)[flag])


@pytest.mark.parametrize("name,graph", GRAPHS, ids=[g[0] for g in GRAPHS])
def test_bitword_kernel_matches_ref(name, graph):
    n, edges = graph
    g, f = _mk(n, edges)
    if int(f.count) == 0:
        pytest.skip("no triplets")
    close_k, ext_k = ops.expand_words_bitword(g, f)
    close_r, ext_r = ref.expand_words_bitword_ref(g, f)
    assert np.array_equal(np.asarray(close_k), np.asarray(close_r))
    assert np.array_equal(np.asarray(ext_k), np.asarray(ext_r))


@pytest.mark.parametrize("tile", [8, 32, 128, 256])
def test_expand_kernel_tile_sweep(tile):
    """BlockSpec tiling must not change results (capacity not ∝ tile)."""
    n, edges = grid_graph(4, 7)
    g, f = _mk(n, edges)
    d = max(g.max_degree, 1)
    from repro.kernels.frontier_expand import frontier_expand_pallas
    cand, cyc, ext = frontier_expand_pallas(
        f.path, f.blocked, f.v1, f.l2, f.vlast, f.count,
        g.offsets, g.neighbors, g.labels, g.adj_bits,
        delta=d, tile=tile, interpret=True)
    cand_r, cyc_r, ext_r = ref.expand_flags_slot_ref(g, f, d)
    assert np.array_equal(np.asarray(cyc), np.asarray(cyc_r))
    assert np.array_equal(np.asarray(ext), np.asarray(ext_r))


@settings(max_examples=10, deadline=None)
@given(n=st.integers(6, 40), p=st.floats(0.1, 0.5), seed=st.integers(0, 10**6))
def test_property_kernels_match_ref(n, p, seed):
    n, edges = random_gnp(n, p, seed)
    g, f = _mk(n, edges)
    d = max(g.max_degree, 1)
    tri_k, trip_k = ops.triplet_flags(g, d)
    tri_r, trip_r = ref.triplet_flags_ref(g, d)
    assert np.array_equal(np.asarray(tri_k), np.asarray(tri_r))
    assert np.array_equal(np.asarray(trip_k), np.asarray(trip_r))
    if int(f.count):
        _, cyc_k, ext_k = ops.expand_flags_slot(g, f, d)
        _, cyc_r, ext_r = ref.expand_flags_slot_ref(g, f, d)
        assert np.array_equal(np.asarray(cyc_k), np.asarray(cyc_r))
        assert np.array_equal(np.asarray(ext_k), np.asarray(ext_r))


@pytest.mark.parametrize("name,graph", GRAPHS, ids=[g[0] for g in GRAPHS])
def test_bitword_fused_counts_match_popcount(name, graph):
    """The fused in-kernel popcounts must equal popcounting the emitted
    words (one-pass mask algebra + reduction — DESIGN.md §6.4)."""
    from repro.core.bitset_graph import popcount
    n, edges = graph
    g, f = _mk(n, edges)
    if int(f.count) == 0:
        pytest.skip("no triplets")
    close_k, ext_k, n_cyc, n_new = ops.bitword_fused_counts(g, f)
    close_r, ext_r = ref.expand_words_bitword_ref(g, f)
    assert np.array_equal(np.asarray(close_k), np.asarray(close_r))
    assert np.array_equal(np.asarray(ext_k), np.asarray(ext_r))
    assert int(n_cyc) == int(popcount(jnp.asarray(close_r)).sum())
    assert int(n_new) == int(popcount(jnp.asarray(ext_r)).sum())


def test_kernel_dead_rows_masked():
    """Rows ≥ count must produce no flags (live-mask correctness)."""
    n, edges = grid_graph(3, 5)
    g, f = _mk(n, edges)
    half = Frontier(path=f.path, blocked=f.blocked, v1=f.v1, l2=f.l2,
                    vlast=f.vlast, count=jnp.int32(max(int(f.count) // 2, 1)))
    d = max(g.max_degree, 1)
    _, cyc, ext = ops.expand_flags_slot(g, half, d)
    c = int(half.count)
    assert not np.asarray(cyc)[c:].any()
    assert not np.asarray(ext)[c:].any()


def test_importing_ops_initialises_no_backend():
    """The kernels choose compiled or interpreted when a program lowers,
    so importing them must not pick a backend (a chip belongs to whoever
    initialises it first)."""
    import os
    import subprocess
    import sys
    code = ("import repro.kernels.ops\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge._backends, xla_bridge._backends\n")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120,
                   env=dict(os.environ, JAX_PLATFORMS="cpu"))
