"""Stage 1 — FindingInitialTripletsParallel (paper Algorithm 2).

The paper launches |V|·Δ² GPU threads; thread j decodes (i_u, i_x, i_y) from
its global id (Eqs. 1–3) and tests the label condition ℓ(u) < ℓ(x) < ℓ(y) plus
adjacency of (x, y).  Here the same 3-D index grid is evaluated as one
vectorized flag computation (tiled by the caller if n·Δ² is large); the
paper's atomic append into C / T(G) becomes deterministic stream compaction.

Two compaction paths (DESIGN.md §2, §6.7):

* ``initial_frontier``        — host nonzero: the plain reference seed
                                the device paths are tested against.
* ``initial_frontier_device`` — device-side: the triplet-flags →
                                cumsum-scatter deal PR 4 built for the
                                sharded path (``core/distributed``),
                                hoisted here for the single-device path.
                                One tiny counts dispatch sizes the bucket,
                                then ONE seeding dispatch scatters every
                                triplet (and triangle bitmap) in place —
                                no host nonzero, no per-row H2D.  The
                                seeding program is vmappable, so a graph
                                batch seeds ALL lanes in one dispatch
                                (``initial_frontier_batched``).

Both produce bit-identical frontiers: cumsum order over the flat (n·Δ·Δ)
grid IS ascending-index order, the exact order ``np.flatnonzero`` walks.
The device-side programs run under the name scope ``repro.seed`` (the
stage's name in a profiler trace; ``jax.named_call`` keeps each program's
own name, so its compiled form is unchanged).
"""
from __future__ import annotations

import functools
from functools import partial

import numpy as np
import jax
import jax.numpy as jnp

from .bitset_graph import BitsetGraph, bit_test
from .frontier import Frontier


@partial(jax.jit, static_argnames=("delta",))
def triplet_flags(g: BitsetGraph, delta: int):
    """Flags over the (n, Δ, Δ) grid.

    Returns (is_triangle, is_triplet) bool arrays of shape (n, Δ, Δ).
    Mirrors Algorithm 2 lines 2–16 with the slot-validity trick of lines 8–9
    (invalid slots encoded as x = −1) replaced by boolean masking.
    """
    n = g.labels.shape[0]
    u = jnp.arange(n, dtype=jnp.int32)[:, None, None]
    ix = jnp.arange(delta, dtype=jnp.int32)[None, :, None]
    iy = jnp.arange(delta, dtype=jnp.int32)[None, None, :]
    k1 = g.offsets[u]
    deg = g.degrees[u]
    slot_ok = (ix < deg) & (iy < deg) & (ix != iy)
    last = jnp.maximum(g.neighbors.shape[0] - 1, 0)
    x = g.neighbors[jnp.clip(k1 + ix, 0, last)]
    y = g.neighbors[jnp.clip(k1 + iy, 0, last)]
    lu, lx, ly = g.labels[u], g.labels[x], g.labels[y]
    label_ok = (lu < lx) & (lx < ly)
    adj_xy = bit_test(g.adj_bits[x], y)
    base = slot_ok & label_ok
    return base & adj_xy, base & ~adj_xy


@partial(jax.jit, static_argnames=("capacity",))
def gather_triplets(g: BitsetGraph, flat_idx: jnp.ndarray, n_valid: jnp.ndarray,
                    capacity: int) -> Frontier:
    """Materialize frontier rows from flat (n·Δ·Δ) grid indices.

    flat_idx: (capacity,) int32 indices into the flattened stage-1 grid
    (entries ≥ n_valid are padding).  Builds path = {x,u,y}, blocked = Adj(u),
    v1 = x, l2 = ℓ(u), vlast = y.
    """
    delta = g.max_degree
    nw = g.adj_bits.shape[1]
    iu = flat_idx // (delta * delta)
    rem = flat_idx % (delta * delta)
    ix = rem // delta
    iy = rem % delta
    last = jnp.maximum(g.neighbors.shape[0] - 1, 0)
    x = g.neighbors[jnp.clip(g.offsets[iu] + ix, 0, last)]
    y = g.neighbors[jnp.clip(g.offsets[iu] + iy, 0, last)]

    def onehot(v):
        wi = (v // 32)[:, None]
        return jnp.where(jnp.arange(nw)[None, :] == wi,
                         jnp.uint32(1) << (v % 32).astype(jnp.uint32)[:, None],
                         jnp.uint32(0))

    live = (jnp.arange(capacity) < n_valid)
    path = jnp.where(live[:, None], onehot(x) | onehot(iu) | onehot(y), 0)
    blocked = jnp.where(live[:, None], g.adj_bits[iu], 0)
    return Frontier(
        path=path,
        blocked=blocked,
        v1=jnp.where(live, x, -1).astype(jnp.int32),
        l2=jnp.where(live, g.labels[iu], 0).astype(jnp.int32),
        vlast=jnp.where(live, y, 0).astype(jnp.int32),
        count=n_valid.astype(jnp.int32),
    )


def initial_frontier(g: BitsetGraph, *, bucket=lambda c: max(1, int(c)),
                     flags_fn=None):
    """Host-side stage 1: flags → host nonzero → gathered Frontier.

    Returns (frontier, triangle_masks (t, nw) uint32 np.ndarray, n_triangles).
    ``flags_fn`` lets the Pallas kernel backend replace ``triplet_flags``.
    """
    nw = g.adj_bits.shape[1]
    if g.m == 0:
        from .frontier import empty_frontier
        return empty_frontier(1, nw), np.zeros((0, nw), np.uint32), 0
    delta = max(g.max_degree, 1)
    fn = flags_fn or triplet_flags
    tri, trip = fn(g, delta)
    tri_idx = np.flatnonzero(np.asarray(tri).reshape(-1))
    trip_idx = np.flatnonzero(np.asarray(trip).reshape(-1))

    cap = bucket(max(len(trip_idx), 1))
    idx = np.full(cap, 0, np.int32)
    idx[:len(trip_idx)] = trip_idx
    frontier = gather_triplets(g, jnp.asarray(idx),
                               jnp.int32(len(trip_idx)), cap)

    # triangles: materialize their bitmaps (vertex sets identify cycles)
    n_tri = len(tri_idx)
    if n_tri:
        tcap = int(n_tri)
        tidx = np.asarray(tri_idx, np.int32)
        tri_f = gather_triplets(g, jnp.asarray(tidx), jnp.int32(n_tri), tcap)
        tri_masks = np.asarray(tri_f.path)
    else:
        tri_masks = np.zeros((0, g.adj_bits.shape[1]), np.uint32)
    return frontier, tri_masks, n_tri


# ---------------------------------------------------------------------------
# Device-side stage 1 (DESIGN.md §6.7) — the PR-4 cumsum-scatter deal,
# hoisted from core/distributed for the single-device path, vmappable so a
# whole batch seeds in one dispatch.
# ---------------------------------------------------------------------------

def _flags_fn(backend: str):
    if backend == "pallas":
        from ..kernels import ops as kops
        return kops.triplet_flags
    return triplet_flags


def _flags_counts(g: BitsetGraph, delta: int, backend: str):
    """Flags + their counts in one traced unit. The flag grids stay on
    device and feed the (jnp-only) seeding program — flags are computed
    ONCE per stage 1, not once for counting and again for seeding."""
    tri, trip = _flags_fn(backend)(g, delta)
    return tri, trip, tri.sum(dtype=jnp.int32), trip.sum(dtype=jnp.int32)


def _seed_from_flags(g: BitsetGraph, tri, trip, capacity: int,
                     tri_capacity: int):
    """One traced seeding unit: precomputed flag grids → cumsum-scatter
    into a Frontier of static ``capacity`` plus triangle bitmaps of static
    ``tri_capacity``. Pure jnp, batch-transparent — ``jax.vmap`` of this
    seeds every lane at once. Returns (frontier, tri_masks, n_tri,
    overflow)."""
    from .expand import compaction_dests
    flat_trip = trip.reshape(-1)
    n_grid = flat_trip.shape[0]
    grid_ids = jnp.arange(n_grid, dtype=jnp.int32)

    dest, total = compaction_dests(flat_trip, capacity)
    idx = jnp.zeros((capacity,), jnp.int32).at[dest].set(grid_ids,
                                                         mode="drop")
    f = gather_triplets(g, idx, jnp.minimum(total, capacity), capacity)
    overflow = jnp.maximum(total - capacity, 0)

    flat_tri = tri.reshape(-1)
    tdest, ttotal = compaction_dests(flat_tri, tri_capacity)
    tidx = jnp.zeros((tri_capacity,), jnp.int32).at[tdest].set(grid_ids,
                                                               mode="drop")
    tri_f = gather_triplets(g, tidx, jnp.minimum(ttotal, tri_capacity),
                            tri_capacity)
    return f, tri_f.path, ttotal, overflow


@functools.lru_cache(maxsize=None)
def _flags_counts_program(delta: int, backend: str, batched: bool):
    fn = jax.named_call(lambda g: _flags_counts(g, delta, backend),
                        name="repro.seed")
    if batched:
        fn = jax.vmap(fn)
    return jax.jit(fn)


@functools.lru_cache(maxsize=None)
def _seed_program(delta: int, capacity: int, tri_capacity: int,
                  batched: bool):
    fn = jax.named_call(
        lambda g, tri, trip: _seed_from_flags(g, tri, trip, capacity,
                                              tri_capacity),
        name="repro.seed")
    if batched:
        fn = jax.vmap(fn)
    return jax.jit(fn)


def initial_frontier_device(g: BitsetGraph, *,
                            bucket=lambda c: max(1, int(c)),
                            backend: str = "jnp", trace=None):
    """Device-side stage 1 for one graph: a flags+counts dispatch sizes the
    bucket (flag grids stay device-resident), then ONE seeding dispatch
    scatters every triplet and triangle in place (no host nonzero).
    Drop-in for ``initial_frontier`` — returns (frontier, triangle_masks
    (t, nw) uint32 np.ndarray, n_triangles), row-for-row identical.
    ``trace`` (a ``WaveTrace``) counts the arrays read to the host."""
    nw = g.adj_bits.shape[1]
    if g.m == 0:
        from .frontier import empty_frontier
        return empty_frontier(1, nw), np.zeros((0, nw), np.uint32), 0
    delta = max(g.max_degree, 1)
    tri, trip, ntri_j, ntrip_j = _flags_counts_program(
        delta, backend, False)(g)
    n_tri, n_trip = (int(x) for x in jax.device_get((ntri_j, ntrip_j)))
    cap = bucket(max(n_trip, 1))
    if trace is not None:
        trace.d2h(3)    # the two counts, then the triangle masks
    # bucket the triangle capacity too: the fused seed program is one jit
    # shape for BOTH scatters, so an exact tcap would recompile it for
    # every distinct triangle count (callers slice to n_tri anyway)
    tcap = bucket(max(n_tri, 1))
    frontier, tri_masks, _, _ = _seed_program(
        delta, cap, tcap, False)(g, tri, trip)
    return frontier, np.asarray(tri_masks)[:n_tri], n_tri


def initial_frontier_batched(gbat: BitsetGraph, *, delta: int, bucket,
                             backend: str = "jnp",
                             capacity: int | None = None,
                             tri_capacity: int | None = None, trace=None):
    """Device-side stage 1 for a stacked graph batch: ONE flags+counts
    dispatch for every lane, then ONE seeding dispatch that cumsum-scatters
    all B frontiers (and triangle bitmaps) — no host nonzero, no per-lane
    H2D.

    Returns (stacked frontier (leaves (B, cap, …)), tri_masks (B, tcap, nw)
    device array, n_tri (B,) np.int64, n_trip (B,) np.int64). The shared
    ``cap`` is the bucket of the largest lane (the batch runs at one
    shape); ``tcap`` is the bucket of the largest lane's triangle count.

    ``capacity`` / ``tri_capacity`` floor the output shapes: the recycling
    scheduler pins them to the running pool's bucket so a re-seed lands at
    the EXACT shape the cached merge/superstep programs were traced at
    (rows stay identical — a larger capacity only grows the zero padding;
    cumsum order over the flat grid does not depend on it). A lane whose
    need exceeds the floor still wins: the floor is a max, never a trim.
    ``trace`` (a ``WaveTrace``) counts the arrays read to the host.
    """
    tri, trip, ntri_j, ntrip_j = _flags_counts_program(
        delta, backend, True)(gbat)
    n_tri, n_trip = (np.asarray(jax.device_get(x), np.int64)
                     for x in (ntri_j, ntrip_j))
    if trace is not None:
        trace.d2h(2)
    cap = bucket(max(int(n_trip.max()), 1))
    if capacity is not None:
        cap = max(cap, int(capacity))
    # bucketed like cap — an exact tcap would recompile the fused seed
    # program per distinct triangle count (lanes are sliced to n_tri[i])
    tcap = bucket(max(int(n_tri.max()), 1))
    if tri_capacity is not None:
        tcap = max(tcap, int(tri_capacity))
    fbat, tri_masks, _, _ = _seed_program(
        delta, cap, tcap, True)(gbat, tri, trip)
    return fbat, tri_masks, n_tri, n_trip
