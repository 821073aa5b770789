"""Pallas TPU kernel for Stage 1 — FindingInitialTripletsParallel.

Paper Algorithm 2: thread j decodes (i_u, i_x, i_y) from its global id and
tests ℓ(u) < ℓ(x) < ℓ(y) plus (x,y) ∈ E.  Here the |V|·Δ² thread grid becomes
a lane-gridded Pallas grid ``(B, np//tu)`` over (graph lane × vertex tile)
pairs (DESIGN.md §6.7); each grid step fills a (TU, Δ·Δ) flag tile, one
vertex row at a time, with the same index algebra (Eqs. 1–3 of the paper)
computed from a lane iota. The (x,y) ∈ E binary search (O(log Δ)) is
replaced by an O(1) adjacency-bitmap probe held in VMEM.  The single-graph
entry point is the B=1 special case — one dispatch seeds every lane of a
graph batch.

Mosaic has no vector gather: the CSR tables are read as scalars (u's
offsets, its Δ neighbors and their labels) and spread over the row's slot
lanes with selects.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _triplet_kernel(offsets_ref, neighbors_ref, labels_ref, adj_ref,
                    tri_ref, trip_ref, *, delta: int, tu: int):
    # every ref carries a leading lane-block dim of 1 (the lane grid axis)
    n = labels_ref.shape[1]
    nw = adj_ref.shape[2]
    last = neighbors_ref.shape[1] - 1
    dd = delta * delta
    slot = jax.lax.broadcasted_iota(jnp.int32, (1, dd), 1)
    ix = slot // delta     # Eq. 2 (relative index of x)
    iy = slot % delta      # Eq. 3 (relative index of y)
    step = pl.program_id(1)     # vertex tile within this lane

    def vertex(uu, carry):
        u = step * tu + uu
        uc = jnp.minimum(u, n - 1)
        k1 = offsets_ref[0, uc, 0]
        deg = offsets_ref[0, uc + 1, 0] - k1
        lu = labels_ref[0, uc, 0]

        def nbr(j):
            x = neighbors_ref[0, jnp.clip(k1 + j, 0, last), 0]
            return jnp.clip(x, 0, n - 1)

        # slot lanes of x = N(u)[ix] and y = N(u)[iy]: labels, and y's id
        def spread(j, acc):
            lx, ly, y = acc
            x = nbr(j)
            lab = labels_ref[0, x, 0]
            return (jnp.where(ix == j, lab, lx), jnp.where(iy == j, lab, ly),
                    jnp.where(iy == j, x, y))

        zero = jnp.zeros((1, dd), jnp.int32)
        lx, ly, y = jax.lax.fori_loop(0, deg, spread, (zero, zero, zero))

        # (x, y) ∈ E: the word of Adj[x] holding y's bit, per slot lane
        yw = y // 32

        def probe(j, acc):
            x = nbr(j)
            for w in range(nw):
                acc = jnp.where((ix == j) & (yw == w), adj_ref[0, x, w], acc)
            return acc

        words = jax.lax.fori_loop(0, deg, probe,
                                  jnp.zeros((1, dd), jnp.uint32))
        adj_xy = ((words >> (y % 32).astype(jnp.uint32))
                  & jnp.uint32(1)) != 0

        slot_ok = (ix < deg) & (iy < deg) & (ix != iy) & (u < n)
        base = slot_ok & (lu < lx) & (lx < ly)
        row = pl.ds(uu, 1)
        tri_ref[0, row, :] = (base & adj_xy).astype(jnp.int32)
        trip_ref[0, row, :] = (base & ~adj_xy).astype(jnp.int32)
        return carry

    jax.lax.fori_loop(0, tu, vertex, 0)


@functools.partial(jax.jit, static_argnames=("delta", "tile", "interpret"))
def triplet_init_lanes(offsets, neighbors, labels, adj_bits,
                       *, delta: int, interpret: bool, tile: int = 8):
    """Lane-gridded stage 1: ONE ``pallas_call`` flags every lane's
    (n, Δ, Δ) triplet grid.  Graph tables carry a leading lane axis
    ((B, n+1), (B, 2m), (B, n), (B, n, nw)); returns (is_triangle,
    is_triplet) of shape (B, n, Δ, Δ)."""
    B, n = labels.shape
    tu = tile
    np_ = -(-n // tu) * tu
    dd = delta * delta

    nbr = neighbors[..., None]
    if nbr.shape[1] == 0:
        nbr = jnp.zeros((B, 1, 1), jnp.int32)
    offs = offsets[..., None]
    labs = labels[..., None]
    lane_whole = lambda a: pl.BlockSpec(
        (1,) + a.shape[1:], lambda b, i: (b,) + (0,) * (a.ndim - 1))

    kernel = functools.partial(_triplet_kernel, delta=delta, tu=tu)
    tri, trip = pl.pallas_call(
        kernel,
        grid=(B, np_ // tu),
        in_specs=[lane_whole(offs), lane_whole(nbr), lane_whole(labs),
                  lane_whole(adj_bits)],
        out_specs=[pl.BlockSpec((1, tu, dd), lambda b, i: (b, i, 0)),
                   pl.BlockSpec((1, tu, dd), lambda b, i: (b, i, 0))],
        out_shape=[jax.ShapeDtypeStruct((B, np_, dd), jnp.int32),
                   jax.ShapeDtypeStruct((B, np_, dd), jnp.int32)],
        interpret=interpret,
        name="triplet_init",
    )(offs, nbr, labs, adj_bits)
    return (tri[:, :n].reshape(B, n, delta, delta) != 0,
            trip[:, :n].reshape(B, n, delta, delta) != 0)


def triplet_init_pallas(offsets, neighbors, labels, adj_bits,
                        *, delta: int, interpret: bool, tile: int = 8):
    """Single-graph entry point — the B=1 lane of ``triplet_init_lanes``.
    Returns (is_triangle, is_triplet) of shape (n, Δ, Δ)."""
    tri, trip = triplet_init_lanes(
        offsets[None], neighbors[None], labels[None], adj_bits[None],
        delta=delta, tile=tile, interpret=interpret)
    return tri[0], trip[0]
