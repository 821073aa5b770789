"""Pallas TPU kernel for Stage 2 — ExpandingChordlessPathsParallel.

This is the paper's hot spot (Algorithm 3): for every in-flight chordless
path and every candidate slot j < Δ, decide cycle / extend / discard.

TPU mapping (DESIGN.md §2):
  * the grid is a LANE GRID ``(B, capp//tp)`` (DESIGN.md §6.7): dim 0 walks
    graph lanes of a batch, dim 1 walks frontier row tiles (TP paths per
    step) within a lane — the analogue of the paper's persistent-thread
    blocks, extended by a tenant axis;
  * each lane's whole graph (CSR neighbors + adjacency bitmap + labels) is
    pinned in VMEM via BlockSpecs with a lane-constant index_map — the
    analogue of the paper's "graph in SM shared memory" trick (§4.2). This
    bounds supported graphs to n·nw·4 + 2m·4 ≲ VMEM (n ≈ 8k on a 16 MB v5e
    core), the same kind of capacity limit the paper accepts for its 64 KB
    SMs;
  * the per-candidate `if` ladder becomes branch-free mask algebra on the
    VPU; chord checking is one word-probe into the *blocked* bitset;
  * no atomics: the kernel only emits flags; prefix-sum compaction happens
    outside (stream compaction — the TPU replacement for the paper's
    serialized index allocation).

Block shapes: path/blocked tiles are (1, TP, nw) uint32 — nw = ⌈n/32⌉ words.
TP defaults to 128 (8×16 sublane×lane friendly); flag outputs are
(1, TP, Δp) with Δp = Δ rounded up to a lane multiple by the wrapper. The
single-graph entry point is the B=1 special case of the same kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _expand_kernel(path_ref, blocked_ref, v1_ref, l2_ref, vlast_ref,
                   offsets_ref, neighbors_ref, labels_ref, adj_ref,
                   cand_ref, cycle_ref, ext_ref, *, delta_p: int):
    # every ref carries a leading lane-block dim of 1 (the lane grid axis);
    # Mosaic has no vector gather, so each path row reads its CSR slots as
    # scalars and spreads them over the row's Δp slot lanes
    tp = path_ref.shape[1]
    n, nw = adj_ref.shape[1], adj_ref.shape[2]
    last = neighbors_ref.shape[1] - 1
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, delta_p), 1)
    widx = jax.lax.broadcasted_iota(jnp.int32, (1, nw), 1)

    def row(r, carry):
        rows = pl.ds(r, 1)
        path = path_ref[0, rows, :]            # (1, nw) uint32
        blocked = blocked_ref[0, rows, :]
        l2 = l2_ref[0, r, 0]
        vc = jnp.clip(vlast_ref[0, r, 0], 0, n - 1)
        k1 = offsets_ref[0, vc, 0]
        deg = offsets_ref[0, vc + 1, 0] - k1
        adj_v1 = adj_ref[0, pl.ds(jnp.clip(v1_ref[0, r, 0], 0, n - 1), 1), :]

        def slot(j, acc):
            cand, cyc, ext = acc
            v = neighbors_ref[0, jnp.clip(k1 + j, 0, last), 0]
            vi = jnp.clip(v, 0, n - 1)
            sh = jnp.full((1, nw), vi % 32, jnp.int32).astype(jnp.uint32)
            bit = jnp.where(widx == vi // 32, jnp.uint32(1) << sh,
                            jnp.uint32(0))
            hit = lambda words: jnp.max(
                jnp.where((words & bit) != 0, 1, 0)) > 0
            valid = ((j < deg) & (labels_ref[0, vi, 0] > l2)    # ℓ(v) > ℓ(v₂)
                     & ~hit(path) & ~hit(blocked))            # chord check
            closes = hit(adj_v1)                               # v ∈ Adj(v₁)
            at = lane == j
            return (jnp.where(at, v, cand),
                    jnp.where(at & valid & closes, 1, cyc),
                    jnp.where(at & valid & ~closes, 1, ext))

        zero = jnp.zeros((1, delta_p), jnp.int32)
        cand, cyc, ext = jax.lax.fori_loop(0, delta_p, slot,
                                           (zero, zero, zero))
        cand_ref[0, rows, :] = cand
        cycle_ref[0, rows, :] = cyc
        ext_ref[0, rows, :] = ext
        return carry

    jax.lax.fori_loop(0, tp, row, 0)


def _pad_to(x, mult, axis=1, fill=0):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=fill)


@functools.partial(jax.jit,
                   static_argnames=("delta", "tile", "interpret"))
def frontier_expand_lanes(path, blocked, v1, l2, vlast, count,
                          offsets, neighbors, labels, adj_bits,
                          *, delta: int, interpret: bool,
                          tile: int = 128):
    """Lane-gridded slot expansion: ONE ``pallas_call`` advances every lane.

    Shapes: ``path``/``blocked`` (B, cap, nw); ``v1``/``l2``/``vlast``
    (B, cap); ``count`` (B,); graph tables (B, n+1)/(B, 2m)/(B, n)/(B, n, nw).
    Returns (cand_v, is_cycle, is_ext), each (B, cap, Δ).
    """
    B, cap, nw = path.shape
    tp = min(tile, max(8, cap))
    delta_p = max(8, -(-delta // 8) * 8)  # pad Δ to a multiple of 8 lanes

    path_p = _pad_to(path, tp)
    blocked_p = _pad_to(blocked, tp)
    capp = path_p.shape[1]
    col = lambda a: _pad_to(a[..., None], tp)
    v1_p, l2_p, vl_p = col(v1), col(l2), col(vlast)
    nbr = _pad_to(neighbors[..., None], 8, fill=0)
    offs = offsets[..., None]
    labs = labels[..., None]

    grid = (B, capp // tp)
    kernel = functools.partial(_expand_kernel, delta_p=delta_p)
    lane_whole = lambda a: pl.BlockSpec(
        (1,) + a.shape[1:], lambda b, i: (b,) + (0,) * (a.ndim - 1))

    cand, cyc, ext = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tp, nw), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, tp, nw), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, tp, 1), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, tp, 1), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, tp, 1), lambda b, i: (b, i, 0)),
            lane_whole(offs), lane_whole(nbr), lane_whole(labs),
            lane_whole(adj_bits),
        ],
        out_specs=[
            pl.BlockSpec((1, tp, delta_p), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, tp, delta_p), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, tp, delta_p), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, capp, delta_p), jnp.int32),
            jax.ShapeDtypeStruct((B, capp, delta_p), jnp.int32),
            jax.ShapeDtypeStruct((B, capp, delta_p), jnp.int32),
        ],
        interpret=interpret,
        name="frontier_expand",
    )(path_p, blocked_p, v1_p, l2_p, vl_p, offs, nbr, labs, adj_bits)

    live = (jnp.arange(cap, dtype=jnp.int32)[None, :]
            < count[:, None])[..., None]
    cand = cand[:, :cap, :delta]
    cyc = (cyc[:, :cap, :delta] != 0) & live
    ext = (ext[:, :cap, :delta] != 0) & live
    return cand, cyc, ext


def frontier_expand_pallas(path, blocked, v1, l2, vlast, count,
                           offsets, neighbors, labels, adj_bits,
                           *, delta: int, interpret: bool,
                           tile: int = 128):
    """Single-graph entry point — the B=1 lane of ``frontier_expand_lanes``.
    Returns (cand_v, is_cycle, is_ext), each (cap, Δ)."""
    cand, cyc, ext = frontier_expand_lanes(
        path[None], blocked[None], v1[None], l2[None], vlast[None],
        count[None], offsets[None], neighbors[None], labels[None],
        adj_bits[None], delta=delta, tile=tile, interpret=interpret)
    return cand[0], cyc[0], ext[0]
