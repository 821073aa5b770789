"""Pallas TPU kernel — bitword (TPU-native) Stage-2 formulation.

Beyond-paper optimization (DESIGN.md §2 'bitword'): instead of Δ candidate
slots per path, compute the *entire* candidate set of each path with
word-parallel mask algebra over uint32 lanes:

    cand  = Adj[v_last] & ~path & ~blocked & labelgt[ℓ(v₂)]
    close = cand & Adj[v₁]          (each set bit = one chordless cycle)
    ext   = cand & ~Adj[v₁]         (each set bit = one extended path)

O(n/32) VPU ops per path, independent of Δ, fully branch-free — this is what
replaces the paper's per-thread neighbor loop + O(t·logΔ) chord re-check.

The kernel is FUSED (DESIGN.md §6.4): the same pass that produces the mask
words also reduces their ``population_count`` per row — both the cycle count
(close words) and the extension count (ext words) — so the wave engine's
counting step costs zero extra memory traffic: the words are still in VMEM
when they are counted.

Batch is a first-class axis (DESIGN.md §6.7): the kernel runs on a
``grid=(B, capp//tp)`` LANE GRID — grid dim 0 walks graph lanes, dim 1 walks
frontier row tiles within a lane; each lane pins its own graph tables in
VMEM. The single-graph entry point is the B=1 special case of the same
kernel, so one compiled shape family serves both ``enumerate`` and
``enumerate_batch``.

Planar layout: this kernel serves the split path, so it sees the largest
buckets (millions of rows). A (cap, nw) or (cap, 1) operand would be tiled
(8, 128) in HBM — 64-128× padding — so the wrapper stacks the frontier
fields as planes, ``(B, 2·nw + 3, cap/128, 128)`` int32 (path words,
blocked words, v₁, ℓ(v₂), v_last), and every field of a row tile is one
dense (S, 128) slab. Mosaic has no vector gather, so the three table
lookups (``Adj[v_last]``, ``Adj[v₁]``, ``labelgt[ℓ(v₂)]``) are one-hot
selects over the n table rows, whose words are read as scalars.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_LANES = 128


def _bitword_kernel(x_ref, adj_ref, labelgt_ref, y_ref, *, nw: int):
    # x: (1, 2nw+3, S, 128) int32 planes; y: (1, 2nw+2, S, 128) int32
    n = adj_ref.shape[1]
    u32 = lambda a: jax.lax.bitcast_convert_type(a, jnp.uint32)
    path = [u32(x_ref[0, w]) for w in range(nw)]
    blocked = [u32(x_ref[0, nw + w]) for w in range(nw)]
    clip = lambda a: jnp.clip(a, 0, n - 1)
    v1, l2, vlast = (clip(x_ref[0, 2 * nw + k]) for k in range(3))
    zero = jnp.zeros(v1.shape, jnp.uint32)

    def body(j, acc):
        last, first, gt = acc
        hit_last, hit_v1, hit_l2 = vlast == j, v1 == j, l2 == j
        return (tuple(jnp.where(hit_last, adj_ref[0, j, w], a)
                      for w, a in enumerate(last)),
                tuple(jnp.where(hit_v1, adj_ref[0, j, w], a)
                      for w, a in enumerate(first)),
                tuple(jnp.where(hit_l2, labelgt_ref[0, j, w], a)
                      for w, a in enumerate(gt)))

    init = ((zero,) * nw,) * 3
    adj_last, adj_v1, gt = jax.lax.fori_loop(0, n, body, init)

    ncyc = jnp.zeros(v1.shape, jnp.int32)
    next_ = jnp.zeros(v1.shape, jnp.int32)
    for w in range(nw):
        cand = adj_last[w] & ~path[w] & ~blocked[w] & gt[w]
        close = cand & adj_v1[w]
        ext = cand & ~adj_v1[w]
        y_ref[0, w] = jax.lax.bitcast_convert_type(close, jnp.int32)
        y_ref[0, nw + w] = jax.lax.bitcast_convert_type(ext, jnp.int32)
        # fused popcount reductions — the words are still in vregs
        ncyc = ncyc + jax.lax.population_count(close).astype(jnp.int32)
        next_ = next_ + jax.lax.population_count(ext).astype(jnp.int32)
    y_ref[0, 2 * nw] = ncyc
    y_ref[0, 2 * nw + 1] = next_


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def bitword_expand_lanes(path, blocked, v1, l2, vlast, count,
                         adj_bits, labelgt_bits,
                         *, interpret: bool, tile: int = 2048):
    """Lane-gridded bitword expansion: ONE ``pallas_call`` advances every
    lane of a graph batch.

    Shapes: ``path``/``blocked`` (B, cap, nw); ``v1``/``l2``/``vlast``/
    ``count`` (B, cap) / (B,); ``adj_bits``/``labelgt_bits`` (B, n, nw).
    Returns (close_words, ext_words, n_cycles_per_row, n_ext_per_row), each
    with the leading lane axis (dead rows zeroed per lane).
    """
    B, cap, nw = path.shape
    tp = min(tile, -(-cap // 1024) * 1024)      # rows per grid step
    capp = -(-cap // tp) * tp
    i32 = lambda a: jax.lax.bitcast_convert_type(a, jnp.int32)
    planes = jnp.concatenate(
        [i32(jnp.swapaxes(path, 1, 2)), i32(jnp.swapaxes(blocked, 1, 2)),
         jnp.stack([v1, l2, vlast], axis=1).astype(jnp.int32)], axis=1)
    planes = jnp.pad(planes, ((0, 0), (0, 0), (0, capp - cap)))
    planes = planes.reshape(B, 2 * nw + 3, capp // _LANES, _LANES)
    s = tp // _LANES
    lane_whole = lambda a: pl.BlockSpec(
        (1,) + a.shape[1:], lambda b, i: (b,) + (0,) * (a.ndim - 1))

    y = pl.pallas_call(
        functools.partial(_bitword_kernel, nw=nw),
        grid=(B, capp // tp),
        in_specs=[pl.BlockSpec((1, 2 * nw + 3, s, _LANES),
                               lambda b, i: (b, 0, i, 0)),
                  lane_whole(adj_bits), lane_whole(labelgt_bits)],
        out_specs=pl.BlockSpec((1, 2 * nw + 2, s, _LANES),
                               lambda b, i: (b, 0, i, 0)),
        out_shape=jax.ShapeDtypeStruct(
            (B, 2 * nw + 2, capp // _LANES, _LANES), jnp.int32),
        interpret=interpret,
        name="bitword_expand",
    )(planes, adj_bits, labelgt_bits)

    y = y.reshape(B, 2 * nw + 2, capp)[:, :, :cap]
    words = lambda k: jnp.swapaxes(
        jax.lax.bitcast_convert_type(y[:, k * nw:(k + 1) * nw], jnp.uint32),
        1, 2)
    live = (jnp.arange(cap, dtype=jnp.int32)[None, :] < count[:, None])
    z = jnp.uint32(0)
    return (jnp.where(live[..., None], words(0), z),
            jnp.where(live[..., None], words(1), z),
            jnp.where(live, y[:, 2 * nw], 0),
            jnp.where(live, y[:, 2 * nw + 1], 0))


def bitword_expand_pallas(path, blocked, v1, l2, vlast, count,
                          adj_bits, labelgt_bits,
                          *, interpret: bool, tile: int = 2048):
    """Single-graph entry point — the B=1 lane of ``bitword_expand_lanes``.
    Returns (close_words, ext_words, n_cycles_per_row, n_ext_per_row)
    for live rows (dead rows are zeroed)."""
    close, ext, ncyc, next_ = bitword_expand_lanes(
        path[None], blocked[None], v1[None], l2[None], vlast[None],
        count[None], adj_bits[None], labelgt_bits[None],
        tile=tile, interpret=interpret)
    return close[0], ext[0], ncyc[0], next_[0]
