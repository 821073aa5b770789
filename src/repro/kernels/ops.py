"""Jit'd wrappers exposing the Pallas kernels with engine-compatible
signatures. Every kernel call goes through ``on_platform``: the program
carries the compiled kernel for a TPU and the interpreted one for every
other platform, and the lowering keeps only the branch of the platform it
compiles for. Nothing here asks for a backend when the module is imported.

Batch transparency (DESIGN.md §6.7): every wrapper carries a
``jax.custom_batching.custom_vmap`` rule that maps ``jax.vmap`` onto the
LANE-GRIDDED kernel variants (``*_lanes``, grid=(B, capp//tp)) instead of
failing or falling back to a per-graph loop.  ``jax.vmap(wave_superstep)``
— the batched plan the service compiles for ``enumerate_batch`` — therefore
issues ONE pallas dispatch per round for the whole batch on this backend,
exactly like the jnp backend.  Unbatched calls execute the B=1 lane of the
same kernels, so both paths share one compiled shape family.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..core.bitset_graph import BitsetGraph
from ..core.frontier import CycleBuffer, Frontier
from .frontier_expand import frontier_expand_lanes, frontier_expand_pallas
from .triplet_init import triplet_init_lanes, triplet_init_pallas
from .bitword_expand import bitword_expand_lanes, bitword_expand_pallas
from .fused_round import (fused_round_lanes, fused_round_pallas,
                          persistent_round_lanes, persistent_round_pallas)


def on_platform(kernel, *args, **static):
    """Call ``kernel(*args, **static)`` compiled on a TPU and interpreted
    (``interpret=True``) on any other platform, decided when the program
    lowers for the device it runs on."""
    return jax.lax.platform_dependent(
        *args, tpu=functools.partial(kernel, interpret=False, **static),
        default=functools.partial(kernel, interpret=True, **static))


# Trace-time observability for the fused round (DESIGN.md §6.8): each entry
# counts how many times a fused-round pallas_call was TRACED into a program
# (kernel builds, not executions — execution count is rounds × 1 by
# construction since the round body contains exactly one pallas_call; tests
# assert that on the jaxpr). Keyed 'single' / 'lanes'.
FUSED_KERNEL_BUILDS = {"single": 0, "lanes": 0,
                       "persistent_single": 0, "persistent_lanes": 0}


def _broadcast_unbatched(tree, tree_batched, axis_size):
    """Give every unbatched leaf the lane axis the batched leaves carry
    (custom_vmap hands us per-leaf batched flags)."""
    return jax.tree_util.tree_map(
        lambda x, b: x if b else jnp.broadcast_to(
            x, (axis_size,) + jnp.shape(x)),
        tree, tree_batched)


# ---------------------------------------------------------------------------
# Slot formulation (frontier_expand kernel)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _slot_flags_op(delta: int):
    @jax.custom_batching.custom_vmap
    def flags(g: BitsetGraph, f: Frontier):
        return on_platform(
            frontier_expand_pallas, f.path, f.blocked, f.v1, f.l2, f.vlast,
            f.count, g.offsets, g.neighbors, g.labels, g.adj_bits,
            delta=delta)

    @flags.def_vmap
    def _rule(axis_size, in_batched, g, f):
        g = _broadcast_unbatched(g, in_batched[0], axis_size)
        f = _broadcast_unbatched(f, in_batched[1], axis_size)
        out = on_platform(
            frontier_expand_lanes, f.path, f.blocked, f.v1, f.l2, f.vlast,
            f.count, g.offsets, g.neighbors, g.labels, g.adj_bits,
            delta=delta)
        return out, (True, True, True)

    return flags


def expand_flags_slot(g: BitsetGraph, f: Frontier, delta: int):
    """Drop-in for core.expand.expand_flags_slot (slot formulation);
    vmap maps onto the lane-gridded kernel."""
    return _slot_flags_op(int(delta))(g, f)


# ---------------------------------------------------------------------------
# Stage 1 (triplet_init kernel)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _triplet_flags_op(delta: int):
    @jax.custom_batching.custom_vmap
    def flags(g: BitsetGraph):
        return on_platform(triplet_init_pallas, g.offsets, g.neighbors,
                           g.labels, g.adj_bits, delta=delta)

    @flags.def_vmap
    def _rule(axis_size, in_batched, g):
        g = _broadcast_unbatched(g, in_batched[0], axis_size)
        out = on_platform(triplet_init_lanes, g.offsets, g.neighbors,
                          g.labels, g.adj_bits, delta=delta)
        return out, (True, True)

    return flags


def triplet_flags(g: BitsetGraph, delta: int):
    """Drop-in for core.triplets.triplet_flags (stage 1); vmap maps onto
    the lane-gridded kernel — one dispatch flags every lane of a batch."""
    return _triplet_flags_op(int(delta))(g)


# ---------------------------------------------------------------------------
# Bitword formulation (bitword_expand kernel, fused popcounts)
# ---------------------------------------------------------------------------

@jax.custom_batching.custom_vmap
def _bitword_rows(g: BitsetGraph, f: Frontier):
    """(close_words, ext_words, per-row cycle counts, per-row ext counts)."""
    return on_platform(
        bitword_expand_pallas, f.path, f.blocked, f.v1, f.l2, f.vlast,
        f.count, g.adj_bits, g.labelgt_bits)


@_bitword_rows.def_vmap
def _bitword_rows_vmap(axis_size, in_batched, g, f):
    g = _broadcast_unbatched(g, in_batched[0], axis_size)
    f = _broadcast_unbatched(f, in_batched[1], axis_size)
    out = on_platform(
        bitword_expand_lanes, f.path, f.blocked, f.v1, f.l2, f.vlast,
        f.count, g.adj_bits, g.labelgt_bits)
    return out, (True, True, True, True)


def expand_words_bitword(g: BitsetGraph, f: Frontier):
    """Drop-in for core.expand.expand_words_bitword (TPU-native)."""
    close, ext, _, _ = _bitword_rows(g, f)
    return close, ext


def bitword_fused_counts(g: BitsetGraph, f: Frontier):
    """Fused mask algebra + per-row popcounts in ONE kernel pass
    (DESIGN.md §6.4). Returns (close_words, ext_words, n_cyc, n_new).
    The scalar reductions ride the same traced unit as the kernel when the
    caller jits (the wave superstep and ``bitword_flags_count`` both do)."""
    close, ext, ncyc, next_ = _bitword_rows(g, f)
    return close, ext, ncyc.sum(), next_.sum()


@jax.jit
def bitword_flags_count(g: BitsetGraph, f: Frontier):
    """Drop-in for core.expand.bitword_flags_count, but the popcounts ride
    the expansion kernel instead of a second HBM pass. Jitted so the scalar
    .sum() reductions fuse into the same dispatch (legacy host engine)."""
    _, ext, n_cyc, n_new = bitword_fused_counts(g, f)
    return ext, n_cyc, n_new


# ---------------------------------------------------------------------------
# Fused round (DESIGN.md §6.8) — the WHOLE guarded expansion round as one
# pallas dispatch: flags, chord test, popcounts, cycle append into the ring,
# two-phase-scatter frontier compaction, overflow guard.
# ---------------------------------------------------------------------------

def _fused_tables(g: BitsetGraph, formulation: str):
    if formulation == "bitword":
        return (g.adj_bits, g.labelgt_bits)
    return (g.offsets, g.neighbors, g.labels, g.adj_bits)


@functools.lru_cache(maxsize=None)
def _fused_round_op(formulation: str, delta: int, store: bool):
    @jax.custom_batching.custom_vmap
    def fused(g: BitsetGraph, f: Frontier, buf: CycleBuffer):
        FUSED_KERNEL_BUILDS["single"] += 1
        return on_platform(
            fused_round_pallas, f.path, f.blocked, f.v1, f.l2, f.vlast,
            f.count, buf.masks, buf.count, _fused_tables(g, formulation),
            formulation=formulation, delta=delta, store=store)

    @fused.def_vmap
    def _rule(axis_size, in_batched, g, f, buf):
        FUSED_KERNEL_BUILDS["lanes"] += 1
        g = _broadcast_unbatched(g, in_batched[0], axis_size)
        f = _broadcast_unbatched(f, in_batched[1], axis_size)
        buf = _broadcast_unbatched(buf, in_batched[2], axis_size)
        out = on_platform(
            fused_round_lanes, f.path, f.blocked, f.v1, f.l2, f.vlast,
            f.count, buf.masks, buf.count, _fused_tables(g, formulation),
            formulation=formulation, delta=delta, store=store)
        return out, (True,) * len(out)

    return fused


def fused_round(g: BitsetGraph, f: Frontier, buf: CycleBuffer, *,
                formulation: str, delta: int, store: bool):
    """Drop-in for the whole body of ``core.expand.expand_count_compact``
    as ONE kernel dispatch. The overflow guard is evaluated INSIDE the
    kernel (guard-tripped lanes copy their inputs through), so no
    ``lax.cond`` branches over the round; only the scalar count/ok
    bookkeeping rides outside. Batch-transparent via ``custom_vmap``.

    Returns (f2, buf2, n_cyc, n_new, ok_frontier, ok_cycles) — the exact
    ``expand_count_compact`` contract.
    """
    out = _fused_round_op(formulation, int(delta), bool(store))(g, f, buf)
    path, blocked, v1, l2, vlast, masks, n_cyc, n_new = out
    cap = f.capacity
    ok_frontier = n_new <= cap
    if store:
        ok_cycles = (buf.count + n_cyc) <= buf.capacity
    else:
        ok_cycles = jnp.bool_(True)
    ok = ok_frontier & ok_cycles
    f2 = Frontier(
        path=path, blocked=blocked, v1=v1, l2=l2, vlast=vlast,
        count=jnp.where(ok, jnp.minimum(n_new, cap),
                        f.count).astype(jnp.int32))
    if store:
        buf2 = CycleBuffer(
            masks=masks,
            count=jnp.where(ok, buf.count + n_cyc,
                            buf.count).astype(jnp.int32))
    else:
        buf2 = buf
    return f2, buf2, n_cyc, n_new, ok_frontier, ok_cycles


# ---------------------------------------------------------------------------
# Persistent multi-round wave kernel (DESIGN.md §6.11)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _persistent_round_op(formulation: str, delta: int, store: bool,
                         rounds: int):
    @jax.custom_batching.custom_vmap
    def persistent(g: BitsetGraph, f: Frontier, buf: CycleBuffer, rlimit):
        FUSED_KERNEL_BUILDS["persistent_single"] += 1
        return on_platform(
            persistent_round_pallas, f.path, f.blocked, f.v1, f.l2, f.vlast,
            f.count, buf.masks, buf.count, rlimit,
            _fused_tables(g, formulation), formulation=formulation,
            delta=delta, store=store, rounds=rounds)

    @persistent.def_vmap
    def _rule(axis_size, in_batched, g, f, buf, rlimit):
        FUSED_KERNEL_BUILDS["persistent_lanes"] += 1
        g = _broadcast_unbatched(g, in_batched[0], axis_size)
        f = _broadcast_unbatched(f, in_batched[1], axis_size)
        buf = _broadcast_unbatched(buf, in_batched[2], axis_size)
        rlimit = _broadcast_unbatched(rlimit, in_batched[3], axis_size)
        out = on_platform(
            persistent_round_lanes, f.path, f.blocked, f.v1, f.l2, f.vlast,
            f.count, buf.masks, buf.count, rlimit,
            _fused_tables(g, formulation), formulation=formulation,
            delta=delta, store=store, rounds=rounds)
        return out, (True,) * len(out)

    return persistent


def persistent_round(g: BitsetGraph, f: Frontier, buf: CycleBuffer, *,
                     formulation: str, delta: int, store: bool,
                     rounds: int, rlimit=None):
    """Up to ``rounds`` complete guarded rounds as ONE kernel dispatch —
    the frontier ping-pongs through scratch between rounds and HBM sees
    exactly one read at launch entry and one write at exit (the ring is
    append-only on top). ``rlimit`` (dynamic, defaults to ``rounds``)
    bounds the rounds actually applied so a superstep can spend a partial
    budget; rounds past it degrade to identity copy-throughs inside the
    kernel. Batch-transparent via ``custom_vmap``.

    Returns (f2, buf2, cyc_hist, new_hist, rounds_done, ok_frontier,
    ok_cycles): histories are the per-round ATTEMPTED totals (entry
    ``rounds_done`` holds the pending overflow after a guard trip), the ok
    flags report the first failing round (True/True when none failed), and
    f2/buf2 carry the state + counts after the last APPLIED round.
    """
    if rlimit is None:
        rlimit = jnp.int32(rounds)
    out = _persistent_round_op(
        formulation, int(delta), bool(store), int(rounds))(g, f, buf,
                                                           rlimit)
    (path, blocked, v1, l2, vlast, masks, ncyc_h, nnew_h, rounds_done,
     okf, okc, fcnt, bcnt) = out
    f2 = Frontier(path=path, blocked=blocked, v1=v1, l2=l2, vlast=vlast,
                  count=fcnt.astype(jnp.int32))
    if store:
        buf2 = CycleBuffer(masks=masks, count=bcnt.astype(jnp.int32))
    else:
        buf2 = buf
    return (f2, buf2, ncyc_h, nnew_h, rounds_done,
            okf.astype(jnp.bool_), okc.astype(jnp.bool_))
