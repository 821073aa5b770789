"""Stage 2 — ExpandingChordlessPathsParallel (paper Algorithm 3).

Two formulations (DESIGN.md §2):

* ``slot``   — paper-faithful: Δ candidate slots per path, candidates gathered
               from CSR ``E_e[V_e[v_last] + j]``; per-candidate bit probes.
* ``bitword``— TPU-native: the whole candidate set of a path computed as
               word-parallel mask algebra over uint32 lanes; candidate count
               via ``lax.population_count``.  O(n/32) VPU ops per path,
               independent of Δ; branch-free.

Both produce identical results (tested).  The paper's atomic appends into
C / T' become prefix-sum compaction; the host-relaunch double buffer (T → T')
is the functional update Frontier → Frontier.

The wave engine (DESIGN.md §6.4) composes these into a single fused round,
``expand_count_compact``: flag computation, cycle counting, cycle gathering
into the device-resident ``CycleBuffer``, and prefix-sum compaction — all
traceable inside ``lax.while_loop`` at fixed capacities, so an entire
superstep of K rounds compiles to one program with zero host syncs.

Backends implement ONE interface (DESIGN.md §6.7): ``ExpandOp`` — the
(formulation × backend) registry every layer of the stack (wave superstep,
legacy host engine, sharded step) programs against. Every op is
batch-transparent: it traces identically with or without a leading lane
axis, so ``jax.vmap`` of the superstep works on every backend (the pallas
ops route vmap onto lane-gridded kernels via ``custom_vmap``).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .bitset_graph import BitsetGraph, bit_test, popcount
from .frontier import CycleBuffer, Frontier, scatter_frontier


# ---------------------------------------------------------------------------
# Flag computation
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("delta",))
def expand_flags_slot(g: BitsetGraph, f: Frontier, delta: int):
    """Per-(path, slot) flags. Returns (cand_v, is_cycle, is_ext), each
    (cap, Δ) — mirrors Algorithm 3 lines 5–15."""
    cap = f.capacity
    j = jnp.arange(delta, dtype=jnp.int32)[None, :]
    k1 = g.offsets[f.vlast][:, None]
    deg = g.degrees[f.vlast][:, None]
    live = (jnp.arange(cap, dtype=jnp.int32) < f.count)[:, None]
    slot_ok = (j < deg) & live
    last = jnp.maximum(g.neighbors.shape[0] - 1, 0)
    v = g.neighbors[jnp.clip(k1 + j, 0, last)]                    # (cap, Δ)
    lab_ok = g.labels[v] > f.l2[:, None]                          # ℓ(v) > ℓ(v₂)
    in_path = bit_test(f.path[:, None, :], v)                     # v ∈ p
    in_blocked = bit_test(f.blocked[:, None, :], v)               # chord check
    closes = bit_test(g.adj_bits[f.v1][:, None, :], v)            # v ∈ Adj(v₁)
    valid = slot_ok & lab_ok & ~in_path & ~in_blocked
    return v, valid & closes, valid & ~closes


@jax.jit
def expand_words_bitword(g: BitsetGraph, f: Frontier):
    """Per-path candidate words. Returns (close_words, ext_words), (cap, nw).

    cand  = Adj[v_last] & ~path & ~blocked & {ℓ(v) > ℓ(v₂)}
    close = cand & Adj[v₁];  ext = cand & ~Adj[v₁]
    """
    cap = f.capacity
    live = (jnp.arange(cap, dtype=jnp.int32) < f.count)[:, None]
    cand = (g.adj_bits[f.vlast] & ~f.path & ~f.blocked
            & g.labelgt_bits[f.l2])
    cand = jnp.where(live, cand, jnp.uint32(0))
    adj1 = g.adj_bits[jnp.clip(f.v1, 0, None)]
    return cand & adj1, cand & ~adj1


def _ctz32(w: jnp.ndarray) -> jnp.ndarray:
    """Count trailing zeros of nonzero uint32 (undefined for 0)."""
    lsb = w & (~w + jnp.uint32(1))
    return jax.lax.population_count(lsb - jnp.uint32(1)).astype(jnp.int32)


@partial(jax.jit, static_argnames=("delta",))
def bitword_to_slots(ext_words: jnp.ndarray, delta: int):
    """Extract ≤Δ set-bit indices per row from (cap, nw) words → (cap, Δ)
    vertex ids (−1 padded). lax.scan over Δ extraction rounds; each round
    takes the lowest set bit across the row (first nonzero word + ctz)."""
    nw = ext_words.shape[1]
    word_idx = jnp.arange(nw, dtype=jnp.int32)[None, :]

    def round_(words, _):
        nz = words != 0
        has = nz.any(axis=1)
        first = jnp.argmax(nz, axis=1).astype(jnp.int32)          # first nonzero word
        w = jnp.take_along_axis(words, first[:, None], axis=1)[:, 0]
        bit = _ctz32(jnp.where(has, w, jnp.uint32(1)))
        v = jnp.where(has, first * 32 + bit, -1)
        clear = jnp.where((word_idx == first[:, None]) & has[:, None],
                          jnp.uint32(1) << jnp.where(has, bit, 0)[:, None].astype(jnp.uint32),
                          jnp.uint32(0))
        return words & ~clear, v

    _, vs = jax.lax.scan(round_, ext_words, None, length=delta)
    return vs.T  # (cap, Δ)


# ---------------------------------------------------------------------------
# Compaction (the paper's atomic-append replacement)
# ---------------------------------------------------------------------------

def compaction_dests(flat_flags: jnp.ndarray, out_cap: int,
                     base: jnp.ndarray | int = 0):
    """Shared prefix-sum destination computation for all stream compactions.

    Flag i scatters to ``base + (#flags before i)``; unflagged or overflowing
    entries are routed to ``out_cap`` (the drop slot of ``.at[].set(mode=
    'drop')``). Returns (dest, total_flagged).
    """
    pos = jnp.cumsum(flat_flags.astype(jnp.int32)) - 1
    total = jnp.where(flat_flags.any(), pos[-1] + 1, 0)
    dest = jnp.where(flat_flags, base + pos, out_cap)
    dest = jnp.where(dest >= out_cap, out_cap, dest)
    return dest.astype(jnp.int32), total.astype(jnp.int32)


def _extension_rows(g: BitsetGraph, f: Frontier, cand_v: jnp.ndarray):
    """Materialize ⟨p, v⟩ rows for every (path, slot) pair (flat layout)."""
    cap, delta = cand_v.shape
    nw = f.n_words
    row = jnp.repeat(jnp.arange(cap, dtype=jnp.int32), delta)
    v = cand_v.reshape(-1)
    vi = jnp.clip(v, 0, None)
    onehot_w = (jnp.uint32(1) << (vi % 32).astype(jnp.uint32))
    wi = (vi // 32).astype(jnp.int32)
    upd = jnp.where(jnp.arange(nw)[None, :] == wi[:, None],
                    onehot_w[:, None], jnp.uint32(0))
    new_path = f.path[row] | upd
    new_blocked = f.blocked[row] | g.adj_bits[f.vlast[row]]
    return row, v, new_path, new_blocked


@partial(jax.jit, static_argnames=("out_cap",), donate_argnums=())
def compact_extensions(g: BitsetGraph, f: Frontier, cand_v: jnp.ndarray,
                       is_ext: jnp.ndarray, out_cap: int) -> tuple[Frontier, jnp.ndarray]:
    """Scatter extended paths ⟨p, v⟩ into a fresh frontier of capacity
    ``out_cap`` using cumsum offsets. Returns (new_frontier, n_dropped)."""
    flat_ext = is_ext.reshape(-1)
    dest, total = compaction_dests(flat_ext, out_cap)
    row, v, new_path, new_blocked = _extension_rows(g, f, cand_v)
    out = scatter_frontier(dest, new_path, new_blocked,
                           f.v1[row], f.l2[row], v,
                           jnp.minimum(total, out_cap), out_cap)
    return out, jnp.maximum(total - out_cap, 0)


# ---------------------------------------------------------------------------
# Gather-based compaction (fused round, DESIGN.md §6.8)
#
# The scatter path above materializes every (path, slot) pair — cap·Δ rows of
# nw words — before compacting them down to ≤cap survivors. The gather
# formulation inverts the data flow: each OUTPUT slot locates its source row
# via a prefix-sum over per-row survivor counts (O(cap), not O(cap·Δ)) and
# rebuilds exactly its own row, so the round's frontier traffic drops from
# O(cap·Δ·nw) to O(cap·nw) — the XLA realization of the two-phase-scatter
# destination computation the fused pallas kernel performs on device.
# Output order is bit-identical to the scatter path: survivors land in
# row-major (row, slot) order, slots in ascending-vertex order for bitword.
# ---------------------------------------------------------------------------

def _source_rows(counts: jnp.ndarray, out_cap: int):
    """Map output slots to source rows through an inclusive prefix sum.

    ``counts`` (cap,) survivors per row → (src, k, valid, total): for output
    slot o, ``src[o]`` is the row owning it, ``k[o]`` the rank within that
    row, ``valid[o]`` whether o < min(total, out_cap)."""
    cap = counts.shape[0]
    incl = jnp.cumsum(counts.astype(jnp.int32))
    total = incl[-1]
    o = jnp.arange(out_cap, dtype=jnp.int32)
    src = jnp.searchsorted(incl, o, side="right").astype(jnp.int32)
    src = jnp.minimum(src, cap - 1)
    k = o - (incl[src] - counts[src])
    valid = o < jnp.minimum(total, out_cap)
    return src, jnp.where(valid, k, 0), valid, total


def _select_kth_bit(words: jnp.ndarray, k: jnp.ndarray) -> jnp.ndarray:
    """Vertex index of the k-th set bit (ascending) of each (R, nw) mask row.

    Branch-free: per-word popcount prefix locates the word, then a 5-step
    binary search over masked popcounts locates the bit within the uint32.
    Undefined where k >= popcount(row) (callers mask those lanes)."""
    pc = jax.lax.population_count(words).astype(jnp.int32)    # (R, nw)
    excl = jnp.cumsum(pc, axis=1) - pc
    in_w = (k[:, None] >= excl) & (k[:, None] < excl + pc)
    wi = jnp.argmax(in_w, axis=1).astype(jnp.int32)
    w = jnp.take_along_axis(words, wi[:, None], axis=1)[:, 0]
    kk = k - jnp.take_along_axis(excl, wi[:, None], axis=1)[:, 0]
    pos = jnp.zeros_like(kk)
    for sh in (16, 8, 4, 2, 1):
        mask = jnp.uint32((1 << sh) - 1)
        c = jax.lax.population_count(w & mask).astype(jnp.int32)
        hi = kk >= c
        kk = jnp.where(hi, kk - c, kk)
        pos = pos + jnp.where(hi, sh, 0)
        w = jnp.where(hi, w >> jnp.uint32(sh), w)
    return wi * 32 + pos


def _gathered_frontier(g: BitsetGraph, f: Frontier, src: jnp.ndarray,
                       v: jnp.ndarray, valid: jnp.ndarray, total, out_cap):
    """Build the compacted frontier from gathered (src, v) pairs — dead
    output rows match ``scatter_frontier``'s zero-init exactly."""
    nw = f.n_words
    vi = jnp.clip(v, 0, None)
    upd = jnp.where(jnp.arange(nw)[None, :] == (vi // 32)[:, None],
                    (jnp.uint32(1) << (vi % 32).astype(jnp.uint32))[:, None],
                    jnp.uint32(0))
    live = valid[:, None]
    new_path = jnp.where(live, f.path[src] | upd, jnp.uint32(0))
    new_blocked = jnp.where(
        live, f.blocked[src] | g.adj_bits[f.vlast[src]], jnp.uint32(0))
    out = Frontier(
        path=new_path, blocked=new_blocked,
        v1=jnp.where(valid, f.v1[src], -1).astype(jnp.int32),
        l2=jnp.where(valid, f.l2[src], 0).astype(jnp.int32),
        vlast=jnp.where(valid, vi, 0).astype(jnp.int32),
        count=jnp.minimum(total, out_cap).astype(jnp.int32))
    return out, jnp.maximum(total - out_cap, 0)


@partial(jax.jit, static_argnames=("out_cap",))
def bitword_compact_gather(g: BitsetGraph, f: Frontier, ext_w: jnp.ndarray,
                           out_cap: int):
    """One-pass bitword compaction: no slot extraction, no cap·Δ row
    materialization — each output slot selects its k-th set extension bit
    straight from the candidate words. Returns (new_frontier, n_dropped)."""
    src, k, valid, total = _source_rows(popcount(ext_w), out_cap)
    v = _select_kth_bit(ext_w[src], k)
    return _gathered_frontier(g, f, src, v, valid, total, out_cap)


@partial(jax.jit, static_argnames=("out_cap",))
def compact_extensions_gather(g: BitsetGraph, f: Frontier,
                              cand_v: jnp.ndarray, is_ext: jnp.ndarray,
                              out_cap: int):
    """Slot-formulation twin of ``bitword_compact_gather``: each output slot
    selects the k-th flagged slot of its source row (slot order preserved —
    bit-identical to the scatter path). Returns (new_frontier, n_dropped)."""
    src, k, valid, total = _source_rows(
        is_ext.sum(axis=1, dtype=jnp.int32), out_cap)
    flags_src = is_ext[src].astype(jnp.int32)                 # (out_cap, Δ)
    excl = jnp.cumsum(flags_src, axis=1) - flags_src
    sel = (flags_src > 0) & (excl == k[:, None])
    j = jnp.argmax(sel, axis=1).astype(jnp.int32)
    v = jnp.take_along_axis(cand_v[src], j[:, None], axis=1)[:, 0]
    return _gathered_frontier(g, f, src, v, valid, total, out_cap)


@jax.jit
def count_ext_and_cycles(is_cycle: jnp.ndarray, is_ext: jnp.ndarray):
    return (is_ext.sum(dtype=jnp.int32), is_cycle.sum(dtype=jnp.int32))


@jax.jit
def bitword_flags_count(g: BitsetGraph, f: Frontier):
    """Count-only round, part 1 (§Perf engine hillclimb): candidate words +
    POPCOUNT cycle/extension counts — no slot extraction for cycles, one
    host sync for exact output sizing."""
    close_w, ext_w = expand_words_bitword(g, f)
    return ext_w, popcount(close_w).sum(), popcount(ext_w).sum()


@partial(jax.jit, static_argnames=("delta", "out_cap"))
def bitword_compact(g: BitsetGraph, f: Frontier, ext_w: jnp.ndarray,
                    delta: int, out_cap: int):
    """Count-only round, part 2: extract extension slots + compact."""
    cand_v = bitword_to_slots(ext_w, delta)
    is_ext = cand_v >= 0
    return compact_extensions(g, f, cand_v, is_ext, out_cap)


def _cycle_rows(f: Frontier, cand_v: jnp.ndarray):
    """Cycle bitmaps for every (path, slot) pair: path | bit(v), flat."""
    cap, delta = cand_v.shape
    nw = f.n_words
    row = jnp.repeat(jnp.arange(cap, dtype=jnp.int32), delta)
    v = jnp.clip(cand_v.reshape(-1), 0, None)
    upd = jnp.where(jnp.arange(nw)[None, :] == (v // 32)[:, None],
                    (jnp.uint32(1) << (v % 32).astype(jnp.uint32))[:, None],
                    jnp.uint32(0))
    return f.path[row] | upd


@partial(jax.jit, static_argnames=("out_cap",))
def gather_cycles(f: Frontier, cand_v: jnp.ndarray, is_cycle: jnp.ndarray,
                  out_cap: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Materialize closed cycles as bitmaps (out_cap, nw): path | bit(v)."""
    flat = is_cycle.reshape(-1)
    dest, total = compaction_dests(flat, out_cap)
    rows = _cycle_rows(f, cand_v)
    nw = f.n_words
    out = jnp.zeros((out_cap, nw), jnp.uint32).at[dest].set(rows, mode="drop")
    return out, jnp.minimum(total, out_cap)


def gather_cycles_into(f: Frontier, cand_v: jnp.ndarray,
                       is_cycle: jnp.ndarray, buf: CycleBuffer) -> CycleBuffer:
    """Append closed cycles to the device-resident CycleBuffer at its write
    offset (wave engine; caller guarantees they fit — guarded upstream)."""
    flat = is_cycle.reshape(-1)
    dest, total = compaction_dests(flat, buf.capacity, base=buf.count)
    rows = _cycle_rows(f, cand_v)
    masks = buf.masks.at[dest].set(rows, mode="drop")
    new_count = jnp.minimum(buf.count + total, buf.capacity)
    return CycleBuffer(masks=masks, count=new_count.astype(jnp.int32))


# ---------------------------------------------------------------------------
# ExpandOp — the one expansion interface every backend implements
# (DESIGN.md §6.7)
# ---------------------------------------------------------------------------

class ExpandOp:
    """One (formulation × backend) implementation of a stage-2 expansion
    round — the single interface the whole stack (wave superstep, legacy
    host engine, sharded ``core/distributed`` step) programs against.

    Contract: every method is BATCH-TRANSPARENT — it traces identically
    whether the operands are single-graph ((cap, nw) frontier leaves,
    (n, nw) graph tables) or carry a leading lane axis under ``jax.vmap``.
    The jnp ops are vmap-transparent by construction; the pallas ops install
    ``custom_vmap`` rules that route vmap onto the lane-gridded kernels
    (grid=(B, capp//tp)) so a batched superstep still issues ONE device
    dispatch per round.

    * ``flags(g, f, delta)`` → ``(flags, n_cyc, n_new)``: the round's flag
      computation plus its cycle/extension counts, no host syncs;
      ``flags`` is formulation-specific (slot: ``(cand_v, is_cyc,
      is_ext)`` per (path, slot); bitword: ``(close_words, ext_words)``).
    * ``apply(g, f, buf, flags, delta, store)`` → ``(f', buf')``: gather
      this round's cycles + compact extensions at fixed capacity — the
      T → T' update.
    * ``apply_fused(...)`` (same signature as ``apply``): the one-pass
      gather compaction variant (DESIGN.md §6.8) — O(cap·nw) frontier
      traffic per round instead of O(cap·Δ·nw). Bit-identical output.
    * ``fused_kernel`` (pallas ops only): the whole guarded round — flags,
      counts, cycle append, compaction — collapses into ONE pallas
      dispatch (``expand_count_compact`` routes there under ``fused``).
    """
    formulation: str
    backend: str
    supports_fused: bool = False   # has apply_fused (gather compaction)
    fused_kernel: bool = False     # whole round is one pallas dispatch

    def flags(self, g: BitsetGraph, f: Frontier, delta: int):
        raise NotImplementedError

    def apply(self, g: BitsetGraph, f: Frontier, buf: CycleBuffer, flags,
              delta: int, store: bool):
        raise NotImplementedError

    def apply_fused(self, g: BitsetGraph, f: Frontier, buf: CycleBuffer,
                    flags, delta: int, store: bool):
        raise NotImplementedError

    def fused_round(self, g: BitsetGraph, f: Frontier, buf: CycleBuffer,
                    delta: int, store: bool):
        """Whole guarded round as one device dispatch (pallas ops only).
        Returns (f2, buf2, n_cyc, n_new, ok_frontier, ok_cycles)."""
        raise NotImplementedError

    def persistent_round(self, g: BitsetGraph, f: Frontier,
                         buf: CycleBuffer, delta: int, store: bool,
                         rounds: int, rlimit):
        """Up to ``rounds`` guarded rounds as ONE device dispatch, frontier
        resident in kernel scratch between rounds (pallas ops only,
        DESIGN.md §6.11). Returns the ``expand_count_compact_multi``
        contract: (f2, buf2, cyc_hist, new_hist, rounds_done, ok_frontier,
        ok_cycles)."""
        raise NotImplementedError


class _SlotApply:
    """Shared slot-formulation T → T' update."""
    supports_fused = True

    def apply(self, g, f, buf, flags, delta, store):
        cand_v, is_cyc, is_ext = flags
        if store:
            buf = gather_cycles_into(f, cand_v, is_cyc, buf)
        f2, _ = compact_extensions(g, f, cand_v, is_ext, f.capacity)
        return f2, buf

    def apply_fused(self, g, f, buf, flags, delta, store):
        cand_v, is_cyc, is_ext = flags
        if store:
            buf = gather_cycles_into(f, cand_v, is_cyc, buf)
        f2, _ = compact_extensions_gather(g, f, cand_v, is_ext, f.capacity)
        return f2, buf


class _BitwordApply:
    """Shared bitword-formulation T → T' update (slot extraction from the
    candidate words, then the same prefix-sum compaction)."""
    supports_fused = True

    def apply(self, g, f, buf, flags, delta, store):
        close_w, ext_w = flags
        cand_v = bitword_to_slots(ext_w, delta)
        is_ext = cand_v >= 0
        if store:
            ccand = bitword_to_slots(close_w, delta)
            buf = gather_cycles_into(f, ccand, ccand >= 0, buf)
        f2, _ = compact_extensions(g, f, cand_v, is_ext, f.capacity)
        return f2, buf

    def apply_fused(self, g, f, buf, flags, delta, store):
        # frontier: straight from the candidate words — no Δ-round slot
        # extraction, no cap·Δ row materialization (DESIGN.md §6.8)
        close_w, ext_w = flags
        if store:
            ccand = bitword_to_slots(close_w, delta)
            buf = gather_cycles_into(f, ccand, ccand >= 0, buf)
        f2, _ = bitword_compact_gather(g, f, ext_w, f.capacity)
        return f2, buf


class SlotXlaExpand(_SlotApply, ExpandOp):
    formulation, backend = "slot", "jnp"

    def flags(self, g, f, delta):
        cand_v, is_cyc, is_ext = expand_flags_slot(g, f, delta)
        n_new, n_cyc = count_ext_and_cycles(is_cyc, is_ext)
        return (cand_v, is_cyc, is_ext), n_cyc, n_new


class SlotPallasExpand(_SlotApply, ExpandOp):
    formulation, backend = "slot", "pallas"
    fused_kernel = True

    def flags(self, g, f, delta):
        from ..kernels import ops as kops
        cand_v, is_cyc, is_ext = kops.expand_flags_slot(g, f, delta)
        n_new, n_cyc = count_ext_and_cycles(is_cyc, is_ext)
        return (cand_v, is_cyc, is_ext), n_cyc, n_new

    def fused_round(self, g, f, buf, delta, store):
        from ..kernels import ops as kops
        return kops.fused_round(g, f, buf, formulation="slot",
                                delta=delta, store=store)

    def persistent_round(self, g, f, buf, delta, store, rounds, rlimit):
        from ..kernels import ops as kops
        return kops.persistent_round(g, f, buf, formulation="slot",
                                     delta=delta, store=store,
                                     rounds=rounds, rlimit=rlimit)


class BitwordXlaExpand(_BitwordApply, ExpandOp):
    formulation, backend = "bitword", "jnp"

    def flags(self, g, f, delta):
        close_w, ext_w = expand_words_bitword(g, f)
        return ((close_w, ext_w), popcount(close_w).sum(),
                popcount(ext_w).sum())


class BitwordPallasExpand(_BitwordApply, ExpandOp):
    formulation, backend = "bitword", "pallas"
    fused_kernel = True

    def flags(self, g, f, delta):
        from ..kernels import ops as kops
        close_w, ext_w, n_cyc, n_new = kops.bitword_fused_counts(g, f)
        return (close_w, ext_w), n_cyc, n_new

    def fused_round(self, g, f, buf, delta, store):
        from ..kernels import ops as kops
        return kops.fused_round(g, f, buf, formulation="bitword",
                                delta=delta, store=store)

    def persistent_round(self, g, f, buf, delta, store, rounds, rlimit):
        from ..kernels import ops as kops
        return kops.persistent_round(g, f, buf, formulation="bitword",
                                     delta=delta, store=store,
                                     rounds=rounds, rlimit=rlimit)


_EXPAND_OPS: dict[tuple[str, str], ExpandOp] = {
    (op.formulation, op.backend): op
    for op in (SlotXlaExpand(), SlotPallasExpand(),
               BitwordXlaExpand(), BitwordPallasExpand())
}


def expand_op(formulation: str, backend: str) -> ExpandOp:
    """The registered ExpandOp for a (formulation, backend) pair."""
    try:
        return _EXPAND_OPS[(formulation, backend)]
    except KeyError:
        raise ValueError(
            f"no ExpandOp registered for formulation={formulation!r}, "
            f"backend={backend!r}; known: {sorted(_EXPAND_OPS)}") from None


# ---------------------------------------------------------------------------
# Fused wave round (DESIGN.md §6.4)
# ---------------------------------------------------------------------------

def fused_kernel_fits(formulation: str, g: BitsetGraph, f: Frontier,
                      buf: CycleBuffer, *, store: bool,
                      persistent: bool) -> bool:
    """The fused-bucket rule for one lane's traced operands: a round
    (``persistent``: a multi-round launch) runs as one fused pallas kernel
    only if the VMEM its blocks need, counted from static shapes with lane
    padding, fits the kernels' budget (``kernels.fused_round.fits_vmem``).
    Buckets past it take the split path."""
    from ..kernels.fused_round import fits_vmem
    return fits_vmem(cap=f.capacity, nw=f.n_words, n=g.adj_bits.shape[-2],
                     n_neighbors=g.neighbors.shape[-1], cyc_cap=buf.capacity,
                     formulation=formulation, store=store,
                     persistent=persistent)


def expand_count_compact(g: BitsetGraph, f: Frontier, buf: CycleBuffer, *,
                         delta: int, store: bool,
                         formulation: str = "slot", backend: str = "jnp",
                         op: ExpandOp | None = None, fused: bool = False):
    """One fused, guarded expansion round — the wave superstep's loop body.

    Combines an ``ExpandOp``'s flag computation and application into a
    single traced unit: flag computation, popcount cycle counting,
    in-buffer cycle gathering, and prefix-sum compaction back into the SAME
    capacity bucket.  If the round would overflow the frontier bucket or
    the cycle buffer it is NOT applied; the caller reads the ``ok_*`` flags
    and escalates to the host (bucket transition).  ``op`` defaults to the
    registered ``expand_op(formulation, backend)``.

    ``fused`` selects the one-pass round (DESIGN.md §6.8) when the op
    supports it: pallas ops with a fused kernel collapse the whole guarded
    round into ONE device dispatch (two-phase scatter, guard evaluated in
    kernel) while the bucket fits the kernel's VMEM budget
    (``fused_kernel_fits``), and take the split path past it; jnp ops swap
    the scatter compaction for the gather formulation (one frontier pass
    instead of two). Output is bit-identical either way; ops without fused
    support fall back to the split path silently.

    Returns (f2, buf2, n_cyc, n_new, ok_frontier, ok_cycles).
    """
    if op is None:
        op = expand_op(formulation, backend)
    if fused and op.fused_kernel and fused_kernel_fits(
            op.formulation, g, f, buf, store=store, persistent=False):
        return op.fused_round(g, f, buf, delta, store)
    flags, n_cyc, n_new = op.flags(g, f, delta)
    ok_frontier = n_new <= f.capacity
    if store:
        ok_cycles = (buf.count + n_cyc) <= buf.capacity
    else:
        ok_cycles = jnp.bool_(True)
    ok = ok_frontier & ok_cycles

    apply = op.apply_fused if (fused and op.supports_fused) else op.apply
    f2, buf2 = jax.lax.cond(
        ok,
        lambda _: apply(g, f, buf, flags, delta, store),
        lambda _: (f, buf),
        None)
    return f2, buf2, n_cyc, n_new, ok_frontier, ok_cycles


def expand_count_compact_multi(g: BitsetGraph, f: Frontier,
                               buf: CycleBuffer, *, delta: int, store: bool,
                               rounds: int, formulation: str = "slot",
                               backend: str = "jnp",
                               op: ExpandOp | None = None,
                               fused: bool = False, rlimit=None):
    """Up to ``rounds`` complete guarded expansion rounds as ONE traced
    unit — the persistent superstep's loop body (DESIGN.md §6.11).

    On pallas ops with a fused kernel (``fused=True``) whose bucket fits
    the VMEM budget this is the
    persistent wave kernel: one ``pallas_call`` with a leading round axis
    whose scratch carries the frontier between rounds, so HBM sees one
    frontier read + one write per LAUNCH instead of per round. Every other
    path runs the bit-identical jnp twin: a ``lax.fori_loop`` over
    ``expand_count_compact`` (which itself resolves gather compaction /
    the single-round kernel per op), with the round-application rules the
    kernel applies in SMEM mirrored in carried scalars.

    ``rlimit`` (dynamic, defaults to ``rounds``) bounds how many rounds may
    be APPLIED — the superstep passes its remaining budget so a static-R
    launch never oversteps ``rounds_limit``; rounds past it are identity
    no-ops that record nothing.

    Returns (f2, buf2, cyc_hist, new_hist, rounds_done, ok_frontier,
    ok_cycles): (rounds,) histories of each ATTEMPTED round's totals
    (entry ``rounds_done`` is the pending overflow after a guard trip;
    entries past the last attempt are 0), ``rounds_done`` counts APPLIED
    rounds, and the ok flags report the first failing round (True/True
    when no round failed).
    """
    if op is None:
        op = expand_op(formulation, backend)
    rounds = int(rounds)
    if rlimit is None:
        rlimit = jnp.int32(rounds)
    if fused and op.fused_kernel and fused_kernel_fits(
            op.formulation, g, f, buf, store=store, persistent=True):
        return op.persistent_round(g, f, buf, delta, store, rounds, rlimit)

    zeros = jnp.zeros((rounds,), jnp.int32)

    def body(r, carry):
        f, buf, ch, nh, done, alive, okf, okc = carry
        f2, buf2, n_cyc, n_new, okf_r, okc_r = expand_count_compact(
            g, f, buf, delta=delta, store=store, op=op, fused=fused)
        alive = alive & (done < rlimit)
        okr = okf_r & okc_r
        applied = alive & okr
        trip = alive & ~okr
        nh = nh.at[r].set(jnp.where(alive, n_new, 0))
        ch = ch.at[r].set(jnp.where(alive, n_cyc, 0))
        # guard-tripped / dead / past-budget rounds must leave the state
        # untouched BIT-FOR-BIT (expand_count_compact's lax.cond already
        # keeps f/buf on a trip, but a not-alive round still recomputes)
        sel = lambda new, old: jax.tree_util.tree_map(
            lambda a, b: jnp.where(applied, a, b), new, old)
        return (sel(f2, f), sel(buf2, buf), ch, nh,
                done + applied.astype(jnp.int32),
                applied & (n_new > 0),
                jnp.where(trip, okf_r, okf), jnp.where(trip, okc_r, okc))

    f2, buf2, ch, nh, done, _, okf, okc = jax.lax.fori_loop(
        0, rounds, body,
        (f, buf, zeros, zeros, jnp.int32(0), jnp.bool_(True),
         jnp.bool_(True), jnp.bool_(True)))
    return f2, buf2, ch, nh, done, okf, okc
