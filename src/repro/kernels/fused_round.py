"""Pallas TPU kernel — the FUSED expansion round (DESIGN.md §6.8).

One ``pallas_call`` per round executes the entire guarded round body that
the split path spreads over a flag kernel plus XLA cumsum/scatter passes:
neighbor-flag expansion, chord test, popcount cycle/extension counting,
accepted-cycle append into the CycleBuffer ring, and in-bucket frontier
compaction — with the overflow guard evaluated *inside* the kernel.

Two-phase scatter over the lane grid ``grid = (B, 2, capp//tp)``:

* **Phase A** (grid dim 1 == 0) walks the frontier tile once, counts each
  tile's survivors (extensions, cycles) into SMEM scratch, zeroes the
  output frontier region, and (store mode) copies the ring through to the
  output buffer.
* **Phase B** (grid dim 1 == 1) turns the per-tile counts into cross-tile
  exclusive offsets (TPU grids execute sequentially, so the scratch
  written at tile 0 of phase B is visible to every later tile), walks the
  tile again and writes every survivor row and cycle bitmap at its FINAL
  position — no XLA ``cumsum``/``scatter`` pass over the frontier ever
  materializes.

Both phases walk a tile one frontier row at a time (``walk``): the
row's table lookups are scalar-indexed row loads, its survivors are
visited in split-path order, and each one is written with a single-row
store at a scalar destination that a running counter carries. Mosaic
lowers all of that; it lowers no vector gather or cumsum.

If the round would overflow the frontier bucket or the ring, phase B
instead copies the input tiles through unchanged (the ``lax.cond`` keep
branch of the split path, evaluated on device), so the host sees the same
(f, buf, pending sizes) contract as ``expand_count_compact``.

Output order is bit-identical to the split path: survivors land in
row-major (row, slot) order — ascending vertex id within a row for the
bitword formulation (lowest-set-bit-first extraction), CSR slot order for
the slot formulation.

VMEM rule: the output frontier (and the ring, in store mode) is a
lane-whole revisited block, and the persistent kernel adds a ping-pong
copy of the frontier in scratch, so a lane's whole bucket lives in VMEM.
``fused_vmem_bytes`` counts those bytes from shapes, lane padding and
double buffering included; ``fits_vmem`` compares them with
``VMEM_BUDGET_BYTES``, and ``core.expand`` routes every bucket over the
budget to the split path before anything is traced.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Scoped VMEM the fused kernels are compiled with (a v5e TensorCore has
# 128 MiB), and the share of it the bucket rule lets their blocks use; the
# rest is left to the compiler's own scratch.
VMEM_LIMIT_BYTES = 100 * 2**20
VMEM_BUDGET_BYTES = 88 * 2**20
TILE_ROWS = 128                 # frontier rows per grid step


def _vmem_bytes(rows: int, lanes: int, lead: int = 1) -> int:
    """Bytes of one 4-byte VMEM buffer of shape (lead, rows, lanes): the
    last two dimensions are tiled (8, 128), so a 1- or nw-wide minor
    dimension occupies 128 lanes."""
    return lead * (-(-rows // 8) * 8) * (-(-lanes // 128) * 128) * 4


def fused_vmem_bytes(*, cap: int, nw: int, n: int, cyc_cap: int,
                     formulation: str, store: bool, persistent: bool,
                     n_neighbors: int = 0) -> int:
    """VMEM one lane of a fused (``persistent=False``) or persistent
    launch needs, from shapes alone: every pipelined block is double
    buffered, the persistent kernel adds its (2, capp, ·) scratch."""
    tp = _tile_rows(cap, TILE_ROWS)
    capp = -(-cap // tp) * tp
    frontier = lambda rows: 2 * _vmem_bytes(rows, nw) + 3 * _vmem_bytes(
        rows, 1)
    if formulation == "bitword":
        tables = 2 * _vmem_bytes(n, nw)
    else:
        tables = (_vmem_bytes(n + 1, 1) + _vmem_bytes(max(n_neighbors, 8), 1)
                  + _vmem_bytes(n, 1) + _vmem_bytes(n, nw))
    blocks = frontier(tp) + tables + frontier(capp)
    if store:
        blocks += 2 * _vmem_bytes(cyc_cap, nw)
    total = 2 * blocks
    if persistent:
        total += 2 * frontier(capp)
    return total


def fits_vmem(**shape) -> bool:
    """True when a launch of ``shape`` (``fused_vmem_bytes`` arguments)
    fits the fused kernels' VMEM budget."""
    return fused_vmem_bytes(**shape) <= VMEM_BUDGET_BYTES


def _tile_rows(cap: int, tile: int) -> int:
    return min(tile, max(8, -(-cap // 8) * 8))


def _popsum(w):
    """Total set bits of a (1, nw) word row, as a scalar."""
    return jnp.sum(jax.lax.population_count(w).astype(jnp.int32))


def _onehot_row(v, nw: int):
    """(1, nw) word row with only bit ``v`` set (scalar v in [0, 32·nw))."""
    widx = jax.lax.broadcasted_iota(jnp.int32, (1, nw), 1)
    sh = jnp.full((1, nw), v % 32, jnp.int32).astype(jnp.uint32)
    return jnp.where(widx == v // 32, jnp.uint32(1) << sh, jnp.uint32(0))


def _hit(words, onehot):
    """Scalar: does the (1, nw) row ``words`` hold the bit of ``onehot``?"""
    return jnp.max(jnp.where((words & onehot) != 0, 1, 0)) > 0


def _each_bit(words, count, d0, put):
    """Call ``put(d, onehot_row, v)`` for the ``count`` set bits of a
    (1, nw) row, lowest bit first, with d = d0, d0+1, ..."""
    nw = words.shape[1]
    widx = jax.lax.broadcasted_iota(jnp.int32, (1, nw), 1)

    def body(k, carry):
        w, d = carry
        fw = jnp.min(jnp.where(w != jnp.uint32(0), widx, nw))
        sel = widx == fw
        oh = jnp.where(sel, w & (~w + jnp.uint32(1)), jnp.uint32(0))
        low = jax.lax.population_count(oh - jnp.uint32(1)).astype(jnp.int32)
        put(d, oh, fw * 32 + jnp.sum(jnp.where(sel, low, 0)))
        return w & ~oh, d + 1

    jax.lax.fori_loop(0, count, body, (words, d0))


class _BitwordRows:
    """Bitword candidate sets of one frontier row (DESIGN.md §2)."""

    def __init__(self, adj_ref, labelgt_ref):
        self.adj, self.labelgt = adj_ref, labelgt_ref

    def walk(self, row, de, dc, put_ext, put_cyc):
        path, blocked, v1, l2, vlast = row
        n = self.adj.shape[1]
        at = lambda ref, v: ref[0, pl.ds(jnp.clip(v, 0, n - 1), 1), :]
        adj_last, adj_v1 = at(self.adj, vlast), at(self.adj, v1)
        cand = adj_last & ~path & ~blocked & at(self.labelgt, l2)
        ext, close = cand & ~adj_v1, cand & adj_v1
        ne, nc = _popsum(ext), _popsum(close)
        if put_ext is not None:
            nb = blocked | adj_last
            _each_bit(ext, ne, de, lambda d, oh, v: put_ext(d, oh, v, nb))
        if put_cyc is not None:
            _each_bit(close, nc, dc, lambda d, oh, v: put_cyc(d, oh))
        return de + ne, dc + nc


class _SlotRows:
    """Paper-faithful slot candidates of one frontier row: the CSR
    neighbors of v_last in slot order, each tested by bit probes."""

    def __init__(self, offsets_ref, neighbors_ref, labels_ref, adj_ref):
        self.offsets, self.neighbors = offsets_ref, neighbors_ref
        self.labels, self.adj = labels_ref, adj_ref

    def walk(self, row, de, dc, put_ext, put_cyc):
        path, blocked, v1, l2, vlast = row
        n, nw = self.adj.shape[1], self.adj.shape[2]
        last = self.neighbors.shape[1] - 1
        vc = jnp.clip(vlast, 0, n - 1)
        k1 = self.offsets[0, vc, 0]
        deg = self.offsets[0, vc + 1, 0] - k1
        adj_last = self.adj[0, pl.ds(vc, 1), :]
        adj_v1 = self.adj[0, pl.ds(jnp.clip(v1, 0, n - 1), 1), :]
        nb = blocked | adj_last

        def slot(j, carry):
            de, dc = carry
            v = jnp.clip(self.neighbors[0, jnp.clip(k1 + j, 0, last), 0],
                         0, n - 1)
            oh = _onehot_row(v, nw)
            valid = ((self.labels[0, v, 0] > l2) & ~_hit(path, oh)
                     & ~_hit(blocked, oh))
            closes = _hit(adj_v1, oh)
            is_ext, is_cyc = valid & ~closes, valid & closes
            if put_ext is not None:
                pl.when(is_ext)(lambda: put_ext(de, oh, v, nb))
            if put_cyc is not None:
                pl.when(is_cyc)(lambda: put_cyc(dc, oh))
            return (de + is_ext.astype(jnp.int32),
                    dc + is_cyc.astype(jnp.int32))

        return jax.lax.fori_loop(0, deg, slot, (de, dc))


def _graph_rows(formulation: str, it):
    if formulation == "bitword":
        return _BitwordRows(next(it), next(it))
    return _SlotRows(*(next(it) for _ in range(4)))


def _row_reader(refs, lead, row0):
    """``load(r)`` → row ``row0 + r`` of a frontier held in refs (path,
    blocked, v1, l2, vlast): two (1, nw) word rows and three scalars."""
    path_ref, blocked_ref, v1_ref, l2_ref, vlast_ref = refs

    def load(r):
        rr = row0 + r
        return (path_ref[lead, pl.ds(rr, 1), :],
                blocked_ref[lead, pl.ds(rr, 1), :],
                v1_ref[lead, rr, 0], l2_ref[lead, rr, 0],
                vlast_ref[lead, rr, 0])
    return load


def _frontier_writer(refs, lead, path, v1, l2):
    """``put_ext(d, onehot, v, nb)``: write the extension ⟨path, v⟩ as row
    ``d`` of the frontier held in refs."""
    opath, oblocked, ov1, ol2, ovlast = refs

    def put(d, oh, v, nb):
        rows = pl.ds(d, 1)
        opath[lead, rows, :] = path | oh
        oblocked[lead, rows, :] = nb
        ov1[lead, rows, :] = jnp.full((1, 1), v1, jnp.int32)
        ol2[lead, rows, :] = jnp.full((1, 1), l2, jnp.int32)
        ovlast[lead, rows, :] = jnp.full((1, 1), v, jnp.int32)
    return put


def _clear_rows(refs, lead, rows, tp: int, nw: int):
    """Dead-row init of a frontier region (``scatter_frontier``'s zeros)."""
    opath, oblocked, ov1, ol2, ovlast = refs
    opath[lead, rows, :] = jnp.zeros((tp, nw), jnp.uint32)
    oblocked[lead, rows, :] = jnp.zeros((tp, nw), jnp.uint32)
    ov1[lead, rows, :] = jnp.full((tp, 1), -1, jnp.int32)
    ol2[lead, rows, :] = jnp.zeros((tp, 1), jnp.int32)
    ovlast[lead, rows, :] = jnp.zeros((tp, 1), jnp.int32)


def _copy_rows(src, src_lead, src_rows, dst, dst_lead, dst_rows):
    for s, d in zip(src, dst):
        d[dst_lead, dst_rows, :] = s[src_lead, src_rows, :]


def _tile_bases(cnt_ref, base_ref, nt: int):
    """Exclusive cross-tile offsets of the per-tile (ext, cyc) counts in
    SMEM; returns the round's (ext, cyc) totals."""
    def acc(t, carry):
        eb, cb = carry
        base_ref[2 * t] = eb
        base_ref[2 * t + 1] = cb
        return eb + cnt_ref[2 * t], cb + cnt_ref[2 * t + 1]
    return jax.lax.fori_loop(0, nt, acc, (jnp.int32(0), jnp.int32(0)))


def _fused_kernel(*refs, formulation: str, cap: int, tp: int, nt: int,
                  nw: int, store: bool, cyc_cap: int):
    """The two-phase fused round. Ref layout (leading lane-block of 1):

    inputs:  path, blocked, v1, l2, vlast (frontier tiles), fcount, bcount
             ((B,) SMEM), <graph tables>, [masks_in]
    outputs: opath, oblocked, ov1, ol2, ovlast (lane-whole), ncyc, nnew
             ((B,) SMEM), [omasks (lane-whole)]
    scratch: cnt (SMEM (2·nt,) per-tile ext/cyc counts),
             base (SMEM (2·nt,) cross-tile exclusive offsets),
             meta (SMEM (2,) — [ok, unused])
    """
    it = iter(refs)
    tile_refs = tuple(next(it) for _ in range(5))
    fcount_ref, bcount_ref = next(it), next(it)
    g = _graph_rows(formulation, it)
    masks_in_ref = next(it) if store else None
    out_refs = tuple(next(it) for _ in range(5))
    ncyc_ref, nnew_ref = next(it), next(it)
    omasks_ref = next(it) if store else None
    cnt_ref, base_ref, meta_ref = next(it), next(it), next(it)

    b = pl.program_id(0)
    p = pl.program_id(1)
    i = pl.program_id(2)
    bcount = bcount_ref[b]
    row0 = pl.multiple_of(i * tp, tp)
    tile = pl.ds(row0, tp)
    nlive = jnp.clip(fcount_ref[b] - row0, 0, tp)
    load = _row_reader(tile_refs, 0, 0)

    # ---- phase A: per-tile survivor counts + output init -----------------
    @pl.when(p == 0)
    def _phase_a():
        te, tc = jax.lax.fori_loop(
            0, nlive, lambda r, c: g.walk(load(r), *c, None, None),
            (jnp.int32(0), jnp.int32(0)))
        cnt_ref[2 * i] = te
        cnt_ref[2 * i + 1] = tc
        _clear_rows(out_refs, 0, tile, tp, nw)
        if store:
            # carry the ring through (rows this round appends overwrite in
            # phase B; everything else must survive the round unchanged)
            @pl.when(i == 0)
            def _ring():
                omasks_ref[0] = masks_in_ref[0]

    # ---- phase B entry: cross-tile exclusive offsets + the guard ---------
    @pl.when((p == 1) & (i == 0))
    def _phase_b_bases():
        tot_e, tot_c = _tile_bases(cnt_ref, base_ref, nt)
        ok = tot_e <= cap
        if store:
            ok = ok & (bcount + tot_c <= cyc_cap)
        meta_ref[0] = ok.astype(jnp.int32)
        ncyc_ref[b] = tot_c
        nnew_ref[b] = tot_e

    # ---- phase B: write survivors/cycles at their final positions --------
    @pl.when(p == 1)
    def _phase_b():
        okv = meta_ref[0] == 1

        @pl.when(okv)
        def _scatter():
            def row(r, carry):
                rw = load(r)
                path, _, v1, l2, _ = rw
                put_ext = _frontier_writer(out_refs, 0, path, v1, l2)

                def put_cyc(d, oh):
                    omasks_ref[0, pl.ds(d, 1), :] = path | oh

                return g.walk(rw, *carry, put_ext,
                              put_cyc if store else None)

            jax.lax.fori_loop(0, nlive, row,
                              (base_ref[2 * i], bcount + base_ref[2 * i + 1]))

        # guard tripped: the round is NOT applied — copy the input tile
        # through so f' == f (the ring already carries its input content)
        @pl.when(~okv)
        def _keep():
            _copy_rows(tile_refs, 0, slice(None), out_refs, 0, tile)


def _scalar_spec():
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def _graph_tables(formulation, graph_tables):
    if formulation == "bitword":
        return tuple(graph_tables)
    offsets, neighbors, labels, adj_bits = graph_tables
    nbr = neighbors[..., None]
    if nbr.shape[1] % 8:
        nbr = jnp.pad(nbr, ((0, 0), (0, (-nbr.shape[1]) % 8), (0, 0)))
    return (offsets[..., None], nbr, labels[..., None], adj_bits)


def _compiler_params():
    return pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)


@functools.partial(
    jax.jit,
    static_argnames=("formulation", "delta", "store", "tile", "interpret"))
def fused_round_lanes(path, blocked, v1, l2, vlast, fcount, bmasks, bcount,
                      graph_tables, *, formulation: str, delta: int,
                      store: bool, interpret: bool, tile: int = TILE_ROWS):
    """Lane-gridded fused round: ONE ``pallas_call`` advances every lane of
    a batch through one guarded expansion round.

    ``graph_tables`` is ``(adj_bits, labelgt_bits)`` for the bitword
    formulation and ``(offsets, neighbors, labels, adj_bits)`` for slot
    (each with the leading lane axis). Returns
    (path', blocked', v1', l2', vlast', masks', n_cyc (B,), n_new (B,)) —
    the un-applied (guard-tripped) lanes pass their inputs through.
    ``delta`` only keys the compiled shape family (a row walks its own
    candidates).
    """
    del delta
    B, cap, nw = path.shape
    tp = _tile_rows(cap, tile)
    pad = (-cap) % tp
    padded = lambda a: jnp.pad(
        a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
    col = lambda a: padded(a[..., None])
    capp = cap + pad
    nt = capp // tp
    cyc_cap = bmasks.shape[1]
    lane_whole = lambda shape: pl.BlockSpec(
        (1,) + shape[1:], lambda b, p, i: (b,) + (0,) * (len(shape) - 1))
    tile_spec = lambda w: pl.BlockSpec((1, tp, w), lambda b, p, i: (b, i, 0))

    gtabs = _graph_tables(formulation, graph_tables)
    in_specs = ([tile_spec(nw), tile_spec(nw), tile_spec(1), tile_spec(1),
                 tile_spec(1), _scalar_spec(), _scalar_spec()]
                + [lane_whole(t.shape) for t in gtabs])
    operands = [padded(path), padded(blocked), col(v1), col(l2), col(vlast),
                fcount.astype(jnp.int32), bcount.astype(jnp.int32)
                ] + list(gtabs)
    if store:
        in_specs.append(lane_whole(bmasks.shape))
        operands.append(bmasks)

    words, ids = (B, capp, nw), (B, capp, 1)
    out_shape = [jax.ShapeDtypeStruct(words, jnp.uint32),
                 jax.ShapeDtypeStruct(words, jnp.uint32),
                 jax.ShapeDtypeStruct(ids, jnp.int32),
                 jax.ShapeDtypeStruct(ids, jnp.int32),
                 jax.ShapeDtypeStruct(ids, jnp.int32),
                 jax.ShapeDtypeStruct((B,), jnp.int32),
                 jax.ShapeDtypeStruct((B,), jnp.int32)]
    out_specs = [lane_whole(words), lane_whole(words), lane_whole(ids),
                 lane_whole(ids), lane_whole(ids), _scalar_spec(),
                 _scalar_spec()]
    if store:
        out_shape.append(jax.ShapeDtypeStruct(bmasks.shape, jnp.uint32))
        out_specs.append(lane_whole(bmasks.shape))

    kernel = functools.partial(
        _fused_kernel, formulation=formulation, cap=cap, tp=tp, nt=nt,
        nw=nw, store=store, cyc_cap=cyc_cap)

    out = pl.pallas_call(
        kernel,
        grid=(B, 2, nt),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.SMEM((2 * nt,), jnp.int32),
                        pltpu.SMEM((2 * nt,), jnp.int32),
                        pltpu.SMEM((2,), jnp.int32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="fused_round",
    )(*operands)

    opath, oblocked, ov1, ol2, ovlast, ncyc, nnew = out[:7]
    omasks = out[7] if store else bmasks
    return (opath[:, :cap], oblocked[:, :cap], ov1[:, :cap, 0],
            ol2[:, :cap, 0], ovlast[:, :cap, 0], omasks, ncyc, nnew)


def fused_round_pallas(path, blocked, v1, l2, vlast, fcount, bmasks, bcount,
                       graph_tables, *, formulation: str, delta: int,
                       store: bool, interpret: bool, tile: int = TILE_ROWS):
    """Single-graph entry point — the B=1 lane of ``fused_round_lanes``."""
    out = fused_round_lanes(
        path[None], blocked[None], v1[None], l2[None], vlast[None],
        fcount[None], bmasks[None], bcount[None],
        tuple(t[None] for t in graph_tables),
        formulation=formulation, delta=delta, store=store, tile=tile,
        interpret=interpret)
    return tuple(x[0] for x in out)


# ---------------------------------------------------------------------------
# Persistent multi-round wave kernel (DESIGN.md §6.11)
#
# One ``pallas_call`` with a leading ROUND axis — grid=(B, R, 2, nt) —
# executes up to R complete guarded rounds back to back. The frontier state
# between rounds never touches HBM: round 0 copies the launch inputs into
# ping-pong scratch buffer 0 tile by tile, round r reads buffer r % 2 and
# scatters into buffer (r + 1) % 2; the final grid step copies the last
# buffer to the output refs ONCE. The live/guard counters ride SMEM across
# grid steps (TPU grids execute sequentially — the same property the
# phase-axis scatter exploits):
#
#   meta[0] ok        — current round applies (phase-B scatter gate)
#   meta[1] alive     — cleared on a guard trip or when the wave dies
#   meta[2] fcount    — live frontier rows after the last applied round
#   meta[3] bcount    — cycle-ring fill after the last applied round
#   meta[4] rounds    — rounds applied so far (the ``rounds_done`` output)
#   meta[5] ring base — bcount snapshot the current round scatters against
#   meta[6] round fc  — fcount snapshot the current round expands from
#   meta[7] okf       — ok_frontier of the first failing round (1 if none)
#   meta[8] okc       — ok_cycles of the first failing round (1 if none)
#
# A round whose guard trips, whose frontier is empty, or that lies past the
# dynamic budget (``rlimit``) degrades to the identity copy-through: phase B
# copies the read buffer into the write buffer unchanged, so the final
# copy-out always publishes the state after the last APPLIED round. The ring
# is append-only, so it needs no ping-pong: the launch's first grid step
# copies the input ring through to the output ref and every applied round
# appends at its SMEM-carried base.
# ---------------------------------------------------------------------------


def _persistent_kernel(*refs, formulation: str, cap: int, tp: int, nt: int,
                       nw: int, store: bool, cyc_cap: int, rounds: int):
    """Ref layout (leading lane-block of 1):

    inputs:  path, blocked, v1, l2, vlast (frontier tiles), fcount, bcount,
             rlimit ((B,) SMEM), <graph tables>, [masks_in]
    outputs: opath, oblocked, ov1, ol2, ovlast (lane-whole),
             nnew_h, ncyc_h ((B·R,) SMEM per-round histories),
             meta_out ((B·8,) SMEM: rounds_done, okf, okc, fcount', bcount'),
             [omasks (lane-whole)]
    scratch: cnt/base (SMEM (2·nt,)), meta (SMEM (16,)),
             spath/sblocked ((2, capp, nw) ping-pong frontier words),
             sv1/sl2/svlast ((2, capp, 1) ping-pong frontier ids)
    """
    it = iter(refs)
    tile_refs = tuple(next(it) for _ in range(5))
    fcount_ref, bcount_ref, rlimit_ref = next(it), next(it), next(it)
    g = _graph_rows(formulation, it)
    masks_in_ref = next(it) if store else None
    out_refs = tuple(next(it) for _ in range(5))
    nnew_h_ref, ncyc_h_ref, meta_out_ref = next(it), next(it), next(it)
    omasks_ref = next(it) if store else None
    cnt_ref, base_ref, meta_ref = next(it), next(it), next(it)
    sbuf = tuple(next(it) for _ in range(5))

    b = pl.program_id(0)
    r = pl.program_id(1)
    p = pl.program_id(2)
    i = pl.program_id(3)
    rb = jax.lax.rem(r, 2)              # read buffer of this round
    wb = 1 - rb                         # write buffer of this round
    row0 = pl.multiple_of(i * tp, tp)
    tile = pl.ds(row0, tp)

    # ---- launch init + round-start snapshots (SMEM) ----------------------
    @pl.when((r == 0) & (p == 0) & (i == 0))
    def _init():
        meta_ref[1] = 1
        meta_ref[2] = fcount_ref[b]
        meta_ref[3] = bcount_ref[b]
        meta_ref[4] = 0
        meta_ref[7] = 1
        meta_ref[8] = 1
        if store:
            omasks_ref[0] = masks_in_ref[0]

    @pl.when((p == 0) & (i == 0))
    def _round_start():
        meta_ref[5] = meta_ref[3]
        meta_ref[6] = meta_ref[2]

    @pl.when((r == 0) & (p == 0))
    def _load_tile():
        _copy_rows(tile_refs, 0, slice(None), sbuf, 0, tile)

    load = _row_reader(sbuf, rb, row0)

    def live_rows():
        return jnp.clip(meta_ref[6] - row0, 0, tp)

    # ---- phase A: per-tile counts + write-buffer init --------------------
    @pl.when(p == 0)
    def _phase_a():
        te, tc = jax.lax.fori_loop(
            0, live_rows(), lambda k, c: g.walk(load(k), *c, None, None),
            (jnp.int32(0), jnp.int32(0)))
        cnt_ref[2 * i] = te
        cnt_ref[2 * i + 1] = tc
        _clear_rows(sbuf, wb, tile, tp, nw)

    # ---- phase B entry: cross-tile bases, the guard, SMEM state advance --
    @pl.when((p == 1) & (i == 0))
    def _phase_b_entry():
        tot_e, tot_c = _tile_bases(cnt_ref, base_ref, nt)
        alive = (meta_ref[1] == 1) & (meta_ref[4] < rlimit_ref[b])
        okf_r = tot_e <= cap
        okc_r = (meta_ref[3] + tot_c <= cyc_cap) if store \
            else (tot_e >= jnp.int32(-1))
        okr = okf_r & okc_r
        ok = alive & okr
        meta_ref[0] = ok.astype(jnp.int32)
        nnew_h_ref[b * rounds + r] = jnp.where(alive, tot_e, 0)
        ncyc_h_ref[b * rounds + r] = jnp.where(alive, tot_c, 0)

        @pl.when(ok)
        def _applied():
            meta_ref[4] = meta_ref[4] + 1
            meta_ref[2] = tot_e
            if store:
                meta_ref[3] = meta_ref[3] + tot_c
            meta_ref[1] = (tot_e > 0).astype(jnp.int32)

        @pl.when(alive & ~okr)
        def _tripped():
            meta_ref[1] = 0
            meta_ref[7] = okf_r.astype(jnp.int32)
            meta_ref[8] = okc_r.astype(jnp.int32)

    # ---- phase B: scatter survivors/cycles, or identity copy-through -----
    @pl.when(p == 1)
    def _phase_b():
        okv = meta_ref[0] == 1

        @pl.when(okv)
        def _scatter():
            def row(k, carry):
                rw = load(k)
                path, _, v1, l2, _ = rw
                put_ext = _frontier_writer(sbuf, wb, path, v1, l2)

                def put_cyc(d, oh):
                    omasks_ref[0, pl.ds(d, 1), :] = path | oh

                return g.walk(rw, *carry, put_ext,
                              put_cyc if store else None)

            jax.lax.fori_loop(
                0, live_rows(), row,
                (base_ref[2 * i], meta_ref[5] + base_ref[2 * i + 1]))

        # round not applied (guard trip / dead / past budget): identity
        @pl.when(~okv)
        def _keep():
            _copy_rows(sbuf, rb, tile, sbuf, wb, tile)

    # ---- final grid step: publish state + counters ONCE ------------------
    @pl.when((r == rounds - 1) & (p == 1) & (i == nt - 1))
    def _finish():
        fb = rounds % 2                  # static: last round's write buffer
        _copy_rows(sbuf, fb, slice(None), out_refs, 0, slice(None))
        for k, slot in enumerate((4, 7, 8, 2, 3)):
            meta_out_ref[b * 8 + k] = meta_ref[slot]
        for k in range(5, 8):
            meta_out_ref[b * 8 + k] = 0


@functools.partial(
    jax.jit,
    static_argnames=("formulation", "delta", "store", "rounds", "tile",
                     "interpret"))
def persistent_round_lanes(path, blocked, v1, l2, vlast, fcount, bmasks,
                           bcount, rlimit, graph_tables, *,
                           formulation: str, delta: int, store: bool,
                           rounds: int, interpret: bool,
                           tile: int = TILE_ROWS):
    """Lane-gridded persistent wave kernel: ONE ``pallas_call`` advances
    every lane of a batch through up to ``rounds`` guarded expansion rounds,
    the frontier resident in scratch between rounds.

    ``rlimit`` (B,) bounds the rounds actually applied (the superstep's
    dynamic budget); rounds past it run as identity copy-throughs. Returns
    (path', blocked', v1', l2', vlast', masks', ncyc_hist (B, R),
    nnew_hist (B, R), rounds_done (B,), ok_frontier (B,), ok_cycles (B,),
    fcount' (B,), bcount' (B,)) where the histories hold each ATTEMPTED
    round's totals (index ``rounds_done`` is the pending overflow on a
    guard trip) and the ok flags report the first failing round (1/1 when
    no round failed).
    """
    del delta
    B, cap, nw = path.shape
    R = int(rounds)
    tp = _tile_rows(cap, tile)
    pad = (-cap) % tp
    padded = lambda a: jnp.pad(
        a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
    col = lambda a: padded(a[..., None])
    capp = cap + pad
    nt = capp // tp
    cyc_cap = bmasks.shape[1]
    lane_whole = lambda shape: pl.BlockSpec(
        (1,) + shape[1:],
        lambda b, r, p, i: (b,) + (0,) * (len(shape) - 1))
    tile_spec = lambda w: pl.BlockSpec((1, tp, w),
                                       lambda b, r, p, i: (b, i, 0))

    gtabs = _graph_tables(formulation, graph_tables)
    in_specs = ([tile_spec(nw), tile_spec(nw), tile_spec(1), tile_spec(1),
                 tile_spec(1), _scalar_spec(), _scalar_spec(),
                 _scalar_spec()]
                + [lane_whole(t.shape) for t in gtabs])
    operands = [padded(path), padded(blocked), col(v1), col(l2), col(vlast),
                fcount.astype(jnp.int32), bcount.astype(jnp.int32),
                rlimit.astype(jnp.int32)] + list(gtabs)
    if store:
        in_specs.append(lane_whole(bmasks.shape))
        operands.append(bmasks)

    words, ids = (B, capp, nw), (B, capp, 1)
    out_shape = [jax.ShapeDtypeStruct(words, jnp.uint32),
                 jax.ShapeDtypeStruct(words, jnp.uint32),
                 jax.ShapeDtypeStruct(ids, jnp.int32),
                 jax.ShapeDtypeStruct(ids, jnp.int32),
                 jax.ShapeDtypeStruct(ids, jnp.int32),
                 jax.ShapeDtypeStruct((B * R,), jnp.int32),
                 jax.ShapeDtypeStruct((B * R,), jnp.int32),
                 jax.ShapeDtypeStruct((B * 8,), jnp.int32)]
    out_specs = [lane_whole(words), lane_whole(words), lane_whole(ids),
                 lane_whole(ids), lane_whole(ids), _scalar_spec(),
                 _scalar_spec(), _scalar_spec()]
    if store:
        out_shape.append(jax.ShapeDtypeStruct(bmasks.shape, jnp.uint32))
        out_specs.append(lane_whole(bmasks.shape))

    kernel = functools.partial(
        _persistent_kernel, formulation=formulation, cap=cap, tp=tp, nt=nt,
        nw=nw, store=store, cyc_cap=cyc_cap, rounds=R)

    out = pl.pallas_call(
        kernel,
        grid=(B, R, 2, nt),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.SMEM((2 * nt,), jnp.int32),
                        pltpu.SMEM((2 * nt,), jnp.int32),
                        pltpu.SMEM((16,), jnp.int32),
                        pltpu.VMEM((2, capp, nw), jnp.uint32),
                        pltpu.VMEM((2, capp, nw), jnp.uint32),
                        pltpu.VMEM((2, capp, 1), jnp.int32),
                        pltpu.VMEM((2, capp, 1), jnp.int32),
                        pltpu.VMEM((2, capp, 1), jnp.int32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="persistent_round",
    )(*operands)

    opath, oblocked, ov1, ol2, ovlast, nnew_h, ncyc_h, meta = out[:8]
    omasks = out[8] if store else bmasks
    meta = meta.reshape(B, 8)
    return (opath[:, :cap], oblocked[:, :cap], ov1[:, :cap, 0],
            ol2[:, :cap, 0], ovlast[:, :cap, 0], omasks,
            ncyc_h.reshape(B, R), nnew_h.reshape(B, R), meta[:, 0],
            meta[:, 1], meta[:, 2], meta[:, 3], meta[:, 4])


def persistent_round_pallas(path, blocked, v1, l2, vlast, fcount, bmasks,
                            bcount, rlimit, graph_tables, *,
                            formulation: str, delta: int, store: bool,
                            rounds: int, interpret: bool,
                           tile: int = TILE_ROWS):
    """Single-graph entry — the B=1 lane of ``persistent_round_lanes``."""
    out = persistent_round_lanes(
        path[None], blocked[None], v1[None], l2[None], vlast[None],
        fcount[None], bmasks[None], bcount[None], rlimit[None],
        tuple(t[None] for t in graph_tables),
        formulation=formulation, delta=delta, store=store, rounds=rounds,
        tile=tile, interpret=interpret)
    return tuple(x[0] for x in out)
