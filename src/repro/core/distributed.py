"""Distributed chordless-cycle enumeration — the sharded wave superstep.

Scaling story (DESIGN.md §5): the frontier — not the graph — is what
explodes (14M live paths on Grid 7×10, unbounded in general), so we shard
frontier ROWS across devices and replicate the (small) graph.

This module is the sharded twin of the single-device wave engine
(``engine.wave_superstep``): instead of one dispatch per round with a
blocking ``int(total_live)`` host sync every iteration (the PR-1 pattern
the wave engine eliminated), the driver fuses up to K expansion rounds PLUS
in-loop diffusion load balancing into one jitted
``shard_map(lax.while_loop)`` program. Termination is detected on device
(the per-round ``psum`` of live counts is carried into the loop condition),
so the host is re-entered only at superstep boundaries: host syncs drop
from O(iterations) to O(iterations / K) — the sharded analogue of the wave
engine's O(bucket transitions).

Stage 1 is a device-side deal: the jitted triplet flags are computed on
every device (replicated graph), each device takes the triplets whose RANK
≡ its axis index (mod ndev) — the same round-robin deal the host used to
perform — and cumsum-scatters them straight into its local shard of the
frontier. No host-side nonzero, no H2D copy of every initial row.

Per-device bucketing: every superstep runs at one per-device bucket (a
power of two between two balance blocks and ``local_capacity``), the
sharded twin of the wave engine's GROW/SHRINK re-bucketing — a round the
busiest device's bucket cannot hold is aborted on every device and the
superstep exits GROW; a wave that shrank well below the bucket exits
SHRINK; the host picks the next bucket with ``dist_next_bucket`` and
resizes every shard. A round's
compaction costs per bucket row, so this work follows the live rows.

Load balance: DFS trees are lopsided, so on balance rounds each device
donates a fixed-size block of tail rows to its ring neighbor iff its live
count exceeds the neighbor's by more than the block size (diffusion load
balancing, Cybenko '89). ``collective_permute`` with static block shapes
keeps XLA happy (no ragged all-to-all). The receiver's live count arrives
via the reverse permute, so a receiver without room for a full block
REFUSES the donation (give = 0) — live rows are never dropped by balancing
(``lost`` is a defensive counter that must stay 0; conservation is
property-tested).

Two-level meshes (DESIGN.md §7): with ``cfg.host_axis`` set the frontier
shards over a ``(host, device)`` mesh — real multi-process or simulated via
``--xla_force_host_platform_device_count`` (``launch/env.py``) — and the
superstep becomes TIERED:

* termination psums nest hierarchically (``psum`` over the device axis,
  then over the host axis);
* diffusion runs on the cheap device ring every ``balance_every`` rounds,
  and on the expensive host ring only every ``cross_balance_every``-th
  balance round, gated additionally by the cross-tier mean load;
* with ``compress_cross_host`` the cross-host hop ships a COMPRESSED wire:
  the mean-load signal goes through ``dist.collectives.ef_psum_tree``
  (int8 wire, error-feedback residual carried in the loop state) and
  donated rows ship as bit-packed paths + ``ef_quantize``d endpoint ids
  (exact for n ≤ 127), with ``blocked``/``l2`` reconstructed receiver-side
  from the chordless-path invariant. Row counts and backpressure stay
  exact int32, so compression never loses rows (``lost`` stays 0).

Compilation and buffer donation are owned by ``core.plan.DistPlan``
(``kind='dist'`` plans in the same ProgramCache the wave path warms);
request routing and autotuning by ``core.service.CycleService`` —
mesh-routed requests resolve ``superstep_rounds`` / ``local_capacity`` /
``balance_every`` (and, on 2-level meshes, ``cross_balance_every`` /
``compress_cross_host``) through ``repro.tune`` like single-device
requests do.

Fault tolerance: the sharded frontier + counters form a pytree —
``checkpoint.save_pytree`` snapshots it at superstep boundaries; a restart
(possibly on a *different* device count) reshards via re-deal of live rows.

Count-only mode (the paper's Grid 8×10 footnote) — cycle *bitmaps* stay
device-local and could be all_gathered, but counting is the scalable output.
"""
from __future__ import annotations

import dataclasses
import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax.experimental.shard_map import shard_map

from .bitset_graph import BitsetGraph
from .engine import (_DONE, _GROW, _RUN, _SHRINK, STATUS_NAMES,
                     EngineConfig, EnumerationResult)
from .frontier import Frontier, with_capacity
from . import expand as E
from . import triplets as T
from ..dist.collectives import ef_psum_tree, ef_quantize
from ..dist import sharding as SH
from ..obs.spans import SpanLog
from ..tune.telemetry import disabled_trace

# counter columns of the sharded superstep's per-device accumulator
# (``counters`` below): cycles found, rows dropped (compaction overflow +
# balance loss), rows moved by intra-host diffusion, rows moved by the
# cross-host hop, and the defensive receiver-overflow counter.
_N_COUNTERS = 5


def as_engine_config(mesh: Mesh, axis: str, cfg: EngineConfig | None,
                     max_iters: int | None = None) -> EngineConfig:
    """Normalize to a mesh-routed ``EngineConfig``.

    (The ``DistEnumConfig`` compat shim is gone — construct
    ``EngineConfig(store=False, mesh=..., axis=...)`` directly.)"""
    if cfg is None:
        out = EngineConfig(store=False, mesh=mesh, axis=axis)
    elif isinstance(cfg, EngineConfig):
        if cfg.mesh is not None and (cfg.mesh is not mesh
                                     or cfg.axis != axis):
            raise ValueError(
                "conflicting meshes: cfg already carries "
                f"mesh/axis={cfg.axis!r} but enumerate_distributed was "
                f"called with a different mesh/axis={axis!r}; pass one or "
                "the other")
        out = cfg if cfg.mesh is not None else dataclasses.replace(
            cfg, mesh=mesh, axis=axis)
    else:
        raise TypeError(
            "DistEnumConfig was removed; pass "
            "EngineConfig(store=False, mesh=..., axis=...) — the old knobs "
            "(local_capacity, balance_block, balance_every, "
            "checkpoint_every, checkpoint_dir) live on EngineConfig now")
    if max_iters is not None:
        out = dataclasses.replace(out, max_iters=max_iters)
    return out


def _row_axes(cfg: EngineConfig) -> tuple[str, ...]:
    """Mesh axes the frontier's row dim shards over — (host, device) on a
    2-level config, the flat data axis otherwise."""
    return (cfg.host_axis, cfg.axis) if cfg.host_axis else (cfg.axis,)


def _fspec(mesh: Mesh, row_axes: tuple[str, ...]) -> Frontier:
    """Frontier PartitionSpec pytree, resolved through the logical-axis
    rules (``dist.sharding``): rows shard over every tier of ``row_axes``,
    bitset words replicate."""
    rules = dict(SH.DEFAULT_RULES, frontier_rows=tuple(row_axes),
                 mask_words=())
    rows = SH.logical_to_spec(("frontier_rows",), rules, mesh)
    return Frontier(path=rows, blocked=rows, v1=rows, l2=rows,
                    vlast=rows, count=rows)


def _reduce_tiers(x, axis: str, host_axis: str | None, reduce=jax.lax.psum):
    """Hierarchical reduction: the device tier first, then the host tier
    (one nested ``reduce`` per mesh level; collapses to a plain one on
    flat meshes)."""
    x = reduce(x, axis)
    if host_axis:
        x = reduce(x, host_axis)
    return x


def _donate(f: Frontier, give: jnp.ndarray, block: int, axis: str,
            axis_size: int):
    """Ring-shift ``block`` tail rows rightward; keep them iff give==0.

    give ∈ {0,1} per device. Sends are unconditional (static shapes); the
    *receiver* learns how many of the incoming rows are real via the
    permuted (give * k) counter and appends only those.

    Returns (f', moved, lost): ``moved`` is the rows this device donated;
    ``lost`` counts receiver-side overflow and is provably 0 when the
    caller's ``give`` carries backpressure (see ``_balance``) — it is kept
    as a defensive invariant, not a legal outcome.
    """
    cap = f.capacity
    cnt = f.count
    k = jnp.minimum(jnp.where(give > 0, block, 0), cnt).astype(jnp.int32)
    start = cnt - k  # tail rows [start, start+k)
    idx = (start + jnp.arange(block, dtype=jnp.int32)) % jnp.maximum(cap, 1)

    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]
    send = lambda x: jax.lax.ppermute(x, axis, perm)

    blk = Frontier(path=f.path[idx], blocked=f.blocked[idx], v1=f.v1[idx],
                   l2=f.l2[idx], vlast=f.vlast[idx], count=k)
    rblk = jax.tree_util.tree_map(send, blk)
    rk = rblk.count

    # drop donated tail locally; append received rows (capacity-clamped)
    new_cnt = cnt - k
    appended = jnp.minimum(rk, cap - new_cnt)
    lost = rk - appended
    dest = new_cnt + jnp.arange(block, dtype=jnp.int32)
    dest = jnp.where(jnp.arange(block) < appended, dest, cap)  # drop pad rows
    f2 = Frontier(
        path=f.path.at[dest].set(rblk.path, mode="drop"),
        blocked=f.blocked.at[dest].set(rblk.blocked, mode="drop"),
        v1=f.v1.at[dest].set(rblk.v1, mode="drop"),
        l2=f.l2.at[dest].set(rblk.l2, mode="drop"),
        vlast=f.vlast.at[dest].set(rblk.vlast, mode="drop"),
        count=new_cnt + appended,
    )
    return f2, k, lost


def _onehot_rows(v: jnp.ndarray, nw: int) -> jnp.ndarray:
    """(len(v), nw) uint32 masks with bit ``v`` set per row."""
    wi = (v // 32)[:, None]
    return jnp.where(jnp.arange(nw)[None, :] == wi,
                     jnp.uint32(1) << (v % 32).astype(jnp.uint32)[:, None],
                     jnp.uint32(0))


def _donate_compressed(g: BitsetGraph, f: Frontier, give: jnp.ndarray,
                       block: int, axis: str, axis_size: int,
                       id_err: jnp.ndarray):
    """Cross-host donation over a COMPRESSED wire (DESIGN.md §7).

    The chordless-path invariant makes most of a frontier row redundant on
    the wire: ``blocked`` is ∪ Adj(v) over the path's INTERNAL vertices
    (path minus v1/vlast — the exact set ``expand`` accumulated it from),
    and ``l2`` is the label of the unique path vertex adjacent to ``v1``
    (every vertex after v2 was admitted through ``~closes``, so exactly one
    path member neighbors v1). So only the bit-packed path (⌈n/8⌉ bytes)
    and the two endpoint ids cross the slow link — int8 via ``ef_quantize``
    against a static unit scale, exact for n ≤ 127 (|round(v) − v| = 0 for
    integer v ≤ 127), with the residuals carried by the caller in the loop
    state and provably zero. The receiver rebuilds ``blocked``/``l2`` from
    its replicated graph, bit-identically to what ``_donate`` would have
    shipped: ≈(8·nw+12)/(⌈n/8⌉+2)× less cross-host traffic per row.

    The row counter ``k`` and the append path stay exact int32 —
    backpressure (and so ``lost == 0``) is preserved under compression.

    Returns (f', moved, lost, id_err').
    """
    cap = f.capacity
    nw = f.n_words
    n = g.labels.shape[0]
    nb = (n + 7) // 8
    cnt = f.count
    k = jnp.minimum(jnp.where(give > 0, block, 0), cnt).astype(jnp.int32)
    start = cnt - k
    idx = (start + jnp.arange(block, dtype=jnp.int32)) % jnp.maximum(cap, 1)
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]
    send = lambda x: jax.lax.ppermute(x, axis, perm)

    # pack: explicit byte extraction (endian-free; path bits ≥ n are 0, so
    # slicing to nb bytes is lossless)
    sh8 = jnp.uint32(8) * jnp.arange(4, dtype=jnp.uint32)
    by = ((f.path[idx][:, :, None] >> sh8[None, None, :])
          & jnp.uint32(0xFF))
    by = by.reshape(block, nw * 4)[:, :nb].astype(jnp.uint8)
    unit = jnp.float32(1.0)
    qv1, _, e1 = ef_quantize(f.v1[idx].astype(jnp.float32), id_err[0],
                             scale=unit)
    qvl, _, e2 = ef_quantize(f.vlast[idx].astype(jnp.float32), id_err[1],
                             scale=unit)

    r_by, r_q1, r_ql, rk = send(by), send(qv1), send(qvl), send(k)

    # receiver: unpack the path, rederive blocked and l2 from the graph
    full = jnp.zeros((block, nw * 4), jnp.uint32).at[:, :nb].set(
        r_by.astype(jnp.uint32))
    w4 = full.reshape(block, nw, 4)
    r_path = (w4[..., 0] | (w4[..., 1] << jnp.uint32(8))
              | (w4[..., 2] << jnp.uint32(16))
              | (w4[..., 3] << jnp.uint32(24)))
    v1r = r_q1.astype(jnp.int32)
    vlr = r_ql.astype(jnp.int32)
    v1c = jnp.clip(v1r, 0, n - 1)
    vlc = jnp.clip(vlr, 0, n - 1)
    pa = r_path & g.adj_bits[v1c]  # path ∩ Adj(v1) = {v2} on live rows
    v2 = E._select_kth_bit(pa, jnp.zeros((block,), jnp.int32))
    l2r = g.labels[jnp.clip(v2, 0, n - 1)].astype(jnp.int32)
    internal = r_path & ~_onehot_rows(v1c, nw) & ~_onehot_rows(vlc, nw)
    vs = jnp.arange(n, dtype=jnp.int32)
    sel = ((internal[:, vs // 32] >> (vs % 32).astype(jnp.uint32))
           & jnp.uint32(1)).astype(bool)                     # (block, n)
    masked = jnp.where(sel[:, :, None], g.adj_bits[None, :, :],
                       jnp.uint32(0))
    blockedr = jax.lax.reduce(masked, jnp.uint32(0), jax.lax.bitwise_or,
                              (1,))

    new_cnt = cnt - k
    appended = jnp.minimum(rk, cap - new_cnt)
    lost = rk - appended
    dest = new_cnt + jnp.arange(block, dtype=jnp.int32)
    dest = jnp.where(jnp.arange(block) < appended, dest, cap)
    f2 = Frontier(
        path=f.path.at[dest].set(r_path, mode="drop"),
        blocked=f.blocked.at[dest].set(blockedr, mode="drop"),
        v1=f.v1.at[dest].set(v1r, mode="drop"),
        l2=f.l2.at[dest].set(l2r, mode="drop"),
        vlast=f.vlast.at[dest].set(vlr, mode="drop"),
        count=new_cnt + appended,
    )
    return f2, k, lost, jnp.stack([e1, e2])


def _balance(f: Frontier, block: int, axis: str, axis_size: int, cap: int,
             do_bal: jnp.ndarray):
    """One diffusion step with receiver backpressure.

    Donate a block of tail rows to the RIGHT ring neighbor iff (a) my live
    count exceeds theirs by more than the block and (b) they have room for
    a full block. The neighbor's count arrives via the reverse permute, so
    a device at capacity refuses donation (give=0) instead of letting the
    receiver drop live rows. ``do_bal`` gates the whole step (``lax.cond``:
    the collectives only execute on balance rounds). Returns
    (f', moved, lost).
    """

    def run(f):
        cnt = f.count
        perm_rev = [((i + 1) % axis_size, i) for i in range(axis_size)]
        rcnt = jax.lax.ppermute(cnt, axis, perm_rev)  # right neighbor's count
        give = ((cnt > rcnt + block)
                & (cap - rcnt >= block)).astype(jnp.int32)
        return _donate(f, give, block, axis, axis_size)

    def skip(f):
        return f, jnp.int32(0), jnp.int32(0)

    return jax.lax.cond(do_bal, run, skip, f)


def _cross_balance(g: BitsetGraph, f: Frontier, block: int, host_axis: str,
                   host_size: int, cap: int, do_cross: jnp.ndarray,
                   compress: bool, ef):
    """One cross-host diffusion step (the expensive tier; DESIGN.md §7).

    Same give rule on the host ring as ``_balance`` on the device ring,
    plus a mean-load gate: donate only when this shard is above the
    cross-tier mean — the global signal that keeps the slow hop quiet when
    imbalance is purely local. In compressed mode the mean arrives through
    ``ef_psum_tree`` (int8 on the wire; the error-feedback residual rides
    ``ef`` across loop rounds, so the quantization error telescopes
    instead of accumulating) and donated rows ship through
    ``_donate_compressed``. The neighbor count and the row counter stay
    exact int32, so receiver backpressure — and therefore ``lost == 0`` —
    holds under compression: compression can never lose rows.

    ``ef = dict(psum_err=f32[], id_err=f32[2, block])``.
    Returns (f', moved, lost, ef').
    """

    def run(args):
        f, ef = args
        cnt = f.count
        perm_rev = [((i + 1) % host_size, i) for i in range(host_size)]
        rcnt = jax.lax.ppermute(cnt, host_axis, perm_rev)
        cntf = cnt.astype(jnp.float32)
        if compress:
            mean, psum_err = ef_psum_tree(cntf, ef["psum_err"], host_axis)
        else:
            mean = jax.lax.psum(cntf, host_axis) / host_size
            psum_err = ef["psum_err"]
        give = ((cntf > mean + block) & (cnt > rcnt + block)
                & (cap - rcnt >= block)).astype(jnp.int32)
        if compress:
            f2, k, lost, id_err = _donate_compressed(
                g, f, give, block, host_axis, host_size, ef["id_err"])
        else:
            f2, k, lost = _donate(f, give, block, host_axis, host_size)
            id_err = ef["id_err"]
        return (f2, dict(psum_err=psum_err, id_err=id_err)), k, lost

    def skip(args):
        f, ef = args
        return (f, ef), jnp.int32(0), jnp.int32(0)

    (f2, ef2), moved, lost = jax.lax.cond(do_cross, run, skip, (f, ef))
    return f2, moved, lost, ef2


def make_balance_step(mesh: Mesh, axis: str, cap: int, block: int):
    """One jitted diffusion-balance step over a sharded frontier.

    Test/debug surface: lets the conservation and backpressure properties
    be probed in isolation (the superstep runs the same ``_balance``).
    Returns ``step(f) -> (f', moved (ndev,), lost (ndev,))``.
    """
    axis_size = int(mesh.shape[axis])
    fspec = _fspec(mesh, (axis,))

    @functools.partial(shard_map, mesh=mesh, in_specs=(fspec,),
                       out_specs=(fspec, P(axis), P(axis)), check_rep=False)
    def step(f):
        f = dataclasses.replace(f, count=f.count[0])
        f2, moved, lost = _balance(f, block, axis, axis_size, cap,
                                   jnp.bool_(True))
        return (dataclasses.replace(f2, count=f2.count[None]),
                moved[None], lost[None])

    return jax.jit(step)


# ---------------------------------------------------------------------------
# Stage 1: device-side deal
# ---------------------------------------------------------------------------

def make_dist_deal(mesh: Mesh, axis: str, g_spec, cap: int, delta: int,
                   host_axis: str | None = None):
    """Device-side stage 1: jitted triplet flags → rank-mod-ndev deal →
    cumsum-scatter straight into the sharded frontier.

    Replaces the host round-robin deal (host nonzero + python loop + H2D of
    every initial row). Each device evaluates the replicated flag grid,
    keeps the triplets whose rank ≡ its GLOBAL index (mod ndev; on a
    2-level mesh the global index is host·D + device) — the exact rows the
    host deal would have sent it — and scatters them into its local
    frontier shard. Triangles are counted by the same rank-sharing trick
    and hierarchically ``psum``-reduced.

    Returns the UNJITTED shard_map callable
    ``deal(g) -> (frontier, meta)`` with replicated
    ``meta = [n_triangles, total_live, overflow]``.
    """
    dev_size = int(mesh.shape[axis])
    host_size = int(mesh.shape[host_axis]) if host_axis else 1
    ndev = dev_size * host_size
    row_axes = (host_axis, axis) if host_axis else (axis,)
    fspec = _fspec(mesh, row_axes)

    @functools.partial(shard_map, mesh=mesh, in_specs=(g_spec,),
                       out_specs=(fspec, P()), check_rep=False)
    def deal(g):
        with jax.named_scope("repro.seed"):
            me = jax.lax.axis_index(axis)
            if host_axis:
                me = me + dev_size * jax.lax.axis_index(host_axis)
            tri, trip = T.triplet_flags(g, delta)
            flat_tri = tri.reshape(-1)
            flat_trip = trip.reshape(-1)
            n_grid = flat_trip.shape[0]
            # deal triplet RANKS round-robin (the host deal's rows % ndev
            # == d)
            rank = jnp.cumsum(flat_trip.astype(jnp.int32)) - 1
            mine = flat_trip & ((rank % ndev) == me)
            dest, total = E.compaction_dests(mine, cap)
            idx = jnp.zeros((cap,), jnp.int32).at[dest].set(
                jnp.arange(n_grid, dtype=jnp.int32), mode="drop")
            f = T.gather_triplets(g, idx, jnp.minimum(total, cap), cap)
            overflow = _reduce_tiers(jnp.maximum(total - cap, 0), axis,
                                   host_axis)
            # triangles: count my round-robin share, psum to the global total
            trank = jnp.cumsum(flat_tri.astype(jnp.int32)) - 1
            my_tri = (flat_tri & ((trank % ndev) == me)).sum(
                dtype=jnp.int32)
            n_tri = _reduce_tiers(my_tri, axis, host_axis)
            live = _reduce_tiers(f.count, axis, host_axis)
            f = dataclasses.replace(f, count=f.count[None])
            return f, jnp.stack([n_tri, live, overflow])

    return deal


# ---------------------------------------------------------------------------
# Stage 2: the sharded wave superstep
# ---------------------------------------------------------------------------

def dist_wire_bytes(n: int, nw: int, compress: bool) -> tuple[int, int]:
    """Modeled wire size of one balance hop: (bytes per donated row,
    per-round stat overhead per device).

    The SAME formula the sharded driver charges into its per-tier metrics
    and trace events — replay and reality share one accounting. Exact rows
    ship path + blocked (nw uint32 words each) + three int32 ids, plus the
    int32 count and the reverse-permuted neighbor count. The compressed
    cross-host wire ships the bit-packed path (⌈n/8⌉ bytes) + two
    ``ef_quantize``d int8 endpoint ids per row (``blocked``/``l2`` are
    reconstructed receiver-side), plus the int8 mean-load payload, its fp32
    shared scale, and the exact counts.
    """
    if compress:
        return (int(n) + 7) // 8 + 2, 17
    return 8 * int(nw) + 12, 8


def dist_bucket_range(cfg) -> tuple[int, int]:
    """(floor, ceiling) of the sharded superstep's per-device bucket.

    The ceiling is ``local_capacity``; the floor is the bucket of two
    balance blocks (capped at the ceiling), so a device can still donate a
    whole block."""
    ceiling = int(cfg.local_capacity)
    return min(cfg.bucket(2 * int(cfg.balance_block)), ceiling), ceiling


def dist_first_bucket(cfg, rows: int) -> int:
    """The bucket the sharded deal runs at, for at most ``rows`` triplets
    per device."""
    floor, ceiling = dist_bucket_range(cfg)
    return min(max(cfg.bucket(max(int(rows), 1)), floor), ceiling)


def dist_next_bucket(cfg, status: str, cur: int, *, need: int,
                     peak: int) -> int:
    """The sharded driver's bucket policy — one function for the driver
    and the tuner's replay twin (``replay_dist``). A superstep at bucket
    ``cur`` exited with ``status`` (a ``STATUS_NAMES`` name): on GROW the
    busiest device's aborted round needed ``need`` rows, so grow to its
    bucket with ``grow_headroom`` extra doublings; on RUN or SHRINK shrink
    to the bucket of ``peak``, the busiest device's live rows, when that is
    smaller. Floor and ceiling from ``dist_bucket_range``."""
    floor, ceiling = dist_bucket_range(cfg)
    if status == "GROW":
        return min(cfg.bucket(cfg.bucket(max(int(need), 1))
                              << max(int(cfg.grow_headroom), 0)), ceiling)
    if status in ("RUN", "SHRINK"):
        return min(max(cfg.bucket(max(int(peak), 1)), floor), cur)
    return cur


def make_dist_superstep(mesh: Mesh, axis: str, g_spec, cfg: EngineConfig,
                        delta: int, k_max: int, bucket: int):
    """Build the UNJITTED sharded wave superstep at the per-device
    ``bucket`` (at most the ceiling ``local_capacity``).

    One ``shard_map(lax.while_loop)`` program runs up to
    min(k_max, rounds_limit) rounds: the config's ``ExpandOp`` (its flags,
    then its compaction at ``bucket`` rows, as the wave's split path runs
    them), a diffusion-balance step
    every ``balance_every`` rounds on the device ring (``lax.cond``-gated
    so the collectives only run on balance rounds), a cross-host donation
    every ``balance_every × cross_balance_every`` rounds on the host ring
    (2-level meshes only; optionally EF-compressed, with the error-feedback
    residuals carried in the while_loop state), and a per-round
    hierarchical ``psum`` of live counts (device tier, then host tier)
    that is carried into the loop condition — the wave terminates ON DEVICE
    the round the global frontier empties, with no host involvement.

    Per-device bucketing (DESIGN.md §5), the sharded twin of the wave
    engine's GROW/SHRINK exits: after a round's flags the busiest device's
    extension count (``pmax`` over the row tiers) is the rows its
    compaction must hold. Below the ceiling a round that would not fit is
    aborted on every device — no compaction, no balance, no history entry
    — and the superstep exits GROW with that count in the history slot of
    the aborted round, for the host to re-bucket. At the ceiling the round
    runs and drops rows, which the host refuses. Above the floor
    (``dist_bucket_range``) the superstep exits SHRINK
    once no device holds more than a quarter of the bucket (checked where
    a launch of ``rounds_per_launch`` rounds ends).

    Compilation (jit + frontier/counter donation + the cross-request
    program cache) is ``core.plan.DistPlan``'s job; the host driver loop is
    ``enumerate_sharded``.

    Returns ``superstep(g, f, counters, rounds_limit, round_base) ->
    (f', counters', rounds_done, status, need_hist, cyc_hist, live_hist)``
    (``round_base`` = rounds completed by earlier supersteps, so both
    balance cadences run over the global round index)
    where ``need_hist`` (k_max,) is the replicated per-round busiest-device
    extension count (applied rounds, then a GROW abort's), and
    ``cyc_hist`` / ``live_hist`` (ndev, k_max) are the per-device per-round
    cycle counts and live counts (the per-device wave profiles the tuner's
    sharded replay twin consumes; their column sums are the global ones).
    """
    floor, ceiling = dist_bucket_range(cfg)
    cap = int(bucket)
    can_grow = cap < ceiling
    shrink_below = cap // 4 if cap > floor else 0
    block = int(cfg.balance_block)
    every = max(int(cfg.balance_every), 1)
    host_axis = cfg.host_axis
    dev_size = int(mesh.shape[axis])
    host_size = int(mesh.shape[host_axis]) if host_axis else 1
    cross_period = every * max(int(cfg.cross_balance_every), 1)
    compress = bool(cfg.compress_cross_host)
    rpl = max(int(getattr(cfg, "rounds_per_launch", 1)), 1)
    op = E.expand_op(cfg.formulation, cfg.backend)
    row_axes = (host_axis, axis) if host_axis else (axis,)
    fspec = _fspec(mesh, row_axes)
    rspec = fspec.count  # P over the row tiers (per-device outputs)
    pmax = functools.partial(_reduce_tiers, axis=axis, host_axis=host_axis,
                             reduce=jax.lax.pmax)

    def balance(g, f2, gidx, active, ef):
        """Both diffusion tiers of one round (``repro.round.balance``):
        the device ring on the ``balance_every`` cadence, the host ring on
        the cross-host cadence, both over the global round index."""
        moved_i = moved_x = lost = jnp.int32(0)
        with jax.named_scope("repro.round.balance"):
            if dev_size > 1:
                do_bal = active & ((gidx % every) == (every - 1))
                f2, moved_i, lost_i = _balance(f2, block, axis, dev_size,
                                               cap, do_bal)
                lost = lost + lost_i
            if host_size > 1:
                do_x = active & ((gidx % cross_period) == (cross_period - 1))
                f2, moved_x, lost_x, ef = _cross_balance(
                    g, f2, block, host_axis, host_size, cap, do_x,
                    compress, ef)
                lost = lost + lost_x
        return f2, moved_i, moved_x, lost, ef

    def round_(g, f, ef, gidx, active):
        """One round on this device's rows: the op's flags, then — when
        the busiest device's extensions fit the bucket — its compaction
        and the balance step. Returns (f', ef', counter increments, need,
        fits)."""
        flags, n_cyc, n_new = op.flags(g, f, delta)
        need = pmax(n_new.astype(jnp.int32))

        def run(args):
            f, ef = args
            f2, drop = op.compact(g, f, flags, delta, cap)
            f2, moved_i, moved_x, lost, ef = balance(g, f2, gidx, active,
                                                     ef)
            return (f2, ef), jnp.stack([n_cyc, drop + lost, moved_i,
                                        moved_x, lost]).astype(jnp.int32)

        def abort(args):
            return args, jnp.zeros((_N_COUNTERS,), jnp.int32)

        if not can_grow:
            (f2, ef), inc = run((f, ef))
            return f2, ef, inc, need, jnp.bool_(True)
        fits = need <= cap
        (f2, ef), inc = jax.lax.cond(fits, run, abort, (f, ef))
        return f2, ef, inc, need, fits

    def exit_status(grown, f, total):
        """RUN, GROW, or SHRINK once no device holds more than a quarter
        of the bucket (above the floor)."""
        status = jnp.where(grown, jnp.int32(_GROW), jnp.int32(_RUN))
        if shrink_below:
            low = pmax(f.count) <= shrink_below
            status = jnp.where(~grown & (total > 0) & low,
                               jnp.int32(_SHRINK), status)
        return status

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(g_spec, fspec, rspec, P(), P()),
        out_specs=(fspec, rspec, P(), P(), P(), rspec, rspec),
        check_rep=False)
    def superstep(g, f, counters, rounds_limit, round_base):
        f = dataclasses.replace(f, count=f.count[0])
        cnts = counters[0]  # (_N_COUNTERS,) cumulative — see _N_COUNTERS

        def cond(c):
            f, cnts, r, total, status, nh, ch, lh, ef = c
            return (status == _RUN) & (r < rounds_limit) & (total > 0)

        def body(c):
            f, cnts, r, total, status, nh, ch, lh, ef = c
            # cadence over the GLOBAL round index (round_base carries the
            # rounds done by earlier supersteps) — the knob means "every N
            # rounds of the run", not of this dispatch
            f2, ef, inc, need, fits = round_(g, f, ef, round_base + r,
                                             jnp.bool_(True))
            total = _reduce_tiers(f2.count, axis, host_axis)
            nh = nh.at[r].set(need)
            ch = ch.at[r].set(inc[0])
            lh = lh.at[r].set(f2.count)
            return (f2, cnts + inc, r + fits.astype(jnp.int32), total,
                    exit_status(~fits, f2, total), nh, ch, lh, ef)

        def body_multi(c):
            # persistent multi-round twin (DESIGN.md §6.11): one while-loop
            # iteration advances up to ``rpl`` masked rounds — past-budget,
            # dead or post-abort inner rounds select the old state, so the
            # applied rounds are bit-identical to the R=1 body (balance
            # cadence still keyed to the GLOBAL round index
            # round_base + r + i).
            f, cnts, r, total, status, nh, ch, lh, ef = c
            rem = rounds_limit - r

            def inner(i, ic):
                f, cnts, total, nh, ch, lh, ef, applied, grown = ic
                active = (i < rem) & (total > 0) & ~grown
                f2, ef2, inc, need, fits = round_(g, f, ef,
                                                  round_base + r + i, active)
                ok = active & fits
                tot2 = _reduce_tiers(f2.count, axis, host_axis)
                idx = jnp.minimum(r + i, jnp.int32(k_max - 1))
                sel = lambda a, b: jax.tree_util.tree_map(
                    lambda x, y: jnp.where(ok, x, y), a, b)
                nh = nh.at[idx].set(jnp.where(active, need, nh[idx]))
                ch = ch.at[idx].set(jnp.where(ok, inc[0], ch[idx]))
                lh = lh.at[idx].set(jnp.where(ok, f2.count, lh[idx]))
                return (sel(f2, f), jnp.where(ok, cnts + inc, cnts),
                        jnp.where(ok, tot2, total), nh, ch, lh,
                        sel(ef2, ef), applied + ok.astype(jnp.int32),
                        grown | (active & ~fits))

            f, cnts, total, nh, ch, lh, ef, applied, grown = (
                jax.lax.fori_loop(0, rpl, inner,
                                  (f, cnts, total, nh, ch, lh, ef,
                                   jnp.int32(0), jnp.bool_(False))))
            return (f, cnts, r + applied, total,
                    exit_status(grown, f, total), nh, ch, lh, ef)

        zeros = jnp.zeros((k_max,), jnp.int32)
        total0 = _reduce_tiers(f.count, axis, host_axis)
        ef0 = dict(psum_err=jnp.float32(0.0),
                   id_err=jnp.zeros((2, block), jnp.float32))
        f, cnts, r, total, status, nh, ch, lh, ef = jax.lax.while_loop(
            cond, body if rpl <= 1 else body_multi,
            (f, cnts, jnp.int32(0), total0, jnp.int32(_RUN), zeros, zeros,
             zeros, ef0))
        status = jnp.where((status != _GROW) & (total == 0),
                           jnp.int32(_DONE), status)
        f = dataclasses.replace(f, count=f.count[None])
        return f, cnts[None], r, status, nh, ch[None], lh[None]

    return superstep


def make_dist_rebucket(mesh: Mesh, row_axes: tuple[str, ...], bucket: int):
    """The UNJITTED per-shard resize to ``bucket`` rows per device: each
    device keeps its live rows (``frontier.with_capacity`` on its shard),
    under the device scope ``repro.rebucket``. The host calls it between
    supersteps, only to a bucket that holds every device's live rows."""
    fspec = _fspec(mesh, row_axes)

    @functools.partial(shard_map, mesh=mesh, in_specs=(fspec,),
                       out_specs=fspec, check_rep=False)
    def rebucket(f):
        with jax.named_scope("repro.rebucket"):
            return with_capacity(f, bucket)

    return rebucket


# ---------------------------------------------------------------------------
# Host driver
# ---------------------------------------------------------------------------

def enumerate_sharded(g: BitsetGraph, cfg: EngineConfig, *, cache=None,
                      trace=None, progress=None, metrics=None,
                      spans: SpanLog | None = None,
                      rid: str = "") -> EnumerationResult:
    """Count all chordless cycles using every device of ``cfg.mesh`` (the
    CycleService sharded path; cfg validated eagerly to count-only at
    construction). Each device's rounds run the config's ``ExpandOp``.
    With ``cfg.host_axis`` the mesh is 2-level and the superstep runs
    tiered (hierarchical psums, intra/cross balancing, optionally
    EF-compressed cross-host donation).

    The host loop relaunches the sharded superstep until the wave dies or
    the |V|−3 budget runs out — one batched readback per superstep, so host
    syncs are O(iterations / superstep_rounds + bucket transitions) + O(1).
    Every superstep runs at one per-device bucket: the deal at the bucket
    of n·C(Δ, 2) wedges per device (every triplet is a wedge), then, after
    each superstep, the bucket ``dist_next_bucket`` picks from its exit
    (GROW: the aborted round's busiest-device need; RUN/SHRINK: the
    busiest device's live rows), between the floor and the ceiling
    ``local_capacity``; a change resizes every shard in the ``rebucket``
    phase. ``cache`` (a
    ``core.plan.ProgramCache``) memoizes the jitted deal + superstep across
    requests on the same mesh/shape; ``trace`` (a ``tune.telemetry
    .WaveTrace``) records per-dispatch events incl. per-device wave peaks
    and per-tier balance traffic; ``metrics`` (a ``obs.MetricsRegistry``)
    accumulates the ``dist_comm_bytes`` / ``dist_balance_moved`` per-tier
    counters. Every host statement between two programs runs inside one
    host phase of ``spans`` (``seed``, ``superstep``, ``readback``,
    ``rebucket``), as in the wave driver; ``rid`` names the request the
    phases record spans for.
    """
    mesh, axis, host_axis = cfg.mesh, cfg.axis, cfg.host_axis
    dev_size = int(mesh.shape[axis])
    host_size = int(mesh.shape[host_axis]) if host_axis else 1
    ndev = dev_size * host_size
    cap = int(cfg.local_capacity)
    block = int(cfg.balance_block)
    k_max = int(cfg.superstep_rounds)
    every = max(int(cfg.balance_every), 1)
    cross_period = every * max(int(cfg.cross_balance_every), 1)
    delta = max(g.max_degree, 1)
    nw = g.adj_bits.shape[1]
    trace = trace if trace is not None else disabled_trace()
    phase = (spans if spans is not None else SpanLog(enabled=False)).phase

    if cfg.compress_cross_host and host_size > 1 and g.n > 127:
        raise ValueError(
            f"compress_cross_host requires n <= 127 (int8 vertex ids are "
            f"exact there); got n={g.n} — disable compression or split "
            "the graph")

    if g.m == 0:  # edgeless: nothing to deal (flag kernels need neighbors)
        return EnumerationResult(
            n_cycles=0, n_triangles=0, cycle_masks=None, iterations=0,
            history=[dict(step=0, T=0, C=0)], stats=dict(
                trace.finalize(rounds=0), n_cycles=0, n_triangles=0,
                iterations=0, dropped=0, moved=0, lost=0, n_devices=ndev,
                moved_intra=0, moved_cross=0, n_hosts=host_size,
                comm_bytes_intra=0, comm_bytes_cross=0,
                per_device_live=[0] * ndev, superstep_rounds=k_max,
                local_capacity=cap, per_device_peak_rows=[0] * ndev,
                live_rows_sum=0, slot_rows_sum=0, bucket_transitions=0,
                grow_aborts=0),
            trace=trace if trace.enabled else None)

    from .plan import DistPlan, PlanKey, ProgramCache

    row_axes = (host_axis, axis) if host_axis else (axis,)
    # one plan per bucket, kept for the run when no cache is shared
    cache = cache if cache is not None else ProgramCache()
    with phase("seed", rid):
        # the deal's bucket: every triplet is a wedge and a graph has at
        # most n·C(Δ, 2) of them, so a device's share bounds its rows
        wedges = g.n * (delta * (delta - 1) // 2)
        bucket = dist_first_bucket(cfg, -(-wedges // ndev))
        rep = jax.sharding.NamedSharding(mesh, P())
        g = jax.tree_util.tree_map(lambda x: jax.device_put(x, rep), g)
        g_spec = jax.tree_util.tree_map(lambda _: P(), g)

        def _plan(tag, rows, builder, donate=()):
            key = PlanKey(kind="dist", bucket=rows, nw=nw, cyc_rows=0,
                          delta=delta, store=False,
                          formulation=cfg.formulation, backend=cfg.backend,
                          k_max=k_max, batch=ndev, donate=bool(donate),
                          extra=(tag, mesh, axis, host_axis,
                                 cfg.balance_block, cfg.balance_every,
                                 cfg.cross_balance_every,
                                 bool(cfg.compress_cross_host),
                                 int(cfg.local_capacity), g.n, g.m))
            return cache.get_or_build(
                key, lambda: DistPlan(key, builder(), donate_argnums=donate))

        def step_plan(rows):
            return _plan("step", rows,
                         lambda: make_dist_superstep(mesh, axis, g_spec, cfg,
                                                     delta, k_max, rows),
                         donate=(1, 2))

        def rebucket_plan(frm, to):
            return _plan(("rebucket", frm), to,
                         lambda: make_dist_rebucket(mesh, row_axes, to))

        deal = _plan("deal", bucket,
                     lambda: make_dist_deal(mesh, axis, g_spec, bucket,
                                            delta, host_axis=host_axis))
        fresh = deal.n_calls == 0
        trace.tic()
        fshard, meta = deal(g)
    with phase("readback", rid):
        n_tri, live, overflow = (int(x) for x in jax.device_get(meta))
        trace.sync()
        trace.d2h()
        trace.dispatch(kind="deal", bucket=bucket, cyc_cap=0, budget=0,
                       rounds=0, status="RUN", enter_count=live,
                       exit_count=live, t_ms=trace.toc_ms(), fresh=fresh,
                       plan_key=str(deal.key), ndev=ndev)
        if overflow:
            raise ValueError(
                f"initial triplets overflow the deal's bucket {bucket} by "
                f"{overflow} rows across {ndev} devices; raise "
                "cfg.local_capacity")

        # modeled per-hop wire bytes (the same formula replay_dist charges)
        row_b, stat_b = dist_wire_bytes(g.n, nw, False)
        xrow_b, xstat_b = dist_wire_bytes(g.n, nw,
                                          bool(cfg.compress_cross_host))

        history = [dict(step=0, T=live, C=n_tri)]
        n_cycles = n_tri
        counters = jax.device_put(
            np.zeros((ndev, _N_COUNTERS), np.int32),
            jax.sharding.NamedSharding(mesh, _fspec(mesh, row_axes).count))
    limit = cfg.max_iters if cfg.max_iters is not None else max(g.n - 3, 0)
    it = 0
    next_ckpt = cfg.checkpoint_every or 0
    prev_moved_i = prev_moved_x = prev_lost = 0
    bytes_intra = bytes_cross = 0
    peak_rows = np.zeros(ndev, np.int64)   # per device, over all rounds
    live_rows_sum = 0                      # Σ over rounds and devices
    slot_rows_sum = 0                      # Σ of the buckets they ran at
    grow_aborts = 0
    while it < limit and live > 0:
        with phase("superstep", rid):
            k = min(k_max, limit - it)
            step = step_plan(bucket)
            fresh = step.n_calls == 0
            trace.tic()
            fshard, counters, r, status, nh, ch, lh = step(
                g, fshard, counters, jnp.int32(k), jnp.int32(it))
        with phase("readback", rid):
            fetched = (r, status, nh, ch, lh, counters)
            r_h, status_h, nh_h, ch_h, lh_h, c_h = jax.device_get(fetched)
            trace.sync()
            trace.d2h(len(fetched))
            r_h = int(r_h)
            status_h = STATUS_NAMES[int(status_h)]
            if r_h == 0 and status_h != "GROW":
                break  # defensive: cond refused on entry (live stale)
            need = int(nh_h[r_h]) if status_h == "GROW" else 0
            grow_aborts += status_h == "GROW"
            ch_round = np.asarray(ch_h)[:, :r_h].sum(axis=0)
            lh_now = np.asarray(lh_h)[:, :r_h]
            th_h = lh_now.sum(axis=0)              # global live per round
            peak_dev = (lh_now.max(axis=1) if r_h
                        else np.zeros(ndev, np.int64))
            peak_rows = np.maximum(peak_rows, peak_dev)
            live_rows_sum += int(lh_now.sum())
            slot_rows_sum += r_h * ndev * bucket
            c_now = np.asarray(c_h)
            dropped_now = int(c_now[:, 1].sum())
            if dropped_now:
                # a dropped row means every later count is silently wrong —
                # fail loudly (the deal-overflow ValueError's stage-2 twin)
                raise RuntimeError(
                    f"sharded frontier overflow: {dropped_now} live rows "
                    f"dropped by compaction at local_capacity={cap} "
                    f"(per-device peaks {[int(x) for x in peak_dev]}); "
                    "raise cfg.local_capacity — a count computed past a "
                    "drop would be silently wrong")
            moved_i_d = int(c_now[:, 2].sum()) - prev_moved_i
            moved_x_d = int(c_now[:, 3].sum()) - prev_moved_x
            lost_d = int(c_now[:, 4].sum()) - prev_lost
            prev_moved_i += moved_i_d
            prev_moved_x += moved_x_d
            prev_lost += lost_d
            # per-tier balance wire traffic of this dispatch: every device
            # sends one block-sized hop on each balance round of its tier
            # (sends are unconditional — static shapes — so cadence, not
            # ``give``, sets the traffic)
            n_bal = sum(1 for i in range(it, it + r_h)
                        if dev_size > 1 and i % every == every - 1)
            n_crs = sum(1 for i in range(it, it + r_h)
                        if host_size > 1 and i % cross_period
                        == cross_period - 1)
            b_intra = n_bal * ndev * (block * row_b + stat_b)
            b_cross = n_crs * ndev * (block * xrow_b + xstat_b)
            bytes_intra += b_intra
            bytes_cross += b_cross
            if metrics is not None:
                if b_intra:
                    metrics.counter("dist_comm_bytes").inc(b_intra,
                                                           tier="intra")
                if b_cross:
                    metrics.counter("dist_comm_bytes").inc(b_cross,
                                                           tier="cross")
                if moved_i_d:
                    metrics.counter("dist_balance_moved").inc(
                        moved_i_d, tier="intra")
                if moved_x_d:
                    metrics.counter("dist_balance_moved").inc(
                        moved_x_d, tier="cross")
            exit_live = int(th_h[-1]) if r_h else live
            trace.dispatch(
                kind="dist", bucket=bucket, cyc_cap=0, budget=k,
                rounds=r_h, status=status_h, t_sizes=th_h,
                c_counts=ch_round, enter_count=live, exit_count=exit_live,
                pending_new=need, t_ms=trace.toc_ms(), fresh=fresh,
                plan_key=str(step.key), ndev=ndev,
                rounds_per_launch=max(int(cfg.rounds_per_launch), 1),
                per_device=tuple(int(x) for x in peak_dev),
                dev_new=np.asarray(nh_h)[:r_h], dev_live=lh_now.max(axis=0),
                moved=moved_i_d + moved_x_d, lost=lost_d,
                moved_cross=moved_x_d,
                comm_bytes_intra=b_intra, comm_bytes_cross=b_cross)
            for i in range(r_h):
                n_cycles += int(ch_round[i])
                rec = dict(step=it + i + 1, T=int(th_h[i]), C=n_cycles)
                history.append(rec)
                if progress:
                    progress(rec)
            it += r_h
            live = exit_live
        if it < limit and live > 0:
            new_bucket = dist_next_bucket(
                cfg, status_h, bucket, need=need,
                peak=int(lh_now[:, -1].max()) if r_h else 0)
            if status_h == "GROW" and new_bucket <= bucket:
                raise RuntimeError(
                    f"sharded superstep at bucket {bucket} aborted a round "
                    f"that needs {need} rows per device, and no larger "
                    f"bucket is left under local_capacity={cap}")
            if new_bucket != bucket:
                with phase("rebucket", rid):
                    fshard = rebucket_plan(bucket, new_bucket)(fshard)
                    trace.transition()
                bucket = new_bucket
        if cfg.checkpoint_every and it >= next_ckpt:
            from .. import checkpoint as ckpt
            ckpt.save_pytree(cfg.checkpoint_dir, it,
                             dict(frontier=fshard, counters=counters))
            next_ckpt = it + cfg.checkpoint_every

    with phase("readback", rid):
        fetched = (counters, fshard.count)
        c_h, live_h = jax.device_get(fetched)
        trace.sync()
        trace.d2h(len(fetched))
    c = np.asarray(c_h)
    assert int(c[:, 0].sum()) == n_cycles - n_tri, \
        "device cycle counter disagrees with history accumulation"
    stats = trace.finalize(rounds=it)
    stats.update(
        n_cycles=n_cycles, n_triangles=n_tri, iterations=it,
        dropped=int(c[:, 1].sum()),
        moved=int(c[:, 2].sum()) + int(c[:, 3].sum()),
        moved_intra=int(c[:, 2].sum()), moved_cross=int(c[:, 3].sum()),
        lost=int(c[:, 4].sum()), n_devices=ndev, n_hosts=host_size,
        comm_bytes_intra=bytes_intra, comm_bytes_cross=bytes_cross,
        per_device_live=[int(x) for x in np.asarray(live_h)],
        superstep_rounds=k_max, local_capacity=cap,
        per_device_peak_rows=[int(x) for x in peak_rows],
        live_rows_sum=live_rows_sum, slot_rows_sum=slot_rows_sum,
        bucket_transitions=trace.n_bucket_transitions,
        grow_aborts=grow_aborts)
    return EnumerationResult(
        n_cycles=n_cycles, n_triangles=n_tri, cycle_masks=None,
        iterations=it, history=history, stats=stats,
        trace=trace if trace.enabled else None)


def enumerate_distributed(g: BitsetGraph, mesh: Mesh, axis: str = "data",
                          cfg: EngineConfig | None = None,
                          max_iters: int | None = None):
    """Compat wrapper: count all chordless cycles using every device on
    ``axis``. Routes through the default ``CycleService`` (so the jitted
    deal + superstep programs are cached across calls on the same mesh).

    Returns dict(n_cycles, n_triangles, iterations, dropped, moved, lost,
    per_device_live, ...) — ``EnumerationResult.stats`` of the run.
    """
    from .service import default_service
    ecfg = as_engine_config(mesh, axis, cfg, max_iters)
    res = default_service().enumerate(g, config=ecfg)
    return dict(res.stats)
