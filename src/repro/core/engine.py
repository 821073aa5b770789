"""Host process (paper Algorithm 4) — drives stage 1 + repeated stage 2.

The paper relaunches the expansion kernel a fixed |V|−3 times with a
double-buffered T/T' to avoid device→host convergence checks over PCIe.
Two engines reproduce that trade-off (DESIGN.md §6.4):

* ``wave`` (default) — device-resident superstep: one jitted program runs up
  to K expansion rounds in a ``lax.while_loop`` at a fixed capacity bucket,
  fusing flag computation, popcount cycle counting, cycle gathering into a
  preallocated device CycleBuffer, and prefix-sum compaction.  The host is
  re-entered only on *bucket transitions*: frontier outgrew its bucket,
  cycle buffer filled, wave died, or the |V|−3 round budget ran out.  Host
  syncs drop from O(iterations) to O(bucket transitions).
* ``host`` — legacy per-round dispatch (kept as the A/B baseline and for
  step-debugging), with all per-round scalars batched into ONE readback per
  round (the `count == 0` probe and the `dropped` assert ride the next
  round's fetch instead of blocking their own).

Modes:
  * store=True  — returns every chordless cycle as a vertex bitmap (the
                  paper's solution matrix S).
  * store=False — count-only (the paper's Grid 8×10 footnote mode).
Backends: 'jnp' (pure JAX) or 'pallas' (kernels/: compiled on a TPU,
interpreted elsewhere).
Formulations: 'slot' (paper-faithful) or 'bitword' (TPU-native).

Layering (DESIGN.md §"Service layer"): this module holds the device
ALGORITHM (``wave_superstep``, the legacy host loop) and ``EngineConfig``;
``core.plan`` owns compilation (jit + donation + the cross-graph program
cache + batch vmap); ``core.service`` owns the host driver loop and the
public session API (``CycleService``). ``enumerate_chordless_cycles`` is a
compat wrapper over the module-level default service.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable

import numpy as np
import jax
import jax.numpy as jnp

from .bitset_graph import BitsetGraph
from . import expand as E
from . import triplets as T
from .frontier import CycleBuffer, Frontier
from ..tune.telemetry import STATUSES, WaveTrace, disabled_trace


def _bucket(c: int, *, growth_bits: int = 1) -> int:
    """Round capacity up to a power-of-2 bucket (the paper's T/T' double
    buffer becomes a small family of jit shapes). growth_bits=2 (×4 buckets)
    was tried for §Perf engine hillclimb iter 4: cold time −18% (half the
    recompiles) but WARM time +50% (dead-row work) — refuted for
    steady-state serving, kept as a knob for one-shot runs."""
    bits = max(4, math.ceil(math.log2(max(c, 1))))
    return 1 << (-(-bits // growth_bits) * growth_bits)


FORMULATIONS = ("slot", "bitword")
BACKENDS = ("jnp", "pallas")
ENGINES = ("wave", "host")


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """All engine knobs in one place (backend × formulation × bucketing),
    including the sharded-path knobs that used to live in ``DistEnumConfig``
    (set ``mesh``/``axis`` to route enumeration through shard_map).

    ``superstep_rounds`` (K) bounds rounds per wave dispatch — it is the
    history-buffer length, NOT a correctness bound: the loop exits early on
    any bucket transition and the host relaunches. The SAME knob budgets
    the sharded wave superstep (``core/distributed.py``), whose loop exits
    only on budget exhaustion or device-detected termination.
    ``cycle_buffer_rows`` sizes the device-resident cycle ring; a single
    round producing more cycles than the whole buffer triggers a host-side
    buffer regrow.

    Validation is EAGER: unknown ``formulation``/``backend``/``engine`` and
    cross-field mismatches raise ``ValueError`` here, at construction, with
    the allowed values listed — not deep inside tracing."""
    store: bool = True
    formulation: str = "slot"      # 'slot' | 'bitword'
    backend: str = "jnp"           # 'jnp' | 'pallas'
    engine: str = "wave"           # 'wave' | 'host'
    growth_bits: int = 1           # bucket granularity (see _bucket)
    superstep_rounds: int = 8      # K — max device rounds per dispatch
    # (K=8 measured best warm time on CPU interpret; raise on real
    # accelerators where dispatch latency dominates — §Perf hillclimb)
    cycle_buffer_rows: int = 4096  # CycleBuffer capacity (store mode)
    grow_headroom: int = 1         # extra ×2 buckets granted on GROW — an
    # aborted GROW round re-runs its expand at the new bucket, so headroom
    # trades dead-row work for fewer wasted peak-size rounds
    fused_round: bool = True       # one-pass round (DESIGN.md §6.8): jnp
    # swaps the cap·Δ scatter compaction for the gather formulation, pallas
    # collapses the whole guarded round into ONE kernel dispatch
    # (two-phase scatter). Bit-identical output; tunable (TUNED_KNOBS).
    rounds_per_launch: int = 1     # R — rounds per kernel launch
    # (DESIGN.md §6.11): the superstep body advances up to R complete
    # guarded rounds per while-iteration through the persistent wave
    # kernel (fused pallas) or its fori_loop jnp twin, so a K-round wave
    # costs ⌈K/R⌉ launches and frontier HBM round-trips instead of K.
    # The trade: a launch always runs R rounds' grid steps, so rounds
    # after a guard trip / wave death are wasted identity copy-throughs.
    # Bit-identical output for any R; tunable (TUNED_KNOBS).
    max_iters: int | None = None
    donate: bool = True            # donate superstep frontier/CycleBuffer
    # buffers to the jitted program (no-copy in-place aliasing; halves peak
    # device memory for the two big (cap, nw) operands)

    # --- sharded path (formerly DistEnumConfig; DESIGN.md §5) -------------
    mesh: object | None = None     # jax.sharding.Mesh — non-None selects
    axis: str = "data"             # the shard_map path in core/distributed
    local_capacity: int = 1 << 14  # frontier rows per device
    balance_block: int = 256       # diffusion donation block (rows)
    balance_every: int = 1         # rounds between balance steps
    checkpoint_every: int = 0      # 0 = off
    checkpoint_dir: str = "/tmp/repro_enum_ckpt"

    # --- 2-level (host, device) mesh (DESIGN.md §7) -----------------------
    host_axis: str | None = None   # outer mesh axis; non-None selects the
    # hierarchical superstep: frontier rows shard over (host_axis, axis),
    # termination psums nest (device tier, then host tier), and balancing
    # becomes tiered — intra-host diffusion on the device ring every
    # `balance_every` rounds, cross-host donation on the host ring only
    # every `cross_balance_every`-th balance round.
    cross_balance_every: int = 4   # balance rounds between cross-host hops
    compress_cross_host: bool = False  # EF-int8 compressed cross-host wire
    # (bit-packed paths + quantized endpoint ids; blocked/l2 are
    # reconstructed receiver-side from the chordless-path invariant).
    # Requires n <= 127 so vertex ids are exact in int8 (checked at
    # enumerate time, where the graph is known).

    def __post_init__(self):
        if self.formulation not in FORMULATIONS:
            raise ValueError(
                f"unknown formulation {self.formulation!r}; allowed: "
                f"{FORMULATIONS}")
        if self.backend not in BACKENDS:
            raise ValueError(
                f"unknown backend {self.backend!r}; allowed: {BACKENDS}")
        if self.engine not in ENGINES:
            raise ValueError(
                f"unknown engine {self.engine!r}; allowed: {ENGINES}")
        for field in ("growth_bits", "superstep_rounds", "cycle_buffer_rows",
                      "rounds_per_launch", "local_capacity", "balance_block",
                      "balance_every", "cross_balance_every"):
            if getattr(self, field) < 1:
                raise ValueError(f"{field} must be >= 1, got "
                                 f"{getattr(self, field)}")
        if self.grow_headroom < 0:
            raise ValueError(
                f"grow_headroom must be >= 0, got {self.grow_headroom}")
        if self.balance_block > self.local_capacity:
            raise ValueError(
                f"balance_block={self.balance_block} exceeds "
                f"local_capacity={self.local_capacity}: a donation block "
                "must fit inside one device's frontier shard")
        if self.mesh is not None:
            # the shard_map path is slot/jnp/count-only (DESIGN.md §5);
            # anything else would fail deep inside shard_map tracing.
            bad = []
            if self.formulation != "slot":
                bad.append(f"formulation={self.formulation!r} (allowed: "
                           f"'slot')")
            if self.backend != "jnp":
                bad.append(f"backend={self.backend!r} (allowed: 'jnp')")
            if self.store:
                bad.append("store=True (allowed: False — counting is the "
                           "scalable output)")
            if bad:
                raise ValueError(
                    "mesh-sharded enumeration only supports the "
                    "slot/jnp/count-only combination; got "
                    + "; ".join(bad))
            if self.host_axis is not None:
                if self.host_axis == self.axis:
                    raise ValueError(
                        f"host_axis and axis must differ, both are "
                        f"{self.axis!r}")
                missing = [a for a in (self.host_axis, self.axis)
                           if a not in self.mesh.shape]
                if missing:
                    raise ValueError(
                        f"mesh axes {missing} not in mesh "
                        f"{dict(self.mesh.shape)}; a 2-level config needs "
                        "both host_axis and axis on the mesh")
        elif self.host_axis is not None:
            raise ValueError("host_axis requires a mesh (2-level sharding "
                             "is a property of the sharded path)")

    def bucket(self, c: int) -> int:
        return _bucket(c, growth_bits=self.growth_bits)


def round_path(cfg: EngineConfig, *, cap: int, nw: int, n: int,
               n_neighbors: int, cyc_cap: int) -> str:
    """Which path a superstep's rounds take at this bucket: 'fused' (each
    round inside a fused pallas kernel — one per round, or one persistent
    launch per ``rounds_per_launch`` rounds) or 'split' (flag kernel plus
    XLA compaction). The static-shape rule ``core.expand`` applies while
    tracing: a launch too large for the persistent kernel loops over
    single-round fused kernels, so the single-round budget decides."""
    from ..kernels.fused_round import fits_vmem
    if not (cfg.fused_round and cfg.backend == "pallas"):
        return "split"
    fits = fits_vmem(cap=cap, nw=nw, n=n, n_neighbors=n_neighbors,
                     cyc_cap=cyc_cap, formulation=cfg.formulation,
                     store=cfg.store, persistent=False)
    return "fused" if fits else "split"


@dataclasses.dataclass
class EnumerationResult:
    n_cycles: int                 # all chordless cycles (incl. triangles)
    n_triangles: int
    cycle_masks: np.ndarray | None  # (n_cycles, nw) uint32, or None if count-only
    iterations: int
    history: list[dict]           # per-iteration |T|, |C| (paper Fig. 4)
    stats: dict | None = None     # dispatch / host-sync accounting
    trace: WaveTrace | None = None  # structured per-dispatch telemetry
    # (repro.tune; populated only when recording was enabled for the run)

    def cycles_as_sets(self, n: int) -> list[frozenset[int]]:
        from .bitset_graph import unpack_bits
        assert self.cycle_masks is not None
        dense = unpack_bits(self.cycle_masks, n)
        return [frozenset(np.flatnonzero(r)) for r in dense]


# ---------------------------------------------------------------------------
# Wave engine (device-resident superstep)
# ---------------------------------------------------------------------------

# superstep exit codes; tune.telemetry.STATUSES is the single source of the
# name vocabulary (code i ↔ STATUSES[i]; DESIGN.md §6.6)
_RUN, _DONE, _GROW, _DRAIN, _SHRINK = range(len(STATUSES))
STATUS_NAMES = dict(enumerate(STATUSES))


def wave_superstep(g: BitsetGraph, f: Frontier, buf: CycleBuffer,
                   rounds_limit: jnp.ndarray, *, delta: int, store: bool,
                   formulation: str, backend: str, k_max: int,
                   fused: bool = False, rounds_per_launch: int = 1):
    """Run up to min(k_max, rounds_limit) fused rounds fully on device.

    UNJITTED device algorithm — compilation (jit + buffer donation + the
    cross-graph program cache + vmap over a graph batch axis) is owned by
    ``core.plan``; execution (the host driver loop) by ``core.service``.
    The round body programs against the ``ExpandOp`` registry
    (DESIGN.md §6.7), whose ops are batch-transparent on every backend —
    ``jax.vmap`` of this function is the batched superstep.

    ``rounds_per_launch`` (R, DESIGN.md §6.11) sets how many complete
    guarded rounds each while-iteration advances as ONE traced unit — the
    persistent wave kernel on fused pallas ops, the ``fori_loop`` jnp twin
    elsewhere — so a K-round wave costs ⌈K/R⌉ kernel launches and frontier
    HBM round-trips instead of K. Results are bit-identical for any R;
    with R>1 the decay (SHRINK) exit is only evaluated at launch
    boundaries, which changes dispatch accounting but no history entry.

    Returns (f', buf', rounds_done, status, t_hist, c_hist, pending_new,
    pending_cyc). ``pending_*`` carry the aborted round's exact sizes so the
    host can pick the next bucket without an extra counting dispatch."""
    op = E.expand_op(formulation, backend)
    cap = f.capacity
    # decay exit: once the wave shrinks well below the bucket, dead-row work
    # dominates — hand back to the host to re-bucket DOWN (shapes are static
    # inside the loop, so shrinking cannot happen here).
    shrink_below = cap // 4 if cap > 16 else 0
    R = int(rounds_per_launch)

    def cond(c):
        f, buf, r, status, th, ch, pn, pc = c
        return (status == _RUN) & (r < rounds_limit) & (f.count > 0)

    def body(c):
        f, buf, r, status, th, ch, pn, pc = c
        f2, buf2, n_cyc, n_new, ok_f, ok_c = E.expand_count_compact(
            g, f, buf, delta=delta, store=store, op=op, fused=fused)
        ok = ok_f & ok_c
        th = th.at[r].set(jnp.where(ok, n_new, 0))
        ch = ch.at[r].set(jnp.where(ok, n_cyc, 0))
        r2 = jnp.where(ok, r + 1, r).astype(jnp.int32)
        shrink = ok & (n_new > 0) & (n_new <= shrink_below)
        status2 = jnp.where(ok,
                            jnp.where(shrink, jnp.int32(_SHRINK),
                                      jnp.int32(_RUN)),
                            jnp.where(ok_f, jnp.int32(_DRAIN),
                                      jnp.int32(_GROW)))
        pn2 = jnp.where(ok, jnp.int32(0), n_new).astype(jnp.int32)
        pc2 = jnp.where(ok, jnp.int32(0), n_cyc).astype(jnp.int32)
        return f2, buf2, r2, status2, th, ch, pn2, pc2

    def body_multi(c):
        f, buf, r, status, th, ch, pn, pc = c
        rem = (rounds_limit - r).astype(jnp.int32)
        f2, buf2, ch_r, nh_r, done, ok_f, ok_c = E.expand_count_compact_multi(
            g, f, buf, delta=delta, store=store, rounds=R, op=op,
            fused=fused, rlimit=rem)
        tripped = ~(ok_f & ok_c)
        # histories hold APPLIED rounds only; the (k_max + R - 1) padding
        # keeps the R-wide window in bounds so the update never clamps.
        mask = jnp.arange(R, dtype=jnp.int32) < done
        th = jax.lax.dynamic_update_slice(th, jnp.where(mask, nh_r, 0), (r,))
        ch = jax.lax.dynamic_update_slice(ch, jnp.where(mask, ch_r, 0), (r,))
        r2 = (r + done).astype(jnp.int32)
        cnt = f2.count
        shrink = ~tripped & (cnt > 0) & (cnt <= shrink_below)
        status2 = jnp.where(tripped,
                            jnp.where(ok_f, jnp.int32(_DRAIN),
                                      jnp.int32(_GROW)),
                            jnp.where(shrink, jnp.int32(_SHRINK),
                                      jnp.int32(_RUN)))
        # on a trip the pending overflow sits at history index ``done``
        pidx = jnp.clip(done, 0, R - 1)
        pn2 = jnp.where(tripped, nh_r[pidx], 0).astype(jnp.int32)
        pc2 = jnp.where(tripped, ch_r[pidx], 0).astype(jnp.int32)
        return f2, buf2, r2, status2, th, ch, pn2, pc2

    hist_len = k_max if R <= 1 else k_max + R - 1
    init = (f, buf, jnp.int32(0), jnp.int32(_RUN),
            jnp.zeros((hist_len,), jnp.int32),
            jnp.zeros((hist_len,), jnp.int32),
            jnp.int32(0), jnp.int32(0))
    f, buf, r, status, th, ch, pn, pc = jax.lax.while_loop(
        cond, body if R <= 1 else body_multi, init)
    th, ch = th[:k_max], ch[:k_max]
    status = jnp.where(((status == _RUN) | (status == _SHRINK))
                       & (f.count == 0), jnp.int32(_DONE), status)
    return f, buf, r, status, th, ch, pn, pc


# ---------------------------------------------------------------------------
# Legacy host-driven engine (per-round dispatch, batched readbacks)
# ---------------------------------------------------------------------------

def _enumerate_host(g: BitsetGraph, cfg: EngineConfig,
                    progress: Callable[[dict], None] | None,
                    trace: WaveTrace | None = None) -> EnumerationResult:
    op = E.expand_op(cfg.formulation, cfg.backend)
    if cfg.backend == "pallas":
        from ..kernels import ops as kops
        trip_flags = kops.triplet_flags
        bitword_count = kops.bitword_flags_count
    else:
        trip_flags = T.triplet_flags
        bitword_count = E.bitword_flags_count

    store, formulation = cfg.store, cfg.formulation
    delta = max(g.max_degree, 1)
    frontier, tri_masks, n_tri = T.initial_frontier(
        g, bucket=cfg.bucket, flags_fn=trip_flags)

    trace = trace if trace is not None else disabled_trace()
    cycles: list[np.ndarray] = [tri_masks] if store else []
    n_cycles = n_tri
    cnt = int(frontier.count)
    trace.sync()
    history = [dict(step=0, T=cnt, C=n_tri)]
    limit = cfg.max_iters if cfg.max_iters is not None else max(g.n - 3, 0)

    # the previous round's `dropped` scalar rides the NEXT round's readback
    # (it is provably 0 — out_cap is sized from the exact n_new — so nothing
    # downstream ever waits on it).
    prev_dropped = None
    it = 0
    while it < limit and cnt > 0:
        it += 1
        cap_in, cnt_in = frontier.capacity, cnt
        trace.tic()

        if formulation == "bitword" and not store:
            # fast path (§Perf engine hillclimb): popcount-only cycle
            # counting, exact output sizing, ONE readback per round.
            ext_w, n_cyc_j, n_new_j = bitword_count(g, frontier)
            trace.launch()
            fetch = (n_cyc_j, n_new_j) + (
                () if prev_dropped is None else (prev_dropped,))
            got = jax.device_get(fetch)
            trace.sync()
            n_cyc, n_new = int(got[0]), int(got[1])
            if prev_dropped is not None:
                assert int(got[2]) == 0
            n_cycles += n_cyc
            frontier, prev_dropped = E.bitword_compact(
                g, frontier, ext_w, delta, cfg.bucket(max(n_new, 1)))
            trace.launch()
            cnt = n_new
            trace.dispatch(
                kind="round", bucket=cap_in, cyc_cap=0, budget=1, rounds=1,
                status="DONE" if n_new == 0 else "RUN", t_sizes=(n_new,),
                c_counts=(n_cyc,), enter_count=cnt_in, exit_count=n_new,
                t_ms=trace.toc_ms(), launches=0)
            rec = dict(step=it, T=n_new, C=n_cycles)
            history.append(rec)
            if progress:
                progress(rec)
            continue

        flags, n_cyc_j, n_new_j = op.flags(g, frontier, delta)
        if formulation == "bitword":
            close_w, ext_w = flags
            cand_v = E.bitword_to_slots(ext_w, delta)
            is_ext = cand_v >= 0
            ccand = E.bitword_to_slots(close_w, delta)
            cyc_src, cyc_flags = ccand, ccand >= 0
        else:
            cand_v, is_cyc, is_ext = flags
            cyc_src, cyc_flags = cand_v, is_cyc
        trace.launch()
        fetch = (n_cyc_j, n_new_j) + (
            () if prev_dropped is None else (prev_dropped,))
        got = jax.device_get(fetch)
        trace.sync()
        n_cyc, n_new = int(got[0]), int(got[1])
        if prev_dropped is not None:
            assert int(got[2]) == 0

        if store and n_cyc:
            masks, _ = E.gather_cycles(frontier, cyc_src, cyc_flags,
                                       cfg.bucket(n_cyc))
            cycles.append(np.asarray(masks)[:n_cyc])
            trace.launch()
            trace.sync()
        n_cycles += n_cyc

        frontier, prev_dropped = E.compact_extensions(
            g, frontier, cand_v, is_ext, cfg.bucket(max(n_new, 1)))
        trace.launch()
        cnt = n_new
        trace.dispatch(
            kind="round", bucket=cap_in, cyc_cap=0, budget=1, rounds=1,
            status="DONE" if n_new == 0 else "RUN", t_sizes=(n_new,),
            c_counts=(n_cyc,), enter_count=cnt_in, exit_count=n_new,
            cyc_fill=n_cyc, t_ms=trace.toc_ms(), launches=0)
        rec = dict(step=it, T=n_new, C=n_cycles)
        history.append(rec)
        if progress:
            progress(rec)

    if prev_dropped is not None:
        assert int(jax.device_get(prev_dropped)) == 0
        trace.sync()

    cycle_masks = None
    if store:
        nw = g.adj_bits.shape[1]
        cycle_masks = (np.concatenate(cycles, axis=0) if cycles
                       else np.zeros((0, nw), np.uint32))
    return EnumerationResult(
        n_cycles=n_cycles, n_triangles=n_tri, cycle_masks=cycle_masks,
        iterations=it, history=history, stats=trace.finalize(rounds=it),
        trace=trace if trace.enabled else None)


def enumerate_chordless_cycles(
    g: BitsetGraph,
    *,
    store: bool = True,
    formulation: str = "slot",
    backend: str = "jnp",
    engine: str = "wave",
    max_iters: int | None = None,
    progress: Callable[[dict], None] | None = None,
    config: EngineConfig | None = None,
) -> EnumerationResult:
    """Enumerate (or count) all chordless cycles of ``g``.

    Thin compat wrapper over the module-level default ``CycleService``
    (core/service.py) — the session API is the primary surface; this keeps
    one-shot calls working AND warm (they share the default service's
    program cache). ``config`` overrides the individual keyword knobs."""
    from .service import default_service
    cfg = config if config is not None else EngineConfig(
        store=store, formulation=formulation, backend=backend, engine=engine,
        max_iters=max_iters)
    return default_service().enumerate(g, config=cfg, progress=progress)
