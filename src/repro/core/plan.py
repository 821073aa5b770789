"""Plan layer — the *compile* half of the plan/execute split (DESIGN.md
§"Service layer").

The paper amortizes ONE kernel build over |V|−3 expansion launches; the JAX
analogue is amortizing one trace+compile of the wave superstep over every
same-shaped request a service ever sees. This module owns that amortization:

* ``PlanKey``    — the cache key: (bucket, nw, cycle-ring rows, Δ, store,
                   formulation, backend, K, batch). One key ↔ one shape ↔
                   exactly one trace.
* ``WavePlan``   — a compiled superstep: ``jax.jit`` with the frontier and
                   CycleBuffer arguments DONATED (``donate_argnums=(1, 2)``)
                   so the two big (cap, nw) operands are updated in place —
                   ~2× lower peak device memory than copy-out. A Python-side
                   ``n_traces`` counter increments only while tracing, so a
                   warm cache is *observable*: repeated same-bucket calls
                   must leave it untouched.
* ``DistPlan``   — the sharded twin: ``jax.jit`` of a
                   ``core.distributed`` shard_map program (the device-side
                   deal or the sharded wave superstep) with the sharded
                   frontier and counter arguments donated, plus the same
                   ``n_traces`` retrace observer. ``PlanKey(kind='dist')``
                   keys them in the same cache the wave path warms.
* ``RecyclePlan`` — the recyclable-batch drain/admit merge (DESIGN.md
                   §6.9): one jitted masked-select that retires finished
                   lanes and seats freshly seeded same-class requests into
                   them IN PLACE (graph pytree, frontier, CycleBuffer all
                   donated). Fixed shapes regardless of how many lanes a
                   boundary touches — one compiled program per pool shape,
                   so continuous admission never retraces.
* ``ProgramCache`` — the per-service LRU of plans with hit/miss/eviction
                   counters (``CycleService.stats``); ``max_plans`` bounds
                   long-lived services. Distinct services deliberately
                   do NOT share plans: a fresh service models the old
                   rebuild-per-call world and is what the serving benchmark
                   measures against.
* ``pad_graph`` / ``batch_graphs`` — the batch padding rules: graphs are
                   padded to the batch maxima (n→n_pad, m→m_pad, Δ→Δ_pad,
                   labels extended bijectively, padding vertices isolated)
                   so a whole batch is ONE stacked pytree the superstep can
                   be vmapped over.
"""
from __future__ import annotations

import dataclasses
from collections import OrderedDict

import numpy as np
import jax
import jax.numpy as jnp

from .bitset_graph import BitsetGraph, n_words_for, pack_bits
from . import engine as _engine
from . import expand as _expand


@dataclasses.dataclass(frozen=True)
class PlanKey:
    """Identity of one compiled program. ``batch=0`` means unbatched;
    ``batch=B`` is the vmapped multi-graph superstep (for ``kind='dist'``
    it carries the device count). ``extra`` carries kind-specific statics
    (the dist programs put their tag, the mesh and its axes, the balance
    knobs, ``local_capacity`` — the superstep's GROW/SHRINK exits depend on
    it — and ``n, m`` there)."""
    kind: str                # 'wave' | 'dist'
    bucket: int              # frontier capacity (rows)
    nw: int                  # mask words per row
    cyc_rows: int            # CycleBuffer capacity (1 in count-only mode)
    delta: int               # max degree Δ (static in the slot formulation)
    store: bool
    formulation: str
    backend: str
    k_max: int               # superstep round budget K
    batch: int = 0
    donate: bool = True      # buffer-donation is part of program identity
    rpl: int = 1             # rounds_per_launch R (DESIGN.md §6.11): the
    # persistent multi-round body is a different traced program per R, so
    # it is part of program identity
    extra: tuple = ()


class WavePlan:
    """One compiled wave superstep (plan half of plan/execute).

    Calling the plan executes it; ``n_traces`` counts how many times jax
    actually (re)traced the wrapped function — the zero-retrace assertion
    of the warm path. ``round_path`` is the path ('fused' or 'split') the
    traced rounds took, recorded while tracing ("" before the first trace).
    ``lower(*args)`` exposes the jit lowering so tests
    can assert the donation aliasing made it into the program
    (an ``XLA_FLAGS=--log-donation``-style check without log scraping).
    """

    def __init__(self, key: PlanKey, *, donate: bool | None = None):
        donate = key.donate if donate is None else donate
        self.key = key
        self.n_traces = 0
        self.n_calls = 0
        self.donated = donate
        self.round_path = ""

        statics = dict(delta=key.delta, store=key.store,
                       formulation=key.formulation, backend=key.backend,
                       k_max=key.k_max, rounds_per_launch=key.rpl)

        def _traced(g, f, buf, rounds_limit):
            # runs once per TRACE (not per call): the retrace observer
            self.n_traces += 1
            with _expand.record_round_paths() as paths:
                out = _engine.wave_superstep(g, f, buf, rounds_limit,
                                             **statics)
            # the loop body is traced once, so its rounds take one path
            (self.round_path,) = set(paths)
            return out

        fn = _traced
        if key.batch:
            # one graph per lane; rounds_limit is per-lane (each graph has
            # its own |V|−3 budget). jax masks lanes whose while-cond ended.
            # Valid for EVERY backend (DESIGN.md §6.7): the jnp expand ops
            # are vmap-transparent and the pallas ops carry custom_vmap
            # rules onto the lane-gridded kernels, so this one vmap IS the
            # batched plan — no per-backend fallback. Donation is
            # unaffected: the stacked frontier/CycleBuffer leaves alias
            # in place exactly like their unbatched shapes.
            fn = jax.vmap(_traced, in_axes=(0, 0, 0, 0))
        self.fn = jax.jit(fn, donate_argnums=(1, 2) if donate else ())

    def __call__(self, g, f, buf, rounds_limit):
        self.n_calls += 1
        return self.fn(g, f, buf, rounds_limit)

    def lower(self, g, f, buf, rounds_limit):
        return self.fn.lower(g, f, buf, rounds_limit)


def merge_lanes(admit, clear, gbat, f, buf, g_new, f_new):
    """Drain/admit merge of one recyclable batch (DESIGN.md §6.9).

    ``admit``/``clear`` are (B,) bool lane masks: admitted lanes take their
    freshly seeded graph + frontier (``g_new``/``f_new``, stage-1 output at
    the pool's pinned capacity), cleared lanes (retired with no successor)
    keep their old leaves but drop their live counts to 0 (stale rows
    beyond the count are never read — the superstep masks by count), and
    everything else passes through untouched. The CycleBuffer count resets
    on BOTH masks: retirement flushed those rows host-side already.

    Per-leaf masked ``where`` keeps every shape fixed no matter how many
    lanes a boundary touches — the whole continuous run reuses ONE compiled
    merge program per pool shape (the no-retrace half of the admission
    protocol; the other half is the seed capacity pin in
    ``triplets.initial_frontier_batched``).
    """
    from .frontier import CycleBuffer, Frontier

    B = admit.shape[0]

    def sel(new, old):
        m = admit.reshape((B,) + (1,) * (old.ndim - 1))
        return jnp.where(m, new, old)

    g = jax.tree_util.tree_map(sel, g_new, gbat)
    fr = Frontier(
        path=sel(f_new.path, f.path), blocked=sel(f_new.blocked, f.blocked),
        v1=sel(f_new.v1, f.v1), l2=sel(f_new.l2, f.l2),
        vlast=sel(f_new.vlast, f.vlast),
        count=jnp.where(admit, f_new.count,
                        jnp.where(clear, 0, f.count)).astype(jnp.int32))
    bb = CycleBuffer(
        masks=buf.masks,
        count=jnp.where(admit | clear, 0, buf.count).astype(jnp.int32))
    return g, fr, bb


class RecyclePlan:
    """One compiled drain/admit merge (``PlanKey(kind='recycle')``).

    Same observability contract as ``WavePlan`` — ``n_traces`` increments
    only while jax traces, so a sustained-traffic run proves its zero-
    retrace claim on ``ProgramCache.n_traces``. The running frontier and
    CycleBuffer (the pool's two big allocations) and the seed frontier are
    donated: the merge updates the pool in place instead of doubling them
    at every admission boundary. The graph pytrees are NOT donated — the
    scheduler memoizes padded/stacked graph batches across boundaries
    (``ContinuousScheduler._stacked``), and a donated cache entry would be
    invalidated on first use.
    """

    def __init__(self, key: PlanKey, *, donate: bool | None = None):
        donate = key.donate if donate is None else donate
        self.key = key
        self.n_traces = 0
        self.n_calls = 0
        self.donated = donate

        def _traced(admit, clear, gbat, f, buf, g_new, f_new):
            # runs once per TRACE (not per call): the retrace observer
            self.n_traces += 1
            return merge_lanes(admit, clear, gbat, f, buf, g_new, f_new)

        self.fn = jax.jit(_traced,
                          donate_argnums=(3, 4, 6) if donate else ())

    def __call__(self, admit, clear, gbat, f, buf, g_new, f_new):
        self.n_calls += 1
        return self.fn(admit, clear, gbat, f, buf, g_new, f_new)

    def lower(self, *args):
        return self.fn.lower(*args)


class DistPlan:
    """One compiled sharded program (deal or superstep; plan half of the
    sharded plan/execute split).

    Wraps an UNJITTED ``core.distributed`` shard_map callable in the same
    observability contract as ``WavePlan``: ``n_traces`` increments only
    while jax traces (the zero-retrace warm-path assertion), ``n_calls``
    counts executions, and ``donate_argnums`` donates the sharded frontier
    + counter buffers so the big per-device operands alias in place across
    supersteps.
    """

    def __init__(self, key: PlanKey, fn, *, donate_argnums: tuple = ()):
        self.key = key
        self.n_traces = 0
        self.n_calls = 0
        self.donated = bool(donate_argnums)

        def _traced(*args):
            # runs once per TRACE (not per call): the retrace observer
            self.n_traces += 1
            return fn(*args)

        self.fn = jax.jit(_traced, donate_argnums=donate_argnums)

    def __call__(self, *args):
        self.n_calls += 1
        return self.fn(*args)

    def lower(self, *args):
        return self.fn.lower(*args)


class ProgramCache:
    """Keyed store of compiled plans with hit/miss accounting.

    ``max_plans`` bounds a long-lived service's cache with LRU eviction
    (plans were previously never freed): a hit refreshes recency, a miss
    beyond the bound evicts the least-recently-used plan — XLA drops the
    compiled executable with it, and a later same-shape request simply
    recompiles (counted in ``evictions``/``cache_misses``). ``None`` keeps
    the unbounded pre-eviction behaviour."""

    def __init__(self, max_plans: int | None = None, metrics=None):
        if max_plans is not None and max_plans < 1:
            raise ValueError(f"max_plans must be >= 1 or None, "
                             f"got {max_plans}")
        self._plans: "OrderedDict[PlanKey, object]" = OrderedDict()
        self.max_plans = max_plans
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self._retired_traces = 0  # n_traces stays monotonic across evictions
        # optional repro.obs registry: push counters mirror hit/miss/evict,
        # pull gauges keep programs/n_traces live views over this cache
        self._m_hits = self._m_misses = self._m_evictions = None
        if metrics is not None:
            self._m_hits = metrics.counter("plan_cache_hits_total")
            self._m_misses = metrics.counter("plan_cache_misses_total")
            self._m_evictions = metrics.counter("plan_evictions_total")
            metrics.gauge("plan_programs").set_fn(lambda: len(self._plans))
            metrics.gauge("plan_traces").set_fn(lambda: self.n_traces)

    def get_or_build(self, key: PlanKey, builder):
        plan = self._plans.get(key)
        if plan is not None:
            self.hits += 1
            if self._m_hits is not None:
                self._m_hits.inc()
            self._plans.move_to_end(key)
            return plan
        self.misses += 1
        if self._m_misses is not None:
            self._m_misses.inc()
        plan = builder()
        self._plans[key] = plan
        while self.max_plans is not None and len(self._plans) > self.max_plans:
            _, evicted = self._plans.popitem(last=False)
            self._retired_traces += getattr(evicted, "n_traces", 0)
            self.evictions += 1
            if self._m_evictions is not None:
                self._m_evictions.inc()
        return plan

    def __len__(self):
        return len(self._plans)

    def __contains__(self, key):
        return key in self._plans

    @property
    def n_traces(self) -> int:
        return (sum(getattr(p, "n_traces", 0) for p in self._plans.values())
                + self._retired_traces)

    def stats(self) -> dict:
        return dict(programs=len(self._plans), cache_hits=self.hits,
                    cache_misses=self.misses, n_traces=self.n_traces,
                    evictions=self.evictions, max_plans=self.max_plans)


# ---------------------------------------------------------------------------
# Batch padding rules (DESIGN.md §"Service layer")
# ---------------------------------------------------------------------------

def pad_graph(g: BitsetGraph, n_pad: int, m_pad: int,
              delta_pad: int) -> BitsetGraph:
    """Pad a graph to shared static shapes so a batch stacks into one pytree.

    Rules: padding vertices are isolated (degree 0, no adjacency bits) and
    take the top labels n..n_pad−1 — the labeling stays a bijection and
    every real vertex keeps its label, so expansion order (and therefore
    every count and mask) is unchanged. ``labelgt_bits`` is recomputed from
    the extended labels; CSR arrays are length-padded (never dereferenced
    for padding vertices: their degree masks every slot)."""
    n, nw_old = g.n, g.adj_bits.shape[1]
    if n_pad < n or m_pad < g.m or delta_pad < g.max_degree:
        raise ValueError(f"pad target ({n_pad}, {m_pad}, {delta_pad}) below "
                         f"graph shape ({n}, {g.m}, {g.max_degree})")
    nw = n_words_for(n_pad)

    offs = np.asarray(g.offsets)
    offsets = np.concatenate(
        [offs, np.full(n_pad - n, offs[-1], np.int32)]).astype(np.int32)
    nbr = np.asarray(g.neighbors)
    neighbors = np.concatenate(
        [nbr, np.zeros(2 * m_pad - len(nbr), np.int32)]).astype(np.int32)
    labels = np.concatenate(
        [np.asarray(g.labels), np.arange(n, n_pad, dtype=np.int32)])
    degrees = np.concatenate(
        [np.asarray(g.degrees), np.zeros(n_pad - n, np.int32)])

    adj = np.zeros((n_pad, nw), np.uint32)
    adj[:n, :nw_old] = np.asarray(g.adj_bits)
    gt = labels[None, :] > np.arange(n_pad)[:, None]
    labelgt = pack_bits(gt.astype(np.uint8))

    return BitsetGraph(
        offsets=jnp.asarray(offsets), neighbors=jnp.asarray(neighbors),
        labels=jnp.asarray(labels), adj_bits=jnp.asarray(adj),
        labelgt_bits=jnp.asarray(labelgt), degrees=jnp.asarray(degrees),
        n=n_pad, m=m_pad, max_degree=delta_pad)


def batch_shape(graphs) -> tuple[int, int, int]:
    """Shared (n_pad, m_pad, delta_pad) for a batch of graphs."""
    n_pad = max(g.n for g in graphs)
    m_pad = max(max(g.m, 1) for g in graphs)
    delta_pad = max(max(g.max_degree, 1) for g in graphs)
    return n_pad, m_pad, delta_pad


def batch_graphs(graphs) -> BitsetGraph:
    """Pad every graph to the batch maxima and stack leaves on axis 0."""
    n_pad, m_pad, delta_pad = batch_shape(graphs)
    padded = [pad_graph(g, n_pad, m_pad, delta_pad) for g in graphs]
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *padded)
