"""CycleService — the *execute* half of the plan/execute split.

Public session API (DESIGN.md §"Service layer"). One service owns one
``ProgramCache`` of compiled wave supersteps; every request — single graph,
graph batch, or stream — is a cheap *execute* against that cache:

* ``service.enumerate(g)``        — one-shot semantics of the old
  ``enumerate_chordless_cycles``, but warm: same-bucket graphs reuse the
  compiled program (cache-hit counters on ``service.stats``).
* ``service.enumerate_batch(gs)`` — multi-tenant workload: graphs are padded
  to shared shapes (core/plan.py padding rules), stacked, and the superstep
  is vmapped over the batch axis; ONE device program advances every tenant.
* ``service.stream(g)``           — generator yielding cycle-mask chunks as
  the device CycleBuffer drains, instead of materializing everything at the
  end; chunks concatenate bit-identically to ``EnumerationResult.cycle_masks``.
* ``service.plan(g)``             — explicit plan step: compile (or fetch)
  the program the first superstep of ``g`` will use, without enumerating.

``cfg.mesh`` non-None routes the request through the sharded wave
superstep in ``core/distributed.py`` — the same ProgramCache warms its
deal + superstep programs (``PlanKey(kind='dist')``) and the same tuner
resolves its knobs. ``enumerate_chordless_cycles`` is a thin wrapper over
the module-level ``default_service()``.

``CycleService(auto_tune=True)`` additionally resolves every request's
config through ``repro.tune`` (DESIGN.md §6.6): first visit of a workload
class records a ``WaveTrace`` and searches the knob space, later visits
execute the stored tuned config with no search and no re-trace;
``trace=True`` records telemetry on every request and ``max_plans``
LRU-bounds the program cache.
"""
from __future__ import annotations

import collections
import time
from typing import Callable, Iterator, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from .bitset_graph import BitsetGraph
from . import triplets as T
from .engine import (STATUS_NAMES, EngineConfig, EnumerationResult, _DONE,
                     _DRAIN, _GROW, _RUN, _SHRINK)
from .frontier import (empty_cycle_buffer, empty_frontier, with_capacity,
                       with_capacity_batched)
from .plan import (PlanKey, ProgramCache, RecyclePlan, WavePlan,
                   batch_graphs, batch_shape)
from ..obs.metrics import MetricsRegistry
from ..obs.spans import SpanLog, new_request_id
from ..tune.telemetry import WaveTrace, disabled_trace

# legacy CycleService.stats request-accounting keys → canonical registry
# metric names (the stats dict is a VIEW over these — DESIGN.md §6.10)
_SERVICE_COUNTERS = dict(
    requests="service_requests_total", graphs="service_graphs_total",
    batches="service_batches_total", streams="service_streams_total",
    sessions="service_sessions_total",
    traces_recorded="service_traces_recorded_total",
    tuned_requests="service_tuned_requests_total")
# divergent legacy stat names across CycleService.stats / serve() /
# serve_recycled(), normalized onto one canonical metric each
_LEGACY_ALIASES = dict(
    cache_hits="plan_cache_hits_total", hits="plan_cache_hits_total",
    cache_misses="plan_cache_misses_total",
    misses="plan_cache_misses_total", evictions="plan_evictions_total",
    programs="plan_programs", n_traces="plan_traces",
    **_SERVICE_COUNTERS)


class CycleService:
    """A session: build jitted wave programs once, execute them per request.

    The paper builds its kernel once and relaunches it |V|−3 times; a
    service extends that amortization ACROSS graphs — every graph whose
    shapes match an already-seen program (same (n, m, Δ) graph shape AND
    same (bucket, nw, mode) frontier shape) executes it with zero
    retraces. Different-sized graphs compile their own programs (jit
    shapes are static); the win is for same-shaped tenant traffic.
    """

    def __init__(self, config: EngineConfig | None = None, *,
                 auto_tune: bool = False, tuner=None,
                 tune_store: "str | object | None" = None,
                 trace: bool = False, max_plans: int | None = None,
                 metrics: MetricsRegistry | None = None, recorder=None):
        """``auto_tune=True`` resolves every request's config through an
        ``repro.tune.AutoTuner``: the first request of a workload class runs
        the base config while recording a ``WaveTrace``, the tuner fits its
        cost model on it and stores the winning knobs, and every later
        same-class request executes the tuned config straight from the
        store (no search, no re-trace). ``tuner`` injects a configured
        ``AutoTuner`` (e.g. with measured trials); ``tune_store`` is a
        ``TuneStore`` or a JSON path for persistence across processes.
        ``trace=True`` records telemetry on every request
        (``service.last_trace``/``service.trace_log``) plus request spans
        (``service.spans``); ``max_plans`` LRU-bounds the program cache
        for long-lived services. ``metrics`` injects a shared
        ``repro.obs.MetricsRegistry`` (default: one per service);
        ``recorder`` attaches a ``repro.obs.FlightRecorder`` that rides
        every run as a telemetry observer (bounded ring + anomaly dumps,
        works even with ``trace=False``).
        """
        self.cfg = config if config is not None else EngineConfig()
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self._obs_t0 = time.perf_counter()   # the shared span/event clock
        self._cache = ProgramCache(max_plans=max_plans,
                                   metrics=self.metrics)
        # request accounting lives IN the registry; the legacy stats dict
        # is a view over it (`stats` property)
        self._m = {name: self.metrics.counter(canon)
                   for name, canon in _SERVICE_COUNTERS.items()}
        self._m_boundary = self.metrics.counter("boundary_ms_total")
        for legacy, canon in _LEGACY_ALIASES.items():
            self.metrics.alias(legacy, canon)
        self._recorder = recorder
        self.last_session = None
        self._trace_enabled = bool(trace)
        self.spans = SpanLog(enabled=self._trace_enabled,
                             origin=self._obs_t0)
        self.trace_log: collections.deque = collections.deque(maxlen=512)
        self.last_trace: WaveTrace | None = None
        self._tuner = tuner
        if tuner is not None and tune_store is not None:
            raise ValueError(
                "pass tune_store to the AutoTuner itself when injecting a "
                "tuner (tuner= already carries its own store)")
        if self._tuner is None and (auto_tune or tune_store is not None):
            # a tune_store alone implies auto_tune: a persistence path the
            # service silently never wrote to would be worse than tuning
            from ..tune import AutoTuner, TuneStore
            store = tune_store
            if isinstance(store, str):
                store = TuneStore(path=store)
            self._tuner = AutoTuner(store=store, metrics=self.metrics)
        if self._tuner is not None and \
                getattr(self._tuner, "_metrics", None) is None:
            # injected tuner: route its counters through this registry too
            self._tuner._metrics = self.metrics

    # -- stats ------------------------------------------------------------

    @property
    def stats(self) -> dict:
        """Program-cache hit/miss/trace counters + request accounting.

        The legacy dict shape (pinned in tests/test_obs.py) is a VIEW over
        the metrics registry — the registry counters are the storage."""
        out = self._cache.stats()
        out.update({name: int(c.value()) for name, c in self._m.items()})
        if self._tuner is not None:
            out["tune"] = self._tuner.stats()
        return out

    # -- tuning (repro.tune integration) ----------------------------------

    def _resolve_config(self, n: int, m: int, delta: int, cfg: EngineConfig,
                        explicit: bool = False, batch: int = 0):
        """Route a request's config through the tuner (DESIGN.md §6.6).

        Returns ``(cfg, tune_key, observe)``: with a stored tuned entry for
        this workload class the tuned config comes back and ``observe`` is
        False (warm hit — no search, no trace); on first visit the base
        config comes back with ``observe=True`` so the run is recorded and
        fed to the tuner afterwards. Mesh-sharded configs resolve like
        single-device ones, against the sharded knob set
        (``superstep_rounds`` × ``local_capacity`` × ``balance_every``,
        keyed by device count — ``tune.DIST_TUNED_KNOBS``). ``explicit``
        per-request configs pass through untouched: the caller pinned the
        knobs (e.g. a memory-bounding ``cycle_buffer_rows``), and a stored
        entry keyed only by workload class must not override them.
        """
        if self._tuner is None or explicit:
            return cfg, None, False
        key = self._tuner.key_for(n, m, delta, cfg, batch=batch)
        tuned = self._tuner.lookup(key, cfg)
        if tuned is not None:
            self._m["tuned_requests"].inc()
            return tuned, key, False
        return cfg, key, True

    def _new_trace(self, observing: bool) -> WaveTrace:
        """Telemetry recorder for one run: retains events when the service
        records traces OR this run feeds the tuner; counters-only (near-zero
        overhead) otherwise. Every trace shares the service clock
        (``origin``) so its events and the request spans land on one
        timeline; an attached FlightRecorder observes events even on the
        disabled path (observer-only — nothing retained per dispatch)."""
        observer = self._recorder.record if self._recorder is not None \
            else None
        if self._trace_enabled or observing:
            tr = WaveTrace(enabled=True, origin=self._obs_t0,
                           observer=observer)
            self._m["traces_recorded"].inc()
            self.last_trace = tr
            self.trace_log.append(tr)
            return tr
        return disabled_trace(origin=self._obs_t0, observer=observer)

    def _after_run(self, g: BitsetGraph, cfg: EngineConfig, tune_key,
                   observe: bool, trace: WaveTrace,
                   res: EnumerationResult) -> None:
        """First-visit hook: hand the recorded run to the tuner (profile →
        cost-model fit → search → store) so the NEXT same-class request
        executes tuned."""
        if not observe or tune_key is None:
            return
        self._tuner.observe(tune_key, cfg, res.history, n=g.n,
                            nw=g.adj_bits.shape[1], traces=(trace,))

    def _request_spans(self, rid: str, t_req: float, trace: WaveTrace,
                       dispatches: bool = True) -> None:
        """Decompose one finished run into request spans (DESIGN.md §6.10):
        a root ``request`` slice covering the whole call plus, where the
        run's host phases did not record them already (``dispatches``), one
        child per recorded dispatch, all on the shared service clock. Only
        runs when spans are enabled AND the run recorded events — the
        disabled path constructs no Span objects at all (overhead
        contract)."""
        if not rid or not self.spans.enabled:
            return
        for wave, ev in enumerate(getattr(trace, "events", ())
                                  if dispatches else ()):
            self.spans.add(ev.kind, rid, ev.t_start_ms,
                           max(ev.wall_ms, ev.t_ms), wave=wave,
                           status=ev.status, rounds=ev.rounds,
                           bucket=ev.bucket,
                           rounds_per_launch=ev.rounds_per_launch,
                           kernel_launches=ev.kernel_launches)
        self.spans.add("request", rid, t_req,
                       self.spans.now_ms() - t_req)

    # -- plan (compile) ---------------------------------------------------

    def _wave_plan(self, g_n: int, g_m: int, cap: int, cyc_cap: int, nw: int,
                   delta: int, cfg: EngineConfig, batch: int = 0) -> WavePlan:
        key = PlanKey(kind="wave", bucket=cap, nw=nw, cyc_rows=cyc_cap,
                      delta=delta, store=cfg.store,
                      formulation=cfg.formulation, backend=cfg.backend,
                      k_max=cfg.superstep_rounds, batch=batch,
                      donate=cfg.donate, rpl=cfg.rounds_per_launch,
                      extra=(g_n, g_m))
        return self._cache.get_or_build(key, lambda: WavePlan(key))

    def _recycle_plan(self, g_n: int, g_m: int, cap: int, cyc_cap: int,
                      nw: int, delta: int, cfg: EngineConfig,
                      batch: int) -> RecyclePlan:
        """The drain/admit merge program of one recyclable pool shape
        (DESIGN.md §6.9) — cached alongside the wave plans, so
        ``ProgramCache.n_traces`` observes its retraces too (the sustained-
        traffic zero-retrace assertion covers admission)."""
        key = PlanKey(kind="recycle", bucket=cap, nw=nw, cyc_rows=cyc_cap,
                      delta=delta, store=cfg.store,
                      formulation=cfg.formulation, backend=cfg.backend,
                      k_max=0, batch=batch, donate=cfg.donate,
                      extra=(g_n, g_m))
        return self._cache.get_or_build(key, lambda: RecyclePlan(key))

    def plan(self, g: BitsetGraph, *, config: EngineConfig | None = None
             ) -> WavePlan:
        """Compile (or fetch) the program ``g``'s first superstep will use.

        Runs stage 1 to learn the initial bucket, then executes the plan
        once on an empty dummy frontier (count 0 → the device loop exits
        immediately) so trace + compile happen NOW, not on the first
        request. Later buckets of the wave compile lazily as reached."""
        cfg = config if config is not None else self.cfg
        if cfg.mesh is not None:
            # the sharded step is built (and cached) on first enumerate
            raise ValueError(
                "plan() supports the single-device wave path only "
                "(mesh=None); the sharded step compiles on first enumerate")
        nw = g.adj_bits.shape[1]
        delta = max(g.max_degree, 1)
        frontier, _, _ = T.initial_frontier_device(
            g, bucket=cfg.bucket, backend=cfg.backend)
        cap = frontier.capacity
        cyc_cap = (cfg.bucket(max(cfg.cycle_buffer_rows, 16))
                   if cfg.store else 1)
        plan = self._wave_plan(g.n, g.m, cap, cyc_cap, nw, delta, cfg)
        # dummy execute — donation consumes the dummies, nothing else does
        plan(g, empty_frontier(cap, nw), empty_cycle_buffer(cyc_cap, nw),
             jnp.int32(0))
        return plan

    # -- execute: single graph --------------------------------------------

    def enumerate(self, g: BitsetGraph, *,
                  config: EngineConfig | None = None,
                  progress: Callable[[dict], None] | None = None
                  ) -> EnumerationResult:
        """Enumerate (or count) all chordless cycles of ``g``."""
        cfg = config if config is not None else self.cfg
        self._m["requests"].inc()
        self._m["graphs"].inc()
        rid = new_request_id() if self.spans.enabled else ""
        t_req = self.spans.now_ms() if rid else 0.0
        with self.spans.phase("enumerate", rid):
            cfg, tkey, observe = self._resolve_config(
                g.n, g.m, max(g.max_degree, 1), cfg,
                explicit=config is not None)
            trace = self._new_trace(observe)
            if cfg.mesh is not None:
                from .distributed import enumerate_sharded
                res = enumerate_sharded(g, cfg, cache=self._cache,
                                        trace=trace, progress=progress,
                                        metrics=self.metrics,
                                        spans=self.spans, rid=rid)
            else:
                gen = self._wave_events(g, cfg, progress, trace, rid=rid)
                chunks: list[np.ndarray] = []
                while True:
                    try:
                        chunks.append(next(gen))
                    except StopIteration as stop:
                        res = stop.value
                        break
                if cfg.store:
                    nw = g.adj_bits.shape[1]
                    with self.spans.phase("drain", rid):
                        res.cycle_masks = (
                            np.concatenate(chunks, axis=0) if chunks
                            else np.zeros((0, nw), np.uint32))
            self._after_run(g, cfg, tkey, observe, trace, res)
        # the wave and sharded drivers' host phases already recorded their
        # dispatches
        self._request_spans(rid, t_req, trace, dispatches=False)
        return res

    def stream(self, g: BitsetGraph, *,
               config: EngineConfig | None = None,
               progress: Callable[[dict], None] | None = None
               ) -> Iterator[np.ndarray]:
        """Yield cycle-mask chunks ((k, nw) uint32) as the device CycleBuffer
        drains. Chunks concatenate bit-identically to the ``cycle_masks`` of
        ``enumerate`` (both consume the same event generator). The generator's
        ``StopIteration.value`` is the ``EnumerationResult`` summary (with
        ``cycle_masks=None`` — the chunks ARE the masks)."""
        cfg = config if config is not None else self.cfg
        # mesh first: a mesh-routed config is count-only by construction, so
        # the store check below would otherwise mask the real problem with a
        # misleading "store=True required" error.
        if cfg.mesh is not None:
            raise NotImplementedError(
                "stream() over the mesh-sharded (shard_map) path is not "
                "implemented: the sharded engine is count-only and keeps no "
                "device-resident CycleBuffer to drain. Use mesh=None for "
                "streaming, or enumerate(config=<mesh cfg>) for sharded "
                "counting.")
        if not cfg.store:
            raise ValueError("stream() requires store=True (count-only "
                             "results have no masks to stream)")
        self._m["requests"].inc()
        self._m["graphs"].inc()
        self._m["streams"].inc()
        rid = new_request_id() if self.spans.enabled else ""
        cfg, tkey, observe = self._resolve_config(
            g.n, g.m, max(g.max_degree, 1), cfg, explicit=config is not None)
        trace = self._new_trace(observe)
        gen = self._wave_events(g, cfg, progress, trace, rid=rid)
        if tkey is None:
            return gen
        return self._observed_stream(gen, g, cfg, tkey, observe, trace)

    def _observed_stream(self, gen, g, cfg, tkey, observe, trace):
        """Forward a stream's chunks, then run the tuner's first-visit hook
        on the summary (streams feed the tuner like enumerate does)."""
        res = yield from gen
        self._after_run(g, cfg, tkey, observe, trace, res)
        return res

    def _wave_events(self, g: BitsetGraph, cfg: EngineConfig,
                     progress: Callable[[dict], None] | None,
                     trace: WaveTrace | None = None, rid: str = ""):
        """The wave driver loop as an event generator: yields drained mask
        chunks (store mode), returns the EnumerationResult (masks unset).
        Port of the PR-1 ``_enumerate_wave`` with the superstep dispatch
        replaced by a ProgramCache lookup. Every host statement between
        two supersteps runs inside one host phase (``SpanLog.phase``), so
        a profiler trace names what the host did while the device idled."""
        phase = self.spans.phase
        delta = max(g.max_degree, 1)
        nw = g.adj_bits.shape[1]
        trace = trace if trace is not None else disabled_trace()
        with phase("seed", rid):
            frontier, tri_masks, n_tri = T.initial_frontier_device(
                g, bucket=cfg.bucket, backend=cfg.backend, trace=trace)
            cyc_cap = (cfg.bucket(max(cfg.cycle_buffer_rows, 16))
                       if cfg.store else 1)
            buf = empty_cycle_buffer(cyc_cap, nw)
        with phase("readback", rid):
            cnt = int(jax.device_get(frontier.count))
            trace.sync()
            trace.d2h()
        n_cycles = n_tri
        history = [dict(step=0, T=cnt, C=n_tri)]
        limit = (cfg.max_iters if cfg.max_iters is not None
                 else max(g.n - 3, 0))
        if cfg.store:
            yield tri_masks

        it = 0
        relaunches = 0
        while it < limit and cnt > 0:
            relaunches += 1
            if relaunches > 4 * limit + 16:
                raise RuntimeError(
                    "wave engine: no progress across relaunches")
            with phase("superstep", rid):
                k = min(cfg.superstep_rounds, limit - it)
                cap_in, cnt_in = frontier.capacity, cnt
                plan = self._wave_plan(g.n, g.m, frontier.capacity, cyc_cap,
                                       nw, delta, cfg)
                fresh = plan.n_calls == 0
                trace.tic()
                frontier, buf, r, status, th, ch, pn, pc = plan(
                    g, frontier, buf, jnp.int32(k))
            with phase("readback", rid):
                fetched = (status, r, th, ch, pn, pc, frontier.count,
                           buf.count)
                (status_h, r_h, th_h, ch_h, pn_h, pc_h, cnt_h,
                 bc_h) = jax.device_get(fetched)
                trace.sync()
                trace.d2h(len(fetched))
                trace.dispatch(
                    kind="superstep", bucket=cap_in, cyc_cap=cyc_cap,
                    budget=k, rounds=int(r_h),
                    status=STATUS_NAMES[int(status_h)],
                    t_sizes=th_h[:int(r_h)], c_counts=ch_h[:int(r_h)],
                    enter_count=cnt_in, exit_count=int(cnt_h),
                    pending_new=int(pn_h), pending_cyc=int(pc_h),
                    cyc_fill=int(bc_h), t_ms=trace.toc_ms(), fresh=fresh,
                    plan_key=str(plan.key),
                    rounds_per_launch=cfg.rounds_per_launch,
                    lane_rids=(rid,) if rid else (),
                    lane_rounds=(it + int(r_h),) if rid else (),
                    round_path=plan.round_path)
                for i in range(int(r_h)):
                    n_cycles += int(ch_h[i])
                    rec = dict(step=it + i + 1, T=int(th_h[i]), C=n_cycles)
                    history.append(rec)
                    if progress:
                        progress(rec)
                it += int(r_h)
                cnt = int(cnt_h)
                status_h = int(status_h)

            if status_h == _DRAIN:
                # cycle buffer full: drain to host, regrow if one round
                # alone exceeds the current buffer.
                chunk = None
                with phase("drain", rid):
                    if int(bc_h):
                        chunk = np.asarray(buf.masks[:int(bc_h)])
                        trace.sync()
                        trace.d2h()
                        trace.drain()
                    cyc_cap = max(cyc_cap, cfg.bucket(max(int(pc_h), 1)))
                    buf = empty_cycle_buffer(cyc_cap, nw)
                if chunk is not None:
                    yield chunk
            elif status_h == _GROW:
                # re-bucket the headroom'd size so the shape stays inside
                # the growth_bits bucket family (off-family shapes would
                # churn recompiles against the SHRINK path).
                with phase("rebucket", rid):
                    new_cap = cfg.bucket(
                        cfg.bucket(max(int(pn_h), 1))
                        << max(cfg.grow_headroom, 0))
                    frontier = with_capacity(frontier, new_cap)
                    trace.transition()
            elif status_h in (_RUN, _SHRINK) and cnt > 0:
                # round budget exhausted / wave decayed below the bucket:
                # shrink as the wave dies down (bounds dead-row work, like
                # the host loop does every round).
                new_cap = cfg.bucket(max(cnt, 1))
                if new_cap < frontier.capacity:
                    with phase("rebucket", rid):
                        frontier = with_capacity(frontier, new_cap)
                        trace.transition()
            elif status_h == _DONE:
                break

        if cfg.store:
            chunk = None
            with phase("readback", rid):
                bc = int(jax.device_get(buf.count))
                trace.sync()
                trace.d2h()
            with phase("drain", rid):
                if bc:
                    chunk = np.asarray(buf.masks[:bc])
                    trace.d2h()
                    trace.drain()
            if chunk is not None:
                yield chunk

        return EnumerationResult(
            n_cycles=n_cycles, n_triangles=n_tri, cycle_masks=None,
            iterations=it, history=history, stats=trace.finalize(rounds=it),
            trace=trace if trace.enabled else None)

    # -- execute: graph batch ---------------------------------------------

    def enumerate_batch(self, graphs: Sequence[BitsetGraph], *,
                        config: EngineConfig | None = None
                        ) -> list[EnumerationResult]:
        """Enumerate a batch of graphs with ONE vmapped device program.

        Padding rules (core/plan.py): every graph is padded to the batch
        maxima (n, m, Δ), frontiers share one capacity bucket, and the
        superstep advances all lanes per dispatch; per-lane |V|−3 budgets
        and exit statuses keep semantics identical to per-graph calls.
        Batch is a first-class axis on EVERY backend (DESIGN.md §6.7): the
        pallas kernels run on a lane grid under the same vmapped plan, so
        there is no per-graph fallback; stage 1 seeds all lanes device-side
        in one dispatch (``T.initial_frontier_batched``). A batch of one
        graph runs as a plain ``enumerate``."""
        cfg = config if config is not None else self.cfg
        if cfg.mesh is not None:
            raise NotImplementedError(
                "enumerate_batch over the mesh-sharded (shard_map) path is "
                "not implemented: the sharded superstep shards ONE graph's "
                "frontier rows across devices and has no graph-lane axis "
                "to batch over. Use mesh=None for batching, or one "
                "enumerate(config=<mesh cfg>) request per graph for "
                "sharded counting.")
        graphs = list(graphs)
        if not graphs:
            return []
        if len(graphs) == 1:
            return [self.enumerate(g, config=cfg) for g in graphs]

        self._m["requests"].inc()
        self._m["graphs"].inc(len(graphs))
        self._m["batches"].inc()
        rid = new_request_id() if self.spans.enabled else ""
        t_req = self.spans.now_ms() if rid else 0.0

        B = len(graphs)
        n_pad, m_pad, delta = batch_shape(graphs)
        # the whole batch runs at the padded shape, so the padded shape —
        # plus the batch-size class — IS the workload class the tuned knobs
        # resolve from; first visits observe the per-lane wave shapes back
        # into the tuner (lane-aware replay, DESIGN.md §6.7).
        cfg, tkey, observe = self._resolve_config(
            n_pad, m_pad, delta, cfg, explicit=config is not None, batch=B)
        trace = self._new_trace(observe)
        gbat = batch_graphs(graphs)
        nw = gbat.adj_bits.shape[-1]

        # host phases are annotated only: the batch's request spans come
        # from its dispatch events (_request_spans)
        phase = self.spans.phase

        # stage 1 device-side: one counts dispatch + ONE seeding dispatch
        # scatter every lane's triplets (and triangle bitmaps) in place —
        # no host nonzero, no per-lane H2D (DESIGN.md §6.7). wall_ms spans
        # the whole boundary (staging included), not just the device time.
        with phase("seed"):
            wall_t0 = time.perf_counter()
            trace.tic()
            fbat, tri_bat, ntris, cnts = T.initial_frontier_batched(
                gbat, delta=delta, bucket=cfg.bucket, backend=cfg.backend,
                trace=trace)
            cap = fbat.path.shape[1]
            trace.sync()
            seed_wall_ms = (time.perf_counter() - wall_t0) * 1e3
            self._m_boundary.inc(seed_wall_ms)
            trace.dispatch(
                kind="seed", bucket=cap, cyc_cap=0, budget=0, rounds=0,
                status="RUN", enter_count=int(cnts.sum()),
                exit_count=int(cnts.sum()), t_ms=trace.toc_ms(), launches=2,
                wall_ms=seed_wall_ms,
                lane_rids=(rid,) * B if rid else ())

            cyc_cap = (cfg.bucket(max(cfg.cycle_buffer_rows, 16))
                       if cfg.store else 1)
            bufbat = empty_cycle_buffer(cyc_cap, nw, batch=B)

            limits = np.array([max(g.n - 3, 0) for g in graphs], np.int64)
            if cfg.max_iters is not None:
                limits = np.minimum(limits, cfg.max_iters)
            its = np.zeros(B, np.int64)
            n_cycles = [int(t) for t in ntris]
            histories = [[dict(step=0, T=int(cnts[i]), C=int(ntris[i]))]
                         for i in range(B)]
            if cfg.store:
                tri_h = np.asarray(tri_bat)
                trace.d2h()
                chunks: list[list[np.ndarray]] = [
                    [tri_h[i, :int(ntris[i])].copy()] for i in range(B)]
            else:
                chunks = [[] for _ in range(B)]

        K = cfg.superstep_rounds
        relaunches = 0
        active = (its < limits) & (cnts > 0)
        while active.any():
            relaunches += 1
            if relaunches > 4 * int(limits.max()) + 16:
                raise RuntimeError(
                    "batched wave engine: no progress across relaunches")
            with phase("superstep"):
                k_i = np.where(active, np.minimum(K, limits - its), 0)
                cap_in, live_in = cap, int(cnts.sum())
                plan = self._wave_plan(n_pad, m_pad, cap, cyc_cap, nw,
                                       delta, cfg, batch=B)
                fresh = plan.n_calls == 0
                trace.tic()
                fbat, bufbat, r, status, th, ch, pn, pc = plan(
                    gbat, fbat, bufbat, jnp.asarray(k_i, jnp.int32))
            with phase("readback"):
                fetched = (status, r, th, ch, pn, pc, fbat.count,
                           bufbat.count)
                (status_h, r_h, th_h, ch_h, pn_h, pc_h, cnt_h,
                 bc_h) = jax.device_get(fetched)
                trace.sync()
                trace.d2h(len(fetched))
                lane_statuses = {int(s) for s in np.asarray(status_h)}
                agg = next(s for s in (_DRAIN, _GROW, _SHRINK, _RUN, _DONE)
                           if s in lane_statuses)
                trace.dispatch(
                    kind="batch", bucket=cap_in, cyc_cap=cyc_cap,
                    budget=int(k_i.max()),
                    rounds=int(np.asarray(r_h).max()),
                    status=STATUS_NAMES[agg],
                    enter_count=live_in,
                    exit_count=int(np.asarray(cnt_h).sum()),
                    cyc_fill=int(np.asarray(bc_h).sum()),
                    t_ms=trace.toc_ms(), fresh=fresh,
                    plan_key=str(plan.key),
                    rounds_per_launch=cfg.rounds_per_launch,
                    lane_rids=(rid,) * B if rid else (),
                    lane_rounds=tuple(
                        int(v) for v in its + np.asarray(r_h, np.int64))
                    if rid else (),
                    round_path=plan.round_path)

                for i in range(B):
                    for j in range(int(r_h[i])):
                        n_cycles[i] += int(ch_h[i, j])
                        histories[i].append(dict(step=int(its[i]) + j + 1,
                                                 T=int(th_h[i, j]),
                                                 C=n_cycles[i]))
                its += np.asarray(r_h, np.int64)
                cnts = np.asarray(cnt_h, np.int64)
                status_h = np.asarray(status_h)

            drains = status_h == _DRAIN
            grows = status_h == _GROW
            if drains.any():
                # drain EVERY lane with pending masks in one host copy;
                # per-lane chunk order stays discovery order.
                with phase("drain"):
                    masks_h = np.asarray(bufbat.masks)
                    trace.d2h()
                    for i in range(B):
                        bc = int(bc_h[i])
                        if bc:
                            chunks[i].append(masks_h[i, :bc].copy())
                            trace.drain()
                    trace.sync()
                    # regrow only from the lanes that actually overflowed —
                    # a simultaneous GROW lane's pending_cyc is an aborted
                    # round's size, not a drain signal.
                    cyc_cap = max(cyc_cap, cfg.bucket(
                        max(int(pc_h[drains].max()), 1)))
                    bufbat = empty_cycle_buffer(cyc_cap, nw, batch=B)
            if grows.any():
                # shared bucket must cover the largest pending lane (a
                # growing lane's need always exceeds the current bucket,
                # so everyone fits afterwards).
                need = max(int(pn_h[i]) for i in np.flatnonzero(grows))
                new_cap = cfg.bucket(
                    cfg.bucket(max(need, 1)) << max(cfg.grow_headroom, 0))
                if new_cap != cap:
                    with phase("rebucket"):
                        fbat = with_capacity_batched(fbat, new_cap)
                        cap = new_cap
                        trace.transition()
            elif not drains.any() and cnts.max() > 0:
                # no transition forced a relaunch size-up: shrink to the
                # largest live lane as the waves die down (skip on the
                # terminal relaunch — mirrors the single-graph cnt > 0
                # guard).
                new_cap = cfg.bucket(max(int(cnts.max()), 1))
                if new_cap < cap:
                    with phase("rebucket"):
                        fbat = with_capacity_batched(fbat, new_cap)
                        cap = new_cap
                        trace.transition()
            active = (its < limits) & (cnts > 0)

        if cfg.store:
            with phase("readback"):
                bc_h = np.asarray(jax.device_get(bufbat.count))
                trace.d2h()
            with phase("drain"):
                if bc_h.any():
                    masks_h = np.asarray(bufbat.masks)
                    trace.d2h()
                    for i in range(B):
                        if int(bc_h[i]):
                            chunks[i].append(
                                masks_h[i, :int(bc_h[i])].copy())
                            trace.drain()
                trace.sync()

        if observe and tkey is not None:
            # first visit of this (shape × batch-size) class: profile the
            # per-lane wave shapes and let the tuner trade superstep_rounds
            # against lane imbalance through the lane-aware replay twin.
            from ..tune import WaveProfile
            profile = WaveProfile.from_batch(
                histories, lane_n=[g.n for g in graphs], n=n_pad, nw=nw,
                max_iters=cfg.max_iters)
            self._tuner.observe_profile(tkey, cfg, profile, traces=(trace,))

        self._request_spans(rid, t_req, trace)
        stats = trace.finalize(rounds=int(its.max()))
        results = []
        for i in range(B):
            masks = None
            if cfg.store:
                masks = (np.concatenate(chunks[i], axis=0) if chunks[i]
                         else np.zeros((0, nw), np.uint32))
            # dispatch/sync/drain counters are SHARED across the batch
            # (one device program advanced all lanes) — `batch`/`lane`
            # flag that; `rounds` is this lane's own.
            results.append(EnumerationResult(
                n_cycles=n_cycles[i], n_triangles=int(ntris[i]),
                cycle_masks=masks, iterations=int(its[i]),
                history=histories[i],
                stats=dict(stats, batch=B, lane=i, rounds=int(its[i]),
                           rounds_per_dispatch=(
                               int(its[i])
                               / max(stats["n_dispatches"], 1)),
                           syncs_per_round=(
                               stats["n_host_syncs"]
                               / max(int(its[i]), 1)))))
        return results


    # -- execute: continuous lane-recycling sessions (DESIGN.md §6.9) ------

    def session(self, *, slots: int | None = None,
                config: EngineConfig | None = None):
        """A ``repro.sched.ContinuousScheduler`` bound to this service.

        The scheduler treats the lanes of ONE batched wave program as a
        recyclable resource: finished lanes retire (results flushed) at
        superstep boundaries and queued same-shape-class requests are
        re-seeded into the freed lanes through the cached seed + merge
        programs — no retrace, no wave-at-a-time barrier. ``slots=None``
        resolves the pool size per shape class through the tuner (stored
        ``slots`` knob) with a fixed default fallback."""
        from ..sched import ContinuousScheduler
        self._m["sessions"].inc()
        sched = ContinuousScheduler(self, slots=slots, config=config)
        self.last_session = sched
        return sched

    def serve_stream(self, graphs: Sequence[BitsetGraph], *,
                     arrivals: Sequence[float] | None = None,
                     slots: int | None = None,
                     config: EngineConfig | None = None
                     ) -> Iterator[tuple[int, EnumerationResult]]:
        """Serve a request stream through a lane-recycling session.

        Yields ``(request_index, EnumerationResult)`` in COMPLETION order
        (short-lived graphs overtake long-lived ones — that is the point);
        results are bit-identical per request to ``enumerate_batch``.
        ``arrivals`` gives each request's arrival offset in seconds (open-
        loop traffic; ``None`` = everything queued up-front). Per-request
        latency and lane-occupancy stats land on ``self.last_session.stats``.
        """
        return self.session(slots=slots, config=config).run(
            graphs, arrivals=arrivals)


# ---------------------------------------------------------------------------
# Module-level default service (the compat wrapper's session)
# ---------------------------------------------------------------------------

_DEFAULT: CycleService | None = None


def default_service() -> CycleService:
    """The shared session behind ``enumerate_chordless_cycles`` — one-shot
    calls stay warm across invocations because they all execute against
    this service's program cache."""
    global _DEFAULT
    if _DEFAULT is None:
        _DEFAULT = CycleService()
    return _DEFAULT


def reset_default_service() -> None:
    """Drop the shared session (for tests that need a cold path)."""
    global _DEFAULT
    _DEFAULT = None
