"""ContinuousScheduler — lane recycling over one batched wave program.

The wave-at-a-time scheduler (``launch/serve.py``) admits a batch, then
every lane rides the dispatch until the SLOWEST lane exits — a finished
lane's dead bucket is pure waste (the replay twin charges it explicitly),
and lane lifetimes are inherently imbalanced on this workload. This module
is the continuous-batching idiom from LLM serving mapped onto the wave
engine (DESIGN.md §6.9):

* one device-resident pool of B lanes, bound to a shape class and padded to
  the CLASS CEILING (pow2 buckets of n/m/Δ — the same buckets
  ``tune.shape_class`` names), so every same-class graph fits the pool's
  static shapes;
* at each superstep boundary, finished lanes RETIRE — their CycleBuffer
  rows flush to the caller as a completed ``EnumerationResult`` — and
  queued same-class requests are ADMITTED into the freed lanes;
* admission re-seeds in place WITHOUT RETRACING: stage 1 runs through the
  cached batched seed program pinned to the pool capacity
  (``triplets.initial_frontier_batched(capacity=...)``), and a cached
  masked-select merge (``core.plan.RecyclePlan``, donated buffers) seats
  the new lanes — every program involved is fixed-shape and lives in the
  service's ``ProgramCache``, so ``stats['n_traces']`` stays flat across a
  sustained run after the first class visit.

Free lanes between boundaries ride along with a zero round budget (the
vmapped superstep's while-cond masks them — same mechanism
``enumerate_batch`` uses for finished lanes), so the dispatch cadence never
waits for admission.

Every boundary runs under a host phase of the service's ``SpanLog``
(``recycle`` for admissions, ``retire`` for retirements, with ``seed``,
``superstep``, ``readback``, ``drain`` and ``rebucket`` around the work
inside), so a profiler trace names what the host did while the device
idled. The phases serve a whole pool, so they are annotated only; each
request's spans are added per lane below.
"""
from __future__ import annotations

import time

import numpy as np
import jax
import jax.numpy as jnp

from ..core import triplets as T
from ..core.bitset_graph import BitsetGraph, n_words_for
from ..core.engine import (STATUS_NAMES, EngineConfig, EnumerationResult,
                           _DONE, _DRAIN, _GROW, _RUN, _SHRINK)
from ..core.frontier import empty_cycle_buffer, with_capacity_batched
from ..core.plan import pad_graph
from ..obs.spans import new_request_id
from ..tune.store import _p2, shape_class
from .lanepool import LanePool, LaneRequest

DEFAULT_SLOTS = 4


def class_shape(g: BitsetGraph) -> tuple[int, int, int]:
    """The shape-class ceiling (pow2 n, m, Δ) every graph of the class pads
    to. Padding to the ceiling instead of the batch maxima costs some dead
    rows but buys SHAPE STABILITY: any same-class graph admits into a
    running pool without changing a single compiled shape."""
    return _p2(g.n), _p2(max(g.m, 1)), _p2(max(g.max_degree, 1))


def graph_class(g: BitsetGraph) -> str:
    return shape_class(g.n, g.m, max(g.max_degree, 1))


class ContinuousScheduler:
    """Continuous lane-recycling scheduler over ONE ``CycleService``.

    ``run(graphs, arrivals=None)`` is a generator yielding
    ``(request_index, EnumerationResult)`` in completion order. One pool
    (one shape class) is live at a time; when it drains and a different
    class is waiting, the scheduler switches pools (the warm ProgramCache
    makes revisits free). ``slots=None`` resolves the pool size per class
    from the tuner's stored ``slots`` knob, falling back to
    ``DEFAULT_SLOTS``.
    """

    def __init__(self, service, *, slots: int | None = None,
                 config: EngineConfig | None = None):
        self.service = service
        self._explicit_cfg = config is not None
        self.cfg_base = config if config is not None else service.cfg
        if self.cfg_base.mesh is not None:
            raise ValueError(
                "lane recycling requires the single-device wave path "
                "(mesh=None): the pool IS one batched wave program's lane "
                "axis")
        self.slots = slots
        self.pool: LanePool | None = None
        self.stats = dict(
            requests=0, completed=0, supersteps=0, boundaries=0,
            admissions=0, retirements=0, pools=0, classes={},
            occupancy_sum=0.0, n_cycles=0, boundary_ms=0.0,
            queue_wait_ms=[], e2e_ms=[], n_dispatches=0, n_host_syncs=0,
            n_d2h_arrays=0, fused_rounds=0, split_rounds=0)
        # registry mirrors (DESIGN.md §6.10): the legacy stats dict above
        # stays the session-local view, every count double-writes into the
        # service's shared MetricsRegistry via _bump (dict == registry is
        # regression-pinned in tests/test_obs.py)
        m = service.metrics
        self._m = {name: m.counter(f"sched_{name}_total")
                   for name in ("requests", "completed", "supersteps",
                                "boundaries", "admissions", "retirements",
                                "pools")}
        self._m_boundary = m.counter("boundary_ms_total")
        self._h_wait = m.histogram("queue_wait_ms")
        self._h_e2e = m.histogram("e2e_ms")
        self._g_live = m.gauge("sched_live_lanes")
        self._g_slots = m.gauge("sched_pool_slots")
        self._spans = service.spans

    def _bump(self, name: str, n: int = 1) -> None:
        self.stats[name] += n
        self._m[name].inc(n)

    def _span_ms(self, t: float) -> float:
        """Scheduler-clock seconds → the shared service span clock (ms)."""
        return (self._t0 - self.service._obs_t0 + t) * 1e3

    # -- derived stats ----------------------------------------------------

    @property
    def mean_occupancy(self) -> float:
        """Mean fraction of pool lanes occupied per superstep dispatch —
        the utilization recycling exists to raise."""
        return self.stats["occupancy_sum"] / max(self.stats["supersteps"], 1)

    def latency_summary(self) -> dict:
        from .traffic import percentiles
        out = dict(mean_lane_occupancy=round(self.mean_occupancy, 4))
        for name in ("queue_wait_ms", "e2e_ms"):
            out.update({f"{name}_{k}": v
                        for k, v in percentiles(self.stats[name]).items()})
        return out

    # -- the run loop -----------------------------------------------------

    def run(self, graphs, arrivals=None):
        """Serve ``graphs`` (arrival offsets in seconds via ``arrivals``;
        None = all queued up-front). Generator of (index, result)."""
        graphs = list(graphs)
        if arrivals is None:
            arrivals = [0.0] * len(graphs)
        if len(arrivals) != len(graphs):
            raise ValueError(f"{len(graphs)} graphs but "
                             f"{len(arrivals)} arrivals")
        self._timed = any(a > 0 for a in arrivals)
        self._t0 = time.perf_counter()
        span_on = self._spans.enabled
        pending = sorted(
            (LaneRequest(idx=i, graph=g, cls=graph_class(g),
                         t_arrival=float(arrivals[i]),
                         rid=new_request_id() if span_on else "")
             for i, g in enumerate(graphs)),
            key=lambda r: (r.t_arrival, r.idx))
        self._bump("requests", len(pending))

        while pending or (self.pool and self.pool.occupied_lanes()):
            now = self._now()
            if self.pool is None or (
                    not self.pool.occupied_lanes()
                    and not self._arrived(pending, self.pool.cls, now)):
                # pool drained (or never opened) and nothing of its class
                # is here: wait for the next arrival and open a pool for
                # the OLDEST arrived request's class
                if not pending:
                    break
                now = self._sleep_until(pending[0].t_arrival)
                with self._spans.phase("recycle"):
                    self._close_pool()
                    self._open_pool(pending, now)
            else:
                with self._spans.phase("recycle"):
                    self._admit(pending, now)
            if not self.pool.occupied_lanes():
                # every admitted lane was dead on arrival (empty graphs);
                # retire them without burning a dispatch
                yield from self._retire_finished()
                continue
            # while same-class work is queued, hold the bucket instead of
            # shrinking as waves die: the next admission re-seeds at the
            # pool floor anyway, and a shrink/regrow pair costs two
            # re-bucketing dispatches per boundary for nothing
            self._hold_shrink = bool(
                self._arrived(pending, self.pool.cls, self._now()))
            self._superstep()
            yield from self._retire_finished()
        self._close_pool()

    # -- clock ------------------------------------------------------------

    def _now(self) -> float:
        return time.perf_counter() - self._t0

    def _sleep_until(self, t: float) -> float:
        now = self._now()
        if self._timed and t > now:
            time.sleep(t - now)
            now = self._now()
        return now

    def _arrived(self, pending, cls: str, now: float):
        """Arrived same-class requests, FIFO (pending is arrival-sorted)."""
        if not self._timed:
            return [r for r in pending if r.cls == cls]
        return [r for r in pending if r.cls == cls and r.t_arrival <= now]

    # -- pool lifecycle ---------------------------------------------------

    def _open_pool(self, pending, now: float) -> None:
        """Bind a fresh pool to the oldest arrived request's class and seed
        the first admission group (one flags+counts + ONE seeding
        dispatch — the PR-5 device-side stage 1, no per-lane H2D)."""
        head = pending[0]
        n_pad, m_pad, d_pad = class_shape(head.graph)
        # slots first (the tuner's own 'sched' knob, keyed by class), then
        # the engine knobs under the (class × pool-size) batch key — the
        # same key enumerate_batch would tune a B-lane batch under
        slots = self._resolve_slots(n_pad, m_pad, d_pad, self.cfg_base)
        cfg, tkey, observe = self.service._resolve_config(
            n_pad, m_pad, d_pad, self.cfg_base,
            explicit=self._explicit_cfg, batch=slots)
        self.pool = LanePool(slots)
        self._cap = None   # fresh pool seeds at its own bucket, no floor
        self._tcap = None  # triangle-capacity floor, pinned the same way
        # sustained traffic repeats graphs: memoize class-ceiling padding
        # (host compute + H2D per admission otherwise) and whole stacked
        # admission groups. The caches live on the SERVICE — sessions are
        # per-stream but the service (and its device) is long-lived, so a
        # familiar graph admits with zero host-side staging. Keyed by
        # object identity; entries hold the graph so its id stays valid.
        self._pad_cache = self.service.__dict__.setdefault(
            "_sched_pad_cache", {})
        self._stack_cache = self.service.__dict__.setdefault(
            "_sched_stack_cache", {})
        self.pool.cls = head.cls
        self._cfg = cfg
        self._tkey, self._observe = tkey, observe
        self._trace = self.service._new_trace(observe)
        self._shape = (n_pad, m_pad, d_pad)
        self._nw = n_words_for(n_pad)
        self._cyc_cap = (cfg.bucket(max(cfg.cycle_buffer_rows, 16))
                         if cfg.store else 1)
        self._bufbat = empty_cycle_buffer(self._cyc_cap, self._nw,
                                          batch=slots)
        self._bc_h = np.zeros(slots, np.int64)
        self._done: list[tuple[LaneRequest, dict]] = []
        self._retired_since_event = 0
        self._relaunches = 0
        self._limit_cap = 1
        self._bump("pools")
        self._g_slots.set(slots)
        self.stats["classes"][head.cls] = \
            self.stats["classes"].get(head.cls, 0) + 1

        reqs = self._arrived(pending, head.cls, now)[:slots]
        for r in reqs:
            pending.remove(r)
        padded = [self._padded(r.graph) for r in reqs]
        # free lanes carry a copy of the first padded graph as dead weight
        # (zero round budget + zeroed host count keep them inert)
        rows = padded + [padded[0]] * (slots - len(padded))
        self._gbat = self._stacked(
            [r.graph for r in reqs] + [reqs[0].graph] * (slots - len(reqs)),
            rows)
        fbat, ntris, ntrips, tri_h = self._seed(self._gbat,
                                                live=len(reqs),
                                                admitted=len(reqs),
                                                reqs=reqs)
        self._fbat = fbat
        self._cap = fbat.path.shape[1]
        for lane, r in enumerate(reqs):
            self._seat(lane, r, ntrips[lane], ntris[lane], tri_h, now)

    def _close_pool(self) -> None:
        """Drop the pool (device state garbage-collects) and run the
        first-visit tuner hook over the class's completed requests — both
        the engine knobs (lane-aware replay with ``recycle=True``) and the
        scheduler's own ``slots`` knob (``replay_sched``)."""
        if self.pool is None:
            return
        tr = self._trace
        self.stats["n_dispatches"] += tr.n_dispatches
        self.stats["n_host_syncs"] += tr.n_host_syncs
        self.stats["n_d2h_arrays"] += tr.n_d2h_arrays
        self.stats["fused_rounds"] += tr.rounds_by_path["fused"]
        self.stats["split_rounds"] += tr.rounds_by_path["split"]
        if self._observe and self._tkey is not None and self._done:
            from ..tune import WaveProfile
            n_pad, m_pad, d_pad = self._shape
            profile = WaveProfile.from_batch(
                [st["history"] for _, st in self._done],
                lane_n=[r.graph.n for r, _ in self._done],
                n=n_pad, nw=self._nw, max_iters=self._cfg.max_iters)
            tuner = self.service._tuner
            tuner.observe_profile(self._tkey, self._cfg, profile,
                                  traces=(self._trace,))
            skey = tuner.key_for_sched(n_pad, m_pad, d_pad, self._cfg)
            if tuner.store.get(skey) is None:
                tuner.tune_slots(profile, self._cfg, key=skey)
        self.pool = None
        self._gbat = self._fbat = self._bufbat = None

    def _resolve_slots(self, n: int, m: int, delta: int, cfg) -> int:
        if self.slots is not None:
            return int(self.slots)
        tuner = self.service._tuner
        if tuner is not None:
            stored = tuner.slots_for(tuner.key_for_sched(n, m, delta, cfg))
            if stored:
                return int(stored)
        return DEFAULT_SLOTS

    def _padded(self, g: BitsetGraph) -> BitsetGraph:
        key = (id(g), self._shape)
        ent = self._pad_cache.get(key)
        if ent is None:
            if len(self._pad_cache) >= 512:
                self._pad_cache.pop(next(iter(self._pad_cache)))
            n_pad, m_pad, d_pad = self._shape
            ent = (g, pad_graph(g, n_pad, m_pad, d_pad))
            self._pad_cache[key] = ent
        return ent[1]

    def _stacked(self, graphs, rows):
        """Stack padded rows into one device pytree, memoized on the row
        graphs' identity (repeated admission groups skip the stack + H2D)."""
        key = (tuple(id(g) for g in graphs), self._shape)
        out = self._stack_cache.get(key)
        if out is None:
            if len(self._stack_cache) >= 256:
                self._stack_cache.pop(next(iter(self._stack_cache)))
            # hold the graphs alongside the stacked pytree: a live ref per
            # id keeps the identity key valid for the cache's lifetime
            out = (graphs,
                   jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *rows))
            self._stack_cache[key] = out
        return out[1]

    # -- admission (the no-retrace re-seed) --------------------------------

    def _seed(self, gbat, *, live: int, admitted: int, reqs=()):
        """Batched stage 1 at the pool's pinned capacity. Returns
        (fbat, n_tri, n_trip, tri_masks host array). ``wall_ms`` on the
        boundary event covers the whole seed (staging included), not just
        the device time, and accumulates into ``boundary_ms_total``."""
        cfg, trace = self._cfg, self._trace
        with self._spans.phase("seed"):
            wall_t0 = time.perf_counter()
            trace.tic()
            fbat, tri_bat, ntris, ntrips = T.initial_frontier_batched(
                gbat, delta=self._shape[2], bucket=cfg.bucket,
                backend=cfg.backend, capacity=self._cap,
                tri_capacity=self._tcap, trace=trace)
            self._tcap = tri_bat.shape[1]
            trace.sync()
            wall_ms = (time.perf_counter() - wall_t0) * 1e3
            self.stats["boundary_ms"] += wall_ms
            self._m_boundary.inc(wall_ms)
            trace.dispatch(
                kind="seed", bucket=fbat.path.shape[1], cyc_cap=0,
                budget=0, rounds=0, status="RUN",
                enter_count=int(ntrips.sum()),
                exit_count=int(ntrips.sum()), t_ms=trace.toc_ms(),
                launches=2, lanes=self.pool.slots, live_lanes=live,
                admitted=admitted, wall_ms=wall_ms,
                lane_rids=tuple(r.rid for r in reqs))
            if self._spans.enabled and reqs:
                t_end = self._spans.now_ms()
                for r in reqs:
                    self._spans.add("seed", r.rid, t_end - wall_ms, wall_ms)
            tri_h = None
            if cfg.store:
                tri_h = np.asarray(tri_bat)
                trace.d2h()
        return fbat, ntris, ntrips, tri_h

    def _seat(self, lane: int, req: LaneRequest, n0: int, n_tri: int,
              tri_h, now: float) -> None:
        limit = max(req.graph.n - 3, 0)
        if self._cfg.max_iters is not None:
            limit = min(limit, self._cfg.max_iters)
        self._limit_cap = max(self._limit_cap, limit)
        chunk = None
        if self._cfg.store:
            chunk = tri_h[lane, :int(n_tri)].copy()
        req.t_admit = now
        self.pool.admit(lane, req, limit=limit, n0=int(n0),
                        n_tri=int(n_tri), tri_chunk=chunk)
        self._bump("admissions")
        # untimed queues arrive at t=0, so the wait is time spent behind
        # earlier admissions — the same convention the legacy path reports
        wait_ms = req.queue_wait_s * 1e3
        self.stats["queue_wait_ms"].append(round(wait_ms, 3))
        self._h_wait.observe(wait_ms, sched="recycle")
        if self._spans.enabled and req.rid:
            self._spans.add("queue_wait", req.rid,
                            self._span_ms(req.t_arrival), wait_ms,
                            lane=lane)

    def _admit(self, pending, now: float) -> None:
        """Deal arrived same-class requests into the free lanes, re-seeding
        donated buffers in place through the cached seed + merge programs
        (no retrace — DESIGN.md §6.9 walks through why)."""
        free = self.pool.free_lanes()
        reqs = self._arrived(pending, self.pool.cls, now)[:len(free)]
        if not reqs:
            if self._retired_since_event:
                self._boundary_event(admitted=0)
            return
        for r in reqs:
            pending.remove(r)
        lanes = free[:len(reqs)]
        n_pad, m_pad, d_pad = self._shape
        B = self.pool.slots

        padded = {lane: self._padded(r.graph)
                  for lane, r in zip(lanes, reqs)}
        by_lane = dict(zip(lanes, reqs))
        filler = next(iter(padded.values()))
        filler_g = by_lane[lanes[0]].graph
        rows = [padded.get(i, filler) for i in range(B)]
        g_new = self._stacked(
            [by_lane[i].graph if i in by_lane else filler_g
             for i in range(B)], rows)
        f_new, ntris, ntrips, tri_h = self._seed(
            g_new, live=len(self.pool.occupied_lanes()) + len(reqs),
            admitted=len(reqs), reqs=reqs)
        new_cap = f_new.path.shape[1]
        if new_cap > self._cap:
            # an incoming lane outgrew the pool bucket: pre-grow the
            # running frontier so the merge (and next superstep) run at
            # the larger shape — a bucket transition, not a retrace for
            # warm shapes
            with self._spans.phase("rebucket"):
                self._fbat = with_capacity_batched(self._fbat, new_cap)
                self._cap = new_cap
                self._trace.transition()

        admit = np.zeros(B, bool)
        admit[lanes] = True
        # lanes retired earlier with no successor: clear their stale live
        # counts in the same merge
        clear = np.array([i not in padded and self.pool.req[i] is None
                          for i in range(B)])
        rplan = self.service._recycle_plan(
            n_pad, m_pad, self._cap, self._cyc_cap, self._nw, d_pad,
            self._cfg, B)
        wall_t0 = time.perf_counter()
        self._trace.tic()
        self._gbat, self._fbat, self._bufbat = rplan(
            jnp.asarray(admit), jnp.asarray(clear), self._gbat, self._fbat,
            self._bufbat, g_new, f_new)
        self._trace.sync()
        merge_ms = (time.perf_counter() - wall_t0) * 1e3
        self._bc_h[admit | clear] = 0
        for lane, r in zip(lanes, reqs):
            self._seat(lane, r, ntrips[lane], ntris[lane], tri_h, now)
        if self._spans.enabled:
            t_end = self._spans.now_ms()
            for lane, r in zip(lanes, reqs):
                self._spans.add("recycle", r.rid, t_end - merge_ms,
                                merge_ms, lane=lane)
        self._boundary_event(admitted=len(reqs),
                             t_ms=self._trace.toc_ms(), wall_ms=merge_ms)

    def _boundary_event(self, *, admitted: int, t_ms: float = 0.0,
                        wall_ms: float = 0.0) -> None:
        retired = self._retired_since_event
        self._retired_since_event = 0
        if wall_ms:
            self.stats["boundary_ms"] += wall_ms
            self._m_boundary.inc(wall_ms)
        self._trace.dispatch(
            kind="recycle", bucket=self._cap, cyc_cap=self._cyc_cap,
            budget=0, rounds=0, status="RUN",
            enter_count=0, exit_count=0, t_ms=t_ms,
            launches=1 if admitted else 0,
            lanes=self.pool.slots,
            live_lanes=len(self.pool.occupied_lanes()),
            retired=retired, admitted=admitted, wall_ms=wall_ms,
            lane_rids=tuple(r.rid if r is not None else ""
                            for r in self.pool.req),
            lane_rounds=tuple(int(v) for v in self.pool.its))
        self._g_live.set(len(self.pool.occupied_lanes()))
        self._bump("boundaries")

    # -- the superstep dispatch -------------------------------------------

    def _superstep(self) -> None:
        """One vmapped wave superstep over the pool — the dispatch body of
        ``CycleService.enumerate_batch`` with the lane bookkeeping routed
        through the ``LanePool`` ledger (free lanes ride with k=0)."""
        pool, cfg, trace = self.pool, self._cfg, self._trace
        B = pool.slots
        self._relaunches += 1
        if self._relaunches > (4 * self._limit_cap + 16) * max(
                self.stats["admissions"], 1):
            raise RuntimeError(
                "continuous scheduler: no progress across relaunches")
        active = pool.active_mask()
        k_i = np.where(active, np.minimum(cfg.superstep_rounds,
                                          pool.limits - pool.its), 0)
        occ = pool.occupied_lanes()
        self._bump("supersteps")
        self.stats["occupancy_sum"] += len(occ) / B
        self._g_live.set(len(occ))

        phase = self._spans.phase
        with phase("superstep"):
            n_pad, m_pad, d_pad = self._shape
            plan = self.service._wave_plan(n_pad, m_pad, self._cap,
                                           self._cyc_cap, self._nw, d_pad,
                                           cfg, batch=B)
            fresh = plan.n_calls == 0
            cap_in, live_in = self._cap, int(pool.cnts[occ].sum())
            trace.tic()
            self._fbat, self._bufbat, r, status, th, ch, pn, pc = plan(
                self._gbat, self._fbat, self._bufbat,
                jnp.asarray(k_i, jnp.int32))
        with phase("readback"):
            fetched = (status, r, th, ch, pn, pc, self._fbat.count,
                       self._bufbat.count)
            (status_h, r_h, th_h, ch_h, pn_h, pc_h, cnt_h,
             bc_h) = jax.device_get(fetched)
            trace.sync()
            trace.d2h(len(fetched))
            status_h = np.asarray(status_h)
            lane_statuses = {int(status_h[i]) for i in occ}
            agg = next((s for s in (_DRAIN, _GROW, _SHRINK, _RUN, _DONE)
                        if s in lane_statuses), _RUN)
            step_ms = trace.toc_ms()
            trace.dispatch(
                kind="batch", bucket=cap_in, cyc_cap=self._cyc_cap,
                budget=int(k_i.max()), rounds=int(np.asarray(r_h).max()),
                status=STATUS_NAMES[agg], enter_count=live_in,
                exit_count=int(sum(int(cnt_h[i]) for i in occ)),
                cyc_fill=int(sum(int(bc_h[i]) for i in occ)),
                t_ms=step_ms, fresh=fresh, plan_key=str(plan.key),
                lanes=B, live_lanes=len(occ),
                lane_rids=tuple(r.rid if r is not None else ""
                                for r in pool.req),
                lane_rounds=tuple(int(pool.its[i]) + int(r_h[i])
                                  for i in range(B)),
                round_path=plan.round_path)
            if self._spans.enabled:
                t_end = self._spans.now_ms()
                for i in occ:
                    self._spans.add(
                        "superstep", pool.req[i].rid, t_end - step_ms,
                        step_ms, lane=i, wave=int(pool.its[i]) + int(r_h[i]),
                        rounds=int(r_h[i]))

            for i in occ:
                for j in range(int(r_h[i])):
                    pool.n_cycles[i] += int(ch_h[i, j])
                    pool.histories[i].append(
                        dict(step=int(pool.its[i]) + j + 1,
                             T=int(th_h[i, j]), C=pool.n_cycles[i]))
                pool.its[i] += int(r_h[i])
                pool.cnts[i] = int(cnt_h[i])
            self._bc_h = np.asarray(bc_h, np.int64)

        drains = [i for i in occ if int(status_h[i]) == _DRAIN]
        grows = [i for i in occ if int(status_h[i]) == _GROW]
        if drains:
            # drain EVERY occupied lane with pending masks in one host
            # copy (free lanes' stale rows are dropped by the reset)
            with phase("drain"):
                masks_h = np.asarray(self._bufbat.masks)
                trace.d2h()
                for i in occ:
                    bc = int(bc_h[i])
                    if bc:
                        pool.chunks[i].append(masks_h[i, :bc].copy())
                        trace.drain()
                trace.sync()
                self._cyc_cap = max(
                    self._cyc_cap,
                    cfg.bucket(max(max(int(pc_h[i]) for i in drains), 1)))
                self._bufbat = empty_cycle_buffer(self._cyc_cap, self._nw,
                                                  batch=B)
                self._bc_h[:] = 0
        if grows:
            need = max(int(pn_h[i]) for i in grows)
            new_cap = cfg.bucket(cfg.bucket(max(need, 1))
                                 << max(cfg.grow_headroom, 0))
            if new_cap != self._cap:
                with phase("rebucket"):
                    self._fbat = with_capacity_batched(self._fbat, new_cap)
                    self._cap = new_cap
                    trace.transition()
        elif (not drains and not getattr(self, "_hold_shrink", False)
              and pool.cnts[occ].max(initial=0) > 0):
            new_cap = cfg.bucket(max(int(pool.cnts[occ].max()), 1))
            if new_cap < self._cap:
                with phase("rebucket"):
                    self._fbat = with_capacity_batched(self._fbat, new_cap)
                    self._cap = new_cap
                    trace.transition()

    # -- retirement --------------------------------------------------------

    def _retire_finished(self):
        """Superstep-boundary drain: flush each finished lane's pending
        CycleBuffer rows and yield its completed result. The lane is FREE
        afterwards; its stale device rows are inert (zero budget) until the
        next admission merges over them. Results go out after the
        ``retire`` phase closes: the caller's work between them is not the
        scheduler's."""
        with self._spans.phase("retire"):
            done = self._retire()
        yield from done

    def _retire(self) -> list:
        pool, cfg = self.pool, self._cfg
        finished = pool.finished_lanes()
        done = []
        if not finished:
            return done
        masks_h = None
        drain_t0 = self._spans.now_ms() if self._spans.enabled else 0.0
        if cfg.store and any(self._bc_h[i] for i in finished):
            with self._spans.phase("drain"):
                masks_h = np.asarray(self._bufbat.masks)
                self._trace.sync()
                self._trace.d2h()
        now = self._now()
        for i in finished:
            drained = False
            if cfg.store and self._bc_h[i]:
                pool.chunks[i].append(
                    masks_h[i, :int(self._bc_h[i])].copy())
                self._trace.drain()
                self._bc_h[i] = 0
                drained = True
                # the device-side count stays stale until the admission
                # merge clears it; rows beyond the host mirror are never
                # re-flushed because retirement is the only reader
            req, state = pool.retire(i)
            req.t_done = now
            self._done.append((req, state))
            self._relaunches = 0
            self._retired_since_event += 1
            self._bump("retirements")
            self._bump("completed")
            self.stats["n_cycles"] += state["n_cycles"]
            e2e = req.e2e_s * 1e3
            self.stats["e2e_ms"].append(round(e2e, 3))
            self._h_e2e.observe(e2e, sched="recycle")
            if self._spans.enabled and req.rid:
                t_done_ms = self._span_ms(req.t_done)
                if drained:
                    self._spans.add("drain", req.rid, drain_t0,
                                    max(t_done_ms - drain_t0, 0.0), lane=i)
                self._spans.add("retire", req.rid, t_done_ms, 0.0, lane=i,
                                rounds=state["iterations"])
                self._spans.add("request", req.rid,
                                self._span_ms(req.t_arrival), e2e, lane=i,
                                idx=req.idx, cls=req.cls)
            done.append((req.idx, self._render(req, state)))
        return done

    def _render(self, req: LaneRequest, state: dict) -> EnumerationResult:
        masks = None
        if self._cfg.store:
            masks = (np.concatenate(state["chunks"], axis=0)
                     if state["chunks"]
                     else np.zeros((0, self._nw), np.uint32))
        return EnumerationResult(
            n_cycles=state["n_cycles"], n_triangles=state["n_triangles"],
            cycle_masks=masks, iterations=state["iterations"],
            history=state["history"],
            stats=dict(recycled=True, pool_slots=self.pool.slots,
                       rounds=state["iterations"],
                       queue_wait_ms=round(req.queue_wait_s * 1e3, 3),
                       e2e_ms=round(req.e2e_s * 1e3, 3)))
