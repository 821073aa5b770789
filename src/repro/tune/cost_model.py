"""Analytic dispatch-cost model — the scoring layer of ``repro.tune``.

Key observation (DESIGN.md §6.6): the per-round wave shape — how many
chordless paths are alive after each expansion round, how many cycles each
round closes — is a property of the GRAPH, not of the engine knobs. A
guarded round that overflows is never applied (the relaunch re-executes it
bit-identically), so every knob assignment walks the exact same |T|/|C|
sequence; knobs only change HOW the walk is chopped into dispatches and how
much padding each dispatch drags along. That makes candidate scoring a pure
host-side computation:

* ``WaveProfile``  — the knob-independent wave shape, extracted from any
                    run's ``history`` (or a recorded ``WaveTrace``).
* ``replay``       — a digital twin of the host driver loop
                    (``core.service._wave_events`` + the superstep's guard
                    logic): chops a profile into dispatches under a
                    candidate config and returns the dispatch/sync/waste
                    accounting that run WOULD have had.
* ``DistProfile`` / ``replay_dist`` — the sharded twin: the same wave shape
                    plus the observed per-device peaks and balance cadence
                    of a ``core.distributed`` run. The dispatch/sync/round
                    chop is exact (the busiest device's per-round rows,
                    recorded in the trace, drive the driver's own bucket
                    policy ``core.distributed.dist_next_bucket``);
                    per-device placement under a
                    DIFFERENT ``balance_every`` / ``local_capacity`` is not
                    replayable without re-running the diffusion, so the
                    twin carries a conservative *feasibility guard*: a
                    candidate whose local capacity cannot provably hold the
                    estimated per-device peak scores infinite and is never
                    picked over the base config (which ran, so is always
                    feasible).
* ``CostModel``    — converts a replay into milliseconds:
                    ``a·dispatches + b·row_work + c·syncs (+ d·programs)``,
                    with (a, b) least-squares fitted from recorded traces
                    (warm dispatches only; fresh-program dispatches fit the
                    compile term ``d``). Falls back to conservative CPU
                    defaults when no timed traces exist, so model-guided
                    ranking works even trace-free.

The replay is exact by construction and is property-tested against the real
driver's counters (``tests/test_tune.py``).
"""
from __future__ import annotations

import collections
import dataclasses

import numpy as np

from .telemetry import STATUSES

# exit statuses by canonical name (single source: telemetry.STATUSES)
_RUN, _DONE, _GROW, _DRAIN, _SHRINK = STATUSES


# ---------------------------------------------------------------------------
# The knob-independent wave shape
# ---------------------------------------------------------------------------

def _lane_shape(history):
    """(n0, t_sizes, c_counts) of one lane's history."""
    if not history:
        return 0, (), ()
    t = tuple(int(h["T"]) for h in history[1:])
    cum = [int(h["C"]) for h in history]
    c = tuple(cum[i + 1] - cum[i] for i in range(len(cum) - 1))
    return int(history[0]["T"]), t, c


@dataclasses.dataclass(frozen=True)
class WaveProfile:
    """Per-round wave shape of one enumeration, independent of engine knobs.

    ``t_sizes[i]`` is |T| after round i+1; ``c_counts[i]`` the cycles closed
    by round i+1 (triangles are stage-1 output and never touch the ring).

    A BATCHED enumeration (``enumerate_batch``) profiles into the same
    class with the per-lane shapes retained (``lane_*`` fields;
    ``from_batch``): ``t_sizes``/``c_counts`` then hold the per-round MAX
    over lanes (what drives the shared bucket and ring), and the lane-aware
    ``replay`` path accounts the lane-padded occupancy — a finished lane
    still burns its full bucket every round until the slowest lane in the
    dispatch exits, which is exactly the superstep_rounds ↔ lane-imbalance
    trade the autotuner searches over (DESIGN.md §6.7).
    """
    n: int                     # |V| (sets the |V|-3 round budget)
    nw: int                    # mask words per row
    n0: int                    # initial frontier size (stage-1 triplets)
    t_sizes: tuple[int, ...]
    c_counts: tuple[int, ...]
    max_iters: int | None = None
    # --- batched profiles only (lanes == 1 otherwise) ---------------------
    lane_n: tuple[int, ...] = ()       # per-lane |V| (per-lane round budget)
    lane_n0: tuple[int, ...] = ()
    lane_t: tuple[tuple[int, ...], ...] = ()
    lane_c: tuple[tuple[int, ...], ...] = ()

    @property
    def lanes(self) -> int:
        return max(len(self.lane_t), 1)

    @property
    def limit(self) -> int:
        lim = max(self.n - 3, 0)
        return lim if self.max_iters is None else min(lim, self.max_iters)

    @property
    def peak(self) -> int:
        return max((self.n0,) + self.t_sizes, default=0)

    @classmethod
    def from_history(cls, history, *, n: int, nw: int,
                     max_iters: int | None = None) -> "WaveProfile":
        """Build from ``EnumerationResult.history`` (step-0 entry holds the
        initial |T| and the triangle count; later C entries are cumulative)."""
        n0, t, c = _lane_shape(history)
        return cls(n=n, nw=nw, n0=n0, t_sizes=t, c_counts=c,
                   max_iters=max_iters)

    @classmethod
    def from_batch(cls, histories, *, lane_n, n: int, nw: int,
                   max_iters: int | None = None) -> "WaveProfile":
        """Lane-aware profile of one batched enumeration: per-lane
        histories retained, aggregates = per-round max over lanes (the
        shared bucket/ring trackers). ``n`` is the padded |V| the batch ran
        at; ``lane_n`` the real per-lane |V| (per-lane round budgets)."""
        shapes = [_lane_shape(h) for h in histories]
        rounds = max((len(t) for _, t, _ in shapes), default=0)
        agg = lambda seqs, i: max((s[i] if i < len(s) else 0 for s in seqs),
                                  default=0)
        t_all = [t for _, t, _ in shapes]
        c_all = [c for _, _, c in shapes]
        return cls(
            n=n, nw=nw, n0=max((n0 for n0, _, _ in shapes), default=0),
            t_sizes=tuple(agg(t_all, i) for i in range(rounds)),
            c_counts=tuple(agg(c_all, i) for i in range(rounds)),
            max_iters=max_iters,
            lane_n=tuple(int(x) for x in lane_n),
            lane_n0=tuple(n0 for n0, _, _ in shapes),
            lane_t=tuple(t_all), lane_c=tuple(c_all))

    def to_json(self) -> dict:
        out = dict(n=self.n, nw=self.nw, n0=self.n0,
                   t_sizes=list(self.t_sizes), c_counts=list(self.c_counts),
                   max_iters=self.max_iters)
        if self.lane_t:
            out.update(lane_n=list(self.lane_n), lane_n0=list(self.lane_n0),
                       lane_t=[list(t) for t in self.lane_t],
                       lane_c=[list(c) for c in self.lane_c])
        return out

    @classmethod
    def from_json(cls, d: dict) -> "WaveProfile":
        return cls(n=int(d["n"]), nw=int(d["nw"]), n0=int(d["n0"]),
                   t_sizes=tuple(d["t_sizes"]), c_counts=tuple(d["c_counts"]),
                   max_iters=d.get("max_iters"),
                   lane_n=tuple(d.get("lane_n", ())),
                   lane_n0=tuple(d.get("lane_n0", ())),
                   lane_t=tuple(tuple(t) for t in d.get("lane_t", ())),
                   lane_c=tuple(tuple(c) for c in d.get("lane_c", ())))


# ---------------------------------------------------------------------------
# Replay: the host driver as a pure function of (profile, config)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ReplaySummary:
    """What one (profile, config) run would cost, in driver events."""
    n_dispatches: int
    n_host_syncs: int
    n_bucket_transitions: int
    n_drains: int
    rounds: int
    row_work: int             # row·word units over every attempted round
    padded_waste: int         # the dead-row share of row_work
    n_programs: int           # distinct (bucket, cyc_cap) shapes → compiles
    peak_bucket: int
    by_cause: dict
    # sharded-twin extras (single-device replays leave the defaults)
    feasible: bool = True     # False: candidate capacity cannot provably
    #                           hold the estimated per-device peak → scored
    #                           infinite, never picked over the base config
    est_peak_device: int = 0  # the guard's per-device peak estimate
    # two-tier link traffic (2-level meshes; DESIGN.md §7) — modeled wire
    # bytes of the balance hops, per tier, charged at per-tier bandwidth
    bytes_intra: int = 0      # intra-host (device-ring) balance bytes
    bytes_cross: int = 0      # cross-host (host-ring) balance bytes
    # persistent multi-round launches (DESIGN.md §6.11): kernel launches
    # (= frontier HBM round-trips) across the run — ⌈attempted/R⌉ per
    # dispatch. R=1 makes this the attempted-round total and leaves every
    # other column (row_work, waste, dispatches, syncs) bit-identical to
    # the pre-persistent twin.
    n_kernel_launches: int = 0


def replay(profile: WaveProfile, cfg, *, recycle: bool = False
           ) -> ReplaySummary:
    """Digital twin of ``core.service._wave_events`` for a candidate config.

    ``cfg`` is duck-typed: needs ``bucket()``, ``store``,
    ``superstep_rounds``, ``grow_headroom``, ``cycle_buffer_rows``. Mirrors
    the driver exactly — superstep guard order (ring check happens with the
    frontier check; GROW outranks DRAIN on a double overflow), SHRINK decay
    threshold at cap//4 (buckets ≤16 never shrink), pending sizes choosing
    the next bucket, and the ring carrying its fill across dispatches.

    Lane-aware profiles (``WaveProfile.from_batch``) replay through the
    batched driver's twin instead (``_replay_batch``). ``recycle=True``
    models the lane-recycling pool of DESIGN.md §6.9: a finished (or
    aborted) lane's dead bucket is NOT charged for the rounds after it
    exits — the waste the continuous scheduler reclaims. Single-lane
    profiles have no dead lanes, so the flag is a no-op there.
    """
    if profile.lanes > 1:
        return _replay_batch(profile, cfg, recycle=recycle)
    limit = profile.limit
    t, c = profile.t_sizes, profile.c_counts
    nw = max(profile.nw, 1)
    rpl = max(int(getattr(cfg, "rounds_per_launch", 1)), 1)
    cnt = profile.n0
    cap = cfg.bucket(max(cnt, 1))
    cyc_cap = cfg.bucket(max(cfg.cycle_buffer_rows, 16)) if cfg.store else 1
    K = cfg.superstep_rounds

    dispatches = syncs = transitions = drains = 0
    row_work = waste = launches = 0
    by_cause: dict[str, int] = {}
    programs = set()
    peak = cap
    fill = 0
    syncs += 1                      # stage-1 count readback
    it = 0
    # a consistent profile ends with |T|=0 or at the round budget; the
    # len(t) bound additionally keeps truncated profiles (max_iters probes)
    # from overrunning
    while it < min(limit, len(t)) and cnt > 0:
        k = min(K, limit - it)
        programs.add((cap, cyc_cap))
        peak = max(peak, cap)
        shrink_below = cap // 4 if cap > 16 else 0
        r = 0
        status = _RUN
        pn = pc = 0
        enter = cnt
        while status == _RUN and r < k and cnt > 0 and it + r < len(t):
            n_new, n_cyc = t[it + r], c[it + r]
            ok_f = n_new <= cap
            ok_c = (fill + n_cyc <= cyc_cap) if cfg.store else True
            row_work += cap * nw
            waste += max(cap - max(cnt, 1), 0) * nw
            if not (ok_f and ok_c):
                status = _DRAIN if ok_f else _GROW
                pn, pc = n_new, n_cyc
                break
            r += 1
            fill += n_cyc if cfg.store else 0
            cnt = n_new
            # the persistent driver evaluates the decay exit only at
            # LAUNCH boundaries (every rpl-th round); rpl=1 keeps the
            # per-round check
            if 0 < n_new <= shrink_below and r % rpl == 0:
                status = _SHRINK
        if status == _RUN and 0 < cnt <= shrink_below:
            status = _SHRINK          # final (partial-launch) boundary
        if status in (_RUN, _SHRINK) and cnt == 0:
            status = _DONE
        # one persistent launch per R attempted rounds; the launch's
        # rounds past the trip/death point degrade to identity
        # copy-through — one frontier pass each, all of it waste
        att = r + (1 if status in (_GROW, _DRAIN) else 0)
        n_launches = -(-att // rpl)
        launches += n_launches
        idle = n_launches * rpl - att
        row_work += idle * cap * nw
        waste += idle * cap * nw
        dispatches += 1
        syncs += 1
        by_cause[status] = by_cause.get(status, 0) + 1
        it += r
        if status == _DRAIN:
            if fill:
                syncs += 1
                drains += 1
                fill = 0
            cyc_cap = max(cyc_cap, cfg.bucket(max(pc, 1)))
        elif status == _GROW:
            cap = cfg.bucket(cfg.bucket(max(pn, 1))
                             << max(cfg.grow_headroom, 0))
            transitions += 1
        elif status in (_RUN, _SHRINK) and cnt > 0:
            new_cap = cfg.bucket(max(cnt, 1))
            if new_cap < cap:
                cap = new_cap
                transitions += 1
        elif status == _DONE:
            break
    if cfg.store:
        syncs += 1
        if fill:
            drains += 1
    return ReplaySummary(
        n_dispatches=dispatches, n_host_syncs=syncs,
        n_bucket_transitions=transitions, n_drains=drains, rounds=it,
        row_work=row_work, padded_waste=waste, n_programs=len(programs),
        peak_bucket=peak, by_cause=by_cause, n_kernel_launches=launches)


# ---------------------------------------------------------------------------
# Batched twin (core/service.enumerate_batch's driver; DESIGN.md §6.7)
# ---------------------------------------------------------------------------

def _lane_superstep(t, c, it, cnt, fill, k, cap, cyc_cap, store,
                    shrink_below, rpl=1):
    """One lane's guarded superstep — the per-lane half of the vmapped
    ``wave_superstep``. Returns (r, status, cnt, fill, pn, pc)."""
    r = 0
    status = _RUN
    pn = pc = 0
    while status == _RUN and r < k and cnt > 0 and it + r < len(t):
        n_new, n_cyc = t[it + r], c[it + r]
        ok_f = n_new <= cap
        ok_c = (fill + n_cyc <= cyc_cap) if store else True
        if not (ok_f and ok_c):
            status = _DRAIN if ok_f else _GROW
            pn, pc = n_new, n_cyc
            break
        r += 1
        fill += n_cyc if store else 0
        cnt = n_new
        # decay exit only at launch boundaries (cf. ``replay``)
        if 0 < n_new <= shrink_below and r % rpl == 0:
            status = _SHRINK
    if status == _RUN and 0 < cnt <= shrink_below:
        status = _SHRINK
    if status in (_RUN, _SHRINK) and cnt == 0:
        status = _DONE
    return r, status, cnt, fill, pn, pc


def _replay_batch(profile: WaveProfile, cfg, *,
                  recycle: bool = False) -> ReplaySummary:
    """Digital twin of ``core.service.enumerate_batch`` for a lane-aware
    profile: per-lane supersteps simulated under the SHARED bucket/ring,
    host transitions aggregated exactly like the batched driver.

    The lane-padded occupancy is what this twin accounts that the
    single-graph twin cannot: every device round costs ``lanes × cap``
    rows, and a lane that finished (or aborted) early still burns its full
    bucket until the dispatch's slowest lane exits — raising
    ``superstep_rounds`` amortizes dispatches but amplifies exactly this
    imbalance waste, which is the trade the autotuner searches.

    ``recycle=True`` stops charging a lane once its rounds in the dispatch
    are spent (the recycling pool masks exited lanes instead of dragging
    their buckets) — the row-work delta between the two flags is exactly
    the recoverable dead-lane waste.
    """
    B = profile.lanes
    t, c = profile.lane_t, profile.lane_c
    nw = max(profile.nw, 1)
    rpl = max(int(getattr(cfg, "rounds_per_launch", 1)), 1)
    limits = []
    for ln in profile.lane_n:
        lim = max(int(ln) - 3, 0)
        if profile.max_iters is not None:
            lim = min(lim, profile.max_iters)
        limits.append(lim)
    cnts = list(profile.lane_n0)
    cap = cfg.bucket(max(max(cnts, default=0), 1))
    cyc_cap = cfg.bucket(max(cfg.cycle_buffer_rows, 16)) if cfg.store else 1
    K = cfg.superstep_rounds

    dispatches = syncs = transitions = drains = 0
    row_work = waste = launches = 0
    by_cause: dict[str, int] = {}
    programs = set()
    peak = cap
    fills = [0] * B
    its = [0] * B

    # stage 1: counts readback + one batched seeding dispatch (the driver's
    # 'seed' trace event counts 2 launches and 1 sync)
    dispatches += 2
    syncs += 1
    by_cause[_RUN] = by_cause.get(_RUN, 0) + 1

    # the len(t) bound keeps truncated profiles (max_iters probes) from
    # spinning on a lane whose history ran out mid-wave — the same guard
    # the single-lane replay carries in its loop condition
    def _active(i):
        return its[i] < min(limits[i], len(t[i])) and cnts[i] > 0

    active = [_active(i) for i in range(B)]
    relaunches = 0
    relaunch_bound = 4 * max(limits, default=0) + 16  # driver's own bound
    while any(active) and relaunches <= relaunch_bound:
        relaunches += 1
        programs.add((cap, cyc_cap))
        peak = max(peak, cap)
        shrink_below = cap // 4 if cap > 16 else 0
        rs, statuses, pns, pcs = [], [], [], []
        enters = list(cnts)
        for i in range(B):
            k = min(K, limits[i] - its[i]) if active[i] else 0
            r, status, cnt, fill, pn, pc = _lane_superstep(
                t[i], c[i], its[i], cnts[i], fills[i], k, cap, cyc_cap,
                cfg.store, shrink_below, rpl)
            rs.append(r)
            statuses.append(status)
            pns.append(pn)
            pcs.append(pc)
            cnts[i] = cnt
            fills[i] = fill
            its[i] += r
        dispatches += 1
        syncs += 1
        agg = next(s for s in (_DRAIN, _GROW, _SHRINK, _RUN, _DONE)
                   if s in statuses)
        by_cause[agg] = by_cause.get(agg, 0) + 1

        # device work: the vmapped while_loop runs until the SLOWEST lane's
        # cond goes false; masked lanes burn their whole bucket every round
        attempts = [rs[i] + (1 if statuses[i] in (_GROW, _DRAIN) else 0)
                    for i in range(B)]
        max_att = max(attempts, default=0)
        # the vmapped persistent launch advances R rounds for ALL lanes;
        # grid rounds past the slowest lane's exit are identity passes
        n_launches = -(-max_att // rpl)
        launches += n_launches
        idle = n_launches * rpl - max_att
        row_work += idle * B * cap * nw
        waste += idle * B * cap * nw
        for j in range(max_att):
            lanes_j = ([i for i in range(B) if j < attempts[i]]
                       if recycle else list(range(B)))
            row_work += len(lanes_j) * cap * nw
            for i in lanes_j:
                enter = enters[i] if j == 0 else (
                    t[i][its[i] - rs[i] + j - 1]
                    if its[i] - rs[i] + j - 1 < len(t[i]) and j <= attempts[i]
                    else 0)
                live = enter if j < attempts[i] else 0
                waste += max(cap - max(live, 1), 0) * nw

        drain_lanes = [i for i in range(B) if statuses[i] == _DRAIN]
        grow_lanes = [i for i in range(B) if statuses[i] == _GROW]
        if drain_lanes:
            for i in range(B):
                if fills[i]:
                    drains += 1
            syncs += 1
            cyc_cap = max(cyc_cap,
                          cfg.bucket(max(max(pcs[i] for i in drain_lanes),
                                         1)))
            fills = [0] * B
        if grow_lanes:
            need = max(pns[i] for i in grow_lanes)
            new_cap = cfg.bucket(cfg.bucket(max(need, 1))
                                 << max(cfg.grow_headroom, 0))
            if new_cap != cap:
                cap = new_cap
                transitions += 1
        elif not drain_lanes and max(cnts, default=0) > 0:
            new_cap = cfg.bucket(max(max(cnts), 1))
            if new_cap < cap:
                cap = new_cap
                transitions += 1
        active = [_active(i) for i in range(B)]

    if cfg.store:
        for i in range(B):
            if fills[i]:
                drains += 1
        syncs += 1
    return ReplaySummary(
        n_dispatches=dispatches, n_host_syncs=syncs,
        n_bucket_transitions=transitions, n_drains=drains,
        rounds=max(its, default=0), row_work=row_work, padded_waste=waste,
        n_programs=len(programs), peak_bucket=peak, by_cause=by_cause,
        n_kernel_launches=launches)


# ---------------------------------------------------------------------------
# Scheduler twin (sched.ContinuousScheduler's drain/admit loop; §6.9)
# ---------------------------------------------------------------------------

def replay_sched(profile: WaveProfile, cfg, *, slots: int) -> ReplaySummary:
    """Digital twin of ``sched.ContinuousScheduler`` for a candidate slot
    count: the profile's lanes become a FIFO request QUEUE served by a
    ``slots``-lane recycling pool.

    This is the trade ``TuneSpace.admit_slots`` searches: more slots
    amortize dispatch/sync overhead across more lanes per launch but widen
    every row of device work (``slots × cap`` rows per round, minus the
    lanes recycling masks off), while fewer slots serve the queue in more
    pool generations, each paying its own seed dispatch. Admission charges
    the driver's seed cost (2 launches + 1 sync, the 'seed'/'recycle'
    boundary events); retirement flushes a storing lane's ring
    (sync + drain). Rounds report the TOTAL rounds advanced across all
    requests (the queue is many enumerations, not one).
    """
    if not profile.lane_t:
        raise ValueError("replay_sched needs a lane-aware profile "
                         "(WaveProfile.from_batch)")
    R = profile.lanes
    B = max(int(slots), 1)
    nw = max(profile.nw, 1)
    rpl = max(int(getattr(cfg, "rounds_per_launch", 1)), 1)
    t_all, c_all, n0_all = profile.lane_t, profile.lane_c, profile.lane_n0
    limits_all = []
    for ln in profile.lane_n:
        lim = max(int(ln) - 3, 0)
        if profile.max_iters is not None:
            lim = min(lim, profile.max_iters)
        limits_all.append(lim)
    queue = collections.deque(range(R))
    K = cfg.superstep_rounds
    cyc_cap = cfg.bucket(max(cfg.cycle_buffer_rows, 16)) if cfg.store else 1

    dispatches = syncs = transitions = drains = 0
    row_work = waste = total_rounds = launches = 0
    by_cause: dict[str, int] = {}
    programs = set()
    cap = peak = 0
    lane_req: list[int | None] = [None] * B
    its = [0] * B
    cnts = [0] * B
    fills = [0] * B

    def _bound(ridx):
        return min(limits_all[ridx], len(t_all[ridx]))

    guard = 0
    guard_bound = 16 * (sum(limits_all) + R + 16)
    while queue or any(r is not None for r in lane_req):
        guard += 1
        if guard > guard_bound:       # truncated-profile backstop
            break
        # --- admit: re-deal queued requests into every free lane ---------
        free = [i for i in range(B) if lane_req[i] is None]
        admitted = False
        while queue and free:
            i = free.pop(0)
            ridx = queue.popleft()
            lane_req[i] = ridx
            its[i] = 0
            cnts[i] = n0_all[ridx]
            fills[i] = 0
            admitted = True
        if admitted:
            dispatches += 2           # batched stage 1 + merge/seed launch
            syncs += 1                # ... and its counts readback
            by_cause[_RUN] = by_cause.get(_RUN, 0) + 1
            occ0 = [i for i in range(B) if lane_req[i] is not None]
            new_cap = cfg.bucket(max(max(cnts[i] for i in occ0), 1))
            if new_cap > cap:
                if cap:
                    transitions += 1  # pre-grow before the merge
                cap = new_cap
        occ = [i for i in range(B) if lane_req[i] is not None]
        act = [i for i in occ
               if its[i] < _bound(lane_req[i]) and cnts[i] > 0]
        if act:
            programs.add((cap, cyc_cap))
            peak = max(peak, cap)
            shrink_below = cap // 4 if cap > 16 else 0
            rs, statuses, pns, pcs = {}, {}, {}, {}
            enters = {i: cnts[i] for i in occ}
            for i in occ:
                ridx = lane_req[i]
                k = min(K, limits_all[ridx] - its[i]) if i in act else 0
                r, status, cnt, fill, pn, pc = _lane_superstep(
                    t_all[ridx], c_all[ridx], its[i], cnts[i], fills[i], k,
                    cap, cyc_cap, cfg.store, shrink_below, rpl)
                rs[i], statuses[i], pns[i], pcs[i] = r, status, pn, pc
                cnts[i], fills[i] = cnt, fill
                its[i] += r
                total_rounds += r
            dispatches += 1
            syncs += 1
            agg = next(s for s in (_DRAIN, _GROW, _SHRINK, _RUN, _DONE)
                       if s in statuses.values())
            by_cause[agg] = by_cause.get(agg, 0) + 1
            # device work: only OCCUPIED lanes that still have rounds left
            # in this dispatch are charged — exited/free lanes are the
            # recycling savings (cf. _replay_batch recycle=True)
            attempts = {i: rs[i] + (1 if statuses[i] in (_GROW, _DRAIN)
                                    else 0) for i in occ}
            max_att = max(attempts.values(), default=0)
            n_launches = -(-max_att // rpl)
            launches += n_launches
            idle = n_launches * rpl - max_att
            row_work += idle * len(occ) * cap * nw
            waste += idle * len(occ) * cap * nw
            for j in range(max_att):
                lanes_j = [i for i in occ if j < attempts[i]]
                row_work += len(lanes_j) * cap * nw
                for i in lanes_j:
                    ridx = lane_req[i]
                    enter = enters[i] if j == 0 else (
                        t_all[ridx][its[i] - rs[i] + j - 1]
                        if its[i] - rs[i] + j - 1 < len(t_all[ridx]) else 0)
                    waste += max(cap - max(enter, 1), 0) * nw
            drain_lanes = [i for i in occ if statuses[i] == _DRAIN]
            grow_lanes = [i for i in occ if statuses[i] == _GROW]
            if drain_lanes:
                for i in occ:
                    if fills[i]:
                        drains += 1
                        fills[i] = 0
                syncs += 1
                cyc_cap = max(cyc_cap,
                              cfg.bucket(max(max(pcs[i]
                                                 for i in drain_lanes), 1)))
            if grow_lanes:
                need = max(pns[i] for i in grow_lanes)
                new_cap = cfg.bucket(cfg.bucket(max(need, 1))
                                     << max(cfg.grow_headroom, 0))
                if new_cap != cap:
                    cap = new_cap
                    transitions += 1
            elif not drain_lanes and max((cnts[i] for i in occ),
                                         default=0) > 0:
                new_cap = cfg.bucket(max(max(cnts[i] for i in occ), 1))
                if new_cap < cap:
                    cap = new_cap
                    transitions += 1
        # --- retire: flush + free every finished lane ---------------------
        for i in occ:
            ridx = lane_req[i]
            if its[i] >= _bound(ridx) or cnts[i] <= 0:
                if cfg.store and fills[i]:
                    drains += 1
                    syncs += 1
                    fills[i] = 0
                lane_req[i] = None
                cnts[i] = 0
    return ReplaySummary(
        n_dispatches=dispatches, n_host_syncs=syncs,
        n_bucket_transitions=transitions, n_drains=drains,
        rounds=total_rounds, row_work=row_work, padded_waste=waste,
        n_programs=len(programs), peak_bucket=peak, by_cause=by_cause,
        n_kernel_launches=launches)


# ---------------------------------------------------------------------------
# Sharded twin (core/distributed.py's superstep driver)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DistProfile:
    """Wave shape of one SHARDED enumeration plus the placement facts the
    feasibility guard needs.

    ``t_sizes`` / ``c_counts`` are GLOBAL per-round totals (knob-independent
    exactly like the single-device profile — placement does not change what
    expands); ``peak_device_live`` is the observed per-device peak of the
    profiling run, valid under ``base_balance_every`` /
    ``base_local_capacity``.
    """
    n: int
    nw: int
    ndev: int
    n0: int                    # initial frontier size (global)
    t_sizes: tuple[int, ...]
    c_counts: tuple[int, ...]
    peak_device_live: int
    base_local_capacity: int
    base_balance_every: int
    balance_block: int
    max_iters: int | None = None
    # 2-level mesh facts (flat runs leave the defaults; DESIGN.md §7)
    nhost: int = 1                     # host-tier size H (ndev = H·D)
    base_cross_balance_every: int = 1  # cross cadence of the profiled run
    # per-device bucketing facts (``TraceEvent.dev_new`` / ``dev_live`` of
    # the profiled run, one entry per round; empty without a trace, when
    # the global sizes stand in — everything on one device)
    dev_new: tuple[int, ...] = ()      # busiest device's rows before balance
    dev_live: tuple[int, ...] = ()     # busiest device's rows after it
    deal_bucket: int = 0               # bucket the deal ran at (0: unknown)

    @property
    def limit(self) -> int:
        lim = max(self.n - 3, 0)
        return lim if self.max_iters is None else min(lim, self.max_iters)

    @property
    def peak(self) -> int:
        return max((self.n0,) + self.t_sizes, default=0)

    @classmethod
    def from_run(cls, history, *, n: int, nw: int, ndev: int, cfg,
                 traces=()) -> "DistProfile":
        """Build from a sharded run's ``history`` + recorded ``WaveTrace``s
        (whose 'dist' events carry the per-device peaks). Without any
        per-device observation the peak falls back to the GLOBAL peak —
        the worst case (everything on one device), which only makes the
        feasibility guard stricter."""
        base = WaveProfile.from_history(history, n=n, nw=nw,
                                        max_iters=cfg.max_iters)
        peak_dev = deal_bucket = 0
        dev_new: list[int] = []
        dev_live: list[int] = []
        for tr in traces:
            for e in getattr(tr, "events", []):
                if e.kind == "dist" and e.per_device:
                    peak_dev = max(peak_dev, max(e.per_device))
                if e.kind == "dist":
                    dev_new.extend(e.dev_new)
                    dev_live.extend(e.dev_live)
                elif e.kind == "deal":
                    deal_bucket = e.bucket
        if peak_dev == 0:
            peak_dev = base.peak
        host_axis = getattr(cfg, "host_axis", None)
        nhost = (int(cfg.mesh.shape[host_axis])
                 if host_axis and getattr(cfg, "mesh", None) is not None
                 else 1)
        return cls(n=n, nw=nw, ndev=max(int(ndev), 1), n0=base.n0,
                   t_sizes=base.t_sizes, c_counts=base.c_counts,
                   peak_device_live=peak_dev,
                   base_local_capacity=int(cfg.local_capacity),
                   base_balance_every=max(int(cfg.balance_every), 1),
                   balance_block=int(cfg.balance_block),
                   max_iters=cfg.max_iters, nhost=max(nhost, 1),
                   base_cross_balance_every=max(
                       int(getattr(cfg, "cross_balance_every", 1)), 1),
                   dev_new=tuple(dev_new), dev_live=tuple(dev_live),
                   deal_bucket=int(deal_bucket))


def replay_dist(profile: DistProfile, cfg) -> ReplaySummary:
    """Digital twin of ``core.distributed.enumerate_sharded`` for a
    candidate config.

    ``cfg`` is duck-typed: needs ``superstep_rounds``, ``local_capacity``,
    ``balance_every``, ``balance_block``, ``grow_headroom`` and ``bucket``.
    Mirrors the driver exactly where the driver is deterministic — the
    K-round dispatch chop with on-device exits (a superstep ends on budget,
    the round the global wave dies, a GROW abort or a SHRINK exit), each
    superstep charged at the per-device bucket it ran at and re-bucketed
    through ``dist_next_bucket``, one deal dispatch + one
    readback per superstep + one final counter fetch. The busiest device's
    rows per round come from the profiled run, so the chop is exact under
    its balance cadence and block and an estimate under another. It is
    conservative where the driver is not deterministic: per-device peaks
    under a different balance cadence are ESTIMATED (scaled linearly with
    the cadence ratio) and a candidate is marked infeasible unless its
    capacity holds twice the estimate (capacities at or above the base
    config's, which demonstrably ran, are always feasible). Balance traffic
    is charged as block·ndev row-work per balance round, and — on 2-level
    profiles — as per-tier WIRE BYTES (``dist_wire_bytes``, the same
    formula the driver meters) so ``CostModel.score`` can price the
    cross-host hop at its own bandwidth: the balance-cadence ↔
    interconnect-bandwidth trade the tuner searches.
    """
    from ..core.distributed import (dist_bucket_range, dist_first_bucket,
                                    dist_next_bucket, dist_wire_bytes)
    limit = profile.limit
    t = profile.t_sizes
    nw = max(profile.nw, 1)
    ndev = max(profile.ndev, 1)
    nhost = max(getattr(profile, "nhost", 1), 1)
    dev_size = max(ndev // nhost, 1)
    cap = int(cfg.local_capacity)
    K = max(int(cfg.superstep_rounds), 1)
    every = max(int(cfg.balance_every), 1)
    block = int(cfg.balance_block)
    cross_every = max(int(getattr(cfg, "cross_balance_every", 1)), 1)
    cross_period = every * cross_every
    compress = bool(getattr(cfg, "compress_cross_host", False))

    # --- feasibility guard ------------------------------------------------
    # the base config's capacity is only known-safe at the base BALANCE
    # CADENCE — a sparser cadence lets per-device peaks grow between
    # balance steps, so it must re-pass the headroom check against the
    # cadence-scaled peak estimate like any other candidate. On 2-level
    # profiles the CROSS cadence scales the estimate too: rows pile up
    # inside a host column between cross hops.
    n0_dev = -(-profile.n0 // ndev)          # deal is an even split
    cadence = -(-every // profile.base_balance_every)
    if nhost > 1:
        base_period = (profile.base_balance_every
                       * profile.base_cross_balance_every)
        cadence = max(cadence, -(-cross_period // max(base_period, 1)))
    est_peak = min(profile.peak,
                   max(profile.peak_device_live, n0_dev) * max(cadence, 1))
    base_ok = (cap >= profile.base_local_capacity
               and every <= profile.base_balance_every
               and (nhost <= 1
                    or cross_every <= profile.base_cross_balance_every))
    feasible = cap >= n0_dev and (base_ok or cap >= 2 * est_peak)

    rpl = max(int(getattr(cfg, "rounds_per_launch", 1)), 1)
    # per-device bucketing: the busiest device's rows per round (the
    # profiled run's, else the global sizes — all rows on one device)
    n_rounds = len(t)
    dev_new = profile.dev_new if len(profile.dev_new) >= n_rounds else t
    dev_live = profile.dev_live if len(profile.dev_live) >= n_rounds else t
    floor, ceiling = dist_bucket_range(cfg)
    b = dist_first_bucket(cfg, profile.deal_bucket or ceiling)
    programs = {("deal", b)}
    peak_bucket = b
    dispatches = 1                            # stage-1 device-side deal
    syncs = 1                                 # ... and its meta readback
    row_work = waste = balance_rounds = cross_rounds = launches = 0
    transitions = 0
    by_cause: dict[str, int] = {_RUN: 1}
    cnt = profile.n0
    it = 0
    while it < min(limit, n_rounds) and cnt > 0:
        k = min(K, limit - it)
        shrink_below = b // 4 if b > floor else 0
        programs.add(("step", b))
        peak_bucket = max(peak_bucket, b)
        status = _RUN
        r = att = 0
        while status == _RUN and r < k and cnt > 0 and it + r < n_rounds:
            att += 1
            row_work += b * ndev * nw
            waste += max(b * ndev - max(cnt, 1), 0) * nw
            if b < ceiling and dev_new[it + r] > b:
                status = _GROW                # aborted: nothing applied
                break
            cnt = t[it + r]
            r += 1
            # global-round cadences, matching the driver's round_base + r
            if dev_size > 1 and (it + r) % every == 0:
                balance_rounds += 1
            if nhost > 1 and (it + r) % cross_period == 0:
                cross_rounds += 1
            # SHRINK is evaluated where a launch of R rounds ends
            launch_end = (att % rpl == 0 or r == k or cnt == 0
                          or it + r == n_rounds)
            if (launch_end and shrink_below and cnt > 0
                    and dev_live[it + r - 1] <= shrink_below):
                status = _SHRINK
        # while-loop iterations of the multi-round body: each advances up
        # to R masked rounds, so inner rounds past the wave's death or an
        # abort still run a (discarded) local step — full passes, all waste
        n_launches = -(-att // rpl)
        launches += n_launches
        idle = n_launches * rpl - att
        row_work += idle * b * ndev * nw
        waste += idle * b * ndev * nw
        if status != _GROW and cnt == 0:
            status = _DONE
        by_cause[status] = by_cause.get(status, 0) + 1
        dispatches += 1
        syncs += 1
        need = dev_new[it + r] if status == _GROW else 0
        it += r
        if r == 0 and status != _GROW:
            break
        if it < limit and cnt > 0:
            nb = dist_next_bucket(cfg, status, b, need=need,
                                  peak=dev_live[it - 1] if r else 0)
            if nb != b:
                programs.add(("rebucket", b, nb))
                transitions += 1
                b = nb
    syncs += 1                                # final counter readback
    row_work += (balance_rounds + cross_rounds) * block * ndev * nw
    row_b, stat_b = dist_wire_bytes(profile.n, nw, False)
    xrow_b, xstat_b = dist_wire_bytes(profile.n, nw, compress)
    bytes_intra = balance_rounds * ndev * (block * row_b + stat_b)
    bytes_cross = cross_rounds * ndev * (block * xrow_b + xstat_b)
    return ReplaySummary(
        n_dispatches=dispatches, n_host_syncs=syncs,
        n_bucket_transitions=transitions, n_drains=0, rounds=it,
        row_work=row_work, padded_waste=waste,
        n_programs=len(programs), peak_bucket=peak_bucket,
        by_cause=by_cause,
        feasible=feasible, est_peak_device=int(est_peak),
        bytes_intra=int(bytes_intra), bytes_cross=int(bytes_cross),
        n_kernel_launches=launches)


# ---------------------------------------------------------------------------
# Milliseconds: fitted linear model over replay terms
# ---------------------------------------------------------------------------

# conservative CPU-interpret defaults (measured magnitudes on the smoke
# grids); relative ranking — the autotuner's need — is robust to these.
# The per-tier link coefficients default to an 8× intra/cross bandwidth
# gap (NVLink-class vs DCN-class); ``fit`` replaces them with MEASURED
# values when 'dist' events carrying per-tier bytes provide enough
# variation to solve for them.
DEFAULT_COEFFS = dict(dispatch_ms=0.6, ms_per_mrow=180.0, sync_ms=0.05,
                      compile_ms=150.0, launch_ms=0.05,
                      intra_ms_per_mb=0.05, cross_ms_per_mb=0.4)


@dataclasses.dataclass
class CostModel:
    """ms ≈ dispatch_ms·D + ms_per_mrow·(rows_attempted/1e6) + sync_ms·S
    (+ compile_ms·P when scoring the cold objective).

    Fitting is an ONLINE sliding-window refit (ROADMAP PR-3 follow-up):
    every ``fit`` call appends its traces' dispatch points to a bounded
    window and re-solves the least squares over the WHOLE window. A model
    that lives inside a long-running service therefore (a) keeps learning
    even when each observation contributes only one or two points, and
    (b) tracks device-load drift — old-regime points age out of the window
    instead of anchoring the coefficients forever.
    """
    dispatch_ms: float = DEFAULT_COEFFS["dispatch_ms"]
    ms_per_mrow: float = DEFAULT_COEFFS["ms_per_mrow"]
    sync_ms: float = DEFAULT_COEFFS["sync_ms"]
    compile_ms: float = DEFAULT_COEFFS["compile_ms"]
    # per-tier link cost (ms per MB on the wire): intra-host rows move over
    # the fast tier, cross-host rows over the slow one. These rank the
    # tuner's cross_balance_every × compress_cross_host grid.
    intra_ms_per_mb: float = DEFAULT_COEFFS["intra_ms_per_mb"]
    cross_ms_per_mb: float = DEFAULT_COEFFS["cross_ms_per_mb"]
    # per kernel launch inside a dispatch (the while-loop round's pallas
    # dispatch + frontier HBM round-trip): the cost ``rounds_per_launch``
    # amortizes ⌈K/R⌉-fold — what makes the tuner's R axis non-trivial
    # against the idle-round row work a persistent launch adds.
    launch_ms: float = DEFAULT_COEFFS["launch_ms"]
    n_fit_events: int = 0
    window: int = 256          # sliding-window length (fit points retained)
    warm_points: list = dataclasses.field(default_factory=list, repr=False)
    fresh_points: list = dataclasses.field(default_factory=list, repr=False)
    dist_points: list = dataclasses.field(default_factory=list, repr=False)

    # -- fitting ---------------------------------------------------------

    def fit(self, traces) -> "CostModel":
        """Append the traces' warm dispatch events to the sliding window
        and refit (a, b) over the window; fresh-program events calibrate
        ``compile_ms`` the same way. Windows still too small (or degenerate)
        leave the current coefficients in place. Returns self (chainable)."""
        for tr in traces:
            for e in getattr(tr, "events", []):
                if e.t_ms <= 0.0:
                    continue
                if e.kind == "dist" and not e.fresh and (
                        e.comm_bytes_intra or e.comm_bytes_cross):
                    # tiered dispatches carry the MODELED wire bytes each
                    # tier moved — enough to measure per-tier bandwidth
                    # (ms/MB) directly instead of trusting the defaults.
                    rows = e.rounds_attempted * e.bucket * max(e.ndev, 1)
                    self.dist_points.append(
                        (rows, e.comm_bytes_intra, e.comm_bytes_cross,
                         e.t_ms))
                if e.kind != "superstep":
                    # only single-graph wave dispatches have the 1-event ↔
                    # 1-launch ↔ bucket·rounds row-work correspondence the
                    # model assumes: 'batch' events advance B lanes per
                    # bucket (no lane count in the event), host 'round'
                    # events fold 2-3 launches + a sync into one t_ms, and
                    # 'dist' events fold ndev-way parallel row work plus
                    # per-round collectives into one wall time (the sharded
                    # twin reuses the fitted coefficients for RANKING, which
                    # is robust to the absolute scale being off — EXCEPT the
                    # per-tier byte columns, measured above)
                    continue
                x = e.rounds_attempted * e.bucket  # frontier-row units
                if e.fresh:
                    self.fresh_points.append((x, e.t_ms))
                else:
                    self.warm_points.append((x, e.t_ms))
        del self.warm_points[:-self.window]
        del self.fresh_points[:-self.window]
        warm_x = [x for x, _ in self.warm_points]
        warm_y = [t for _, t in self.warm_points]
        if len(warm_x) >= 3 and len(set(warm_x)) >= 2:
            A = np.stack([np.ones(len(warm_x)), np.asarray(warm_x) / 1e6],
                         axis=1)
            sol, *_ = np.linalg.lstsq(A, np.asarray(warm_y), rcond=None)
            a, b = float(sol[0]), float(sol[1])
            if a > 0 and b > 0:     # degenerate fits keep the coefficients
                self.dispatch_ms, self.ms_per_mrow = a, b
                self.n_fit_events = len(warm_x)
        if self.fresh_points:
            over = [t - self.predict_dispatch(x)
                    for x, t in self.fresh_points]
            est = float(np.median(over))
            if est > 0:
                self.compile_ms = est
        # per-tier bandwidth: ms ≈ a + b·rows/1e6 + i·MB_intra + c·MB_cross
        # over warm dist dispatches. Needs variation in BOTH byte columns
        # (e.g. an A/B with compression toggled) to be solvable; degenerate
        # windows keep the default 8× intra/cross gap.
        del self.dist_points[:-self.window]
        if len(self.dist_points) >= 5:
            bi = [p[1] for p in self.dist_points]
            bc = [p[2] for p in self.dist_points]
            if len(set(bi)) >= 2 and len(set(bc)) >= 2:
                A = np.stack([np.ones(len(self.dist_points)),
                              np.asarray([p[0] for p in self.dist_points],
                                         dtype=float) / 1e6,
                              np.asarray(bi, dtype=float) / 1e6,
                              np.asarray(bc, dtype=float) / 1e6], axis=1)
                y = np.asarray([p[3] for p in self.dist_points])
                sol, *_ = np.linalg.lstsq(A, y, rcond=None)
                im, cm = float(sol[2]), float(sol[3])
                if im > 0 and cm > 0:
                    self.intra_ms_per_mb, self.cross_ms_per_mb = im, cm
        return self

    def predict_dispatch(self, row_units: float) -> float:
        return self.dispatch_ms + self.ms_per_mrow * row_units / 1e6

    # -- scoring ---------------------------------------------------------

    @staticmethod
    def _replay_for(profile, cfg):
        """Route to the twin matching the profile: sharded profiles (or any
        mesh-routed cfg) replay through the dist twin."""
        if isinstance(profile, DistProfile):
            return replay_dist(profile, cfg)
        return replay(profile, cfg)

    def score(self, profile, cfg, *, objective: str = "warm") -> float:
        """Predicted ms for one enumeration of ``profile`` under ``cfg``.
        ``objective='warm'`` assumes programs are cached (steady-state
        serving); ``'cold'`` charges each distinct shape a compile.
        Infeasible sharded candidates score ``inf`` (never picked)."""
        rep = self._replay_for(profile, cfg)
        if not rep.feasible:
            return float("inf")
        rows = rep.row_work / max(profile.nw, 1)  # back to row units
        ms = (self.dispatch_ms * rep.n_dispatches
              + self.launch_ms * rep.n_kernel_launches
              + self.ms_per_mrow * rows / 1e6
              + self.sync_ms * rep.n_host_syncs
              + self.intra_ms_per_mb * rep.bytes_intra / 1e6
              + self.cross_ms_per_mb * rep.bytes_cross / 1e6)
        if objective == "cold":
            ms += self.compile_ms * rep.n_programs
        return ms

    def score_sched(self, profile, cfg, slots: int, *,
                    objective: str = "warm") -> float:
        """Predicted ms to serve the profile's lanes as a request queue
        through a ``slots``-lane recycling pool (``replay_sched``) — the
        scoring function behind ``TuneSpace.admit_slots``."""
        rep = replay_sched(profile, cfg, slots=slots)
        rows = rep.row_work / max(profile.nw, 1)  # back to row units
        ms = (self.dispatch_ms * rep.n_dispatches
              + self.launch_ms * rep.n_kernel_launches
              + self.ms_per_mrow * rows / 1e6
              + self.sync_ms * rep.n_host_syncs)
        if objective == "cold":
            ms += self.compile_ms * rep.n_programs
        return ms

    def breakdown(self, profile, cfg, *, objective: str = "warm") -> dict:
        rep = self._replay_for(profile, cfg)
        return dict(score_ms=round(self.score(profile, cfg,
                                              objective=objective), 4),
                    objective=objective,
                    n_dispatches=rep.n_dispatches,
                    n_kernel_launches=rep.n_kernel_launches,
                    n_host_syncs=rep.n_host_syncs,
                    n_bucket_transitions=rep.n_bucket_transitions,
                    n_drains=rep.n_drains,
                    row_work=rep.row_work, padded_waste=rep.padded_waste,
                    n_programs=rep.n_programs, peak_bucket=rep.peak_bucket,
                    by_cause=dict(rep.by_cause), feasible=rep.feasible,
                    est_peak_device=rep.est_peak_device,
                    bytes_intra=rep.bytes_intra, bytes_cross=rep.bytes_cross)

    def to_json(self) -> dict:
        return dict(dispatch_ms=self.dispatch_ms,
                    ms_per_mrow=self.ms_per_mrow,
                    sync_ms=self.sync_ms, compile_ms=self.compile_ms,
                    launch_ms=self.launch_ms,
                    intra_ms_per_mb=self.intra_ms_per_mb,
                    cross_ms_per_mb=self.cross_ms_per_mb,
                    n_fit_events=self.n_fit_events)
