"""Wave-shape telemetry — the measurement layer of ``repro.tune``.

The wave engine's per-dispatch history (frontier occupancy, bucket
transitions, cycle-buffer fill) used to live in an ad-hoc ``stats`` dict and
was thrown away after each run. This module turns it into a structured,
recordable stream:

* ``TraceEvent``  — one host↔device interaction (a wave superstep dispatch,
                    a batched superstep, or a sharded superstep),
                    carrying the full wave shape of that dispatch: bucket
                    capacity, per-round frontier sizes and cycle counts,
                    exit status (by CAUSE: GROW / SHRINK / DRAIN / DONE /
                    RUN), pending sizes of an aborted round, cycle-buffer
                    fill, and host wall time.
* ``WaveTrace``   — the recorder. Aggregate counters (dispatches, syncs,
                    arrays read to the host, transitions-by-cause, drains,
                    rounds by path) are ALWAYS maintained —
                    they are a handful of int adds and back the legacy
                    ``EnumerationResult.stats`` dict — but per-dispatch
                    ``TraceEvent`` objects are retained only when the trace
                    is ``enabled``: the disabled recorder allocates nothing
                    per dispatch beyond those adds (near-zero overhead).

The schema is deliberately free of any ``repro.core`` import so the engine
can emit events without an import cycle (core → tune.telemetry only).
DESIGN.md §6.6 documents the schema; ``cost_model.WaveProfile`` consumes it.
"""
from __future__ import annotations

import dataclasses
import time


# Canonical exit-status names (the wave superstep's transition causes).
# ``FULL`` in the issue's vocabulary is the cycle-ring overflow — engine
# code calls it DRAIN; both names resolve to the same cause here.
STATUSES = ("RUN", "DONE", "GROW", "DRAIN", "SHRINK")


@dataclasses.dataclass(frozen=True)
class TraceEvent:
    """One recorded host↔device interaction (see DESIGN.md §6.6).

    ``t_sizes`` / ``c_counts`` are the per-APPLIED-round frontier sizes and
    cycle counts inside this dispatch (length == ``rounds``); an aborted
    round's exact sizes ride in ``pending_new`` / ``pending_cyc`` instead.
    ``bucket`` is the frontier capacity the dispatch ran at, ``enter_count``
    the live rows on entry — their difference is the padded-row waste the
    cost model charges for.
    """
    kind: str                  # 'superstep' | 'round' | 'batch' | 'dist'
    #                            | 'deal' | 'seed' | 'recycle'
    bucket: int                # frontier capacity (rows) during the dispatch
    cyc_cap: int               # CycleBuffer capacity (1 in count-only mode)
    budget: int                # round budget k granted to the dispatch
    rounds: int                # rounds actually applied
    status: str                # one of STATUSES
    t_sizes: tuple[int, ...]   # per-round |T'| (frontier size after round)
    c_counts: tuple[int, ...]  # per-round |C| found
    enter_count: int           # live frontier rows on entry
    exit_count: int            # live frontier rows on exit
    pending_new: int           # aborted round's exact |T'| (GROW) or 0
    pending_cyc: int           # aborted round's exact |C| (DRAIN) or 0
    cyc_fill: int              # CycleBuffer fill on exit
    t_ms: float                # host wall time of the dispatch (incl. sync)
    t_start_ms: float = 0.0    # dispatch start on the recorder clock (ms
    #                            since the trace origin — the service passes
    #                            ONE origin to every recorder + the span
    #                            log, so events/spans share a timeline)
    wall_ms: float = 0.0       # host wall time of the FULL boundary this
    #                            event closes (staging + padding + dispatch
    #                            + merge) — seed/recycle events only; the
    #                            boundary overhead t_ms alone was blind to
    #                            (the PR-7 small-scale loss), rolled up as
    #                            the boundary_ms_total metric
    fresh: bool = False        # first execution of a fresh program (t_ms
    #                            includes trace+compile; the cost-model fit
    #                            separates these from warm dispatches)
    plan_key: str = ""         # stable identity of the compiled program
    #                            (str(PlanKey)) — distinguishes a cold
    #                            compile of a NEW key from a re-trace of
    #                            one that already ran warm (FlightRecorder
    #                            warm_retrace trigger)
    # --- sharded dispatches ('dist' / 'deal' events) only ----------------
    ndev: int = 0              # devices the dispatch spanned (0: unsharded;
    #                            row-work terms scale by max(ndev, 1))
    per_device: tuple[int, ...] = ()  # per-device PEAK live rows inside the
    #                            dispatch — the placement fact the sharded
    #                            replay twin's feasibility guard consumes
    dev_new: tuple[int, ...] = ()  # per applied round, the busiest device's
    #                            extension rows before balancing (what its
    #                            bucket must hold; a GROW abort's own sits
    #                            in ``pending_new``)
    dev_live: tuple[int, ...] = ()  # per applied round, the busiest
    #                            device's live rows after balancing — with
    #                            ``dev_new`` what the sharded replay twin's
    #                            bucket policy reads
    moved: int = 0             # rows shipped by diffusion balancing (both
    #                            tiers; ``moved - moved_cross`` is intra)
    lost: int = 0              # receiver-side balance overflow (must be 0
    #                            under backpressure; defensive counter)
    # --- 2-level mesh dispatches (DESIGN.md §7) --------------------------
    moved_cross: int = 0       # rows shipped over the cross-host tier
    comm_bytes_intra: int = 0  # modeled wire bytes of intra-host balance
    #                            hops inside this dispatch (block-sized
    #                            sends × ``distributed.dist_wire_bytes``)
    comm_bytes_cross: int = 0  # modeled wire bytes of the cross-host hops
    #                            (compressed when the run compresses them —
    #                            the quantity the tier-aware cost model
    #                            consumes)
    # --- lane-recycling dispatches ('recycle' + scheduler 'batch'/'seed'
    # events) only — DESIGN.md §6.9 ------------------------------------
    lanes: int = 0             # pool size B of the recyclable batch
    live_lanes: int = 0        # occupied lanes at the dispatch (occupancy
    #                            numerator: mean occupancy = Σ live/lanes)
    retired: int = 0           # lanes freed at this boundary (results
    #                            flushed to their callers)
    admitted: int = 0          # queued requests re-dealt into freed lanes
    #                            at this boundary (without retracing)
    lane_rids: tuple = ()      # per-lane request id riding the dispatch
    #                            ("" for free lanes) — the attribution that
    #                            turns a dispatch stream into per-request
    #                            spans (repro.obs, DESIGN.md §6.10)
    lane_rounds: tuple = ()    # per-lane rounds applied this dispatch (the
    #                            per-lane slice of ``rounds``, which is the
    #                            max across lanes)
    rounds_per_launch: int = 1  # R the dispatch ran with (DESIGN.md §6.11):
    #                            each while-iteration of the superstep is
    #                            ONE kernel launch advancing up to R rounds,
    #                            so this dispatch cost ``kernel_launches``
    #                            launches / frontier HBM round-trips

    @property
    def rounds_attempted(self) -> int:
        """Applied rounds plus the aborted attempt (GROW/DRAIN re-execute
        the round after the host reacts — that attempt's row work is real)."""
        return self.rounds + (1 if self.status in ("GROW", "DRAIN") else 0)

    def row_work(self, n_words: int) -> int:
        """Word-rows touched by this dispatch (dead rows included; sharded
        dispatches scan ``bucket`` rows on EACH of ``ndev`` devices)."""
        return (self.rounds_attempted * self.bucket * max(self.ndev, 1)
                * n_words)

    @property
    def kernel_launches(self) -> int:
        """Kernel launches (= frontier HBM round-trips) this dispatch paid:
        ⌈rounds_attempted / R⌉ — one persistent launch advances up to R
        rounds with the frontier resident in scratch between them."""
        return -(-self.rounds_attempted // max(self.rounds_per_launch, 1))

    def padded_waste(self, n_words: int) -> int:
        """Word-rows spent on PADDING (capacity minus live rows), the
        dead-row work the autotuner trades against dispatch count. Round i
        of the dispatch entered with ``enter_count`` (i=0) or
        ``t_sizes[i-1]`` rows — matching ``cost_model.replay``'s per-round
        accounting. Sharded dispatches pad to ``bucket × ndev`` total rows."""
        cap = self.bucket * max(self.ndev, 1)
        entries = ((self.enter_count,) + self.t_sizes)[:self.rounds_attempted]
        return sum(max(cap - max(e, 1), 0) for e in entries) * n_words


class WaveTrace:
    """Recorder for one enumeration run.

    Counters always accumulate; ``events`` fills only when ``enabled``.
    ``finalize(rounds)`` renders the legacy stats dict (the exact shape
    ``EnumerationResult.stats`` has always carried), so its consumers —
    the ``bench/`` harness and the tests — see one stable shape.
    """

    __slots__ = ("enabled", "events", "n_dispatches", "n_host_syncs",
                 "n_d2h_arrays", "n_bucket_transitions", "n_drains",
                 "n_kernel_launches",
                 "by_cause", "rounds_by_path", "_t0", "_origin", "_ticked",
                 "observer")

    def __init__(self, enabled: bool = True, origin: float | None = None,
                 observer=None):
        """``origin`` is the perf_counter epoch ``t_start_ms`` is relative
        to (the service passes one shared epoch so every recorder — and the
        span log — lands on a single timeline). ``observer`` is called with
        each TraceEvent as it is recorded (the flight-recorder hook); an
        observer forces event CONSTRUCTION but not retention, so a bounded
        ring can watch a run whose full trace is off."""
        self.enabled = enabled
        self.events: list[TraceEvent] = []
        self.n_dispatches = 0
        self.n_host_syncs = 0
        # arrays copied device-to-host: a device_get of a tuple reads each
        # array on its own, so this counts reads where n_host_syncs counts
        # the driver's waits
        self.n_d2h_arrays = 0
        self.n_bucket_transitions = 0
        self.n_drains = 0
        self.n_kernel_launches = 0
        self.by_cause: dict[str, int] = {}
        # rounds (aborted attempts included) per round path: 'fused' — one
        # fused pallas kernel per round or launch — or 'split'; the path
        # is the one the dispatched program took when it was traced
        self.rounds_by_path = {"fused": 0, "split": 0}
        self._t0 = 0.0
        self._origin = time.perf_counter() if origin is None else origin
        self._ticked = False
        self.observer = observer

    # -- timing ----------------------------------------------------------

    def tic(self) -> None:
        """Mark the start of a dispatch (cheap even when disabled — the
        wall time also feeds the fitted cost model)."""
        self._t0 = time.perf_counter()
        self._ticked = True

    def toc_ms(self) -> float:
        return (time.perf_counter() - self._t0) * 1e3

    # -- recording -------------------------------------------------------

    def sync(self, n: int = 1) -> None:
        self.n_host_syncs += n

    def d2h(self, n: int = 1) -> None:
        """Count ``n`` arrays copied device-to-host by one read."""
        self.n_d2h_arrays += n

    def drain(self) -> None:
        self.n_drains += 1

    def transition(self) -> None:
        self.n_bucket_transitions += 1

    def dispatch(self, *, kind: str, bucket: int, cyc_cap: int, budget: int,
                 rounds: int, status: str, t_sizes=(), c_counts=(),
                 enter_count: int = 0, exit_count: int = 0,
                 pending_new: int = 0, pending_cyc: int = 0,
                 cyc_fill: int = 0, t_ms: float = 0.0,
                 fresh: bool = False, plan_key: str = "",
                 launches: int = 1, ndev: int = 0,
                 per_device=(), dev_new=(), dev_live=(),
                 moved: int = 0, lost: int = 0,
                 moved_cross: int = 0, comm_bytes_intra: int = 0,
                 comm_bytes_cross: int = 0,
                 lanes: int = 0, live_lanes: int = 0, retired: int = 0,
                 admitted: int = 0, wall_ms: float = 0.0, lane_rids=(),
                 lane_rounds=(), rounds_per_launch: int = 1,
                 t_start_ms: float | None = None,
                 round_path: str = "") -> None:
        self.n_dispatches += launches
        if kind in ("superstep", "batch", "dist"):
            att = rounds + (1 if status in ("GROW", "DRAIN") else 0)
            self.n_kernel_launches += -(-att // max(rounds_per_launch, 1))
            if round_path:
                self.rounds_by_path[round_path] += att
        self.by_cause[status] = self.by_cause.get(status, 0) + 1
        if not self.enabled and self.observer is None:
            self._ticked = False
            return
        if t_start_ms is None:
            # the matching tic() marked the dispatch start; un-tic'd events
            # (boundary markers without a timed section) stamp "now"
            base = self._t0 if self._ticked else time.perf_counter()
            t_start_ms = (base - self._origin) * 1e3
        self._ticked = False
        ev = TraceEvent(
            kind=kind, bucket=bucket, cyc_cap=cyc_cap, budget=budget,
            rounds=rounds, status=status, t_sizes=tuple(int(t) for t in t_sizes),
            c_counts=tuple(int(c) for c in c_counts),
            enter_count=int(enter_count), exit_count=int(exit_count),
            pending_new=int(pending_new), pending_cyc=int(pending_cyc),
            cyc_fill=int(cyc_fill), t_ms=float(t_ms),
            t_start_ms=float(t_start_ms), wall_ms=float(wall_ms),
            fresh=bool(fresh), plan_key=str(plan_key),
            ndev=int(ndev), per_device=tuple(int(x) for x in per_device),
            dev_new=tuple(int(x) for x in dev_new),
            dev_live=tuple(int(x) for x in dev_live),
            moved=int(moved), lost=int(lost),
            moved_cross=int(moved_cross),
            comm_bytes_intra=int(comm_bytes_intra),
            comm_bytes_cross=int(comm_bytes_cross), lanes=int(lanes),
            live_lanes=int(live_lanes), retired=int(retired),
            admitted=int(admitted),
            lane_rids=tuple(str(r) for r in lane_rids),
            lane_rounds=tuple(int(r) for r in lane_rounds),
            rounds_per_launch=int(rounds_per_launch))
        if self.enabled:
            self.events.append(ev)
        if self.observer is not None:
            self.observer(ev)

    # -- summaries -------------------------------------------------------

    def row_work(self, n_words: int) -> int:
        return sum(e.row_work(n_words) for e in self.events)

    def padded_waste(self, n_words: int) -> int:
        return sum(e.padded_waste(n_words) for e in self.events)

    def finalize(self, rounds: int) -> dict:
        """Legacy ``EnumerationResult.stats`` dict + transition causes."""
        out = dict(n_dispatches=self.n_dispatches,
                   n_host_syncs=self.n_host_syncs,
                   n_d2h_arrays=self.n_d2h_arrays,
                   n_bucket_transitions=self.n_bucket_transitions,
                   n_drains=self.n_drains,
                   rounds=rounds,
                   n_kernel_launches=self.n_kernel_launches,
                   fused_rounds=self.rounds_by_path["fused"],
                   split_rounds=self.rounds_by_path["split"],
                   rounds_per_dispatch=rounds / max(self.n_dispatches, 1),
                   syncs_per_round=self.n_host_syncs / max(rounds, 1))
        if self.by_cause:
            # one entry per DISPATCH exit status (sums to the number of
            # recorded dispatch events, incl. RUN/DONE — not a transition
            # count; n_bucket_transitions is the transition counter)
            out["exit_causes"] = dict(self.by_cause)
        return out


def disabled_trace(origin: float | None = None,
                   observer=None) -> WaveTrace:
    """A counters-only recorder (no event retention; an ``observer`` still
    sees each event flow past — the flight-recorder path)."""
    return WaveTrace(enabled=False, origin=origin, observer=observer)
