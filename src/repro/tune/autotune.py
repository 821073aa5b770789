"""Autotuner — the search layer of ``repro.tune``.

Closes the loop from measurement to configuration: a recorded wave profile
(telemetry) is replayed under every candidate knob set (cost model), the
candidates are ranked, optionally the top few are actually run and timed
(measured trials), and the winner is written to the persistent store so the
next same-class request skips everything.

The searched knobs are exactly the ones DESIGN.md §6.4 flags as
shape-dependent — ``superstep_rounds`` (K), ``growth_bits``,
``grow_headroom``, and (store mode) ``cycle_buffer_rows``. All four are
equivalence-preserving by construction (a guarded round is never applied;
the relaunch re-executes it bit-identically), which is property-tested in
``tests/test_tune.py``: a tuned config must produce bit-identical
``cycle_masks`` to the default config.

Mesh-routed configs search the SHARDED knob set instead
(``DIST_TUNED_KNOBS``: ``superstep_rounds`` × ``local_capacity`` ×
``balance_every``, DESIGN.md §5) through ``cost_model.replay_dist`` — the
sharded twin's feasibility guard keeps capacity candidates that could drop
rows out of the running.

The base config is always one of the candidates, so with measured trials
the tuner can never pick a knob set that measured WORSE than the default.
"""
from __future__ import annotations

import dataclasses
import itertools

from .cost_model import CostModel, DistProfile, WaveProfile
from .store import TuneKey, TuneStore, _p2, shape_class

# the shape-dependent, equivalence-preserving knobs the tuner may touch
TUNED_KNOBS = ("superstep_rounds", "growth_bits", "grow_headroom",
               "cycle_buffer_rows", "rounds_per_launch")
# the mesh-routed (sharded) knob set: round budget per superstep, frontier
# rows per device, and the diffusion-balance cadence. local_capacity is
# equivalence-preserving only while nothing overflows — the replay twin's
# feasibility guard scores risky candidates infinite, and the driver counts
# any drop it could not prevent. The last two axes are 2-level-mesh-only
# (cross-host balance cadence and EF-compressed wire, DESIGN.md §7) — both
# equivalence-preserving (placement/encoding only), searched only when the
# base config names a host_axis.
DIST_TUNED_KNOBS = ("superstep_rounds", "local_capacity", "balance_every",
                    "cross_balance_every", "compress_cross_host")
# the continuous-scheduler knob set (DESIGN.md §6.9). NOT part of ``apply``'s
# allow-list on purpose: "slots" is a scheduler-layer resource count, not an
# EngineConfig field — a stored sched entry applied to an engine config must
# drop it rather than raise, which ``apply``'s TUNED+DIST filter already
# guarantees. Sched entries live under their own ``engine="sched"`` TuneKey.
SCHED_TUNED_KNOBS = ("slots",)


def _device_kind() -> str:
    import jax
    try:
        return jax.devices()[0].platform
    except Exception:  # pragma: no cover - no backend at all
        return "unknown"


@dataclasses.dataclass(frozen=True)
class TuneSpace:
    """The searched knob grid (defaults span the regimes §6.4 measured:
    small K for CPU-interpret dispatch costs, large K for accelerators;
    fine vs coarse buckets; headroom 0-2). Mesh-routed configs search the
    sharded axes (``DIST_TUNED_KNOBS``) instead."""
    superstep_rounds: tuple = (4, 8, 16, 32)
    # persistent multi-round launches (DESIGN.md §6.11): R rounds of one
    # superstep fuse into ONE kernel dispatch with the frontier resident
    # in scratch (pallas); on the jnp backend the same R rounds fold into
    # one traced fori_loop. Equivalence-preserving for any R — guarded
    # rounds inside a launch degrade to identity copy-through.
    rounds_per_launch: tuple = (1, 2, 4, 8)
    growth_bits: tuple = (1, 2)
    grow_headroom: tuple = (0, 1, 2)
    cycle_buffer_rows: tuple = (1024, 4096, 16384)
    # sharded axes
    local_capacity: tuple = (1 << 12, 1 << 14, 1 << 16)
    balance_every: tuple = (1, 2, 4)
    # 2-level-mesh axes (searched only when base_cfg.host_axis is set)
    cross_balance_every: tuple = (1, 2, 4, 8)
    compress_cross_host: tuple = (False, True)
    # continuous-scheduler axis: admission slot counts (pool lane widths)
    # searched by ``AutoTuner.tune_slots`` via ``CostModel.score_sched``
    admit_slots: tuple = (2, 4, 8)

    def knob_sets(self, base_cfg) -> list[dict]:
        """Every candidate as a knob dict; the base config's own knobs are
        always candidate 0 (the do-nothing option)."""
        if getattr(base_cfg, "mesh", None) is not None:
            axes = dict(superstep_rounds=self.superstep_rounds,
                        local_capacity=self.local_capacity,
                        balance_every=self.balance_every)
            if getattr(base_cfg, "host_axis", None):
                axes["cross_balance_every"] = self.cross_balance_every
                axes["compress_cross_host"] = self.compress_cross_host
        else:
            axes = dict(superstep_rounds=self.superstep_rounds,
                        growth_bits=self.growth_bits,
                        grow_headroom=self.grow_headroom,
                        rounds_per_launch=self.rounds_per_launch)
            if base_cfg.store:
                axes["cycle_buffer_rows"] = self.cycle_buffer_rows
        base = {k: getattr(base_cfg, k) for k in axes}
        names = list(axes)
        out, seen = [base], {tuple(base[k] for k in names)}
        for combo in itertools.product(*(axes[k] for k in names)):
            if combo in seen:
                continue
            kn = dict(zip(names, combo))
            # EngineConfig rejects local_capacity < balance_block eagerly;
            # never emit a candidate that cannot even construct
            if kn.get("local_capacity", base_cfg.balance_block) \
                    < base_cfg.balance_block:
                continue
            seen.add(combo)
            out.append(kn)
        return out


class AutoTuner:
    """Per-workload-class knob search with a persistent warm path.

    ``trials=0`` (default) ranks purely by the cost model — cheap enough to
    run inline in a service request. ``trials=N`` with a ``measure``
    callable additionally times the model's top-N candidates (base config
    included) and picks the measured winner.
    """

    def __init__(self, store: TuneStore | None = None,
                 model: CostModel | None = None,
                 space: TuneSpace | None = None,
                 trials: int = 0, objective: str = "warm",
                 device_kind: str | None = None, metrics=None):
        self.store = store if store is not None else TuneStore()
        self.model = model if model is not None else CostModel()
        self.space = space if space is not None else TuneSpace()
        self.trials = trials
        self.objective = objective
        self._device_kind = device_kind
        self._counters = dict(searches=0, candidates_scored=0, trials_run=0,
                              warm_hits=0, lookup_misses=0, observations=0)
        # optional repro.obs.MetricsRegistry: every counter double-writes
        # as tune_<name>_total (the dict stays the legacy stats() view)
        self._metrics = metrics

    def _bump(self, name: str, n: int = 1) -> None:
        self._counters[name] += n
        if self._metrics is not None:
            self._metrics.counter(f"tune_{name}_total").inc(n)

    # -- identity --------------------------------------------------------

    @property
    def device_kind(self) -> str:
        if self._device_kind is None:
            self._device_kind = _device_kind()
        return self._device_kind

    def key_for(self, n: int, m: int, delta: int, cfg,
                batch: int = 0) -> TuneKey:
        """``batch`` is the request's lane count (0: unbatched); it keys as
        a power-of-two batch-size class — lane imbalance changes which
        round budget wins, so batched classes tune separately."""
        mesh = getattr(cfg, "mesh", None)
        host_axis = getattr(cfg, "host_axis", None)
        nhost = int(mesh.shape[host_axis]) if mesh is not None and \
            host_axis else 0
        ndev = int(mesh.shape[cfg.axis]) * max(nhost, 1) \
            if mesh is not None else 0
        return TuneKey(shape=shape_class(n, m, delta), store=cfg.store,
                       formulation=cfg.formulation, backend=cfg.backend,
                       engine="dist" if ndev else "wave",
                       device_kind=self.device_kind, ndev=ndev,
                       batch=_p2(batch) if batch else 0, nhost=nhost)

    def key_for_sched(self, n: int, m: int, delta: int, cfg) -> TuneKey:
        """Key for a CONTINUOUS-SCHEDULER entry ({'slots': N}) of one shape
        class. ``engine='sched'`` separates it from the engine-knob entries
        (same free-form engine string mechanism 'dist' uses), and batch
        stays 0 — the slot count is the OUTPUT of this entry, not part of
        its identity."""
        return TuneKey(shape=shape_class(n, m, delta), store=cfg.store,
                       formulation=cfg.formulation, backend=cfg.backend,
                       engine="sched", device_kind=self.device_kind)

    # -- warm path -------------------------------------------------------

    def slots_for(self, key: TuneKey, default: int | None = None):
        """Stored admission slot count for a sched key, or ``default``."""
        knobs = self.store.get(key)
        if knobs is None:
            self._bump("lookup_misses")
            return default
        self._bump("warm_hits")
        return int(knobs.get("slots", default or 0)) or default

    def lookup(self, key: TuneKey, cfg):
        """Stored tuned config for ``key`` (no search, no trace), or None."""
        knobs = self.store.get(key)
        if knobs is None:
            self._bump("lookup_misses")
            return None
        self._bump("warm_hits")
        return self.apply(knobs, cfg)

    @staticmethod
    def apply(knobs: dict, cfg):
        """Overlay tuned knobs on a base config (only TUNED_KNOBS /
        DIST_TUNED_KNOBS; every correctness-relevant field of ``cfg`` is
        preserved verbatim, and knobs that no longer exist, such as
        ``fused_round`` in older stored entries, are ignored). A stored
        ``local_capacity`` below THIS base config's ``balance_block`` is
        dropped rather than applied —
        ``TuneKey`` does not carry ``balance_block``, so an entry tuned
        under a smaller block must not make a warm lookup raise (or
        shrink) on a base config with a bigger one."""
        allowed = TUNED_KNOBS + DIST_TUNED_KNOBS
        tuned = {k: v for k, v in knobs.items() if k in allowed}
        if tuned.get("local_capacity", 0) and \
                tuned["local_capacity"] < getattr(cfg, "balance_block", 0):
            tuned.pop("local_capacity")
        return dataclasses.replace(cfg, **tuned)

    # -- search ----------------------------------------------------------

    def tune(self, profile: WaveProfile, base_cfg, *,
             key: TuneKey | None = None, traces=(), measure=None):
        """Search the knob space for ``profile``; returns the tuned config.

        ``traces`` (recorded ``WaveTrace``s with timings) refit the cost
        model first; ``measure(cfg) -> ms`` enables measured trials of the
        model's top candidates. With ``key``, the winner is persisted.
        """
        self._bump("searches")
        if traces:
            self.model.fit(traces)
        candidates = self.space.knob_sets(base_cfg)
        scored = sorted(
            ((self.model.score(profile, self.apply(kn, base_cfg),
                               objective=self.objective), i, kn)
             for i, kn in enumerate(candidates)),
            key=lambda t: (t[0], t[1]))
        self._bump("candidates_scored", len(scored))
        source, best_ms, best = "model", scored[0][0], scored[0][2]
        if measure is not None and self.trials > 0:
            # never TIME an infeasible candidate: a config that drops
            # frontier rows does less work and would measure fastest —
            # wall time alone cannot veto incorrectness
            pool = [kn for ms, _, kn in scored[:self.trials]
                    if ms != float("inf")]
            if candidates[0] not in pool:   # base config always measured
                pool.append(candidates[0])
            timed = []
            for kn in pool:
                ms = float(measure(self.apply(kn, base_cfg)))
                timed.append((ms, kn))
                self._bump("trials_run")
            best_ms, best = min(timed, key=lambda t: t[0])
            source = "measured"
        if key is not None:
            self.store.put(key, best, meta=dict(
                source=source, score_ms=round(best_ms, 4),
                objective=self.objective,
                n_candidates=len(candidates),
                profile=dict(rounds=len(profile.t_sizes),
                             peak=profile.peak, n0=profile.n0),
                model=self.model.to_json()))
        return self.apply(best, base_cfg)

    def tune_slots(self, profile: WaveProfile, base_cfg, *,
                   key: TuneKey | None = None, traces=()) -> int:
        """Search ``TuneSpace.admit_slots`` for the slot count that serves
        ``profile``'s lanes-as-a-queue cheapest (``CostModel.score_sched``
        over the scheduler twin). Persists ``{'slots': N}`` under ``key``
        (an ``engine='sched'`` key from ``key_for_sched``); returns N.
        Needs a lane-aware profile — single-lane profiles have no queue to
        model, so the default slot count is returned unsearched."""
        if not profile.lane_t:
            return int(self.space.admit_slots[0])
        self._bump("searches")
        if traces:
            self.model.fit(traces)
        scored = sorted(
            ((self.model.score_sched(profile, base_cfg, s,
                                     objective=self.objective), s)
             for s in self.space.admit_slots),
            key=lambda t: (t[0], t[1]))
        self._bump("candidates_scored", len(scored))
        best_ms, best = scored[0]
        if key is not None:
            self.store.put(key, {"slots": int(best)}, meta=dict(
                source="model", score_ms=round(best_ms, 4),
                objective=self.objective,
                n_candidates=len(scored),
                profile=dict(rounds=len(profile.t_sizes),
                             peak=profile.peak, n0=profile.n0,
                             lanes=profile.lanes)))
        return int(best)

    def observe(self, key: TuneKey, base_cfg, history, *, n: int, nw: int,
                traces=(), measure=None):
        """Convenience: profile a finished run's history, then ``tune``.
        This is the service's first-visit hook (record → model → store).
        Mesh-routed configs profile into a ``DistProfile`` (per-device
        peaks from the recorded trace) and replay through the sharded twin."""
        mesh = getattr(base_cfg, "mesh", None)
        if mesh is not None:
            host_axis = getattr(base_cfg, "host_axis", None)
            ndev = int(mesh.shape[base_cfg.axis]) * (
                int(mesh.shape[host_axis]) if host_axis else 1)
            profile = DistProfile.from_run(
                history, n=n, nw=nw, ndev=ndev, cfg=base_cfg,
                traces=traces)
        else:
            profile = WaveProfile.from_history(
                history, n=n, nw=nw, max_iters=base_cfg.max_iters)
        return self.observe_profile(key, base_cfg, profile, traces=traces,
                                    measure=measure)

    def observe_profile(self, key: TuneKey, base_cfg, profile, *,
                        traces=(), measure=None):
        """First-visit hook for a PREBUILT profile — the batched service
        path profiles its per-lane histories into one lane-aware
        ``WaveProfile`` (``from_batch``) and hands it here; the lane-aware
        replay twin then scores candidates with lane-padded occupancy."""
        self._bump("observations")
        return self.tune(profile, base_cfg, key=key, traces=traces,
                         measure=measure)

    # -- stats -----------------------------------------------------------

    def stats(self) -> dict:
        out = dict(self._counters)
        out["store"] = self.store.stats()
        return out
