"""Timeline export — Chrome/Perfetto trace rendering + flight recorder
(DESIGN.md §6.10).

``to_perfetto`` renders a TraceEvent stream (``tune.telemetry``) plus a
span set (``obs.spans``) as a Chrome ``trace_event`` JSON document that
``ui.perfetto.dev`` (or ``chrome://tracing``) opens directly:

* pid 1 "lanes"    — one track (tid) per pool lane; every wave dispatch a
                     lane rode is a complete-event slice tagged with the
                     request id riding it and the rounds applied;
* pid 2 "requests" — one track per request id; the span tree (queue_wait
                     → seed → superstep… → recycle/retire → drain) under
                     its ``request`` root;
* pid 3 "engine"   — seed / recycle / deal boundary dispatches;
* counter tracks   — frontier rows, cycle-ring fill, live lanes, and (for
                     hierarchical dispatches) per-tier interconnect bytes
                     and balance-moved rows (intra vs cross series);
* instant events   — guard trips and bucket GROW / SHRINK / DRAIN
                     transitions.

Timestamps are microseconds on the shared service clock (spans and events
carry the same origin), so slices and spans line up without reconciliation.
A document whose ``otherData`` carries ``origin_unix_ns`` (the span log's
origin on the profiler's host clock, as ``launch/serve.py --trace-out``
writes it) lines up with a JAX profiler trace by one subtraction: a slice
at ``ts`` µs sits at ``origin_unix_ns + 1e3·ts - profile_start_time`` ns
of the trace.

``validate_perfetto`` is the schema gate (required keys, per-track
monotonic timestamps, span nesting) the tests hold every export to, so
the export can't silently rot.

``FlightRecorder`` is the always-on anomaly net: a bounded ring of recent
TraceEvents (attached to ``WaveTrace`` as an observer, so it sees events
even when full trace retention is off) that auto-dumps itself to a JSON
file when it detects a guard-trip storm, a warm-path retrace, or an
occupancy collapse.
"""
from __future__ import annotations

import collections
import dataclasses
import json
import os

PID_LANES, PID_REQUESTS, PID_ENGINE = 1, 2, 3
_PROCESS_NAMES = {PID_LANES: "lanes", PID_REQUESTS: "requests",
                  PID_ENGINE: "engine"}

# dispatch kinds that advance frontiers on lane tracks vs boundary kinds
# that live on the engine track
_LANE_KINDS = ("superstep", "batch", "dist")
_ENGINE_KINDS = ("seed", "recycle", "deal")

TRACE_SCHEMA = "repro.obs/perfetto/v1"


def collect_events(service) -> list:
    """Every retained TraceEvent of a service, across all its recorded
    runs, in time order (``CycleService.trace_log`` keeps the per-run
    ``WaveTrace`` recorders; they share the service clock)."""
    events = [e for tr in service.trace_log for e in tr.events]
    events.sort(key=lambda e: e.t_start_ms)
    return events


def _meta(te, pid, name, tid=None):
    ev = {"ph": "M", "pid": pid, "tid": 0 if tid is None else tid,
          "ts": 0, "name": "process_name" if tid is None else "thread_name",
          "args": {"name": name}}
    te.append(ev)


def to_perfetto(events, spans=(), *, meta: dict | None = None) -> dict:
    """Render events + spans as a Chrome ``trace_event`` JSON dict."""
    te: list[dict] = []
    lanes_seen: set[int] = set()
    req_tids: dict[str, int] = {}

    def req_tid(rid: str) -> int:
        return req_tids.setdefault(rid, len(req_tids))

    for ev in sorted(events, key=lambda e: e.t_start_ms):
        ts = ev.t_start_ms * 1e3           # us
        dur = max(ev.t_ms, 0.0) * 1e3
        args = dict(kind=ev.kind, status=ev.status, bucket=ev.bucket,
                    rounds=ev.rounds, enter=ev.enter_count,
                    exit=ev.exit_count)
        if ev.kind in _LANE_KINDS:
            if ev.lane_rids:
                for lane, rid in enumerate(ev.lane_rids):
                    rounds = (ev.lane_rounds[lane]
                              if lane < len(ev.lane_rounds) else 0)
                    if not rid and not rounds:
                        continue           # free lane: nothing rode it
                    lanes_seen.add(lane)
                    te.append({"ph": "X", "cat": "wave",
                               "name": f"{ev.kind}[{ev.status}]",
                               "pid": PID_LANES, "tid": lane,
                               "ts": ts, "dur": dur,
                               "args": dict(args, rid=rid, rounds=rounds)})
            else:
                lanes_seen.add(0)
                te.append({"ph": "X", "cat": "wave",
                           "name": f"{ev.kind}[{ev.status}]",
                           "pid": PID_LANES, "tid": 0, "ts": ts,
                           "dur": dur, "args": args})
        elif ev.kind in _ENGINE_KINDS:
            te.append({"ph": "X", "cat": "boundary", "name": ev.kind,
                       "pid": PID_ENGINE, "tid": 0, "ts": ts,
                       "dur": max(dur, ev.wall_ms * 1e3),
                       "args": dict(args, wall_ms=ev.wall_ms,
                                    admitted=ev.admitted,
                                    retired=ev.retired)})
        # counter tracks sample at dispatch END (the post-dispatch truth)
        t_end = ts + dur
        te.append({"ph": "C", "name": "frontier_rows", "pid": PID_LANES,
                   "tid": 0, "ts": t_end, "args": {"rows": ev.exit_count}})
        te.append({"ph": "C", "name": "ring_fill", "pid": PID_LANES,
                   "tid": 0, "ts": t_end, "args": {"rows": ev.cyc_fill}})
        if ev.lanes:
            te.append({"ph": "C", "name": "live_lanes", "pid": PID_LANES,
                       "tid": 0, "ts": t_end,
                       "args": {"lanes": ev.live_lanes}})
        if ev.comm_bytes_intra or ev.comm_bytes_cross:
            # per-tier interconnect traffic of hierarchical dispatches —
            # one multi-series counter track, intra vs cross stacked
            te.append({"ph": "C", "name": "dist_comm_bytes",
                       "pid": PID_LANES, "tid": 0, "ts": t_end,
                       "args": {"intra": ev.comm_bytes_intra,
                                "cross": ev.comm_bytes_cross}})
        if ev.moved or ev.moved_cross:
            te.append({"ph": "C", "name": "dist_balance_moved",
                       "pid": PID_LANES, "tid": 0, "ts": t_end,
                       "args": {"intra": ev.moved - ev.moved_cross,
                                "cross": ev.moved_cross}})
        if ev.status in ("GROW", "SHRINK", "DRAIN"):
            te.append({"ph": "i", "s": "p",
                       "name": f"guard:{ev.status}", "pid": PID_LANES,
                       "tid": 0, "ts": t_end,
                       "args": {"pending_new": ev.pending_new,
                                "pending_cyc": ev.pending_cyc}})

    for sp in sorted(spans, key=lambda s: (s.rid, s.t_start_ms)):
        args = dict(sp.attrs)
        if sp.lane >= 0:
            args["lane"] = sp.lane
        if sp.wave >= 0:
            args["wave"] = sp.wave
        te.append({"ph": "X", "cat": "span", "name": sp.name,
                   "pid": PID_REQUESTS, "tid": req_tid(sp.rid),
                   "ts": sp.t_start_ms * 1e3, "dur": sp.dur_ms * 1e3,
                   "args": dict(args, rid=sp.rid)})

    head: list[dict] = []
    for pid, name in _PROCESS_NAMES.items():
        _meta(head, pid, name)
    for lane in sorted(lanes_seen):
        _meta(head, PID_LANES, f"lane {lane}", tid=lane)
    for rid, tid in sorted(req_tids.items(), key=lambda kv: kv[1]):
        _meta(head, PID_REQUESTS, rid, tid=tid)

    return {"traceEvents": head + te, "displayTimeUnit": "ms",
            "otherData": dict(schema=TRACE_SCHEMA, **(meta or {}))}


def validate_perfetto(doc: dict, *, slack_ms: float = 5.0) -> list[str]:
    """Schema gate for an exported trace. Checks (1) required keys on the
    document and on every event, (2) per-track monotonic timestamps for
    complete events, (3) span nesting — every non-root span of a request
    lies inside its ``request`` root (within ``slack_ms`` of clock-read
    jitter). Returns a problem list; empty == valid."""
    errs: list[str] = []
    if not isinstance(doc, dict) or "traceEvents" not in doc:
        return ["document must be a dict with a traceEvents list"]
    if doc.get("otherData", {}).get("schema") != TRACE_SCHEMA:
        errs.append(f"otherData.schema != {TRACE_SCHEMA}")
    events = doc["traceEvents"]
    if not isinstance(events, list):
        return errs + ["traceEvents is not a list"]

    last_ts: dict[tuple, float] = {}
    roots: dict[tuple, tuple[float, float]] = {}
    children: dict[tuple, list[tuple[str, float, float]]] = {}
    for i, ev in enumerate(events):
        if not isinstance(ev, dict):
            errs.append(f"traceEvents[{i}]: not a dict")
            continue
        ph = ev.get("ph")
        if ph is None:
            errs.append(f"traceEvents[{i}]: missing ph")
            continue
        for req in ("pid", "tid", "ts"):
            if req not in ev:
                errs.append(f"traceEvents[{i}] (ph={ph}): missing {req!r}")
        if ph == "X":
            if ev.get("dur", -1) < 0:
                errs.append(f"traceEvents[{i}]: X event with negative/"
                            f"missing dur")
            track = (ev.get("pid"), ev.get("tid"))
            ts = float(ev.get("ts", 0))
            if ts < last_ts.get(track, float("-inf")):
                errs.append(f"traceEvents[{i}]: non-monotonic ts on track "
                            f"{track} ({ts} < {last_ts[track]})")
            last_ts[track] = ts
            if ev.get("pid") == PID_REQUESTS:
                key = (ev.get("tid"), ev.get("args", {}).get("rid", ""))
                span = (ev.get("name", ""), ts, ts + float(ev.get("dur", 0)))
                if ev.get("name") == "request":
                    roots[key] = (span[1], span[2])
                else:
                    children.setdefault(key, []).append(span)
        elif ph == "C":
            if not isinstance(ev.get("args"), dict) or not ev["args"]:
                errs.append(f"traceEvents[{i}]: counter without args")
        elif ph == "M":
            if "name" not in ev or "args" not in ev:
                errs.append(f"traceEvents[{i}]: metadata missing name/args")

    slack = slack_ms * 1e3
    for key, kids in children.items():
        root = roots.get(key)
        if root is None:
            errs.append(f"request track {key}: spans without a "
                        f"'request' root")
            continue
        lo, hi = root
        for name, s, e in kids:
            if s < lo - slack or e > hi + slack:
                errs.append(
                    f"request track {key}: span {name!r} "
                    f"[{s:.0f}, {e:.0f}]us escapes root "
                    f"[{lo:.0f}, {hi:.0f}]us (+{slack:.0f}us slack)")
    return errs


def write_json(path: str, doc: dict) -> str:
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)
    return path


class FlightRecorder:
    """Bounded ring of recent TraceEvents that auto-dumps on anomalies.

    Attach it to a service (``CycleService(recorder=...)``) and it rides
    every run as a ``WaveTrace`` observer — events flow through it even
    when full trace retention is off, but only the last ``capacity`` are
    held. Triggers (each rate-limited to one dump per ``cooldown``
    events):

    * ``guard_storm``        — ≥ ``storm_trips`` GROW/DRAIN guard trips in
                               the last ``storm_window`` dispatches (the
                               bucket/ring thrash signature);
    * ``warm_retrace``       — a ``fresh=True`` dispatch of a program
                               (``plan_key``) that already ran warm (the
                               zero-retrace contract broke mid-flight;
                               a cold compile of a never-seen key is NOT
                               a retrace);
    * ``occupancy_collapse`` — a pool dispatch with live/total lanes below
                               ``occupancy_floor`` after ``min_events``
                               warm-up (admission starving the pool).

    Dumps land in ``dump_dir`` as ``flight-<seq>-<reason>.json`` (and are
    always appended to ``self.dumps`` for in-process inspection).
    """

    def __init__(self, capacity: int = 512, dump_dir: str | None = None, *,
                 occupancy_floor: float = 0.25, storm_window: int = 32,
                 storm_trips: int = 8, min_events: int = 64,
                 cooldown: int = 256):
        self.ring: collections.deque = collections.deque(maxlen=capacity)
        self.dump_dir = dump_dir
        self.occupancy_floor = float(occupancy_floor)
        self.storm_window = int(storm_window)
        self.storm_trips = int(storm_trips)
        self.min_events = int(min_events)
        self.cooldown = int(cooldown)
        self.n_seen = 0
        self.dumps: list[dict] = []
        self.trips: dict[str, int] = {}
        self._recent_guards: collections.deque = collections.deque(
            maxlen=self.storm_window)
        self._warm_programs: set = set()
        self._last_dump: dict[str, int] = {}
        self._seq = 0

    def record(self, ev) -> None:
        """Observer hook (``WaveTrace(observer=recorder.record)``)."""
        self.ring.append(ev)
        self.n_seen += 1
        # program identity: the plan key when dispatches carry one,
        # (kind, bucket) as the degraded proxy for events that don't
        prog = ev.plan_key or (ev.kind, ev.bucket)
        if ev.fresh and prog in self._warm_programs:
            self._trip("warm_retrace")
        elif not ev.fresh:
            self._warm_programs.add(prog)
        self._recent_guards.append(1 if ev.status in ("GROW", "DRAIN")
                                   else 0)
        if (len(self._recent_guards) == self.storm_window
                and sum(self._recent_guards) >= self.storm_trips):
            self._trip("guard_storm")
        if (ev.lanes and ev.kind in _LANE_KINDS
                and self.n_seen > self.min_events
                and ev.live_lanes / ev.lanes < self.occupancy_floor):
            self._trip("occupancy_collapse")

    def _trip(self, reason: str) -> None:
        self.trips[reason] = self.trips.get(reason, 0) + 1
        last = self._last_dump.get(reason)
        if last is not None and self.n_seen - last < self.cooldown:
            return
        self._last_dump[reason] = self.n_seen
        self.dump(reason)

    def dump(self, reason: str = "manual") -> str | None:
        doc = dict(reason=reason, n_seen=self.n_seen,
                   trips=dict(self.trips),
                   events=[dataclasses.asdict(e) for e in self.ring])
        self.dumps.append(doc)
        if self.dump_dir is None:
            return None
        os.makedirs(self.dump_dir, exist_ok=True)
        self._seq += 1
        path = os.path.join(self.dump_dir,
                            f"flight-{self._seq:03d}-{reason}.json")
        with open(path, "w") as f:
            json.dump(doc, f, indent=1)
        return path
