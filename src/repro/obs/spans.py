"""Request spans — per-request latency decomposition (DESIGN.md §6.10).

A request-id is minted at every ``CycleService`` entry point
(``enumerate`` / ``enumerate_batch`` / ``stream`` / ``serve_stream``) and
flows through ``LanePool``/``ContinuousScheduler`` admission into the
TraceEvent stream (``TraceEvent.lane_rids``), so each request decomposes
into a tree of named slices on one shared clock:

    request                       (root: arrival → completion == e2e)
      queue_wait                  (arrival → lane admission)
      seed                        (stage-1 device seed of its lane)
      superstep × N               (each wave dispatch the lane rode,
                                   tagged with lane index + wave ordinal)
      recycle                     (admission-merge boundary it rode in on)
      drain / retire              (CycleBuffer flush, lane retirement)

``SpanLog.phase`` is the one way the host driver opens a phase: it always
opens a ``jax.profiler.TraceAnnotation`` named ``repro.<phase>`` (a no-op
unless the profiler runs), and on an enabled log it also records the
interval as a ``Span`` of its request. The driver's phases are
``enumerate`` (a whole single-graph request), ``seed`` (stage 1),
``superstep`` (plan lookup, argument staging and the program call),
``readback`` (device-to-host reads of status, counts and histories),
``drain`` (cycle-ring flush), ``rebucket`` (frontier capacity change) and
the scheduler's ``recycle`` / ``retire`` boundaries. Host phases and the
device operations of a profiler trace share one clock: ``origin_unix_ns``
is the log's origin on the profiler's host clock (Unix nanoseconds), so
a span starts at ``origin_unix_ns + t_start_ms * 1e6`` there.

This is the substrate the ROADMAP's deadline/priority admission control
will schedule against: "where did this request's milliseconds go" is
answerable from the span log alone, without re-running anything.

The log is disabled by default — ``SpanLog.add`` on a disabled log is a
single attribute check, and every call site guards span construction on
``log.enabled`` so the disabled path allocates NOTHING per dispatch (the
telemetry overhead contract, tested in ``tests/test_obs.py``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import itertools
import time

from jax.profiler import TraceAnnotation

# Span names, in the order a request walks them.
SPAN_NAMES = ("request", "enumerate", "queue_wait", "seed", "superstep",
              "readback", "rebucket", "recycle", "retire", "drain")
# the profiler annotation of each phase (an unknown name is a KeyError)
_ANNOTATIONS = {name: "repro." + name for name in SPAN_NAMES}

_REQ_IDS = itertools.count(1)


def new_request_id(prefix: str = "r") -> str:
    """Process-unique request id (``r000001``, ...). Monotone so sorted
    request ids are arrival-ordered within one process."""
    return f"{prefix}{next(_REQ_IDS):06d}"


@dataclasses.dataclass
class Span:
    """One named slice of one request's lifetime. ``lane`` is the pool
    lane it rode (-1: not lane-bound), ``wave`` the dispatch ordinal
    within its session (-1: not a dispatch slice)."""
    rid: str
    name: str
    t_start_ms: float
    dur_ms: float
    lane: int = -1
    wave: int = -1
    attrs: dict = dataclasses.field(default_factory=dict)

    @property
    def t_end_ms(self) -> float:
        return self.t_start_ms + self.dur_ms


class SpanLog:
    """Bounded recorder of request spans on one clock.

    ``origin`` is the perf_counter epoch all ``t_start_ms`` values are
    relative to — the service passes the SAME origin to its ``WaveTrace``
    recorders, so spans and TraceEvents land on one timeline and the
    Perfetto export needs no clock reconciliation. ``origin_unix_ns`` is
    that epoch on the profiler's host clock.
    """

    def __init__(self, enabled: bool = True, origin: float | None = None,
                 maxlen: int = 262_144):
        self.enabled = bool(enabled)
        now = time.perf_counter()
        unix_ns = time.time_ns()
        self._origin = now if origin is None else origin
        self.origin_unix_ns = unix_ns - round((now - self._origin) * 1e9)
        self.maxlen = int(maxlen)
        self.spans: list[Span] = []

    def now_ms(self) -> float:
        return (time.perf_counter() - self._origin) * 1e3

    @contextlib.contextmanager
    def phase(self, name: str, rid: str = "", **attrs):
        """Run the body as host phase ``name``: under the profiler
        annotation ``repro.<name>`` always, and as a ``Span`` of request
        ``rid`` when the log is enabled. A phase without a request id (the
        scheduler's, which serve a whole pool) is annotated only."""
        with TraceAnnotation(_ANNOTATIONS[name]):
            if not (self.enabled and rid):
                yield
                return
            t0 = self.now_ms()
            try:
                yield
            finally:
                self.add(name, rid, t0, self.now_ms() - t0, **attrs)

    def add(self, name: str, rid: str, t_start_ms: float, dur_ms: float, *,
            lane: int = -1, wave: int = -1, **attrs) -> None:
        if not self.enabled or len(self.spans) >= self.maxlen:
            return
        self.spans.append(Span(rid=rid, name=name,
                               t_start_ms=float(t_start_ms),
                               dur_ms=max(float(dur_ms), 0.0),
                               lane=int(lane), wave=int(wave),
                               attrs=attrs))

    def clear(self) -> None:
        self.spans.clear()

    # -- queries -----------------------------------------------------------

    def roots(self) -> dict[str, Span]:
        """The ``request`` root span per rid (last one wins — there should
        only ever be one)."""
        return {sp.rid: sp for sp in self.spans if sp.name == "request"}

    def rollup(self, rid: str) -> dict:
        """Where did this request's milliseconds go: per-name summed slice
        durations + the root e2e, the reconciliation the acceptance tests
        assert (Σslices ≈ e2e within boundary slack)."""
        out: dict[str, float] = {}
        root = 0.0
        for sp in self.spans:
            if sp.rid != rid:
                continue
            if sp.name == "request":
                root = sp.dur_ms
            else:
                out[sp.name] = out.get(sp.name, 0.0) + sp.dur_ms
        return dict(e2e_ms=root, slices_ms=out,
                    accounted_ms=sum(out.values()))
