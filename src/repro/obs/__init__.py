"""repro.obs — unified observability across the serving stack
(DESIGN.md §6.10).

Three parts, threaded through every layer:

* ``metrics``  — labeled counters/gauges/histograms with a JSON
                 ``snapshot()``; ``CycleService``, ``ContinuousScheduler``,
                 ``launch.serve``, ``ProgramCache`` and ``AutoTuner`` all
                 emit through one ``MetricsRegistry``, and the legacy
                 stats-dict shapes are preserved as views over it.
* ``spans``    — request-ids minted at every service entry point, each
                 request decomposed into queue_wait → seed → superstep
                 slices → recycle/retire → drain on one shared clock;
                 ``SpanLog.phase`` opens each host phase under a
                 ``repro.*`` profiler annotation as well.
* ``export``   — Chrome/Perfetto ``trace_event`` rendering of the
                 TraceEvent stream + span set (per-lane tracks, counter
                 tracks, guard-trip instants), the schema validators the
                 CI gate runs, and the ``FlightRecorder`` anomaly ring.
"""
from .metrics import (DEFAULT_MS_BUCKETS, Counter, Gauge, Histogram,
                      MetricsRegistry, validate_metrics)
from .spans import SPAN_NAMES, Span, SpanLog, new_request_id
from .export import (FlightRecorder, collect_events, to_perfetto,
                     validate_perfetto, write_json)

__all__ = [
    "DEFAULT_MS_BUCKETS", "Counter", "Gauge", "Histogram",
    "MetricsRegistry", "validate_metrics",
    "SPAN_NAMES", "Span", "SpanLog", "new_request_id",
    "FlightRecorder", "collect_events", "to_perfetto", "validate_perfetto",
    "write_json",
]
