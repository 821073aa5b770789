"""Serving launcher: continuous-batching graph-request scheduler over ONE
shared CycleService.

    PYTHONPATH=src python -m repro.launch.serve --requests 24 --slots 4
    PYTHONPATH=src python -m repro.launch.serve --requests 24 --recycle

Production structure on the paper's workload: a queue of enumeration
requests (mixed-size graphs) feeds fixed-size batch slots. Two schedulers
share this file:

* the WAVE-AT-A-TIME path (``serve``): COALESCE by shape class
  (DESIGN.md §6.7) — each wave picks the oldest request's
  ``tune.shape_class`` and pulls up to ``slots`` same-class requests from
  anywhere in the queue into ONE batched device dispatch
  (``CycleService.enumerate_batch``). Every lane rides the dispatch until
  the slowest lane exits; a finished lane's dead bucket is waste.
* the LANE-RECYCLING path (``--recycle`` → ``CycleService.serve_stream``,
  DESIGN.md §6.9): finished lanes retire at superstep boundaries and
  queued same-class requests are re-seeded into the freed lanes without
  retracing — the continuous-batching idiom proper.

Both paths export the same serving metrics at the end: per-request
queue-wait and end-to-end latency (p50/p99), mean lane occupancy (the
utilization recycling exists to raise), warm ms/graph, and the program-
cache hit rate.

(The LM decode-loop demo this file used to host lives on in
``examples/serve_lm.py``.)
"""
from __future__ import annotations

import argparse
import time


# device counters both serving paths sum over their runs
_DEVICE_COUNTERS = ("n_dispatches", "n_host_syncs", "fused_rounds",
                    "split_rounds")


def build_request_queue(n_requests: int, seed: int):
    """Mixed multi-tenant traffic: small grids + G(n, p) instances."""
    import numpy as np
    from ..core import build_graph
    from ..core.graphs import grid_graph, random_gnp

    rng = np.random.default_rng(seed)
    queue = []
    for i in range(n_requests):
        kind = i % 3
        if kind == 0:
            r, c = rng.integers(3, 5), rng.integers(3, 6)
            n, edges = grid_graph(int(r), int(c))
        elif kind == 1:
            n, edges = random_gnp(int(rng.integers(10, 18)), 0.3,
                                  int(rng.integers(1 << 30)))
        else:  # repeat shape → exercises the warm program cache
            n, edges = grid_graph(4, 4)
        queue.append(build_graph(n, edges))
    return queue


def _shape_class(g) -> str:
    from ..tune import shape_class
    return shape_class(g.n, g.m, max(g.max_degree, 1))


def _pop_class_batch(queue, slots: int):
    """Pop the next coalesced wave off ``queue`` IN PLACE.

    Class-FIFO contract (pinned by ``tests/test_sched.py``): the wave's
    class is the OLDEST request's; up to ``slots`` same-class requests are
    taken in queue order from anywhere in the queue; remaining requests
    keep their relative order. Returns (batch, original_indices, cls).
    Indices are popped in descending order so earlier pops never shift the
    positions of later ones.
    """
    cls = _shape_class(queue[0])
    idx = [i for i, g in enumerate(queue)
           if _shape_class(g) == cls][:slots]
    batch = [queue[i] for i in idx]
    for i in reversed(idx):
        queue.pop(i)
    return batch, idx, cls


def _percentiles(xs_ms):
    from ..sched.traffic import percentiles
    return percentiles(xs_ms)


def serve(service, queue, *, slots: int = 4, verbose: bool = True) -> dict:
    """Drain ``queue`` through ``service`` with shape-class coalescing.

    Each wave: take the oldest request's shape class, pull up to ``slots``
    same-class requests (queue order preserved within the class) into one
    batched dispatch; singletons fall through to ``enumerate``. Returns the
    scheduler stats dict: waves, coalesced_lanes, per-class wave counts,
    total cycles, per-request wave latencies, plus the serving metrics the
    recycling path reports too — per-request queue wait / end-to-end
    latency (every request "arrives" when serve() starts, so queue wait is
    time spent behind earlier waves) and ``mean_lane_occupancy`` (per wave:
    lane-rounds lived / lane-rounds dispatched — the dead-lane drag of
    wave-at-a-time scheduling shows up here as occupancy < 1). Both paths
    also report ``cycles_by_request`` (in queue order) and the device
    counters summed over their runs: dispatches, host syncs, and rounds
    by path (fused kernel or split).
    """
    queue = list(queue)
    order = list(range(len(queue)))
    stats = dict(requests=0, waves=0, coalesced_lanes=0, solo_requests=0,
                 n_cycles=0, classes={}, cycles_by_request=[0] * len(queue),
                 **{k: 0 for k in _DEVICE_COUNTERS})
    # registry mirrors (DESIGN.md §6.10): the returned dict stays the
    # legacy view, every count double-writes into the service's registry
    m = service.metrics
    mc = {name: m.counter(f"serve_{name}_total")
          for name in ("requests", "waves", "coalesced_lanes",
                       "solo_requests")}
    h_wait = m.histogram("queue_wait_ms")
    h_e2e = m.histogram("e2e_ms")
    latencies = []
    queue_wait_ms: list[float] = []
    e2e_ms: list[float] = []
    occupancy_sum = 0.0
    t_start = time.perf_counter()
    while queue:
        batch, idx, cls = _pop_class_batch(queue, slots)
        ids = [order[i] for i in idx]
        for i in reversed(idx):
            order.pop(i)

        t1 = time.perf_counter()
        results = (service.enumerate_batch(batch) if len(batch) > 1
                   else [service.enumerate(batch[0])])
        t2 = time.perf_counter()
        dt = t2 - t1

        queue_wait_ms += [round((t1 - t_start) * 1e3, 3)] * len(batch)
        e2e_ms += [round((t2 - t_start) * 1e3, 3)] * len(batch)
        for _ in batch:
            h_wait.observe((t1 - t_start) * 1e3, sched="wave")
            h_e2e.observe((t2 - t_start) * 1e3, sched="wave")
        # lane-rounds lived over lane-rounds dispatched: every lane rides
        # until the slowest lane's wave dies
        rounds = [r.iterations + 1 for r in results]
        occupancy_sum += sum(rounds) / (len(batch) * max(rounds))

        latencies.append(dt / len(batch))
        stats["requests"] += len(batch)
        mc["requests"].inc(len(batch))
        stats["waves"] += 1
        mc["waves"].inc()
        stats["classes"][cls] = stats["classes"].get(cls, 0) + 1
        if len(batch) > 1:
            stats["coalesced_lanes"] += len(batch)
            mc["coalesced_lanes"].inc(len(batch))
        else:
            stats["solo_requests"] += 1
            mc["solo_requests"].inc()
        total = sum(r.n_cycles for r in results)
        stats["n_cycles"] += total
        for rid, r in zip(ids, results):
            stats["cycles_by_request"][rid] = r.n_cycles
        for k in _DEVICE_COUNTERS:   # a batch's lanes share one run's
            stats[k] += results[0].stats[k]
        if verbose:
            print(f"wave {stats['waves']}: [{cls}] {len(batch)} lane(s), "
                  f"{total} cycles, {dt * 1e3 / len(batch):.1f} ms/graph")
    stats["latencies_ms"] = [round(x * 1e3, 2) for x in latencies]
    stats["queue_wait_ms"] = queue_wait_ms
    stats["e2e_ms"] = e2e_ms
    stats["mean_lane_occupancy"] = round(
        occupancy_sum / max(stats["waves"], 1), 4)
    for name, xs in (("queue_wait_ms", queue_wait_ms), ("e2e_ms", e2e_ms)):
        stats.update({f"{name}_{k}": v
                      for k, v in _percentiles(xs).items()})
    return stats


def serve_recycled(service, queue, *, slots=None, arrivals=None,
                   verbose: bool = True) -> dict:
    """Drain ``queue`` through the lane-recycling scheduler
    (``CycleService.serve_stream``) and return the same serving-metrics
    dict shape ``serve`` produces, from the session's own stats."""
    n_done = 0
    n_cycles = 0
    by_request = [0] * len(queue)
    for ridx, res in service.serve_stream(queue, slots=slots,
                                          arrivals=arrivals):
        n_done += 1
        n_cycles += res.n_cycles
        by_request[ridx] = res.n_cycles
        if verbose:
            print(f"done {n_done}/{len(queue)}: request {ridx}, "
                  f"{res.n_cycles} cycles, "
                  f"{res.stats['rounds']} rounds")
    sess = service.last_session
    stats = dict(requests=sess.stats["requests"], n_cycles=n_cycles,
                 waves=sess.stats["supersteps"],
                 boundaries=sess.stats["boundaries"],
                 admissions=sess.stats["admissions"],
                 retirements=sess.stats["retirements"],
                 pools=sess.stats["pools"],
                 classes=dict(sess.stats["classes"]),
                 queue_wait_ms=list(sess.stats["queue_wait_ms"]),
                 e2e_ms=list(sess.stats["e2e_ms"]),
                 cycles_by_request=by_request,
                 **{k: sess.stats[k] for k in _DEVICE_COUNTERS})
    stats.update(sess.latency_summary())
    return stats


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--slots", type=int, default=4,
                    help="max same-class graphs coalesced into one "
                         "batched device program")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--store", action="store_true",
                    help="materialize cycle masks (default: count-only)")
    ap.add_argument("--formulation", default="bitword",
                    choices=("slot", "bitword"))
    ap.add_argument("--backend", default="jnp", choices=("jnp", "pallas"))
    ap.add_argument("--recycle", action="store_true",
                    help="serve through the continuous lane-recycling "
                         "scheduler (repro.sched) instead of "
                         "wave-at-a-time coalescing")
    ap.add_argument("--metrics-json", default=None, metavar="PATH",
                    help="write the unified metrics-registry snapshot "
                         "(repro.obs) to PATH after serving")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="record telemetry + request spans and write a "
                         "Chrome/Perfetto trace_event JSON to PATH "
                         "(open at ui.perfetto.dev)")
    ap.add_argument("--flight-dir", default=None, metavar="DIR",
                    help="attach a FlightRecorder that auto-dumps recent "
                         "telemetry to DIR on guard storms / warm "
                         "retraces / occupancy collapse")
    args = ap.parse_args()

    from ..core import CycleService, EngineConfig
    from ..launch.env import enable_compile_cache
    from ..obs import FlightRecorder

    enable_compile_cache()
    recorder = (FlightRecorder(dump_dir=args.flight_dir)
                if args.flight_dir else None)
    service = CycleService(EngineConfig(store=args.store,
                                        formulation=args.formulation,
                                        backend=args.backend),
                           trace=args.trace_out is not None,
                           recorder=recorder)
    queue = build_request_queue(args.requests, args.seed)

    t0 = time.perf_counter()
    if args.recycle:
        sched = serve_recycled(service, queue, slots=args.slots)
    else:
        sched = serve(service, queue, slots=args.slots)
    wall = time.perf_counter() - t0

    s = service.stats
    hit_rate = s["cache_hits"] / max(s["cache_hits"] + s["cache_misses"], 1)
    done = sched["requests"]
    if args.recycle:
        print(f"all {done} requests served in {wall:.2f}s "
              f"({done / max(wall, 1e-9):.1f} graphs/s)")
        print(f"scheduler: {sched['waves']} supersteps, "
              f"{sched['boundaries']} recycle boundaries, "
              f"{sched['admissions']} admissions / "
              f"{sched['retirements']} retirements over "
              f"{sched['pools']} pool(s), "
              f"{len(sched['classes'])} shape classes")
    else:
        lat = sched["latencies_ms"]
        steady = f"{min(lat):.1f} ms/graph" if lat else "n/a"
        print(f"all {done} requests served in {wall:.2f}s "
              f"({done / max(wall, 1e-9):.1f} graphs/s; "
              f"steady-state {steady})")
        print(f"scheduler: {sched['waves']} waves, "
              f"{sched['coalesced_lanes']} coalesced lanes "
              f"({sched['coalesced_lanes'] / max(done, 1):.0%} of requests), "
              f"{sched['solo_requests']} solo, "
              f"{len(sched['classes'])} shape classes")
    print(f"latency: queue-wait p50 {sched['queue_wait_ms_p50']:.1f} ms / "
          f"p99 {sched['queue_wait_ms_p99']:.1f} ms, "
          f"e2e p50 {sched['e2e_ms_p50']:.1f} ms / "
          f"p99 {sched['e2e_ms_p99']:.1f} ms, "
          f"mean lane occupancy {sched['mean_lane_occupancy']:.2f}")
    print(f"service: {s['programs']} compiled programs, "
          f"{s['cache_hits']} hits / {s['cache_misses']} misses "
          f"({hit_rate:.0%} hit rate), {s['n_traces']} traces")

    if args.metrics_json:
        from ..obs import validate_metrics
        service.metrics.to_json(
            args.metrics_json, recycle=args.recycle,
            requests=args.requests, slots=args.slots)
        errs = validate_metrics(service.metrics.snapshot())
        print(f"metrics snapshot -> {args.metrics_json}"
              + (f" ({len(errs)} schema problems!)" if errs else ""))
    if args.trace_out:
        from ..obs import (collect_events, to_perfetto, validate_perfetto,
                           write_json)
        doc = to_perfetto(collect_events(service), service.spans.spans,
                          meta=dict(recycle=args.recycle,
                                    requests=args.requests))
        errs = validate_perfetto(doc)
        write_json(args.trace_out, doc)
        print(f"perfetto trace -> {args.trace_out} "
              f"({len(doc['traceEvents'])} events"
              + (f", {len(errs)} schema problems!)" if errs else ")"))
    if recorder is not None and recorder.dumps:
        print(f"flight recorder: {len(recorder.dumps)} dump(s) "
              f"-> {args.flight_dir} ({dict(recorder.trips)})")


if __name__ == "__main__":
    main()
