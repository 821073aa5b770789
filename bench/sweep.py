"""Find the knee of a serving configuration: the highest rate it sustains.

    python3 bench/sweep.py --config table1_tenants --rates 40 80 160 \\
        --seconds 40 --seed 7

One process warms the service once (as ``bench/drivers/open_loop.py``
does, at the first rate) and then serves one window of open-loop traffic
at each rate in turn, drawn by ``bench/traffic.py`` from the seed. For
each rate it prints one JSON line: the requests offered, those answered
by the window's close, the backlog (arrived and not yet answered) at each
quarter of the window, the seconds from the close to the last answer,
the latency percentiles and the programs compiled or loaded in the
window (there should be none). A rate is sustained where completions keep pace
with arrivals: the backlog at the close is under one second of arrivals.
The knee is the highest sustained rate; the serving cells' traffic files
record it and offer fixed fractions of it. Exits 2 without a TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="table1_tenants")
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--drain-s", type=float, default=60.0)
    ap.add_argument("--warmup-seconds", type=float, default=10.0)
    ap.add_argument("--warmup-passes", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

    import time
    import jax
    import numpy as np
    from bench import run as harness, traffic
    from bench.drivers import open_loop
    from repro.core import build_graph

    with open(os.path.join(BENCH_DIR, "configs", args.config + ".json")) as f:
        config = json.load(f)
    harness.enable_compile_cache(ROOT)
    dev = harness.device_summary()
    if dev["platform"] != "tpu":
        print(f"sweep: needs a TPU; JAX found {dev['platform']}",
              file=sys.stderr)
        return 2
    t = time.perf_counter()
    svc, catalogue, passes = open_loop.setup(
        config, False, args.rates[0], args.warmup_seconds,
        args.warmup_passes, args.seed)
    print(json.dumps(dict(warmup_s=time.perf_counter() - t,
                          warmup_pass_compiles=passes, device=dev)),
          flush=True)
    for rate in args.rates:
        n = max(1, round(rate * args.seconds))
        specs = traffic.request_sequence(config["families"], n, args.seed)
        offsets = traffic.arrivals(n, rate, args.seed)
        requests = [build_graph(*catalogue[open_loop._key(s)])
                    for s in specs]
        loads: dict = {}

        def count(event, duration, fun_name="?", **_):
            if event == harness.COMPILE_EVENT:
                loads[fun_name] = loads.get(fun_name, 0.0) + duration
        jax.monitoring.register_event_duration_secs_listener(count)
        t0, done, _ = open_loop.serve(svc, requests, offsets,
                                      time.perf_counter, args.seconds,
                                      args.drain_s)
        jax.monitoring.unregister_event_duration_listener(count)
        rel = done - t0
        answered = ~np.isnan(rel)
        backlog = {}
        for q in (0.25, 0.5, 0.75, 1.0):
            at = q * args.seconds
            backlog[f"{q:g}"] = int((offsets <= at).sum()
                                    - (rel[answered] <= at).sum())
        lat = (rel[answered] - offsets[answered]) * 1e3
        print(json.dumps(dict(
            rate_per_s=rate, offered=n,
            answered_by_close=int((rel[answered] <= args.seconds).sum()),
            answered=int(answered.sum()), backlog=backlog,
            sustained=backlog["1"] < rate,
            last_answer_after_close_s=float(np.nanmax(rel) - args.seconds),
            e2e_ms={f"p{p}": traffic.percentile(lat, p)
                    for p in (50, 95, 99)},
            session=svc.last_session.latency_summary(),
            programs_loaded=loads)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
