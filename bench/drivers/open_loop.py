"""Open-loop serving: independent tenants send graphs at a fixed mean rate.

Config keys: ``families`` (the request catalogue: per family a share and
its graphs), ``service`` and ``engine`` (the service's options). Traffic
keys: ``rate_per_s`` (the offered load, a fixed fraction ``knee_factor``
of ``knee_per_s``, the highest rate the knee sweep of ``bench/sweep.py``
found sustained), ``store``, ``warmup_seconds`` and ``warmup_passes``
(the open-loop passes of set-up, see ``setup``) and ``drain_s`` (how
long after the window's close the run waits for the answers still due).

The window offers ``rate_per_s * --seconds`` requests, drawn and timed by
``bench/traffic.py`` from the seed, to ``CycleService.serve_stream`` as
arrival offsets: the service admits each at its due time, so a stall
delays the requests behind it and that shows in their latency. Each
request is a graph object of its own, built before the window. A
request's latency runs from its due time to the moment its result reaches
the benchmark. ``e2e_p95_ms`` is the 95th percentile over every request
of the window; ``graphs_per_s`` counts the results that reached the
benchmark before the window closed, over the window's length. After the
close the run keeps taking results until every request is answered, or
``drain_s`` has passed, and compares each answer with the reference.
"""
from __future__ import annotations

import json
import time

import numpy as np

from bench import graphs, reference, traffic
from bench.run import COMPILE_EVENT, Outcome


def _key(spec) -> str:
    return json.dumps(spec, sort_keys=True)


# gaps between the warm-up's arrivals within a family: from inside one
# superstep to past a whole wave, so that requests are admitted into pools
# at every bucket the waves pass through, growing and shrinking
WARM_GAPS_S = (0.0005, 0.001, 0.002, 0.004, 0.008, 0.016, 0.032, 0.064)


def setup(config: dict, store: bool, rate: float, warmup_seconds: float,
          warmup_passes: int, seed: int):
    """The service, warmed up, and the catalogue as ``{key: (n, edges)}``.

    The window compiles nothing only where set-up has reached every
    program it uses; the service builds one per pool shape, frontier
    bucket and kind (superstep, admission merge) on first use, and which
    buckets an admission meets depends on the timing of the traffic. So
    set-up serves every graph alone, then each family round-robin at the
    gaps of ``WARM_GAPS_S``, then open-loop passes of the cell's own
    traffic, ``warmup_passes`` of them, each ``warmup_seconds`` of it at
    ``rate``, the first drawn from the run's own seed and pass ``k`` from
    ``seed + k``: the same work in every run. Also returns, for each
    pass, the programs it compiled or loaded (none, once set-up has
    reached the window's)."""
    import jax
    from repro.core import CycleService, EngineConfig, build_graph

    families = config["families"]
    catalogue = {_key(s): graphs.from_spec(s)
                 for fam in families.values() for s in fam["graphs"]}
    svc = CycleService(EngineConfig(store=store, **config["engine"]),
                       **config["service"])
    for n_e in catalogue.values():
        list(svc.serve_stream([build_graph(*n_e)]))
    for fam in families.values():
        specs = fam["graphs"] * len(WARM_GAPS_S)
        gaps = [WARM_GAPS_S[i % len(WARM_GAPS_S)] for i in range(len(specs))]
        list(svc.serve_stream(
            [build_graph(*catalogue[_key(s)]) for s in specs],
            arrivals=list(np.cumsum([0.0] + gaps[:-1]))))
    compiles, passes = [], []

    def count(event, _duration, **_):
        if event == COMPILE_EVENT:
            compiles.append(event)
    n_warm = max(1, round(rate * warmup_seconds))
    jax.monitoring.register_event_duration_secs_listener(count)
    try:
        for k in range(warmup_passes):
            compiles.clear()
            wspecs = traffic.request_sequence(families, n_warm, seed + k)
            list(svc.serve_stream(
                [build_graph(*catalogue[_key(s)]) for s in wspecs],
                arrivals=list(traffic.arrivals(n_warm, rate, seed + k))))
            passes.append(len(compiles))
    finally:
        jax.monitoring.unregister_event_duration_listener(count)
    return svc, catalogue, passes


def serve(svc, requests, offsets, t0_hook, seconds: float, drain_s: float,
          on_close=None, annotate=None):
    """Offer ``requests`` at ``offsets`` and take results until all are
    answered or ``drain_s`` has passed since the window closed. Returns
    (t0, done times with NaN for none, answers by request)."""
    import contextlib
    n = len(requests)
    done = np.full(n, np.nan)
    answers: list = [None] * n
    stream = svc.serve_stream(requests, arrivals=list(offsets))
    t0 = t0_hook()
    close, deadline = t0 + seconds, t0 + seconds + drain_s
    closed = False
    while True:
        with (annotate("serve") if annotate else contextlib.nullcontext()):
            item = next(stream, None)
        now = time.perf_counter()
        if item is None:
            break
        idx, res = item
        done[idx], answers[idx] = now, res.n_cycles
        if not closed and now >= close:
            closed = True
            if on_close:
                on_close()
        if now >= deadline:
            break
    if not closed and on_close:
        on_close()
    stream.close()
    return t0, done, answers


def run(run) -> Outcome:
    from repro.core import build_graph

    t, c = run.traffic, run.config
    rate = float(t["rate_per_s"])
    n_req = max(1, round(rate * run.seconds))
    specs = traffic.request_sequence(c["families"], n_req, run.seed)
    offsets = traffic.arrivals(n_req, rate, run.seed)
    with run.annotate("warmup"):
        svc, catalogue, passes = setup(c, bool(t["store"]), rate,
                               float(t["warmup_seconds"]),
                               int(t["warmup_passes"]), run.seed)
    requests = [build_graph(*catalogue[_key(s)]) for s in specs]
    traces0 = svc.stats.get("n_traces") or 0

    t0, done, answers = serve(svc, requests, offsets, run.begin_window,
                              run.seconds, float(t["drain_s"]),
                              on_close=run.end_window,
                              annotate=run.annotate)
    close = t0 + run.seconds
    n_traces = (svc.stats.get("n_traces") or 0) - traces0
    session = getattr(svc, "last_session", None)
    session_stats = dict(getattr(session, "stats", None) or {})
    del svc, requests, session

    want = {k: reference.enumerate_cycles(*ne).count
            for k, ne in catalogue.items()}
    answered = ~np.isnan(done)
    errors = [abs(answers[i] - want[_key(specs[i])])
              for i in np.flatnonzero(answered)]
    wrong = sum(e != 0 for e in errors)
    missing = int(n_req - answered.sum())
    latency_ms = (done[answered] - (t0 + offsets[answered])) * 1e3
    e2e = dict(graphs_per_s=float((done[answered] <= close).sum())
               / run.seconds)
    if answered.any():
        e2e["e2e_p95_ms"] = traffic.percentile(latency_ms, 95)
    return Outcome(
        attempted=n_req, failed=wrong + missing, end_to_end=e2e,
        counters=dict(session=session_stats, n_traces_in_window=n_traces,
                      warmup_pass_compiles=passes,
                      completed=int(answered.sum())),
        checks={"count_error_max": (max(errors, default=0), 0),
                "missing_answers": (missing, 0)})
