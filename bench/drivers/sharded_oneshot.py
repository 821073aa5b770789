"""Sharded one-shot counting: one client counts a whole graph on a mesh of
chips, back to back.

Traffic keys as ``oneshot.py``'s: ``graph``, ``store`` (false: the sharded
path counts only) and ``paths_extended``. The configuration adds ``chips``
and ``mesh``: a 1-D mesh of the first ``chips`` devices on the axis
``mesh.axis``, over which the service shards the frontier's rows.

Set-up builds the mesh, the service and the graph and runs one whole
enumeration, which compiles the deal and the sharded superstep. The window
then runs enumerations back to back and ends with the first that ends
after ``--seconds``; ``oneshot_s`` is the window's length over the
enumerations in it, each ending when its count is on the host. After the
window every count is compared with ``bench/reference.py``'s exactly, and
every enumeration must report no row dropped by a full device frontier
and none lost by balancing.
"""
from __future__ import annotations

import time

import numpy as np

from bench import graphs, reference
from bench.run import Outcome


def run(run) -> Outcome:
    import jax
    from jax.sharding import Mesh
    from repro.core import CycleService, EngineConfig, build_graph

    conf, t = run.config, run.traffic
    if t["store"]:
        raise ValueError("the sharded path counts only; set store false")
    n, edges = graphs.from_spec(t["graph"])
    axis = conf["mesh"]["axis"]
    mesh = Mesh(np.array(jax.devices()[:conf["chips"]]), (axis,))
    svc = CycleService(EngineConfig(store=False, mesh=mesh, axis=axis,
                                    **conf["engine"]), **conf["service"])
    g = build_graph(n, edges)
    with run.annotate("warmup"):
        svc.enumerate(g)
    traces0 = svc.stats.get("n_traces") or 0

    counts, stats = [], []
    t0 = run.begin_window()
    while True:
        with run.annotate("enumerate"):
            res = svc.enumerate(g)
        counts.append(res.n_cycles)
        stats.append(dict(res.stats or {}))
        if time.perf_counter() - t0 >= run.seconds:
            break
    t1 = run.end_window()
    n_traces = (svc.stats.get("n_traces") or 0) - traces0
    del svc, g, res

    ref = reference.enumerate_cycles(n, edges)
    count_err = [abs(c - ref.count) for c in counts]
    dropped = [s.get("dropped", 0) for s in stats]
    lost = [s.get("lost", 0) for s in stats]
    checks = {"count_error_max": (max(count_err), 0),
              "dropped_max": (max(dropped), 0),
              "lost_max": (max(lost), 0)}
    failed = sum(e != 0 or d != 0 or x != 0
                 for e, d, x in zip(count_err, dropped, lost))
    counters = dict(enumerations=stats, n_traces_in_window=n_traces,
                    n_vertices=n, paths_extended=t["paths_extended"])
    return Outcome(attempted=len(counts), failed=failed,
                   end_to_end=dict(oneshot_s=(t1 - t0) / len(counts)),
                   counters=counters, checks=checks)
