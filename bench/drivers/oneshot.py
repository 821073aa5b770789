"""One-shot enumeration: one client runs whole enumerations back to back.

Traffic keys: ``graph`` (a name or edge list, see ``bench/graphs.py``),
``store`` (return every cycle's vertex set, or count only) and
``paths_extended`` (the paths the reference extends on this graph in this
numbering, which the roofline metric reads). The seed changes no work:
the graph and its numbering are fixed, because renumbering a graph moves
its work by a fifth either way.

Set-up builds the service and the graph and runs one whole enumeration,
which compiles every program the graph's wave reaches. The window then
runs enumerations back to back and ends with the first that ends after
``--seconds``; ``oneshot_s`` is the window's length over the enumerations
in it, each ending when its result is on the host. Every answer of the
window is compared with the reference: the count exactly, and with
``store`` the set of vertex sets exactly.
"""
from __future__ import annotations

import time

import numpy as np

from bench import graphs, reference
from bench.run import Outcome


def _set_difference(got: np.ndarray, want: np.ndarray) -> int:
    """Vertex sets in one answer and not the other, duplicates counted."""
    if got.shape == want.shape and np.array_equal(got, want):
        return 0
    g = [r.tobytes() for r in got]
    w = {r.tobytes() for r in want}
    return len(set(g) ^ w) + len(g) - len(set(g))


def run(run) -> Outcome:
    from repro.core import CycleService, EngineConfig, build_graph

    t = run.traffic
    n, edges = graphs.from_spec(t["graph"])
    store = bool(t["store"])
    svc = CycleService(EngineConfig(store=store, **run.config["engine"]),
                       **run.config["service"])
    g = build_graph(n, edges)
    with run.annotate("warmup"):
        svc.enumerate(g)
    traces0 = svc.stats.get("n_traces") or 0

    answers, stats = [], []
    t0 = run.begin_window()
    while True:
        with run.annotate("enumerate"):
            res = svc.enumerate(g)
        answers.append((res.n_cycles, res.cycle_masks))
        stats.append(dict(res.stats or {}))
        if time.perf_counter() - t0 >= run.seconds:
            break
    t1 = run.end_window()
    n_traces = (svc.stats.get("n_traces") or 0) - traces0
    del svc, g, res

    ref = reference.enumerate_cycles(n, edges, store=store)
    count_err = [abs(c - ref.count) for c, _ in answers]
    checks = {"count_error_max": (max(count_err), 0)}
    failed = sum(e != 0 for e in count_err)
    if store:
        diffs = [_set_difference(
            reference.canonical(reference.words32_to_64(m, n)), ref.cycles)
            for _, m in answers]
        checks["vertex_set_diff_max"] = (max(diffs), 0)
        failed = sum(e != 0 or d != 0 for e, d in zip(count_err, diffs))
    counters = dict(enumerations=stats, n_traces_in_window=n_traces,
                    n_vertices=n, paths_extended=t["paths_extended"],
                    stored_cycles=[c for c, _ in answers] if store else None)
    return Outcome(attempted=len(answers), failed=failed,
                   end_to_end=dict(oneshot_s=(t1 - t0) / len(answers)),
                   counters=counters, checks=checks)
