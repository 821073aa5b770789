"""The control: a run with the system replaced by a reference that drops paths.

    python3 bench/control.py --workload <name> --seeds 1 2 3 --seconds 40

Every number a cell compares is exact (a count, a set of vertex sets, a
count of missing answers), so a correct run reads 0 in each and every
limit is 0. The configurations state no precision; their guarantee is that
every chordless cycle is found exactly once. The control breaks it the
way a tempting shortcut would: it is the plain reference
(``bench/reference.py``) with a frontier buffer that holds only the
largest power of two below the widest level of the graph and silently
drops the rest, as a fixed-size buffer that overflows would. It takes the
system's place behind the same driver, at the cell's own size and load:
``repro.core``'s ``CycleService``, ``EngineConfig`` and ``build_graph``
are replaced in this process, so the control touches nothing of the
system. For each seed the script prints the run's result line, and it
exits 1 unless every run came out not correct.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import types

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


class ControlGraph:
    def __init__(self, n, edges, **_):
        self.n, self.edges = int(n), [tuple(e) for e in edges]


class ControlService:
    """Answers as the system's service does, from the capped reference."""

    def __init__(self, config=None, **_):
        self.store = bool(getattr(config, "store", False))
        self.stats = {"n_traces": 0}
        self._caps: dict = {}

    def enumerate(self, g, **_):
        from bench import reference
        key = (g.n, tuple(g.edges))
        if key not in self._caps:
            self._caps[key] = reference.control_cap(
                reference.enumerate_cycles(g.n, g.edges))
        r = reference.enumerate_cycles(g.n, g.edges, store=self.store,
                                       frontier_cap=self._caps[key])
        masks = None
        if self.store:
            import numpy as np
            words = -(-g.n // 32)
            m64 = r.cycles
            m32 = np.zeros((len(m64), 2 * m64.shape[1]), np.uint32)
            m32[:, 0::2] = (m64 & np.uint64(0xFFFFFFFF)).astype(np.uint32)
            m32[:, 1::2] = (m64 >> np.uint64(32)).astype(np.uint32)
            masks = m32[:, :words]
        return types.SimpleNamespace(n_cycles=r.count, cycle_masks=masks,
                                     stats={})

    def serve_stream(self, graphs, *, arrivals=None, **_):
        graphs = list(graphs)
        arrivals = list(arrivals) if arrivals is not None else \
            [0.0] * len(graphs)
        t0 = time.perf_counter()
        for i in sorted(range(len(graphs)), key=lambda i: arrivals[i]):
            wait = t0 + arrivals[i] - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            yield i, self.enumerate(graphs[i])


def control_config(**fields):
    return types.SimpleNamespace(**fields)


# what the control replaces in ``repro.core``
REPLACEMENTS = dict(CycleService=ControlService, EngineConfig=control_config,
                    build_graph=ControlGraph)


def install() -> None:
    """Put the control in the system's place for this process."""
    import repro.core as core
    for name, obj in REPLACEMENTS.items():
        setattr(core, name, obj)


def run_control(root: str, workload: str, seeds, seconds: float) -> list:
    from bench import run as harness
    install()
    cell = harness.load_cell(root, workload)
    return [harness.run_cell(cell, root, seed=s, seconds=seconds,
                             trace=False) for s in seeds]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    results = run_control(ROOT, args.workload, args.seeds, args.seconds)
    for seed, res in zip(args.seeds, results):
        res.pop("window", None)
        print(json.dumps(dict(seed=seed, **res)), flush=True)
    return 0 if not any(r["correct"] for r in results) else 1


if __name__ == "__main__":
    sys.exit(main())
