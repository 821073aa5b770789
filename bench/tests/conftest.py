"""Fixtures of the benchmark's CPU tests: a copy of the benchmark with
tiny cells added the way a later change would add them, by files and
``BENCHMARK.json`` entries alone."""
from __future__ import annotations

import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for path in (ROOT, os.path.join(ROOT, "src")):
    if path not in sys.path:
        sys.path.insert(0, path)

TINY_CELLS = {
    # name: (config, traffic, traffic file)
    "tiny_count": ("table1_oneshot", "tiny_count",
                   {"driver": "oneshot", "graph": "Grid_4x4", "store": False,
                    "paths_extended": 133}),
    "tiny_store": ("table1_oneshot", "tiny_store",
                   {"driver": "oneshot", "graph": "Grid_4x4", "store": True,
                    "paths_extended": 133}),
    "tiny_mix": ("tiny_tenants", "tiny_mix",
                 {"driver": "open_loop", "store": False, "knee_per_s": 40,
                  "knee_factor": 1.0, "rate_per_s": 40,
                  "warmup_seconds": 0.1, "warmup_passes": 1,
                  "drain_s": 60}),
}
TINY_FAMILIES = {
    "grids": {"share": 0.5, "graphs": ["Grid_3x4"]},
    "complete_bipartite": {"share": 0.25, "graphs": ["K_3_3"]},
    "cycles": {"share": 0.25,
               "graphs": ["C_8", {"name": "square_with_tail", "n": 5,
                                  "edges": [[0, 1], [1, 2], [2, 3],
                                            [3, 0], [3, 4]]}]},
}
DUMMY_METRIC = '''"""A per-layer metric added as a file: requests per completed one."""


def read(r):
    done = r["counters"].get("completed")
    return 1.0 if done else None
'''


def load_spec() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    """A checkout-like root: the benchmark as committed, plus the tiny
    cells, a tenants configuration and a per-layer metric, each in a file
    of its own, and their entries in ``BENCHMARK.json``."""
    root = str(tmp_path_factory.mktemp("bench_root"))
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    spec = load_spec()
    with open(os.path.join(ROOT, "bench", "configs",
                           "table1_tenants.json")) as f:
        tenants = json.load(f)
    tenants["families"] = TINY_FAMILIES
    with open(os.path.join(root, "bench", "configs",
                           "tiny_tenants.json"), "w") as f:
        json.dump(tenants, f)
    spec["configs"].append({"name": "tiny_tenants", "source": "test",
                            "file": "bench/configs/tiny_tenants.json",
                            "reduced": [], "why": "test"})
    for name, (config, traffic, body) in TINY_CELLS.items():
        with open(os.path.join(root, "bench", "traffic",
                               traffic + ".json"), "w") as f:
            json.dump(body, f)
        spec["workloads"].append({"name": name, "config": config,
                                  "traffic": traffic, "chips": 1,
                                  "why": "test"})
    with open(os.path.join(root, "bench", "metrics",
                           "tiny_dummy.py"), "w") as f:
        f.write(DUMMY_METRIC)
    for m in spec["end_to_end"]:
        if m["name"] == "oneshot_s":
            m["workloads"] += ["tiny_count", "tiny_store"]
    # the serving metrics and a scheduler reader, added as a later serving
    # cell would add them
    for name, unit, better in (("graphs_per_s", "graphs/s", "higher"),
                               ("e2e_p95_ms", "ms", "lower")):
        spec["end_to_end"].append({"name": name, "unit": unit,
                                   "better": better, "bound": 0.25,
                                   "source": "host_clock",
                                   "workloads": ["tiny_mix"]})
    spec["per_layer"].append({"name": "queue_wait_p95_ms", "unit": "ms",
                              "better": "lower", "source": "program_span",
                              "layer": "scheduler", "moves": "e2e_p95_ms",
                              "workloads": ["tiny_mix"]})
    spec["per_layer"].append({"name": "tiny_dummy", "unit": "1",
                              "better": "lower", "source": "program_counter",
                              "layer": "scheduler", "moves": "graphs_per_s",
                              "workloads": ["tiny_mix"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return root


@pytest.fixture
def run_tiny(tiny_root):
    """Run one tiny cell through the harness on the CPU: everything a run
    does except the look for a chip."""
    from bench import run as harness

    def go(name: str, *, seed: int = 2**31 + 11, seconds: float = 0.5):
        cell = harness.load_cell(tiny_root, name)
        return harness.run_cell(cell, tiny_root, seed=seed,
                                seconds=seconds, trace=False)
    return go
