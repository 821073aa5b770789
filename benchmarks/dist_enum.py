"""Distributed-enumeration benchmarks (TPU chips in process, or simulated
CPU devices in a child process — ``launch.env.run_multi_device``).

``run``/``main`` — the original scaling sweep: same graph on 1/2/4/8 fake
devices, verifying count invariance and reporting wall time.

``multihost_smoke`` — the 2-level-mesh A/B (DESIGN.md §7): the same graph
enumerated on a flat 8-device mesh, a hierarchical 2×4 (host × device)
mesh, and the 2×4 mesh with the EF-compressed cross-host wire. Asserts
bit-identical counts and |T| histories across all three arms, zero
lost/dropped rows, ≥4× lower cross-host wire bytes under compression (both
the driver's metered bytes and the replay twin's modeled bytes), unchanged
dispatch/sync counts vs the flat arm, and that the tuner's
``cross_balance_every`` pick is the argmin of the cost-model scores.
Writes ``results/BENCH_multihost_smoke.json``.
"""
from __future__ import annotations

import json
import os
import sys

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
RESULTS_DIR = os.path.join(os.path.dirname(__file__), "results")
if SRC not in sys.path:
    sys.path.insert(0, SRC)
from repro.launch.env import run_multi_device  # noqa: E402

CODE = """
import time, numpy as np, jax
from jax.sharding import Mesh
from repro.core import EngineConfig, build_graph
from repro.core.distributed import enumerate_distributed
from repro.core.graphs import grid_graph

ndev = {ndev}
mesh = Mesh(np.array(jax.devices())[:ndev].reshape(ndev,), ('data',))
n, edges = grid_graph(5, 9)
g = build_graph(n, edges)
t0 = time.perf_counter()
out = enumerate_distributed(
    g, mesh, cfg=EngineConfig(store=False, local_capacity=1<<15,
                              balance_block=128))
dt = time.perf_counter() - t0
print(f"{{out['n_cycles']}},{{dt*1e3:.1f}},{{out['dropped']}}")
"""


def run():
    rows = []
    for ndev in (1, 2, 4, 8):
        try:
            out = run_multi_device(CODE.format(ndev=ndev), ndev,
                                   src_path=SRC)
        except RuntimeError:
            out = ""
        if not out:
            rows.append((f"dist_enum_{ndev}dev", -1,
                         "ERROR" if out == "" else "SKIPPED"))
            continue
        count, ms, dropped = out.strip().splitlines()[-1].split(",")
        rows.append((f"dist_enum_{ndev}dev", float(ms) * 1e3,
                     f"cycles={count};dropped={dropped}"))
    return rows


# --- 2-level hierarchical mesh A/B (host sim, 8 fake devices) --------------
# The graph must keep n <= 16: the compressed row is ceil(n/8)+2 bytes vs
# 8*nw+12 exact, so small-n graphs are where the >=4x wire-byte gate holds
# (n=16, nw=1: 1288 vs 273 B per 64-row block+stats ~ 4.7x).
_MULTIHOST_CODE = """
import dataclasses, json, time, numpy as np, jax
from jax.sharding import Mesh
from repro.core import (CycleService, EngineConfig, build_graph,
                        sequential_chordless_cycles)
from repro.core.graphs import grid_graph
from repro.tune.autotune import AutoTuner
from repro.tune.cost_model import CostModel, DistProfile, replay_dist

n, edges = grid_graph(4, 4)
edges = list(edges) + [(0, 5), (10, 15)]   # chords: non-trivial blocking
g = build_graph(n, edges)
ref, _ = sequential_chordless_cycles(n, edges)
nw = int(g.adj_bits.shape[1])

dev = np.array(jax.devices())[:8]
flat = Mesh(dev.reshape(8,), ("data",))
hier = Mesh(dev.reshape(2, 4), ("host", "data"))
common = dict(store=False, superstep_rounds=4, local_capacity=1 << 12,
              balance_block=16, balance_every=1)
arms = dict(
    flat=EngineConfig(mesh=flat, axis="data", **common),
    hier=EngineConfig(mesh=hier, axis="data", host_axis="host",
                      cross_balance_every=2, **common),
    hier_comp=EngineConfig(mesh=hier, axis="data", host_axis="host",
                           cross_balance_every=2, compress_cross_host=True,
                           **common))
svc = CycleService()
rows, results = {}, {}
for arm, cfg in arms.items():
    t0 = time.perf_counter()
    res = svc.enumerate(g, config=cfg)
    cold = time.perf_counter() - t0
    warm = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        res = svc.enumerate(g, config=cfg)
        warm = min(warm, time.perf_counter() - t0)
    results[arm] = res
    s = res.stats
    rows[arm] = dict(
        arm=arm, n_cycles=int(res.n_cycles), ref=int(ref),
        history=[int(h["T"]) for h in res.history],
        n_dispatches=int(s["n_dispatches"]),
        n_host_syncs=int(s["n_host_syncs"]),
        moved_intra=int(s.get("moved_intra", 0)),
        moved_cross=int(s.get("moved_cross", 0)),
        lost=int(s["lost"]), dropped=int(s["dropped"]),
        comm_bytes_intra=int(s.get("comm_bytes_intra", 0)),
        comm_bytes_cross=int(s.get("comm_bytes_cross", 0)),
        t_cold_ms=round(cold * 1e3, 2), t_warm_ms=round(warm * 1e3, 2))

# replay twin: modeled per-tier bytes for both hier arms under the SAME
# profile (byte accounting must agree with the driver's metered stats)
prof = DistProfile.from_run(results["hier"].history, n=g.n, nw=nw,
                            ndev=8, cfg=arms["hier"])
model = CostModel()
modeled = {a: replay_dist(prof, arms[a]) for a in ("hier", "hier_comp")}

# tuner: grid argmin must hold along the cross_balance_every axis
tuner = AutoTuner(model=model)
tuned = tuner.tune(prof, arms["hier"])
scores = {c: model.score(prof, dataclasses.replace(
              tuned, cross_balance_every=c))
          for c in (1, 2, 4, 8)}
doc = dict(
    rows=rows,
    modeled={a: dict(bytes_intra=r.bytes_intra, bytes_cross=r.bytes_cross)
             for a, r in modeled.items()},
    tuner=dict(pick=int(tuned.cross_balance_every),
               compress_pick=bool(tuned.compress_cross_host),
               scores={str(c): round(s, 4) for c, s in scores.items()}))
print(json.dumps(doc))
"""


def multihost_smoke(out_path: str | None = None):
    """Flat-vs-hierarchical-vs-compressed A/B on 8 devices (simulated
    unless 8 TPU chips are attached); see module docstring for the
    asserted gates. Returns None (skipped) on a smaller TPU host."""
    out = run_multi_device(_MULTIHOST_CODE, 8, src_path=SRC)
    if out is None:
        print("multihost smoke: skipped (needs 8 devices)")
        return None
    doc = json.loads(out.strip().splitlines()[-1])
    rows = doc["rows"]
    flat, hier, comp = rows["flat"], rows["hier"], rows["hier_comp"]

    # equivalence: every arm reproduces the reference count and the flat
    # arm's per-round |T| history bit-for-bit (placement never changes
    # what expands), with nothing lost or dropped
    for r in rows.values():
        assert r["n_cycles"] == r["ref"], r
        assert r["history"] == flat["history"], (r["arm"], "history")
        assert r["lost"] == 0 and r["dropped"] == 0, r
    # dispatch/sync gate: the hierarchy adds collectives INSIDE the
    # superstep, never extra dispatches or host syncs
    for r in (hier, comp):
        assert r["n_dispatches"] == flat["n_dispatches"], r
        assert r["n_host_syncs"] == flat["n_host_syncs"], r
    # wire-byte gate: the EF-compressed cross-host wire is >=4x smaller,
    # in both the driver's metered bytes and the replay twin's model —
    # and twin == driver (one shared formula)
    assert comp["comm_bytes_cross"] > 0, comp
    driver_ratio = hier["comm_bytes_cross"] / comp["comm_bytes_cross"]
    m_hier, m_comp = doc["modeled"]["hier"], doc["modeled"]["hier_comp"]
    model_ratio = m_hier["bytes_cross"] / max(m_comp["bytes_cross"], 1)
    assert driver_ratio >= 4.0, (driver_ratio, rows)
    assert model_ratio >= 4.0, (model_ratio, doc["modeled"])
    for arm, m in (("hier", m_hier), ("hier_comp", m_comp)):
        assert m["bytes_cross"] == rows[arm]["comm_bytes_cross"], (arm, m)
        assert m["bytes_intra"] == rows[arm]["comm_bytes_intra"], (arm, m)
    # tuner gate: the stored pick is the argmin of the model scores along
    # the cross_balance_every axis (grid winner beats all single-axis
    # perturbations)
    scores = {int(c): s for c, s in doc["tuner"]["scores"].items()}
    pick = doc["tuner"]["pick"]
    assert scores[pick] == min(scores.values()), doc["tuner"]

    out_doc = dict(benchmark="multihost_smoke", graph="Grid_4x4+2chords",
                   mesh="2x4 (host x device), flat 8-dev control",
                   rows=[flat, hier, comp],
                   cross_bytes_ratio=round(driver_ratio, 2),
                   modeled_cross_ratio=round(model_ratio, 2),
                   tuner=doc["tuner"])
    path = out_path or os.path.join(RESULTS_DIR,
                                    "BENCH_multihost_smoke.json")
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        json.dump(out_doc, f, indent=2)
    print(f"multihost smoke: {flat['n_cycles']} cycles on all 3 arms, "
          f"cross-host bytes {hier['comm_bytes_cross']} -> "
          f"{comp['comm_bytes_cross']} ({driver_ratio:.1f}x smaller "
          f"compressed), tuner cross_balance_every={pick} -> {path}")
    return out_doc


def main():
    print("name,us_per_call,derived")
    for name, us, derived in run():
        print(f"{name},{us:.1f},{derived}")


if __name__ == "__main__":
    main()
