"""Chip smoke test: the served enumeration path on one TPU, at paper scale.

    python3 chip_smoke.py              # one chip: the phases below
    python3 chip_smoke.py --chips 4    # the sharded path on a 4-chip mesh

Phases, in one process that shares one compile cache
(``JAX_COMPILATION_CACHE_DIR`` if set, else ``<checkout>/.jax_cache``):

* one-shot — Grid_6x10 count-only through ``CycleService`` (bitword), once
  on the Pallas backend and once on jnp: 800,139 chordless cycles and no
  triangles (paper Table 1);
* store — Grid_5x10 with every cycle stored, Pallas backend: 52,620
  cycles whose vertex sets equal the sequential reference's;
* served — 32 requests of ``launch.serve.build_request_queue`` on the
  Pallas backend, through wave-at-a-time batching (``serve``) and through
  lane recycling (``serve_recycled``); each count equals the sequential
  reference's.

``--chips 4`` runs only the sharded path: Grid_7x10 count-only on a
4-device mesh, each device's rounds on the bitword/Pallas path at 2^23
rows (the benchmark's ``table1_sharded4`` configuration), checked against
Table 1's 8,136,453 with no dropped or lost rows and frontier rows on
every device.

Each phase prints one line of counters beside the device kind. Any
mismatch or error exits non-zero before the last line, which is the JSON
object ``{"ok": true, "device": {...}}``. Without a TPU the script exits
non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))


def _device():
    import jax
    d = jax.devices()[0]
    return dict(platform=d.platform, kind=d.device_kind,
                count=len(jax.devices()))


def _peak_bytes():
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use", "not reported")


def _report(phase: str, kind: str, **fields) -> dict:
    """One phase line: every number beside the device kind."""
    fields = dict(fields, peak_bytes_in_use=_peak_bytes())
    print(f"[{kind}] {phase}: " + " ".join(f"{k}={v}" for k, v in
                                          fields.items()), flush=True)
    return fields


def _timed(fn):
    t = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t


def _graph(name: str):
    from repro.core import build_graph
    from repro.core.graphs import PAPER_TABLE1, grid_graph
    if name in PAPER_TABLE1:
        builder, n_tri, n_long = PAPER_TABLE1[name]
        n, edges = builder()
        return build_graph(n, edges), (n, edges), n_tri + n_long
    rows, cols = (int(x) for x in name.split("_")[1].split("x"))
    n, edges = grid_graph(rows, cols)
    return build_graph(n, edges), (n, edges), None


def _edges(g):
    """The edge list of a built graph, read back from its CSR tables."""
    import numpy as np
    offsets, nbr = np.asarray(g.offsets), np.asarray(g.neighbors)
    return [(u, int(v)) for u in range(g.n)
            for v in nbr[offsets[u]:offsets[u + 1]] if u < v]


def _run_stats(res) -> dict:
    s = res.stats
    return dict(dispatches=s["n_dispatches"], host_syncs=s["n_host_syncs"],
                fused_rounds=s["fused_rounds"],
                split_rounds=s["split_rounds"])


def one_shot(name: str, backend: str, kind: str, *,
             expect: int | None = None) -> dict:
    """Count-only enumeration of one graph, cold (compiles) then warm."""
    from repro.core import CycleService, EngineConfig
    from repro.core.ref_sequential import sequential_chordless_cycles
    g, (n, edges), table = _graph(name)
    expect = expect if expect is not None else table
    if expect is None:
        expect = sequential_chordless_cycles(n, edges, store=False)[0]
    svc = CycleService(EngineConfig(store=False, formulation="bitword",
                                    backend=backend))
    cold, t_cold = _timed(lambda: svc.enumerate(g))
    warm, t_warm = _timed(lambda: svc.enumerate(g))
    for res in (cold, warm):
        assert res.n_cycles == expect, (name, backend, res.n_cycles, expect)
        assert res.n_triangles == 0, (name, backend, res.n_triangles)
    return _report(f"one_shot {name} {backend}", kind,
                   cycles=warm.n_cycles, triangles=warm.n_triangles,
                   cold_s=t_cold, warm_s=t_warm,
                   **_run_stats(warm))


def store_mode(name: str, kind: str, *, rounds_per_launch: int = 1) -> dict:
    """Every cycle of one graph stored on the Pallas backend; vertex sets
    must equal the sequential reference's."""
    from repro.core import CycleService, EngineConfig
    from repro.core.ref_sequential import sequential_chordless_cycles
    g, (n, edges), table = _graph(name)
    count, cycles = sequential_chordless_cycles(n, edges)
    assert table is None or count == table, (name, count, table)
    want = sorted(tuple(sorted(c)) for c in cycles)
    svc = CycleService(EngineConfig(store=True, formulation="bitword",
                                    backend="pallas",
                                    rounds_per_launch=rounds_per_launch))
    cold, t_cold = _timed(lambda: svc.enumerate(g))
    warm, t_warm = _timed(lambda: svc.enumerate(g))
    for res in (cold, warm):
        got = sorted(tuple(sorted(c)) for c in res.cycles_as_sets(g.n))
        assert res.n_cycles == count == len(got), (res.n_cycles, count)
        assert got == want, f"{name}: stored vertex sets differ"
    return _report(f"store {name} pallas", kind, cycles=warm.n_cycles,
                   vertex_sets_equal=True, cold_s=t_cold,
                   warm_s=t_warm, **_run_stats(warm))


def served(n_requests: int, kind: str, *, recycle: bool, seed: int = 0,
           slots: int = 4) -> dict:
    """``n_requests`` mixed requests through one scheduler, cold then
    warm; every request's count must equal the sequential reference's."""
    from repro.core import CycleService, EngineConfig
    from repro.core.ref_sequential import sequential_chordless_cycles
    from repro.launch.serve import build_request_queue, serve, serve_recycled
    queue = build_request_queue(n_requests, seed)
    want = [sequential_chordless_cycles(g.n, _edges(g), store=False)[0]
            for g in queue]
    svc = CycleService(EngineConfig(store=False, formulation="bitword",
                                    backend="pallas"))
    run = ((lambda: serve_recycled(svc, queue, slots=slots, verbose=False))
           if recycle else
           (lambda: serve(svc, queue, slots=slots, verbose=False)))
    cold, t_cold = _timed(run)
    warm, t_warm = _timed(run)
    for st in (cold, warm):
        bad = [i for i, (got, ref) in
               enumerate(zip(st["cycles_by_request"], want)) if got != ref]
        assert not bad, f"requests {bad} disagree with the reference"
    name = "serve_recycled" if recycle else "serve"
    return _report(f"{name} {n_requests} requests pallas", kind,
                   matched=f"{n_requests}/{n_requests}",
                   cycles=warm["n_cycles"], cold_s=t_cold,
                   warm_s=t_warm,
                   dispatches=warm["n_dispatches"],
                   host_syncs=warm["n_host_syncs"],
                   fused_rounds=warm["fused_rounds"],
                   split_rounds=warm["split_rounds"])


def sharded(name: str, kind: str, *, n_devices: int, local_capacity: int,
            balance_block: int, expect: int | None = None) -> dict:
    """Count-only enumeration on a 1-D mesh of ``n_devices`` devices, each
    device's rounds on the bitword/Pallas path (the benchmark's
    ``table1_sharded4`` configuration): exact count, no dropped or lost
    rows, frontier rows on every device."""
    import numpy as np
    import jax
    from jax.sharding import Mesh
    from repro.core import CycleService, EngineConfig
    from repro.core.ref_sequential import sequential_chordless_cycles
    g, (n, edges), table = _graph(name)
    expect = expect if expect is not None else table
    if expect is None:
        expect = sequential_chordless_cycles(n, edges, store=False)[0]
    devices = jax.devices()[:n_devices]
    assert len(devices) == n_devices, (len(devices), n_devices)
    mesh = Mesh(np.array(devices), ("data",))
    cfg = EngineConfig(mesh=mesh, store=False, formulation="bitword",
                       backend="pallas", local_capacity=local_capacity,
                       balance_block=balance_block)
    svc = CycleService(cfg)
    res, t_cold = _timed(lambda: svc.enumerate(g))
    s = res.stats
    peaks = np.array(s["per_device_peak_rows"])
    assert res.n_cycles == expect, (name, res.n_cycles, expect)
    assert s["dropped"] == 0 and s["lost"] == 0, (s["dropped"], s["lost"])
    assert (peaks > 0).all(), f"rows not on every device: {peaks.tolist()}"
    peak_total = max(h["T"] for h in res.history)
    return _report(f"sharded {name} {n_devices} devices bitword pallas",
                   kind, cycles=res.n_cycles, dropped=s["dropped"],
                   lost=s["lost"], moved=s["moved"],
                   per_device_peak_rows=",".join(str(int(x)) for x in peaks),
                   peak_frontier_rows=peak_total,
                   local_capacity=local_capacity, cold_s=t_cold,
                   dispatches=s["n_dispatches"],
                   host_syncs=s["n_host_syncs"],
                   rounds=s["rounds"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded Grid_7x10 path on a "
                         "4-chip mesh")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch.env import enable_compile_cache
    cache = enable_compile_cache()
    dev = _device()
    if dev["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev['platform']} "
              f"({dev['kind']})", file=sys.stderr)
        return 2
    kind = dev["kind"]
    print(f"[{kind}] devices={dev['count']} compile_cache={cache}",
          flush=True)

    if args.chips == 4:
        assert dev["count"] >= 4, f"--chips 4 needs 4 devices, {dev}"
        # Grid_7x10's frontier peaks at 14,454,265 rows; a device holds up
        # to 2^23 of them — twice its even share, so the busiest device
        # (1.33x its share on a v5e 2x2) never drops a row
        sharded("Grid_7x10", kind, n_devices=4, local_capacity=1 << 23,
                balance_block=1 << 15)
    else:
        one_shot("Grid_6x10", "pallas", kind)
        one_shot("Grid_6x10", "jnp", kind)
        store_mode("Grid_5x10", kind, rounds_per_launch=4)
        served(args.requests, kind, recycle=False, seed=args.seed)
        served(args.requests, kind, recycle=True, seed=args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": kind, "count": dev["count"]}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
