"""Benchmark harness — one section per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full] [--smoke] [--nightly]
                                            [--check]

Sections:
  engine   — host vs fused wave engine A/B → results/BENCH_engine.json
  table1   — paper Table 1 (counts validated vs published values + timings)
  fig4     — paper Fig. 4 (|T|/|C| evolution waves)
  kernels  — per-kernel microbench (pallas interpret vs jnp oracle)
  dist     — distributed-enumeration scaling (1..8 fake devices)
  roofline — the (arch × shape) dry-run roofline table (if results exist)

``--smoke`` runs only the CI-time subset: table1-style validation on the
4×4 mesh, the warm-cache serving scenario (shared CycleService vs one-shot,
→ results/BENCH_service_smoke.json), the tuned-vs-default autotuner A/B
(→ results/BENCH_tune_smoke.json), the fused-round contract — one pallas
dispatch per round on the traced jaxpr plus the fused-vs-split A/B
(→ results/BENCH_fused_smoke.json) — the persistent multi-round kernel
contract (⌈K/R⌉ dispatches per superstep on the traced jaxpr) plus the
R-launches-vs-one-persistent-launch A/B (>=1.5x warm us/round asserted on
at least one smoke class, → results/BENCH_persistent_smoke.json) — the
sustained-traffic serving A/B
(lane recycling vs wave-at-a-time, >=1.5x ms/graph asserted,
→ results/BENCH_serve_smoke.json) — the 2-level hierarchical-mesh A/B
(flat 8-dev vs 2×4 host×device vs EF-compressed cross-host wire, equal
counts/histories + >=4x cross-host byte reduction asserted,
→ results/BENCH_multihost_smoke.json) — plus the engine A/B JSON emission on
the two smallest graphs, asserting the wave engine's warm us/round beats
the host engine on every smoke graph class. ``--nightly`` runs the paper's footnote-scale
Grid_7x10 + Grid_8x10 count-only targets via the wave engine, the
sharded per-round-vs-superstep A/B (→ results/BENCH_dist_smoke.json,
>=2x dispatch reduction asserted), and the batched-pallas vs per-graph
loop A/B (→ results/BENCH_batch_smoke.json, >=1.5x amortized ms/graph
asserted). ``--check``
is the CI regression gate: it re-runs the smoke suite into a temp dir and
fails (exit 1) if any tracked ms/graph metric regressed >25% against the
committed ``results/BENCH_*.json`` baselines.

Output: ``name,us_per_call,derived`` CSV blocks + BENCH_engine.json.
"""
from __future__ import annotations

import json
import os
import sys
import tempfile

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "results")

# >25% ms/graph regression vs the committed baseline fails the gate —
# but only when the absolute slowdown also exceeds the slack floor:
# the smoke metrics are single-digit-ms measurements where shared-CPU
# scheduling noise alone exceeds 25%, and a sub-5ms delta is never the
# regression this gate exists to catch.
CHECK_TOLERANCE = 1.25
CHECK_ABS_SLACK_MS = 5.0


def _load_baseline(name: str) -> dict | None:
    path = os.path.join(RESULTS_DIR, name)
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


def check() -> int:
    """Regression gate: fresh smoke metrics vs committed BENCH_* baselines.

    Compares, per metric: engine-A/B warm ms (per graph × engine), the
    serving scenario's warm ms/graph, and the autotuner's tuned ms/graph.
    A missing baseline file skips its section (first run records it via
    ``--smoke``); a >25% slowdown on any metric fails. Returns the number
    of failures (the CLI exits nonzero on any).
    """
    from . import engine_bench
    failures: list[str] = []
    checked = 0

    def cmp(label: str, fresh_ms: float, base_ms: float):
        nonlocal checked
        checked += 1
        ratio = fresh_ms / max(base_ms, 1e-9)
        bad = (ratio > CHECK_TOLERANCE
               and fresh_ms - base_ms > CHECK_ABS_SLACK_MS)
        flag = "FAIL" if bad else "ok"
        print(f"  {flag:4s} {label}: fresh {fresh_ms:.2f} ms vs baseline "
              f"{base_ms:.2f} ms ({ratio:.2f}x)")
        if bad:
            failures.append(label)

    with tempfile.TemporaryDirectory() as tmp:
        base = _load_baseline("BENCH_engine_smoke.json")
        if base:
            print("== check: engine A/B (warm ms) ==")
            by_key = {(r["graph"], r["engine"]): r for r in base["rows"]}
            for fresh in engine_bench.run(["Grid_5x6", "K_8_8"]):
                b = by_key.get((fresh["graph"], fresh["engine"]))
                if b:
                    cmp(f"engine[{fresh['graph']},{fresh['engine']}]",
                        fresh["t_warm_ms"], b["t_warm_ms"])
        base = _load_baseline("BENCH_service_smoke.json")
        if base:
            print("== check: warm-cache serving (ms/graph) ==")
            row = engine_bench.service_smoke(
                out_path=os.path.join(tmp, "service.json"))
            cmp("service.warm", row["warm_ms_per_graph"],
                base["warm_ms_per_graph"])
            cmp("service.batch", row["batch_ms_per_graph"],
                base["batch_ms_per_graph"])
        base = _load_baseline("BENCH_tune_smoke.json")
        if base:
            print("== check: autotuner (tuned ms/graph) ==")
            doc = engine_bench.tune_smoke(
                out_path=os.path.join(tmp, "tune.json"))
            by_graph = {r["graph"]: r for r in base["rows"]}
            for fresh in doc["rows"]:
                b = by_graph.get(fresh["graph"])
                if b:
                    cmp(f"tune[{fresh['graph']}]",
                        fresh["tuned_ms_per_graph"],
                        b["tuned_ms_per_graph"])
        base = _load_baseline("BENCH_dist_smoke.json")
        if base:
            print("== check: sharded wave superstep (warm ms) ==")
            doc = engine_bench.dist_smoke(
                out_path=os.path.join(tmp, "dist.json"))
            by_arm = {r["arm"]: r for r in base["rows"]}
            for fresh in doc["rows"]:
                b = by_arm.get(fresh["arm"])
                if b:
                    cmp(f"dist[{fresh['arm']}]", fresh["t_warm_ms"],
                        b["t_warm_ms"])
        base = _load_baseline("BENCH_multihost_smoke.json")
        if base:
            print("== check: 2-level hierarchical mesh (warm ms) ==")
            from . import dist_enum
            doc = dist_enum.multihost_smoke(
                out_path=os.path.join(tmp, "multihost.json"))
            by_arm = {r["arm"]: r for r in base["rows"]}
            for fresh in doc["rows"]:
                b = by_arm.get(fresh["arm"])
                if b:
                    cmp(f"multihost[{fresh['arm']}]", fresh["t_warm_ms"],
                        b["t_warm_ms"])
        base = _load_baseline("BENCH_batch_smoke.json")
        if base:
            print("== check: batched pallas (ms/graph) ==")
            row = engine_bench.batch_smoke(
                out_path=os.path.join(tmp, "batch.json"))
            cmp("batch.batched", row["batch_ms_per_graph"],
                base["batch_ms_per_graph"])
            cmp("batch.loop", row["loop_ms_per_graph"],
                base["loop_ms_per_graph"])
        base = _load_baseline("BENCH_fused_smoke.json")
        if base:
            print("== check: fused round (warm ms + dispatch contract) ==")
            doc = engine_bench.fused_smoke(
                out_path=os.path.join(tmp, "fused.json"))
            by_graph = {r["graph"]: r for r in base["rows"]}
            for fresh in doc["rows"]:
                b = by_graph.get(fresh["graph"])
                if b:
                    cmp(f"fused[{fresh['graph']}]", fresh["fused_ms"],
                        b["fused_ms"])
        base = _load_baseline("BENCH_persistent_smoke.json")
        if base:
            print("== check: persistent multi-round kernel (warm ms) ==")
            doc = engine_bench.persistent_smoke(
                out_path=os.path.join(tmp, "persistent.json"))
            by_graph = {r["graph"]: r for r in base["rows"]}
            for fresh in doc["rows"]:
                b = by_graph.get(fresh["graph"])
                if b:
                    cmp(f"persistent[{fresh['graph']}]",
                        fresh["persistent_ms"], b["persistent_ms"])
        base = _load_baseline("BENCH_serve_smoke.json")
        if base:
            print("== check: sustained serving (ms/graph) ==")
            from . import serve_bench
            row = serve_bench.serve_smoke(
                out_path=os.path.join(tmp, "serve.json"))
            cmp("serve.baseline", row["baseline_ms_per_graph"],
                base["baseline_ms_per_graph"])
            cmp("serve.recycle", row["recycle_ms_per_graph"],
                base["recycle_ms_per_graph"])
        print("== check: observability export schema ==")
        from . import serve_bench as sb
        row = sb.obs_smoke(out_dir=tmp)
        checked += 1
        if row["metrics_problems"] or row["trace_problems"]:
            print(f"  FAIL obs: {row['metrics_problems']} metrics / "
                  f"{row['trace_problems']} trace schema problems")
            failures.append("obs.schema")
        else:
            print(f"  ok   obs: metrics + perfetto schemas valid "
                  f"({row['n_trace_events']} events, "
                  f"{row['n_spans']} spans)")

    if not checked:
        print("check: no committed baselines found — run --smoke first")
    if failures:
        print(f"check: {len(failures)} regression(s) >"
              f"{(CHECK_TOLERANCE - 1):.0%}: {failures}")
    else:
        print(f"check: {checked} metric(s) within "
              f"{(CHECK_TOLERANCE - 1):.0%} of baseline")
    return len(failures)


def main() -> None:
    full = "--full" in sys.argv
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
    from repro.launch.env import enable_compile_cache
    enable_compile_cache()
    if "--check" in sys.argv:
        sys.exit(1 if check() else 0)

    if "--smoke" in sys.argv:
        from . import engine_bench
        print("== smoke (4x4 mesh) ==")
        engine_bench.smoke()
        print("\n== warm-cache serving (shared CycleService vs one-shot) ==")
        engine_bench.service_smoke()
        print("\n== autotuner (tuned vs default) ==")
        engine_bench.tune_smoke()
        print("\n== fused round (one-dispatch contract + A/B) ==")
        engine_bench.fused_smoke()
        print("\n== persistent multi-round kernel (ceil(K/R) contract "
              "+ launch A/B) ==")
        engine_bench.persistent_smoke()
        print("\n== sustained serving (lane recycling vs wave-at-a-time) ==")
        from . import serve_bench
        serve_bench.serve_smoke()
        print("\n== 2-level hierarchical mesh (flat vs 2x4 vs compressed) ==")
        from . import dist_enum
        dist_enum.multihost_smoke()
        print("\n== observability export (metrics + perfetto schema) ==")
        serve_bench.obs_smoke()
        print("\n== engine A/B (smoke subset) ==")
        # separate file: must not clobber the tracked full-suite baseline
        engine_bench.main(["Grid_5x6", "K_8_8"],
                          out_name="BENCH_engine_smoke.json",
                          require_wave_wins=True)
        return

    if "--nightly" in sys.argv:
        from . import engine_bench
        print("== nightly (paper footnote scale, wave engine) ==")
        engine_bench.nightly()
        print("\n== dist smoke (per-round vs sharded wave superstep) ==")
        engine_bench.dist_smoke()
        print("\n== batch smoke (batched pallas vs per-graph loop) ==")
        engine_bench.batch_smoke()
        return

    print("== engine A/B ==")
    from . import engine_bench
    engine_bench.main()

    print("\n== paper_table1 ==")
    from . import paper_table1
    paper_table1.main(full)

    print("\n== paper_fig4 ==")
    from . import paper_fig4
    paper_fig4.main()

    print("\n== kernel_bench ==")
    from . import kernel_bench
    kernel_bench.main()

    print("\n== dist_enum ==")
    from . import dist_enum
    dist_enum.main()

    print("\n== roofline (16x16) ==")
    from . import roofline_table
    roofline_table.main("16x16")
    print("\n== roofline (2x16x16, compile proof) ==")
    roofline_table.main("2x16x16")


if __name__ == "__main__":
    main()
