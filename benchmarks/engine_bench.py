"""Engine A/B: legacy host-driven loop vs fused device-resident wave engine.

Measures, per Fig.-4 benchmark graph: wall clock (cold = incl. jit, warm =
steady state), rounds, dispatches, host syncs — and derives the metrics the
perf trajectory is tracked by (us/round, rounds/dispatch, syncs/round).

Emits ``benchmarks/results/BENCH_engine.json`` (machine-readable; one entry
per graph × engine) so every future PR can diff against this one.
``tune_smoke`` adds the autotuner's tuned-vs-default A/B
(→ ``results/BENCH_tune_smoke.json``); ``benchmarks/run.py --check`` gates
regressions against the committed baselines.
"""
from __future__ import annotations

import json
import os
import time

from repro.core import build_graph, enumerate_chordless_cycles
from repro.core.graphs import PAPER_TABLE1, grid_graph

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "results")

# the Fig. 4 evolution graphs + a dense bipartite stressor
GRAPHS = ["Grid_5x6", "Grid_4x10", "Grid_6x6", "K_8_8"]


def _time_engine(g, engine: str, repeats: int = 3):
    t0 = time.perf_counter()
    res = enumerate_chordless_cycles(g, store=False, formulation="bitword",
                                     engine=engine)
    cold = time.perf_counter() - t0
    warm = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        res = enumerate_chordless_cycles(g, store=False,
                                         formulation="bitword", engine=engine)
        warm = min(warm, time.perf_counter() - t0)
    return res, cold, warm


def run(graph_names=None):
    rows = []
    for name in (graph_names or GRAPHS):
        build, tri_gt, clc_gt = PAPER_TABLE1[name]
        n, edges = build()
        g = build_graph(n, edges)
        per_graph = {}
        for engine in ("host", "wave"):
            res, cold, warm = _time_engine(g, engine)
            assert res.n_triangles == tri_gt, (name, engine)
            assert res.n_cycles - tri_gt == clc_gt, (name, engine)
            s = res.stats
            rounds = max(s["rounds"], 1)
            per_graph[engine] = dict(
                graph=name, engine=engine, n=n, m=len(edges),
                n_cycles=res.n_cycles, rounds=s["rounds"],
                t_cold_ms=round(cold * 1e3, 2),
                t_warm_ms=round(warm * 1e3, 2),
                us_per_round=round(warm * 1e6 / rounds, 2),
                n_dispatches=s["n_dispatches"],
                n_host_syncs=s["n_host_syncs"],
                rounds_per_dispatch=round(s["rounds_per_dispatch"], 3),
                syncs_per_round=round(s["syncs_per_round"], 4),
            )
        h, w = per_graph["host"], per_graph["wave"]
        w["dispatch_reduction"] = round(
            h["n_dispatches"] / max(w["n_dispatches"], 1), 2)
        w["sync_reduction"] = round(
            h["n_host_syncs"] / max(w["n_host_syncs"], 1), 2)
        w["warm_speedup"] = round(h["t_warm_ms"] / max(w["t_warm_ms"], 1e-9),
                                  2)
        # the fused-round acceptance metric (DESIGN.md §6.8): warm per-round
        # cost of the wave engine relative to the host engine — >1 means the
        # wave round is cheaper than a host round on this graph class
        w["us_per_round_vs_host"] = round(
            h["us_per_round"] / max(w["us_per_round"], 1e-9), 2)
        # cold = one-shot wall clock incl. compiles — the paper's
        # T_par-total analogue; the superstep compiles ~¼ the programs.
        w["cold_speedup"] = round(h["t_cold_ms"] / max(w["t_cold_ms"], 1e-9),
                                  2)
        rows += [h, w]
    return rows


def emit(rows, path=None) -> str:
    path = path or os.path.join(RESULTS_DIR, "BENCH_engine.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(dict(benchmark="engine_ab",
                       unit_notes=dict(t="milliseconds", us_per_round="µs"),
                       rows=rows), f, indent=2)
    return path


def smoke():
    """CI-time sanity: table1-style count validation on the 4×4 mesh plus a
    single host-vs-wave A/B on it. Seconds, not minutes."""
    n, edges = grid_graph(4, 4)
    g = build_graph(n, edges)
    ref = None
    for engine in ("host", "wave"):
        res = enumerate_chordless_cycles(g, store=False,
                                         formulation="bitword", engine=engine)
        assert res.n_triangles == 0
        if ref is None:
            ref = res.n_cycles
        assert res.n_cycles == ref, (engine, res.n_cycles, ref)
    print(f"smoke OK: grid 4x4 -> {ref} chordless cycles (both engines)")
    return ref


def service_smoke(n_graphs: int = 6, out_path: str | None = None):
    """Warm-cache serving scenario: N same-bucket graphs through ONE shared
    CycleService vs N one-shot calls that each rebuild their programs (a
    fresh service per graph — the pre-service world). Reports amortized
    ms/graph per arm + the batched path, asserts the ≥1.5× warm win, and
    writes ``results/BENCH_service_smoke.json``."""
    import time as _time

    from repro.core import CycleService, EngineConfig

    cfg = EngineConfig(store=False, formulation="bitword")
    n, edges = grid_graph(4, 4)
    graphs = [build_graph(n, edges) for _ in range(n_graphs)]

    # arm A — one-shot: every request pays plan (trace + compile) again
    t0 = _time.perf_counter()
    counts_cold = [CycleService(cfg).enumerate(g).n_cycles for g in graphs]
    oneshot_ms = (_time.perf_counter() - t0) * 1e3 / n_graphs

    # arm B — shared service: request 1 compiles, the rest execute warm
    svc = CycleService(cfg)
    t0 = _time.perf_counter()
    counts_warm = [svc.enumerate(g).n_cycles for g in graphs]
    warm_ms = (_time.perf_counter() - t0) * 1e3 / n_graphs
    warm_stats = dict(svc.stats)

    # arm C — the multi-tenant path: whole batch in one vmapped program.
    # First call includes the batched-plan + stage-1 seed compiles (cold);
    # the gate metric is the WARM steady-state ms/graph, matching the
    # warm-serving story arms A/B measure.
    t0 = _time.perf_counter()
    counts_batch = [r.n_cycles for r in svc.enumerate_batch(graphs)]
    batch_cold_ms = (_time.perf_counter() - t0) * 1e3 / n_graphs
    batch_t = float("inf")
    for _ in range(2):
        t0 = _time.perf_counter()
        counts_batch = [r.n_cycles for r in svc.enumerate_batch(graphs)]
        batch_t = min(batch_t, _time.perf_counter() - t0)
    batch_ms = batch_t * 1e3 / n_graphs

    assert counts_cold == counts_warm == counts_batch, "arms disagree"
    speedup = oneshot_ms / max(warm_ms, 1e-9)
    row = dict(benchmark="service_smoke", n_graphs=n_graphs,
               graph="Grid_4x4", n_cycles=counts_warm[0],
               oneshot_ms_per_graph=round(oneshot_ms, 2),
               warm_ms_per_graph=round(warm_ms, 2),
               batch_ms_per_graph=round(batch_ms, 2),
               batch_cold_ms_per_graph=round(batch_cold_ms, 2),
               warm_speedup=round(speedup, 2),
               cache=warm_stats)
    path = out_path or os.path.join(RESULTS_DIR, "BENCH_service_smoke.json")
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        json.dump(row, f, indent=2)
    print(f"service smoke: one-shot {oneshot_ms:.1f} ms/graph, "
          f"warm {warm_ms:.1f} ms/graph ({speedup:.1f}x), "
          f"batch {batch_ms:.1f} ms/graph -> {path}")
    assert speedup >= 1.5, (
        f"warm serving must amortize >=1.5x over one-shot, got {speedup:.2f}")
    return row


def tune_smoke(out_path: str | None = None):
    """Tuned-vs-default A/B for the ``repro.tune`` subsystem.

    Per smoke grid: record a traced default run, let the ``AutoTuner`` fit
    its cost model and run measured trials (default config always in the
    trial pool, so the chosen knobs can never have measured worse than the
    default), then independently re-measure both configs warm. Writes
    ``results/BENCH_tune_smoke.json``. Asserts (a) the tuner's pick met or
    beat the default inside the trials — exact by argmin construction — and
    (b) the independent re-measurement stays within noise of the default
    (regression tripwire).
    """
    import time as _time

    from repro.core import CycleService, EngineConfig
    from repro.tune import AutoTuner, TUNED_KNOBS, TuneStore, WaveProfile

    smoke_grids = [("Grid_4x4", (4, 4)), ("Grid_5x6", (5, 6))]
    cfg = EngineConfig(store=False, formulation="bitword")
    store = TuneStore()
    rows = []
    for name, (r_, c_) in smoke_grids:
        n, edges = grid_graph(r_, c_)
        g = build_graph(n, edges)

        # one traced default run: the profile + cost-model fit input
        rec = CycleService(cfg, trace=True)
        res = rec.enumerate(g)
        profile = WaveProfile.from_history(res.history, n=g.n,
                                           nw=g.adj_bits.shape[1])

        # measured trials run warm against one shared service
        svc = CycleService(cfg)
        trial_log: list[tuple[dict, float]] = []

        def measure(c, _svc=svc, _g=g, _log=trial_log):
            _svc.enumerate(_g, config=c)          # compile/warm
            best = float("inf")
            for _ in range(3):
                t0 = _time.perf_counter()
                _svc.enumerate(_g, config=c)
                best = min(best, _time.perf_counter() - t0)
            ms = best * 1e3
            _log.append(({k: getattr(c, k) for k in TUNED_KNOBS}, ms))
            return ms

        tuner = AutoTuner(store=store, trials=4)
        key = tuner.key_for(g.n, g.m, max(g.max_degree, 1), cfg)
        tuned_cfg = tuner.tune(profile, cfg, key=key, traces=(res.trace,),
                               measure=measure)
        n_trials = len(trial_log)   # before the re-measurements below

        base_knobs = {k: getattr(cfg, k) for k in TUNED_KNOBS}
        tuned_knobs = {k: getattr(tuned_cfg, k) for k in base_knobs}
        # headline ms/graph come from the SAME trial block (one warm
        # service, interleaved candidates) — the apples-to-apples numbers
        # the argmin ran over; tuned <= default is exact by construction
        # because the default is always in the pool.
        default_ms = next(ms for kn, ms in trial_log if kn == base_knobs)
        tuned_ms = min(ms for _, ms in trial_log)
        assert tuned_ms <= default_ms, (name, trial_log)

        # independent warm re-measurement of both arms (noise tripwire)
        re_default = measure(cfg)
        re_tuned = (re_default if tuned_knobs == base_knobs
                    else measure(tuned_cfg))
        res_t = svc.enumerate(g, config=tuned_cfg)
        assert res_t.n_cycles == res.n_cycles, (name, "tuned count differs")
        # noise tripwire with an absolute slack floor — these are
        # single-digit-ms measurements where shared-CPU scheduling noise
        # alone exceeds 15% (same rationale as run.py's CHECK_ABS_SLACK_MS)
        assert re_tuned <= re_default * 1.15 + 5.0, (
            f"{name}: tuned {re_tuned:.2f} ms vs default "
            f"{re_default:.2f} ms on re-measurement")

        rows.append(dict(
            graph=name, n=n, m=len(edges), n_cycles=res.n_cycles,
            default_knobs=base_knobs, tuned_knobs=tuned_knobs,
            default_ms_per_graph=round(default_ms, 2),
            tuned_ms_per_graph=round(tuned_ms, 2),
            speedup=round(default_ms / max(tuned_ms, 1e-9), 3),
            remeasured_default_ms=round(re_default, 2),
            remeasured_tuned_ms=round(re_tuned, 2),
            n_trials=n_trials, tune_key=key.as_str()))
        print(f"tune smoke {name}: default {default_ms:.1f} ms, "
              f"tuned {tuned_ms:.1f} ms ({rows[-1]['speedup']}x) "
              f"knobs={tuned_knobs}")

    # warm-hit path: a second service sharing the store executes tuned
    # configs straight away — no search, no trace
    warm_svc = CycleService(cfg, tuner=AutoTuner(store=store))
    g = build_graph(*grid_graph(4, 4))
    warm_res = warm_svc.enumerate(g)
    ts = warm_svc.stats["tune"]
    assert ts["searches"] == 0 and ts["warm_hits"] >= 1, ts
    assert warm_svc.stats["traces_recorded"] == 0, "warm hit re-traced"

    doc = dict(benchmark="tune_smoke",
               base_config=dict(store=False, formulation="bitword",
                                engine="wave", backend="jnp"),
               rows=rows,
               warm_hit=dict(n_cycles=warm_res.n_cycles,
                             tune_stats=ts,
                             traces_recorded=0))
    path = out_path or os.path.join(RESULTS_DIR, "BENCH_tune_smoke.json")
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        json.dump(doc, f, indent=2)
    print(f"wrote {path}")
    return doc


def fused_smoke(out_path: str | None = None):
    """Fused-round smoke (DESIGN.md §6.8): the one-dispatch property plus a
    fused-vs-split wave A/B.

    Asserts on the TRACED PROGRAM that the pallas fused round is exactly one
    ``pallas_call`` with zero scatter/cumsum/sort passes outside it (and
    that the split round demonstrably is not — the contrast row), checks
    fused/split cycle counts agree on every smoke graph, measures warm
    fused-vs-split wall clock with the jnp backend (the fast backend on this
    container; pallas runs under interpret), and writes
    ``results/BENCH_fused_smoke.json`` for the ``run.py --check`` gate.
    """
    import time as _time

    import jax

    from repro.analysis.dispatch import (assert_fused_round_program,
                                         compaction_prims_outside_kernel,
                                         primitive_counts)
    from repro.core import CycleService, EngineConfig
    from repro.core import expand as E
    from repro.core.frontier import empty_cycle_buffer
    from repro.core.triplets import initial_frontier

    # -- dispatch contract on the traced round body -----------------------
    n, edges = grid_graph(4, 4)
    g = build_graph(n, edges)
    f, _, _ = initial_frontier(g, bucket=lambda c: 64)
    buf = empty_cycle_buffer(256, g.adj_bits.shape[1])
    d = max(g.max_degree, 1)
    pal = E.expand_op("bitword", "pallas")

    def fused_body(g, f, buf):
        return E.expand_count_compact(g, f, buf, delta=d, store=True,
                                      op=pal, fused=True)

    def split_body(g, f, buf):
        return E.expand_count_compact(g, f, buf, delta=d, store=True,
                                      op=pal, fused=False)

    fused_prims = assert_fused_round_program(fused_body, g, f, buf)
    split_prims = primitive_counts(jax.make_jaxpr(split_body)(g, f, buf))
    split_leak = compaction_prims_outside_kernel(split_prims)
    assert split_leak, "split round unexpectedly has no compaction passes"

    # -- equivalence + warm A/B on the smoke graphs ------------------------
    rows = []
    for name in ("Grid_4x4", "Grid_5x6"):
        if name == "Grid_4x4":
            n, edges = grid_graph(4, 4)
        else:
            n, edges = PAPER_TABLE1[name][0]()
        g = build_graph(n, edges)
        per_arm = {}
        counts = {}
        for arm, fused in (("fused", True), ("split", False)):
            svc = CycleService(EngineConfig(store=False,
                                            formulation="bitword",
                                            fused_round=fused))
            res = svc.enumerate(g)
            counts[arm] = res.n_cycles
            warm = float("inf")
            for _ in range(3):
                t0 = _time.perf_counter()
                res = svc.enumerate(g)
                warm = min(warm, _time.perf_counter() - t0)
            rounds = max(res.stats["rounds"], 1)
            per_arm[arm] = dict(t_warm_ms=round(warm * 1e3, 2),
                                us_per_round=round(warm * 1e6 / rounds, 2))
        assert counts["fused"] == counts["split"], (name, counts)
        rows.append(dict(
            graph=name, n=n, m=len(edges), n_cycles=counts["fused"],
            fused_ms=per_arm["fused"]["t_warm_ms"],
            split_ms=per_arm["split"]["t_warm_ms"],
            fused_us_per_round=per_arm["fused"]["us_per_round"],
            split_us_per_round=per_arm["split"]["us_per_round"],
            fused_speedup=round(per_arm["split"]["t_warm_ms"]
                                / max(per_arm["fused"]["t_warm_ms"], 1e-9),
                                2)))
        print(f"fused smoke {name}: fused {rows[-1]['fused_ms']:.1f} ms vs "
              f"split {rows[-1]['split_ms']:.1f} ms "
              f"({rows[-1]['fused_speedup']}x), {counts['fused']} cycles")

    doc = dict(benchmark="fused_smoke",
               dispatch_contract=dict(
                   fused_pallas_calls=fused_prims.get("pallas_call", 0),
                   fused_compaction_prims_outside_kernel=0,
                   split_compaction_prims_outside_kernel=sum(
                       split_leak.values())),
               rows=rows)
    path = out_path or os.path.join(RESULTS_DIR, "BENCH_fused_smoke.json")
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f_:
        json.dump(doc, f_, indent=2)
    print(f"fused smoke: one pallas dispatch per round confirmed on the "
          f"jaxpr (split round leaks {sum(split_leak.values())} compaction "
          f"passes) -> {path}")
    return doc


def persistent_smoke(out_path: str | None = None):
    """Persistent multi-round kernel smoke (DESIGN.md §6.11): the
    ⌈K/R⌉-dispatch property plus the launch-amortization A/B.

    Asserts on the TRACED PROGRAM that an unrolled K-round superstep at
    ``rounds_per_launch`` R contains exactly ⌈K/R⌉ pallas_calls (R=1
    reproduces the §6.8 one-dispatch-per-round contract), then times the
    thing the persistent kernel actually changes: R warm kernel launches
    (a host loop of jitted single fused rounds) vs ONE warm persistent
    launch advancing the same R rounds with the frontier resident in
    scratch. Classes are sized so every round runs live (no guard trip, no
    frontier death — both arms do identical per-round work) and the
    ≥1.5× warm us/round win is asserted on the best class. End-to-end
    service rows (R=1 vs tuned R through ``CycleService``) are reported
    informationally: on this interpret-mode CPU container the host driver
    dominates end-to-end, so the launch win only shows at kernel scope.
    Writes ``results/BENCH_persistent_smoke.json`` for ``run.py --check``.
    """
    import time as _time

    import jax
    import jax.numpy as jnp

    from repro.analysis.dispatch import assert_superstep_dispatches
    from repro.core import CycleService, EngineConfig
    from repro.core import expand as E
    from repro.core.frontier import empty_cycle_buffer
    from repro.core.triplets import initial_frontier
    from repro.kernels.fused_round import (fused_round_pallas,
                                           persistent_round_pallas)
    from repro.kernels.ops import _fused_tables, on_platform

    # -- dispatch contract: ⌈K/R⌉ pallas_calls on the unrolled superstep --
    n, edges = grid_graph(4, 4)
    g = build_graph(n, edges)
    f, _, _ = initial_frontier(g, bucket=lambda c: 64)
    buf = empty_cycle_buffer(256, g.adj_bits.shape[1])
    d = max(g.max_degree, 1)
    pal = E.expand_op("bitword", "pallas")
    budget = 4
    contract = {}
    for rpl in (1, 2, 4):
        def superstep(g, f, buf, rpl=rpl):
            for _ in range(-(-budget // rpl)):
                f, buf, *_ = E.expand_count_compact_multi(
                    g, f, buf, delta=d, store=True, rounds=rpl,
                    formulation="bitword", backend="pallas", op=pal,
                    fused=True)
            return f, buf

        prims = assert_superstep_dispatches(superstep, g, f, buf,
                                            budget=budget,
                                            rounds_per_launch=rpl)
        contract[f"R={rpl}"] = prims.get("pallas_call", 0)

    # -- kernel-scope A/B: R separate launches vs one persistent launch ---
    # (graph, bucket, R) sized so rounds_done == R with no guard trip:
    # both arms then execute identical per-round work and the delta is
    # pure launch + frontier-HBM-round-trip overhead.
    classes = [("Grid_3x3", (3, 3), 16, 4), ("Grid_4x4", (4, 4), 64, 8)]
    rows = []
    for name, (gr, gc), bucket, R in classes:
        n, edges = grid_graph(gr, gc)
        g = build_graph(n, edges)
        delta = int(g.max_degree)
        f, _, _ = initial_frontier(g, bucket=lambda c: bucket)
        buf = empty_cycle_buffer(256, g.adj_bits.shape[1])
        tabs = _fused_tables(g, "bitword")

        def one(p, b, v1, l2, vl, cnt, bm, bc, *, tabs=tabs, delta=delta):
            return on_platform(fused_round_pallas, p, b, v1, l2, vl, cnt,
                               bm, bc, tabs, formulation="bitword",
                               delta=delta, store=False)

        def pers(p, b, v1, l2, vl, cnt, bm, bc, *, tabs=tabs, delta=delta,
                 R=R):
            return on_platform(persistent_round_pallas, p, b, v1, l2, vl,
                               cnt, bm, bc, jnp.int32(R), tabs,
                               formulation="bitword", delta=delta,
                               store=False, rounds=R)

        jone, jpers = jax.jit(one), jax.jit(pers)
        args = (f.path, f.blocked, f.v1, f.l2, f.vlast, f.count,
                buf.masks, buf.count)
        out = jpers(*args)
        rounds_done = int(out[8])
        assert rounds_done == R, (
            f"{name}: persistent launch retired {rounds_done}/{R} rounds — "
            f"resize the class so the A/B compares live rounds only")
        jax.block_until_ready(jone(*args))

        def loop_arm():
            p, b, v1, l2, vl, cnt, bm, bc = args
            for _ in range(R):
                p, b, v1, l2, vl, _m, _nc, n_new = jone(p, b, v1, l2, vl,
                                                        cnt, bm, bc)
                cnt = n_new
            jax.block_until_ready(cnt)

        def pers_arm():
            jax.block_until_ready(jpers(*args))

        def best_of(fn, reps=5):
            t = float("inf")
            for _ in range(reps):
                t0 = _time.perf_counter()
                fn()
                t = min(t, _time.perf_counter() - t0)
            return t

        t_loop, t_pers = best_of(loop_arm), best_of(pers_arm)
        rows.append(dict(
            graph=name, n=n, m=len(edges), bucket=bucket,
            rounds_per_launch=R, rounds_done=rounds_done,
            loop_ms=round(t_loop * 1e3, 3),
            persistent_ms=round(t_pers * 1e3, 3),
            loop_us_per_round=round(t_loop * 1e6 / R, 2),
            persistent_us_per_round=round(t_pers * 1e6 / R, 2),
            speedup=round(t_loop / max(t_pers, 1e-9), 2)))
        print(f"persistent smoke {name}: {R} launches "
              f"{rows[-1]['loop_us_per_round']:.0f} us/round vs one "
              f"persistent launch {rows[-1]['persistent_us_per_round']:.0f} "
              f"us/round ({rows[-1]['speedup']}x)")

    best = max(r["speedup"] for r in rows)
    assert best >= 1.5, (
        f"persistent kernel won only {best}x warm us/round (need >=1.5x on "
        f"at least one smoke class): {rows}")

    # -- end-to-end service rows (informational, not gated) ---------------
    service_rows = []
    n, edges = grid_graph(4, 4)
    g = build_graph(n, edges)
    counts = {}
    for R in (1, 8):
        svc = CycleService(EngineConfig(store=False, formulation="bitword",
                                        backend="pallas", fused_round=True,
                                        rounds_per_launch=R))
        res = svc.enumerate(g)
        counts[R] = res.n_cycles
        warm = float("inf")
        for _ in range(3):
            t0 = _time.perf_counter()
            res = svc.enumerate(g)
            warm = min(warm, _time.perf_counter() - t0)
        s = res.stats
        service_rows.append(dict(
            graph="Grid_4x4", rounds_per_launch=R, n_cycles=res.n_cycles,
            t_warm_ms=round(warm * 1e3, 2),
            us_per_round=round(warm * 1e6 / max(s["rounds"], 1), 2),
            n_kernel_launches=s["n_kernel_launches"]))
    assert counts[1] == counts[8], ("persistent service diverged", counts)
    assert (service_rows[1]["n_kernel_launches"]
            < service_rows[0]["n_kernel_launches"]), service_rows

    doc = dict(benchmark="persistent_smoke",
               dispatch_contract=contract,
               best_kernel_speedup=best,
               rows=rows,
               service_rows=service_rows)
    path = out_path or os.path.join(RESULTS_DIR,
                                    "BENCH_persistent_smoke.json")
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f_:
        json.dump(doc, f_, indent=2)
    print(f"persistent smoke: ceil(K/R) dispatches confirmed on the jaxpr "
          f"{contract}, best kernel-scope win {best}x, service launches "
          f"{service_rows[0]['n_kernel_launches']} -> "
          f"{service_rows[1]['n_kernel_launches']} -> {path}")
    return doc


def batch_smoke(n_graphs: int = 8, out_path: str | None = None):
    """Batched-pallas A/B (DESIGN.md §6.7): ``enumerate_batch`` — one
    lane-gridded device program advancing all lanes — vs the per-graph loop
    it replaced (the old ``cfg.backend == 'pallas'`` service fallback:
    warm per-graph ``enumerate`` calls). Same-shape batch, so the whole win
    is dispatch amortization, not padding luck. Asserts bit-identical
    results (counts AND per-lane histories), one batched dispatch per
    superstep via trace counters, and the ≥1.5× amortized ms/graph win;
    writes ``results/BENCH_batch_smoke.json``."""
    import time as _time

    from repro.core import CycleService, EngineConfig

    cfg = EngineConfig(store=False, formulation="bitword", backend="pallas")
    n, edges = grid_graph(4, 4)
    graphs = [build_graph(n, edges) for _ in range(n_graphs)]
    svc = CycleService(cfg, trace=True)

    # warm both arms (compile once), checking equivalence on the way
    loop_res = [svc.enumerate(g) for g in graphs]
    batch_res = svc.enumerate_batch(graphs)
    tr = svc.last_trace
    kinds = [e.kind for e in tr.events]
    assert kinds.count("seed") == 1, kinds       # ONE stage-1 seeding
    assert set(kinds) <= {"seed", "batch"}, kinds  # no per-graph dispatches
    n_supersteps = kinds.count("batch")
    for a, b in zip(loop_res, batch_res):
        assert a.n_cycles == b.n_cycles, "batched pallas count differs"
        assert a.history == b.history, "batched pallas history differs"

    loop_t = batch_t = float("inf")
    for _ in range(3):
        t0 = _time.perf_counter()
        [svc.enumerate(g) for g in graphs]
        loop_t = min(loop_t, _time.perf_counter() - t0)
        t0 = _time.perf_counter()
        svc.enumerate_batch(graphs)
        batch_t = min(batch_t, _time.perf_counter() - t0)
    loop_ms = loop_t * 1e3 / n_graphs
    batch_ms = batch_t * 1e3 / n_graphs
    speedup = loop_ms / max(batch_ms, 1e-9)

    row = dict(benchmark="batch_smoke", n_graphs=n_graphs, graph="Grid_4x4",
               backend="pallas", formulation="bitword",
               n_cycles=batch_res[0].n_cycles,
               batch_supersteps=n_supersteps,
               loop_ms_per_graph=round(loop_ms, 2),
               batch_ms_per_graph=round(batch_ms, 2),
               batch_speedup=round(speedup, 2))
    path = out_path or os.path.join(RESULTS_DIR, "BENCH_batch_smoke.json")
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        json.dump(row, f, indent=2)
    print(f"batch smoke: per-graph loop {loop_ms:.1f} ms/graph, batched "
          f"{batch_ms:.1f} ms/graph ({speedup:.2f}x, {n_supersteps} "
          f"superstep dispatches for {n_graphs} lanes) -> {path}")
    assert speedup >= 1.5, (
        f"batched pallas must amortize >=1.5x over the per-graph loop, "
        f"got {speedup:.2f}")
    return row


_DIST_SMOKE_CODE = """
import json, time, numpy as np, jax
from jax.sharding import Mesh
from repro.core import (CycleService, EngineConfig, build_graph,
                        enumerate_chordless_cycles)
from repro.core.graphs import grid_graph

ndev = 4
mesh = Mesh(np.array(jax.devices())[:ndev].reshape(ndev,), ('data',))
n, edges = grid_graph(5, 6)
g = build_graph(n, edges)
ref = enumerate_chordless_cycles(g, store=False).n_cycles
rows = {}
for arm, k in (('per_round', 1), ('superstep', 8)):
    cfg = EngineConfig(store=False, mesh=mesh, local_capacity=1 << 13,
                       balance_block=64, superstep_rounds=k)
    svc = CycleService(cfg)
    t0 = time.perf_counter()
    res = svc.enumerate(g)
    cold = time.perf_counter() - t0
    warm = float('inf')
    for _ in range(3):
        t0 = time.perf_counter()
        res = svc.enumerate(g)
        warm = min(warm, time.perf_counter() - t0)
    assert res.n_cycles == ref, (arm, res.n_cycles, ref)
    s = res.stats
    assert s['dropped'] == 0 and s['lost'] == 0, s
    rows[arm] = dict(
        arm=arm, superstep_rounds=k, n_cycles=res.n_cycles,
        rounds=s['iterations'], n_dispatches=s['n_dispatches'],
        n_host_syncs=s['n_host_syncs'],
        t_cold_ms=round(cold * 1e3, 2), t_warm_ms=round(warm * 1e3, 2))
print(json.dumps(rows))
"""


def dist_smoke(out_path: str | None = None):
    """Sharded-path A/B: per-round driver (K=1, one dispatch + one sync per
    round — the pre-superstep pattern) vs the sharded wave superstep (K=8)
    on a 4-device mesh, equal cycle counts enforced. Runs on 4 chips in
    process on a TPU, else in a subprocess simulating 4 CPU devices (the
    bench process must keep seeing 1 device); asserts the >=2x
    dispatch/sync reduction, and writes ``results/BENCH_dist_smoke.json``.
    Returns None (skipped) on a TPU host with fewer than 4 chips."""
    from repro.launch.env import run_multi_device
    out = run_multi_device(_DIST_SMOKE_CODE, 4, src_path=os.path.join(
        os.path.dirname(__file__), "..", "src"))
    if out is None:
        print("dist smoke: skipped (needs 4 devices)")
        return None
    rows = json.loads(out.strip().splitlines()[-1])
    pr, ss = rows["per_round"], rows["superstep"]
    assert pr["n_cycles"] == ss["n_cycles"], rows
    doc = dict(benchmark="dist_smoke", graph="Grid_5x6", n_devices=4,
               rows=[pr, ss],
               dispatch_reduction=round(
                   pr["n_dispatches"] / max(ss["n_dispatches"], 1), 2),
               sync_reduction=round(
                   pr["n_host_syncs"] / max(ss["n_host_syncs"], 1), 2),
               warm_speedup=round(
                   pr["t_warm_ms"] / max(ss["t_warm_ms"], 1e-9), 2))
    assert doc["dispatch_reduction"] >= 2.0, doc
    path = out_path or os.path.join(RESULTS_DIR, "BENCH_dist_smoke.json")
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        json.dump(doc, f, indent=2)
    print(f"dist smoke: per-round {pr['n_dispatches']} dispatches / "
          f"{pr['t_warm_ms']:.1f} ms, superstep {ss['n_dispatches']} / "
          f"{ss['t_warm_ms']:.1f} ms "
          f"({doc['dispatch_reduction']}x fewer dispatches, "
          f"{doc['warm_speedup']}x warm) -> {path}")
    return doc


# paper's footnote scale, wave engine count-only — nightly, NOT in --smoke.
# Grid_8x10 is the paper's 71.5M-cycle footnote graph (Table 1).
NIGHTLY_GRAPHS = ["Grid_7x10", "Grid_8x10"]


def nightly():
    """CI-nightly target: Grid_7x10 count-only via the wave engine (the
    paper's footnote scale; ~8.1M chordless cycles, frontier peaks in the
    millions of rows). Validates against Table 1 and appends timings to
    ``results/BENCH_engine_nightly.json``."""
    rows = []
    for name in NIGHTLY_GRAPHS:
        build, tri_gt, clc_gt = PAPER_TABLE1[name]
        n, edges = build()
        g = build_graph(n, edges)
        t0 = time.perf_counter()
        res = enumerate_chordless_cycles(g, store=False,
                                         formulation="bitword", engine="wave")
        dt = time.perf_counter() - t0
        assert res.n_triangles == tri_gt, name
        assert res.n_cycles - tri_gt == clc_gt, name
        s = res.stats
        rows.append(dict(graph=name, n=n, m=len(edges),
                         n_cycles=res.n_cycles, t_ms=round(dt * 1e3, 1),
                         rounds=s["rounds"], n_dispatches=s["n_dispatches"],
                         n_host_syncs=s["n_host_syncs"]))
        print(f"nightly {name}: {res.n_cycles} cycles in {dt:.1f}s "
              f"({s['n_dispatches']} dispatches)")
    path = emit(rows, os.path.join(RESULTS_DIR, "BENCH_engine_nightly.json"))
    print(f"wrote {path}")
    return rows


def main(graph_names=None, out_name: str = "BENCH_engine.json",
         require_wave_wins: bool = False):
    rows = run(graph_names)
    if require_wave_wins:
        # fused-round acceptance: warm us_per_round must beat the host
        # engine on EVERY smoke graph class
        losers = [r for r in rows if r["engine"] == "wave"
                  and r["us_per_round_vs_host"] < 1.0]
        assert not losers, (
            "wave us_per_round lost to the host engine on: "
            + ", ".join(f"{r['graph']} ({r['us_per_round_vs_host']}x)"
                        for r in losers))
    hdr = ("graph,engine,rounds,t_cold_ms,t_warm_ms,us_per_round,"
           "dispatches,host_syncs,rounds_per_dispatch,syncs_per_round")
    print(hdr)
    for r in rows:
        print(f"{r['graph']},{r['engine']},{r['rounds']},{r['t_cold_ms']},"
              f"{r['t_warm_ms']},{r['us_per_round']},{r['n_dispatches']},"
              f"{r['n_host_syncs']},{r['rounds_per_dispatch']},"
              f"{r['syncs_per_round']}")
    path = emit(rows, os.path.join(RESULTS_DIR, out_name))
    print(f"wrote {path}")
    return rows


if __name__ == "__main__":
    import sys
    if "--smoke" in sys.argv:
        smoke()
        service_smoke()
        tune_smoke()
    elif "--nightly" in sys.argv:
        nightly()
        batch_smoke()
    else:
        main()
