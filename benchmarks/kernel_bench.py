"""Kernel micro-benchmarks: Pallas (interpret) vs pure-jnp oracle per stage,
plus slot- vs bitword-formulation engine timing — the per-call numbers
behind the paper's T_par-proc column. CSV: name,us_per_call,derived."""
from __future__ import annotations

import time

import jax
import numpy as np

from repro.core import build_graph
from repro.core.graphs import grid_graph, complete_bipartite, random_gnp
from repro.core.triplets import initial_frontier, triplet_flags
from repro.core import expand as E
from repro.kernels import ops


def _time(fn, *args, reps=5):
    fn(*args)  # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / reps * 1e6


def run():
    rows = []
    for name, (n, edges) in [("grid6x10", grid_graph(6, 10)),
                             ("K_20_20", complete_bipartite(20, 20)),
                             ("gnp128", random_gnp(128, 0.15, 0))]:
        g = build_graph(n, edges)
        f, _, _ = initial_frontier(g)
        d = max(g.max_degree, 1)
        rows.append((f"triplet_jnp_{name}",
                     _time(triplet_flags, g, d), f"grid={n}x{d}x{d}"))
        rows.append((f"triplet_pallas_{name}",
                     _time(ops.triplet_flags, g, d), "pallas"))
        rows.append((f"expand_slot_jnp_{name}",
                     _time(E.expand_flags_slot, g, f, d), f"cap={f.capacity}"))
        rows.append((f"expand_slot_pallas_{name}",
                     _time(ops.expand_flags_slot, g, f, d), "pallas"))
        rows.append((f"expand_bitword_jnp_{name}",
                     _time(E.expand_words_bitword, g, f), f"nw={g.n_words}"))
        rows.append((f"expand_bitword_pallas_{name}",
                     _time(ops.expand_words_bitword, g, f), "pallas"))
    rows += run_lanes()
    rows += run_fused()
    rows += run_persistent()
    return rows


def run_fused():
    """Fused vs split round rows with the analytic bytes-moved roofline
    (DESIGN.md §6.8): measured µs per guarded round next to the modeled
    per-round HBM traffic of each implementation — split (two passes +
    cap·Δ scatter materialization), gather (fused jnp), kernel (fused
    pallas, one pass)."""
    import jax.numpy as jnp
    from repro.core.frontier import empty_cycle_buffer
    from repro.analysis.roofline import wave_round_row

    rows = []
    for name, (n, edges) in [("grid6x10", grid_graph(6, 10)),
                             ("K_20_20", complete_bipartite(20, 20))]:
        g = build_graph(n, edges)
        f, _, _ = initial_frontier(g)
        d = max(g.max_degree, 1)
        cap, nw = f.capacity, g.n_words
        buf = empty_cycle_buffer(1, nw)

        def round_(fused, op):
            out = E.expand_count_compact(g, f, buf, delta=d, store=False,
                                         op=op, fused=fused)
            return jax.block_until_ready(out[0].path)

        jnp_op = E.expand_op("bitword", "jnp")
        pal_op = E.expand_op("bitword", "pallas")
        us_split = _time(lambda: round_(False, jnp_op))
        us_gather = _time(lambda: round_(True, jnp_op))
        us_kernel = _time(lambda: round_(True, pal_op))
        model = wave_round_row(name, cap, nw, d)
        rows += [
            (f"round_split_{name}", us_split,
             f"bytes={model['bytes_split']} "
             f"bound_us={model['bound_us_split']:.2f}"),
            (f"round_gather_{name}", us_gather,
             f"bytes={model['bytes_gather']} "
             f"bound_us={model['bound_us_gather']:.2f}"),
            (f"round_kernel_{name}", us_kernel,
             f"bytes={model['bytes_kernel']} "
             f"bound_us={model['bound_us_kernel']:.2f} "
             f"traffic={model['traffic_ratio']:.1f}x_less"),
        ]
    return rows


def run_persistent():
    """Launch-overhead rows (DESIGN.md §6.11): per-round µs of R separate
    fused-round launches vs ONE persistent launch advancing R rounds with
    the frontier resident in scratch, at R ∈ {2, 4, 8} — next to the
    modeled per-round HBM traffic each pays (the persistent column divides
    the kernel's per-launch frontier round-trip by R)."""
    import jax.numpy as jnp
    from repro.analysis.roofline import wave_round_row
    from repro.core.frontier import empty_cycle_buffer
    from repro.kernels.fused_round import (fused_round_pallas,
                                           persistent_round_pallas)
    from repro.kernels.ops import _fused_tables

    n, edges = grid_graph(4, 4)
    g = build_graph(n, edges)
    d = max(g.max_degree, 1)
    f, _, _ = initial_frontier(g, bucket=lambda c: 64)
    buf = empty_cycle_buffer(256, g.adj_bits.shape[1])
    tabs = _fused_tables(g, "bitword")
    args = (f.path, f.blocked, f.v1, f.l2, f.vlast, f.count,
            buf.masks, buf.count)

    jone = jax.jit(lambda *a: ops.on_platform(
        fused_round_pallas, *a, tabs, formulation="bitword", delta=d,
        store=False))

    rows = []
    for R in (2, 4, 8):
        jpers = jax.jit(lambda *a, R=R: ops.on_platform(
            persistent_round_pallas, *a, jnp.int32(R), tabs,
            formulation="bitword", delta=d, store=False, rounds=R))

        def loop_arm():
            p, b, v1, l2, vl, cnt, bm, bc = args
            for _ in range(R):
                p, b, v1, l2, vl, _m, _nc, n_new = jone(p, b, v1, l2, vl,
                                                        cnt, bm, bc)
                cnt = n_new
            return cnt

        us_loop = _time(loop_arm, reps=20) / R
        us_pers = _time(lambda: jpers(*args), reps=20) / R
        model = wave_round_row("grid4x4", f.capacity, g.n_words, d,
                               rounds_per_launch=R)
        rows += [
            (f"round_launch_loop_R{R}_grid4x4", us_loop,
             f"{R} launches; bytes/round={model['bytes_kernel']}"),
            (f"round_persistent_R{R}_grid4x4", us_pers,
             f"1 launch; bytes/round={model['bytes_persistent']} "
             f"amortized={us_loop / max(us_pers, 1e-9):.2f}x"),
        ]
    return rows


def run_lanes(B: int = 4):
    """Lane-gridded kernel rows (DESIGN.md §6.7): one grid=(B, capp//tp)
    pallas call for a B-lane frontier stack vs B single-lane calls — the
    per-call dispatch amortization ``enumerate_batch`` rides."""
    import jax.numpy as jnp
    from repro.kernels.bitword_expand import bitword_expand_lanes

    n, edges = grid_graph(5, 8)
    g = build_graph(n, edges)
    f, _, _ = initial_frontier(g)
    stack = lambda a: jnp.stack([a] * B)
    args1 = (f.path, f.blocked, f.v1, f.l2, f.vlast, f.count,
             g.adj_bits, g.labelgt_bits)
    argsB = tuple(stack(a) for a in args1)

    def loop_single(*args):
        return [ops.expand_words_bitword(g, f) for _ in range(B)]

    us_lanes = _time(lambda: ops.on_platform(bitword_expand_lanes, *argsB))
    us_loop = _time(loop_single)
    return [
        (f"bitword_lanes_B{B}_grid5x8", us_lanes,
         f"grid=({B},cap/tp) one call"),
        (f"bitword_loop_B{B}_grid5x8", us_loop,
         f"{B} single calls; lanes={us_loop / max(us_lanes, 1e-9):.2f}x"),
    ]


def main():
    print("name,us_per_call,derived")
    for name, us, derived in run():
        print(f"{name},{us:.1f},{derived}")


if __name__ == "__main__":
    main()
