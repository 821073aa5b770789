import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()
# ^ MUST precede any jax import: jax locks the device count on first init.

"""Multi-pod dry-run: lower + compile every (arch × shape) cell on the
production meshes and extract the roofline terms.

    PYTHONPATH=src python -m repro.launch.dryrun --all          # single-pod
    PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-0.5b \
        --shape train_4k --rules '{"seq": ["model"]}'

Results append to dryrun_results/dryrun_<mesh>.json (one row per cell:
memory_analysis, cost_analysis, collective bytes, roofline terms).
"""
import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..analysis import hlo as H
from ..analysis import roofline as RL
from ..configs.base import (all_archs, cell_is_skipped, get_config,
                            shapes_for)
from ..dist.sharding import DEFAULT_RULES, tree_shardings
from ..train import trainer as TR
from . import specs as S
from .mesh import make_production_mesh


def lower_cell(cfg, shape, mesh, *, rules=None, remat="dots",
               donate=True, unroll=False, serve_dtype=None,
               microbatches: int = 1):
    """Build + lower + compile one cell. Returns (compiled, lowered)."""
    rules = dict(DEFAULT_RULES, **(rules or {}))
    ab_in, in_logical = S.input_specs(cfg, shape)
    in_sh = tree_shardings(in_logical, ab_in, mesh, rules)

    import jax.numpy as jnp
    dtype = serve_dtype or jnp.float32
    params_ab, params_logical = S.model_abstract(cfg, shape, dtype=dtype)
    step, kind = S.make_step(cfg, shape, mesh=mesh, rules=rules, remat=remat,
                             unroll=unroll,
                             tcfg=TR.TrainConfig(adamw=S._adamw_for(cfg),
                                                 microbatches=microbatches)
                             if microbatches > 1 else None)

    if kind == "train":
        tcfg = TR.TrainConfig(adamw=S._adamw_for(cfg),
                              microbatches=microbatches)
        state_ab = TR.abstract_state(params_ab, tcfg)
        state_logical = TR.state_logical(params_logical, tcfg, params_ab)
        state_sh = tree_shardings(state_logical, state_ab, mesh, rules)
        metrics_sh = None  # let XLA choose (scalars)
        jf = jax.jit(step,
                     in_shardings=(state_sh, in_sh),
                     out_shardings=(state_sh, metrics_sh),
                     donate_argnums=(0,) if donate else ())
        lowered = jf.lower(state_ab, ab_in)
    else:
        params_sh = tree_shardings(params_logical, params_ab, mesh, rules)
        jf = jax.jit(step, in_shardings=(params_sh, in_sh),
                     out_shardings=None,
                     donate_argnums=(1,) if donate and "cache" in ab_in else ())
        lowered = jf.lower(params_ab, ab_in)
    compiled = lowered.compile()
    return compiled, lowered


def _n_layers(cfg) -> int:
    return getattr(cfg, "n_layers", 0)


def _with_layers(cfg, n: int):
    return dataclasses.replace(cfg, n_layers=n)


def _cost_triple(compiled, lowered):
    ca = H.cost_analysis_dict(compiled)
    try:
        txt = compiled.as_text()
    except Exception:
        txt = lowered.as_text()
    coll = H.collective_bytes(txt)
    return (float(ca.get("flops", 0.0)), float(ca.get("bytes accessed", 0.0)),
            float(sum(v for k, v in coll.items() if not k.startswith("_"))),
            coll)


def exact_costs(cfg, shape, mesh, *, rules=None, remat="dots",
                serve_dtype=None):
    """Per-device (flops, bytes, collective bytes), exact in depth.

    Layered archs: scan bodies are costed once by XLA, so we compile
    UNROLLED 1-layer and 2-layer versions and extrapolate linearly —
    exact because layers are homogeneous:
        cost(L) = cost(1) + (L-1)·(cost(2) - cost(1)).
    Non-layered archs (recsys CIN, gat): single exact compile.
    """
    L = _n_layers(cfg)
    if cfg.family == "recsys" or (cfg.family == "gnn" and cfg.kind == "gat"):
        c, l = lower_cell(cfg, shape, mesh, rules=rules, remat=remat,
                          serve_dtype=serve_dtype)
        f, b, cb, coll = _cost_triple(c, l)
        return f, b, cb, coll, "exact"
    c1, l1 = lower_cell(_with_layers(cfg, 1), shape, mesh, rules=rules,
                        remat=remat, unroll=True, serve_dtype=serve_dtype)
    f1, b1, cb1, coll1 = _cost_triple(c1, l1)
    c2, l2 = lower_cell(_with_layers(cfg, 2), shape, mesh, rules=rules,
                        remat=remat, unroll=True, serve_dtype=serve_dtype)
    f2, b2, cb2, coll2 = _cost_triple(c2, l2)
    k = L - 1
    coll = {key: coll1.get(key, 0) + k * (coll2.get(key, 0) - coll1.get(key, 0))
            for key in set(coll1) | set(coll2)}
    return (f1 + k * (f2 - f1), b1 + k * (b2 - b1), cb1 + k * (cb2 - cb1),
            coll, "extrapolated_1_2")


def run_cell(arch: str, shape_name: str, *, multi_pod: bool, rules=None,
             remat="dots", tag="", compile_only: bool = False,
             mesh_override: str | None = None, serve_dtype=None,
             window: int = 0, microbatches: int = 1) -> dict:
    cfg = get_config(arch)
    if window:  # beyond-paper long-context variant (covers long_500k cells)
        cfg = dataclasses.replace(cfg, attention="window", window=window)
    shape = next(s for s in shapes_for(cfg) if s.name == shape_name)
    skip = cell_is_skipped(cfg, shape)
    mesh_name = mesh_override or ("2x16x16" if multi_pod else "16x16")
    if skip:
        row = dict(name=f"{cfg.name}/{shape.name}", mesh=mesh_name,
                   skipped=skip)
        print(f"SKIP {row['name']}: {skip}")
        return row
    if mesh_override:
        # same chip count, different logical topology (§Perf hillclimbs,
        # e.g. serving-EP (32,8)); axes named (pod,)data,model
        dims = tuple(int(x) for x in mesh_override.split("x"))
        axes = ("pod", "data", "model")[-len(dims):]
        mesh = jax.make_mesh(dims, axes)
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    with mesh:
        # 1. full-depth SCANNED program: the deployable artifact — proves
        #    lower+compile works and gives the per-device memory picture.
        compiled, lowered = lower_cell(cfg, shape, mesh, rules=rules,
                                       remat=remat, serve_dtype=serve_dtype,
                                       microbatches=microbatches)
        ma = compiled.memory_analysis()
        if compile_only:   # multi-pod pass: prove lower+compile; costs on
            ca = H.cost_analysis_dict(compiled)     # the single-pod table
            row = dict(name=f"{cfg.name}/{shape.name}", mesh=mesh_name,
                       compiled=True, compile_s=round(time.time() - t0, 1),
                       flops_per_dev_scanbody=float(ca.get("flops", 0)),
                       temp_bytes_per_dev=float(
                           getattr(ma, "temp_size_in_bytes", 0) if ma else 0),
                       arg_bytes_per_dev=float(
                           getattr(ma, "argument_size_in_bytes", 0) if ma else 0))
            print(f"OK(compile-only) {row['name']} [{mesh_name}] "
                  f"compile={row['compile_s']}s")
            print(f"   memory_analysis: {ma}")
            return row
        # 2. depth-exact costs (unrolled 1/2-layer extrapolation).
        flops, bytes_, coll_total, coll, method = exact_costs(
            cfg, shape, mesh, rules=rules, remat=remat,
            serve_dtype=serve_dtype)

    peak = 0.0
    if ma is not None:
        peak = float(getattr(ma, "argument_size_in_bytes", 0)
                     + getattr(ma, "temp_size_in_bytes", 0)
                     + getattr(ma, "output_size_in_bytes", 0)
                     - getattr(ma, "alias_size_in_bytes", 0))
    chips = mesh.size
    r = RL.Roofline(
        name=f"{cfg.name}/{shape.name}",
        mesh="x".join(str(s) for s in mesh.devices.shape),
        chips=chips,
        hlo_flops=flops * chips,
        hlo_bytes=bytes_ * chips,
        coll_bytes=coll_total,
        model_flops=RL.model_flops_for(cfg, shape),
        peak_memory_bytes=peak,
    )
    row = r.row()
    row["collectives"] = {k: v for k, v in coll.items()}
    row["cost_method"] = method
    row["compile_s"] = round(time.time() - t0, 1)
    if tag:
        row["tag"] = tag
    print(f"OK {row['name']} [{mesh_name}] compile={row['compile_s']}s")
    print(f"   memory_analysis: {ma}")
    print(f"   cost_analysis ({method}): flops/dev={flops:.3e} "
          f"bytes/dev={bytes_:.3e} coll_bytes/dev={coll_total:.3e}")
    print(f"   roofline: compute={row['t_compute_s']:.4f}s "
          f"memory={row['t_memory_s']:.4f}s "
          f"collective={row['t_collective_s']:.4f}s "
          f"-> {row['bottleneck']} bound; "
          f"useful={row['useful_flop_frac']:.2f} "
          f"roofline_frac={row['roofline_frac']:.3f}")
    return row


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--remat", default="dots")
    ap.add_argument("--rules", default=None, help="JSON logical→axes overrides")
    ap.add_argument("--compile-only", action="store_true",
                    help="skip cost extrapolation (multi-pod proof pass)")
    ap.add_argument("--mesh", dest="mesh_override", default=None,
                    help="override mesh dims, e.g. 32x8 (same chip count)")
    ap.add_argument("--microbatches", type=int, default=1,
                    help="gradient-accumulation factor for train cells")
    ap.add_argument("--window", type=int, default=0,
                    help="run with sliding-window attention (enables the "
                         "long_500k cells)")
    ap.add_argument("--serve-bf16", action="store_true",
                    help="store serve params in bf16 (halves weight-gather "
                         "traffic at decode)")
    ap.add_argument("--tag", default="")
    ap.add_argument("--out", default="dryrun_results")
    args = ap.parse_args()

    rules = None
    if args.rules:
        rules = {k: tuple(v) for k, v in json.loads(args.rules).items()}

    cells = []
    if args.all:
        for arch in all_archs():
            cfg = get_config(arch)
            for shape in shapes_for(cfg):
                cells.append((arch, shape.name))
    else:
        cfg = get_config(args.arch)
        shapes = [args.shape] if args.shape else \
            [s.name for s in shapes_for(cfg)]
        cells = [(args.arch, s) for s in shapes]

    meshes = [args.multi_pod]
    if args.both_meshes:
        meshes = [False, True]

    os.makedirs(args.out, exist_ok=True)
    for multi_pod in meshes:
        mesh_name = "2x16x16" if multi_pod else "16x16"
        path = os.path.join(args.out, f"dryrun_{mesh_name}"
                            + (f"_{args.tag}" if args.tag else "") + ".json")
        rows = []
        if os.path.exists(path):
            with open(path) as f:
                rows = json.load(f)
        done = {r["name"] for r in rows}
        for arch, shape_name in cells:
            name = f"{arch}/{shape_name}"
            if name in done:
                print(f"cached {name}")
                continue
            try:
                import jax.numpy as _jnp
                row = run_cell(arch, shape_name, multi_pod=multi_pod,
                               rules=rules, remat=args.remat, tag=args.tag,
                               compile_only=args.compile_only,
                               mesh_override=args.mesh_override,
                               serve_dtype=_jnp.bfloat16 if args.serve_bf16
                               else None, window=args.window,
                               microbatches=args.microbatches)
            except Exception as e:
                traceback.print_exc()
                row = dict(name=name, mesh=mesh_name, error=str(e)[:500])
            rows.append(row)
            with open(path, "w") as f:
                json.dump(rows, f, indent=1)
    print("done")


if __name__ == "__main__":
    main()
