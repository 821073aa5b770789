"""Run one cell of the benchmark once and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A cell is one entry of ``workloads`` in ``BENCHMARK.json``. The harness
finds everything else by name, so a cell, a traffic mix, a driver or a
per-layer metric is added with files and entries alone:

* ``bench/configs/<config>.json``: the deployment, the service's options
  and, for serving, the request catalogue;
* ``bench/traffic/<traffic>.json``: the traffic mix; its ``driver`` names
  ``bench/drivers/<driver>.py``, whose ``run(run)`` sets up, measures the
  window and checks every answer of the window against
  ``bench/reference.py``;
* ``bench/metrics/<metric>.py``: one reader per per-layer metric, whose
  ``read(reading)`` returns a number, or None where it finds nothing.

With ``--trace 0`` the result carries the cell's end-to-end metrics, taken
on the host clock; with ``--trace 1`` the measured window runs under the
JAX profiler and the result carries the cell's per-layer metrics, the
device's busy time and a breakdown of device operations and idle gaps.
Either way every answer is checked, and each number compared is printed
beside its limit: as the last lines on standard error, and under
``checks``, the last key of the result line, which is the last line of
standard output.

The system under test is imported from ``src/`` of the same checkout.
JAX's persistent compilation cache lives in ``JAX_COMPILATION_CACHE_DIR``
where that is set, else in ``.jax_cache`` of the checkout. Without a TPU,
or with fewer chips than the cell asks for, the run exits 2 and prints no
result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gzip  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
WINDOW = "bench.window"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list      # BENCHMARK.json entries this cell reports
    per_layer: list


def load_cell(root: str, name: str) -> Cell:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    conf = next(c for c in spec["configs"] if c["name"] == w["config"])
    with open(os.path.join(root, conf["file"])) as f:
        config = json.load(f)
    with open(os.path.join(root, "bench", "traffic",
                           w["traffic"] + ".json")) as f:
        traffic = json.load(f)
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if name in m.get("workloads", [name])
                 and m["moves"] in reported]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=per_layer)


def load_module(path: str):
    spec = importlib.util.spec_from_file_location(
        "bench_" + os.path.basename(path)[:-3].replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Outcome:
    """What a driver hands back: counts of the window's answers, its
    end-to-end metrics, what the per-layer readers read, and each number
    compared with the reference as ``name: (value, limit)``."""
    attempted: int
    failed: int
    end_to_end: dict
    counters: dict
    checks: dict


class Run:
    """One run of one cell, as a driver sees it: the cell's files, the
    seed and the window's length, and the window's bracket, which also
    starts and stops the profiler in a traced run."""

    def __init__(self, cell: Cell, root: str, seed: int, seconds: float,
                 trace: bool):
        self.cell, self.root = cell, root
        self.config, self.traffic = cell.config, cell.traffic
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.trace_dir = os.path.join(root, ".bench_out", "trace")
        self.t0 = self.t1 = None
        self.loads_in_window: dict = {}     # program -> seconds
        self.memory_peak_bytes = None
        self._window = None
        self._tracing = False

    def annotate(self, name: str):
        import jax
        return jax.profiler.TraceAnnotation("bench." + name)

    def _count_load(self, event: str, duration: float, fun_name="?",
                    **_) -> None:
        if event == COMPILE_EVENT and self._window is not None:
            self.loads_in_window[fun_name] = \
                self.loads_in_window.get(fun_name, 0.0) + duration

    def begin_window(self) -> float:
        import jax
        if self.trace:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            self._tracing = True
        jax.monitoring.register_event_duration_secs_listener(
            self._count_load)
        self._window = jax.profiler.TraceAnnotation(WINDOW)
        self._window.__enter__()
        self.t0 = time.perf_counter()
        return self.t0

    def end_window(self) -> float:
        """Close the measured window and read the device's peak memory.
        A traced run keeps the profiler on until ``finish``."""
        import jax
        self.t1 = time.perf_counter()
        self._window.__exit__(None, None, None)
        self._window = None
        jax.monitoring.unregister_event_duration_listener(self._count_load)
        self.memory_peak_bytes = memory_peak_bytes(self.cell.chips)
        return self.t1

    def finish(self) -> None:
        if self._tracing:
            import jax
            jax.profiler.stop_trace()
            self._tracing = False


def memory_peak_bytes(chips: int):
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:chips]]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def enable_compile_cache(root: str) -> str:
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        root, ".jax_cache")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def device_summary() -> dict:
    import jax
    devs = jax.devices()
    return dict(platform=devs[0].platform, kind=devs[0].device_kind,
                count=len(devs))


def read_per_layer(cell: Cell, root: str, outcome: Outcome,
                   reduction) -> dict:
    with open(os.path.join(root, "bench", "peaks.json")) as f:
        peaks = json.load(f)
    kind = device_summary()["kind"]
    if kind not in peaks:
        raise KeyError(f"no peaks for device kind {kind!r} in "
                       "bench/peaks.json")
    reading = dict(counters=outcome.counters, trace=reduction,
                   peaks=peaks[kind], traffic=cell.traffic,
                   config=cell.config)
    out = {}
    for m in cell.per_layer:
        reader = load_module(os.path.join(root, "bench", "metrics",
                                          m["name"] + ".py"))
        value = reader.read(reading)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run_cell(cell: Cell, root: str, *, seed: int, seconds: float,
             trace: bool, t_start: float = T_START,
             keep_trace: str | None = None) -> dict:
    """Run the cell once on whatever devices JAX has and return the
    result line's object. ``main`` refuses to get here without a chip."""
    driver = load_module(os.path.join(root, "bench", "drivers",
                                      cell.traffic["driver"] + ".py"))
    run = Run(cell, root, seed, seconds, trace)
    try:
        outcome = driver.run(run)
    finally:
        run.finish()
    e2e = dict(outcome.end_to_end, setup_s=run.t0 - t_start)
    device = dict(device_summary(),
                  memory_peak_bytes=run.memory_peak_bytes)
    result = dict(correct=None, attempted=outcome.attempted,
                  failed=outcome.failed, metrics={}, device=device)
    if not trace:
        result["metrics"] = {m["name"]: {"value": e2e[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
    else:
        from bench import devtrace
        reduction = devtrace.reduce(devtrace.load(run.trace_dir))
        if keep_trace:
            src = devtrace.newest_file(run.trace_dir)
            with open(src, "rb") as f, gzip.open(keep_trace, "wb") as g:
                shutil.copyfileobj(f, g)
        shutil.rmtree(run.trace_dir, ignore_errors=True)
        result["metrics"] = read_per_layer(cell, root, outcome, reduction)
        device.update(busy_s=reduction.busy_s, window_s=reduction.window_s)
        result["breakdown"] = dict(device_ops=reduction.top_ops(),
                                   idle_gaps=reduction.top_idle())
    result["correct"] = bool(outcome.failed == 0 and all(
        value <= limit for value, limit in outcome.checks.values()))
    result["checks"] = {name: {"value": value, "limit": limit}
                        for name, (value, limit) in outcome.checks.items()}
    result["window"] = dict(
        loads=run.loads_in_window,
        n_traces=outcome.counters.get("n_traces_in_window"),
        warmup=outcome.counters.get("warmup_pass_compiles"))
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="also write the traced window's .xplane.pb, "
                         "gzipped, to this file")
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

    cell = load_cell(ROOT, args.workload)
    import jax
    cache = enable_compile_cache(ROOT)
    dev = device_summary()
    if dev["platform"] != "tpu" or dev["count"] < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} TPU chip(s); JAX "
              f"found {dev['count']} {dev['platform']} device(s) "
              f"({dev['kind']})", file=sys.stderr)
        return 2
    print(f"[{dev['kind']}] {args.workload} seed={args.seed} "
          f"seconds={args.seconds} trace={args.trace} "
          f"compile_cache={cache} jax={jax.__version__}", flush=True)
    result = run_cell(cell, ROOT, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), keep_trace=args.keep_trace)
    window = result.pop("window")
    print(f"[{dev['kind']}] window: n_traces={window['n_traces']} "
          f"warm-up passes' compiles={window['warmup']} "
          f"programs compiled or loaded={len(window['loads'])} "
          f"{window['loads']}", flush=True)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(f"check correct: {result['correct']}", file=sys.stderr,
          flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
