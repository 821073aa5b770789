"""Reduce a JAX profiler trace to device busy time, top ops and idle gaps.

The profiler writes one ``.xplane.pb`` per traced window. Its planes named
``/device:TPU:<i>`` carry a line ``XLA Ops`` with one event per operation
that ran on that chip (Pallas kernels appear as their custom calls), and a
line ``XLA Modules`` with one event per program. The host plane
``/host:CPU`` carries a line per host thread; the benchmark's own
``jax.profiler.TraceAnnotation`` events (named ``bench.*``) sit on the
thread that drives the system, with the runtime's events nested inside.

* busy: the union of a chip's ``XLA Ops`` intervals inside the window
  (the host annotation ``bench.window``), averaged over the chips;
* device ops: self time per operation (an op such as ``while`` that
  holds others on the line is charged only the time none of them
  covers), keyed ``<program>/<operation> <first result shape>``, the
  program without the hash XLA appends: one program compiles for each
  frontier bucket, and the shape tells the buckets apart;
* idle gaps: the stretches of the first chip's window that no operation
  covers, each labelled by what the driving thread was inside at the
  gap's midpoint (the innermost ``bench.*`` annotation, then the
  innermost runtime event, or ``python`` where only Python ran), and
  summed per label.

On a TPU v5 lite the device clock of the trace reads about 1 ms earlier
than the host clock (device programs appear to start before the host
launches them), so a label is reliable only for gaps of several ms.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import gzip
import os
import re

WINDOW = "bench.window"
_DEVICE = re.compile(r"/device:TPU:\d+")
_PROGRAM = re.compile(r"\(\d+\)$")
_SHAPE = re.compile(r"[a-z]+\d*\[[\d,]*\]")


@dataclasses.dataclass
class Reduction:
    n_chips: int
    window_s: float
    busy_s: float                          # mean over chips
    op_seconds: dict                       # key -> device seconds, all chips
    idle_by_label: dict                    # label -> seconds, first chip

    def top_ops(self, k: int = 10) -> list:
        return _top(self.op_seconds, k)

    def top_idle(self, k: int = 10) -> list:
        return _top(self.idle_by_label, k)


def _top(d: dict, k: int) -> list:
    return [[name, s] for name, s in
            sorted(d.items(), key=lambda kv: (-kv[1], kv[0]))[:k]]


def newest_file(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str):
    """ProfileData of one trace: an ``.xplane.pb`` (or ``.xplane.pb.gz``)
    file, or the directory the profiler wrote (its newest file)."""
    import jax
    if os.path.isdir(path):
        path = newest_file(path)
    with open(path, "rb") as f:
        data = f.read()
    if path.endswith(".gz"):
        data = gzip.decompress(data)
    return jax.profiler.ProfileData.from_serialized_xspace(data)


def _line(plane, name):
    for line in plane.lines:
        if line.name == name:
            return line
    return None


def _events(line):
    return [(e.start_ns, e.start_ns + e.duration_ns, e.name)
            for e in line.events] if line is not None else []


def _union(intervals, lo, hi):
    """Merged, clipped intervals, in order."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _self_times(intervals):
    """(start, end, name, self time) of nested intervals of one line."""
    out, stack = [], []
    for s, e, name in sorted(intervals, key=lambda t: (t[0], -t[1])):
        while stack and out[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            out[stack[-1]][3] -= min(e, out[stack[-1]][1]) - s
        out.append([s, e, name, e - s])
        stack.append(len(out) - 1)
    return out


def _op_key(module: str, op_text: str) -> str:
    op, _, rest = op_text.partition(" = ")
    shape = _SHAPE.search(rest)
    key = f"{_PROGRAM.sub('', module)}/{op.lstrip('%')}"
    return f"{key} {shape.group(0)}" if shape else key


def _driver_line(host):
    """The host thread that holds the window annotation, and the window."""
    for line in host.lines:
        for e in line.events:
            if e.name == WINDOW:
                return line, e.start_ns, e.start_ns + e.duration_ns
    return None, None, None


def _labels(thread_events, mids):
    """Label each midpoint (ascending) by the innermost ``bench.*`` event
    and the innermost event of the thread that cover it. Events of one
    thread nest, so one sweep with a stack finds them."""
    evs = sorted(thread_events, key=lambda t: (t[0], -t[1]))
    stack, i, out = [], 0, []
    for mid in mids:
        while i < len(evs) and evs[i][0] <= mid:
            while stack and stack[-1][1] <= evs[i][0]:
                stack.pop()
            stack.append(evs[i])
            i += 1
        while stack and stack[-1][1] < mid:
            stack.pop()
        bench = next((e[2] for e in reversed(stack)
                      if e[2].startswith("bench.")), "outside")
        inner = stack[-1][2] if stack else ""
        if not inner or inner.startswith("bench."):
            inner = "python"
        out.append(f"{bench}>{inner}")
    return out


def reduce(pd) -> Reduction:
    planes = [p for p in pd.planes if _DEVICE.fullmatch(p.name)]
    if not planes:
        raise ValueError("the trace holds no TPU device plane")
    host = next((p for p in pd.planes if p.name == "/host:CPU"), None)
    thread, lo, hi = _driver_line(host) if host is not None else (None,) * 3
    if thread is None:
        raise ValueError(f"the trace holds no {WINDOW!r} annotation")

    op_seconds: dict = {}
    busy = []
    first_union = None
    for plane in sorted(planes, key=lambda p: int(p.name.rsplit(":", 1)[1])):
        ops = [(max(s, lo), min(e, hi), name) for s, e, name in
               _events(_line(plane, "XLA Ops")) if e > lo and s < hi]
        mods = sorted(_events(_line(plane, "XLA Modules")))
        starts = [m[0] for m in mods]
        for s, e, name, own in _self_times(ops):
            j = bisect.bisect_right(starts, s) - 1
            module = mods[j][2] if j >= 0 and mods[j][1] >= s else "?"
            key = _op_key(module, name)
            op_seconds[key] = op_seconds.get(key, 0.0) + own * 1e-9
        union = _union([(s, e) for s, e, _ in ops], lo, hi)
        busy.append(sum(e - s for s, e in union) * 1e-9)
        if first_union is None:
            first_union = union

    gaps, t = [], lo
    for s, e in first_union:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    idle: dict = {}
    mids = [(s + e) / 2 for s, e in gaps]
    for (s, e), label in zip(gaps, _labels(_events(thread), mids)):
        idle[label] = idle.get(label, 0.0) + (e - s) * 1e-9
    return Reduction(n_chips=len(planes), window_s=(hi - lo) * 1e-9,
                     busy_s=sum(busy) / len(busy), op_seconds=op_seconds,
                     idle_by_label=idle)
