"""Read a profiler trace's scopes and host phases from the raw XSpace.

``jax.profiler.ProfileData`` exposes the stats of each event, but not the
stats of an event's metadata, and that is where a TPU trace keeps each
operation's ``tf_op``: the JAX name stack of the operation, such as
``jit(_traced)/while/body/repro.round.compact/jit(bitword_compact_gather)/
...``. This module decodes the few XSpace fields it needs straight from
the protobuf wire format (no tensorflow, no generated classes) and gives:

* ``scope_seconds``: device self time per innermost ``repro.*`` component
  of ``tf_op`` (``none`` for an operation outside every such scope),
  summed over the chips, inside the window;
* ``idle_by_phase``: the stretches of the first chip's window that no
  operation covers, each charged to the innermost ``repro.*`` host event
  of the driving thread at the gap's midpoint (``none`` where the thread
  was inside no such event);
* ``seconds_under(component)``: device self time of the operations whose
  ``tf_op`` holds one path component, such as
  ``jit(bitword_compact_gather)``.

Busy time, the window and the self-time rule are those of
``bench/devtrace.py``, so the scopes sum to its ``busy_s`` times the
number of chips. From the root of a checkout,
``python3 -m bench.xspace <trace> --enumerations <n>`` prints the
breakdown of one traced window (``bench/run.py --keep-trace``) as JSON.
"""
from __future__ import annotations

import dataclasses
import gzip
import os
import struct

from bench import devtrace

SCOPE = "repro."
NONE = "none"


# -- protobuf wire format ----------------------------------------------------

def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, wire type, value) of one message; length-delimited
    values come back as memoryview slices."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val = buf[i:i + size]
            i += size
        elif wire == 1:
            val = struct.unpack_from("<q", buf, i)[0]
            i += 8
        elif wire == 5:
            val = struct.unpack_from("<i", buf, i)[0]
            i += 4
        else:
            raise ValueError(f"unsupported wire type {wire}")
        yield field, wire, val


def _signed(v):
    return v - (1 << 64) if v >= 1 << 63 else v


def _str(v):
    return bytes(v).decode("utf-8", "replace")


@dataclasses.dataclass
class _Plane:
    name: str = ""
    lines: list = dataclasses.field(default_factory=list)   # (name, events)
    event_names: dict = dataclasses.field(default_factory=dict)
    event_stats: dict = dataclasses.field(default_factory=dict)
    stat_names: dict = dataclasses.field(default_factory=dict)
    stats: dict = dataclasses.field(default_factory=dict)   # name -> value


def _stat(buf):
    """(metadata id, value) of one XStat: a string, a number, or a
    reference to a stat metadata id (``("ref", id)``)."""
    mid, val = 0, None
    for f, _, v in _fields(buf):
        if f == 1:
            mid = v
        elif f == 5:
            val = _str(v)
        elif f == 7:
            val = ("ref", v)
        elif f in (3, 4):
            val = _signed(v)
        elif f == 2:
            val = struct.unpack("<d", struct.pack("<q", v))[0]
    return mid, val


def _event_metadata(buf, plane):
    mid, name, stats = 0, "", []
    for f, _, v in _fields(buf):
        if f == 1:
            mid = v
        elif f == 2:
            name = _str(v)
        elif f == 5:
            stats.append(_stat(v))
    plane.event_names[mid] = name
    if stats:
        plane.event_stats[mid] = stats


def _line(buf):
    """(name, [(start ns, end ns, metadata id)]) of one XLine."""
    name, t0_ns, events = "", 0, []
    for f, _, v in _fields(buf):
        if f == 2:
            name = _str(v)
        elif f == 3:
            t0_ns = _signed(v)
        elif f == 4:
            mid = off = dur = 0
            for ef, _, ev in _fields(v):
                if ef == 1:
                    mid = ev
                elif ef == 2:
                    off = _signed(ev)
                elif ef == 3:
                    dur = _signed(ev)
            events.append((off, dur, mid))
    return name, [(t0_ns + off / 1e3, t0_ns + (off + dur) / 1e3, mid)
                  for off, dur, mid in events]


def _map_entry(buf):
    key, val = 0, b""
    for f, _, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            val = v
    return key, val


def _plane(buf):
    p = _Plane()
    stats = []
    for f, _, v in _fields(buf):
        if f == 2:
            p.name = _str(v)
        elif f == 3:
            p.lines.append(_line(v))
        elif f == 4:
            _event_metadata(_map_entry(v)[1], p)
        elif f == 5:
            sid, body = _map_entry(v)
            for sf, _, sv in _fields(body):
                if sf == 2:
                    p.stat_names[sid] = _str(sv)
        elif f == 6:
            stats.append(_stat(v))
    p.stats = {p.stat_names.get(sid, ""): val for sid, val in stats}
    return p


def read_planes(path: str) -> list:
    """The planes of one ``.xplane.pb`` (or ``.xplane.pb.gz``) file, or of
    the newest such file under a directory."""
    if os.path.isdir(path):
        path = devtrace.newest_file(path)
    with open(path, "rb") as f:
        data = f.read()
    if path.endswith(".gz"):
        data = gzip.decompress(data)
    buf = memoryview(data)
    return [_plane(v) for f, _, v in _fields(buf) if f == 1]


def profile_start_ns(planes) -> int:
    """The profiler's start on the host clock (Unix nanoseconds): every
    event time of the trace counts from it, so an event starts at
    ``profile_start_ns + start`` in the clock of ``time.time_ns()``."""
    for p in planes:
        if "profile_start_time" in p.stats:
            return int(p.stats["profile_start_time"])
    raise ValueError("the trace holds no profile_start_time")


# -- reduction ---------------------------------------------------------------

def tf_ops(plane) -> dict:
    """Event metadata id -> ``tf_op`` of a device plane."""
    want = {sid for sid, name in plane.stat_names.items() if name == "tf_op"}
    out = {}
    for mid, stats in plane.event_stats.items():
        for sid, val in stats:
            if sid in want:
                if isinstance(val, tuple):
                    val = plane.stat_names.get(val[1], "")
                out[mid] = val
    return out


def innermost_scope(tf_op: str) -> str:
    for part in reversed(tf_op.split("/")):
        if part.startswith(SCOPE):
            return part
    return NONE


@dataclasses.dataclass
class Scopes:
    n_chips: int
    window_s: float
    busy_s: float                # mean over chips, as devtrace reads it
    op_paths: dict               # tf_op -> device self seconds, all chips
    scope_seconds: dict          # innermost repro.* scope -> seconds
    idle_by_phase: dict          # innermost repro.* host event -> seconds

    def seconds_under(self, component: str) -> float:
        return sum(s for op, s in self.op_paths.items()
                   if component in op.split("/"))


def _window(host):
    for name, events in host.lines:
        for s, e, mid in events:
            if host.event_names.get(mid) == devtrace.WINDOW:
                return events, s, e
    return None, None, None


def _phase_at(events, names, mids):
    """Innermost ``repro.*`` event of one thread covering each midpoint
    (ascending), else ``none``."""
    evs = sorted(((s, e, names.get(m, "")) for s, e, m in events
                  if names.get(m, "").startswith(SCOPE)),
                 key=lambda t: (t[0], -t[1]))
    stack, i, out = [], 0, []
    for mid in mids:
        while i < len(evs) and evs[i][0] <= mid:
            stack.append(evs[i])
            i += 1
        stack = [ev for ev in stack if ev[1] >= mid]
        out.append(stack[-1][2] if stack else NONE)
    return out


def reduce(path: str) -> Scopes:
    planes = read_planes(path)
    devices = sorted((p for p in planes
                      if devtrace._DEVICE.fullmatch(p.name)),
                     key=lambda p: int(p.name.rsplit(":", 1)[1]))
    if not devices:
        raise ValueError("the trace holds no TPU device plane")
    host = next((p for p in planes if p.name == "/host:CPU"), None)
    thread, lo, hi = _window(host) if host is not None else (None,) * 3
    if thread is None:
        raise ValueError(f"the trace holds no {devtrace.WINDOW!r} annotation")

    op_paths: dict = {}
    busy, first_union = [], None
    for plane in devices:
        ops_line = next((ev for name, ev in plane.lines
                         if name == "XLA Ops"), [])
        ops = [(max(s, lo), min(e, hi), mid) for s, e, mid in ops_line
               if e > lo and s < hi]
        paths = tf_ops(plane)
        for s, e, mid, own in devtrace._self_times(ops):
            key = paths.get(mid, "")
            op_paths[key] = op_paths.get(key, 0.0) + own * 1e-9
        union = devtrace._union([(s, e) for s, e, _ in ops], lo, hi)
        busy.append(sum(e - s for s, e in union) * 1e-9)
        if first_union is None:
            first_union = union

    scopes: dict = {}
    for op, s in op_paths.items():
        key = innermost_scope(op)
        scopes[key] = scopes.get(key, 0.0) + s

    gaps, t = [], lo
    for s, e in first_union:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    idle: dict = {}
    labels = _phase_at(thread, host.event_names,
                       [(s + e) / 2 for s, e in gaps])
    for (s, e), label in zip(gaps, labels):
        idle[label] = idle.get(label, 0.0) + (e - s) * 1e-9
    return Scopes(n_chips=len(devices), window_s=(hi - lo) * 1e-9,
                  busy_s=sum(busy) / len(busy), op_paths=op_paths,
                  scope_seconds=scopes, idle_by_phase=idle)


def per_enumeration_ms(scopes: Scopes, n: int) -> dict:
    """Milliseconds per enumeration of a one-shot window of ``n``
    enumerations: device time under each ``repro.*`` scope (``device``)
    and first-chip idle time inside each host phase (``idle``)."""
    return dict(
        device={k: 1e3 * s / n for k, s in scopes.scope_seconds.items()},
        idle={k: 1e3 * s / n for k, s in scopes.idle_by_phase.items()})


def main(argv=None) -> int:
    import argparse
    import json
    ap = argparse.ArgumentParser(
        description="Print the scope and host-phase breakdown of one "
                    "traced window as JSON.")
    ap.add_argument("trace", help=".xplane.pb(.gz) file, or the directory "
                                  "the profiler wrote")
    ap.add_argument("--enumerations", type=int, default=0,
                    help="enumerations in the window: also print "
                         "milliseconds per enumeration")
    args = ap.parse_args(argv)
    sc = reduce(args.trace)
    out = dict(window_s=sc.window_s, busy_s=sc.busy_s,
               scope_seconds=sc.scope_seconds,
               idle_by_phase=sc.idle_by_phase,
               top_ops=sorted(sc.op_paths.items(),
                              key=lambda kv: -kv[1])[:10])
    if args.enumerations:
        out["per_enumeration_ms"] = per_enumeration_ms(sc, args.enumerations)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
