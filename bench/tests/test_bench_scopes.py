"""Device scopes and host phases read from the raw XSpace of a trace, and
the driver's device-to-host read counter.

``data/v5e_grid6x10_count.xplane.pb.gz`` predates the ``repro.*`` scopes
and phases: every operation and every gap of it reads ``none``, and the
``tf_op`` paths still name the implementation.
``data/v5e_grid6x10_count_scopes.xplane.pb.gz`` is the traced window of
``python3 bench/run.py --workload oneshot_grid6x10_count --seed
2147483902 --seconds 1 --trace 1 --keep-trace <file>`` on one TPU v5 lite
with the scopes and phases in the program: one whole Grid_6x10
enumeration. That run printed ``busy_s`` 8.569262359, ``window_s``
8.664602297 and ``device_idle_pct.oneshot`` 1.1003383044252413.
"""
from __future__ import annotations

import gzip
import os

import pytest

from bench import devtrace, xspace
from bench.run import load_module

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DATA = os.path.join(ROOT, "bench", "tests", "data")
OLD = os.path.join(DATA, "v5e_grid6x10_count.xplane.pb.gz")
NEW = os.path.join(DATA, "v5e_grid6x10_count_scopes.xplane.pb.gz")


@pytest.fixture(scope="module")
def old():
    return xspace.reduce(OLD)


@pytest.fixture(scope="module")
def new():
    return xspace.reduce(NEW)


def test_tf_op_reader_finds_the_split_compaction(old):
    """One Grid_6x10 enumeration: the split path's compaction holds 8.4061
    of the 8.5697 device seconds, the flag kernel and the fused rounds
    the rest."""
    assert old.busy_s == pytest.approx(8.5697, abs=5e-5)
    assert old.seconds_under("jit(bitword_compact_gather)") == \
        pytest.approx(8.4061, abs=5e-5)
    assert old.seconds_under("jit(bitword_expand_lanes)") == \
        pytest.approx(0.1240, abs=5e-5)
    assert old.seconds_under("jit(fused_round_lanes)") == \
        pytest.approx(0.0269, abs=5e-5)
    # about half of all device time is the binary search of _source_rows
    search = old.seconds_under("jit(searchsorted)")
    assert 0.45 < search / old.busy_s < 0.6


def test_reduction_agrees_with_devtrace(old):
    red = devtrace.reduce(devtrace.load(OLD))
    assert old.n_chips == red.n_chips == 1
    assert old.window_s == pytest.approx(red.window_s, abs=1e-9)
    assert old.busy_s == pytest.approx(red.busy_s, abs=1e-6)
    assert sum(old.op_paths.values()) == pytest.approx(old.busy_s)


def test_a_trace_without_scopes_reads_none(old):
    assert set(old.scope_seconds) == {"none"}
    assert sum(old.scope_seconds.values()) == pytest.approx(old.busy_s)
    assert set(old.idle_by_phase) == {"none"}
    assert sum(old.idle_by_phase.values()) == pytest.approx(
        old.window_s - old.busy_s)


def test_scopes_of_one_grid6x10_enumeration(new):
    """The anchor of ``compact_device_ms.oneshot``: the split path's
    compaction is 98% of the device time of a Grid_6x10 enumeration."""
    red = devtrace.reduce(devtrace.load(NEW))
    assert red.busy_s == pytest.approx(8.569262359, abs=1e-9)
    assert red.window_s == pytest.approx(8.664602297, abs=1e-9)
    assert new.busy_s == pytest.approx(red.busy_s, abs=1e-6)
    sc = new.scope_seconds
    assert sum(sc.values()) == pytest.approx(new.busy_s)
    assert set(sc) == {"none", "repro.seed", "repro.round.fused",
                       "repro.round.flags", "repro.round.compact"}
    assert sc["repro.round.compact"] == pytest.approx(8.40567, abs=5e-5)
    assert sc["repro.round.compact"] / new.busy_s > 0.9
    assert sc["repro.round.flags"] == pytest.approx(0.12447, abs=5e-5)
    assert sc["repro.round.fused"] == pytest.approx(0.02687, abs=5e-5)
    # the same operations as before the scopes, under a stable name
    assert new.seconds_under("jit(bitword_compact_gather)") == \
        pytest.approx(sc["repro.round.compact"], abs=1e-9)


def test_idle_phases_of_one_grid6x10_enumeration(new):
    """The anchor of the idle-phase readers: every idle gap of the
    enumeration falls inside a ``repro.*`` host phase."""
    idle = new.idle_by_phase
    assert sum(idle.values()) == pytest.approx(new.window_s - new.busy_s)
    named = sum(s for phase, s in idle.items() if phase != "none")
    assert named >= 0.9 * sum(idle.values())
    assert idle["repro.readback"] == pytest.approx(0.034060, abs=5e-6)
    assert idle["repro.rebucket"] == pytest.approx(0.035676, abs=5e-6)
    assert "repro.drain" not in idle          # a count-only run


def test_profile_start_is_on_the_unix_clock():
    planes = xspace.read_planes(OLD)
    # 2026, in Unix nanoseconds
    assert 1.7e18 < xspace.profile_start_ns(planes) < 1.9e18


def test_innermost_scope_and_phase():
    path = ("jit(_traced)/while/body/repro.round.flags/cond/repro.x/"
            "jit(bitword_compact_gather)/gather:")
    assert xspace.innermost_scope(path) == "repro.x"
    assert xspace.innermost_scope("jit(_traced)/while:") == "none"
    names = {1: "repro.enumerate", 2: "repro.readback", 3: "DevicePut",
             4: "repro.drain"}
    thread = [(0, 100, 1), (10, 20, 2), (12, 14, 3), (30, 40, 4)]
    assert xspace._phase_at(thread, names, [5, 13, 25, 35, 150]) == [
        "repro.enumerate", "repro.readback", "repro.enumerate",
        "repro.drain", "none"]


def _varint(n):
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(num, payload):
    if isinstance(payload, int):
        return _varint(num << 3) + _varint(payload)
    return _varint(num << 3 | 2) + _varint(len(payload)) + payload


def test_wire_reader_on_a_hand_built_space(tmp_path):
    """A device plane with two nested ops under a window annotation: the
    scope of each op comes from its metadata's ``tf_op``, referenced by
    a stat metadata id, as a TPU trace keeps it."""
    stat_md = _field(1, 7) + _field(2, b"tf_op")
    ev_md = lambda mid, name, op: _field(1, mid) + _field(2, name) + \
        _field(5, _field(1, 7) + _field(5, op))
    ops = (_field(2, b"XLA Ops") + _field(3, 1000)
           + _field(4, _field(1, 1) + _field(2, 0) + _field(3, 8_000_000))
           + _field(4, _field(1, 2) + _field(2, 2_000_000)
                    + _field(3, 3_000_000)))
    device = (_field(2, b"/device:TPU:0") + _field(3, ops)
              + _field(4, _field(1, 1) + _field(2, ev_md(
                  1, b"%while", b"jit(f)/repro.round.fused/while:")))
              + _field(4, _field(1, 2) + _field(2, ev_md(
                  2, b"%fusion.1", b"jit(f)/repro.round.compact/x:")))
              + _field(5, _field(1, 7) + _field(2, stat_md)))
    thread = (_field(2, b"main") + _field(3, 0)
              + _field(4, _field(1, 1) + _field(2, 0)
                       + _field(3, 12_000_000))
              + _field(4, _field(1, 2) + _field(2, 9_500_000)
                       + _field(3, 2_000_000)))
    host = (_field(2, b"/host:CPU") + _field(3, thread)
            + _field(4, _field(1, 1) + _field(2, _field(1, 1) + _field(
                2, devtrace.WINDOW.encode())))
            + _field(4, _field(1, 2) + _field(2, _field(1, 2) + _field(
                2, b"repro.readback"))))
    path = tmp_path / "t.xplane.pb.gz"
    with gzip.open(path, "wb") as f:
        f.write(_field(1, device) + _field(1, host))
    sc = xspace.reduce(str(path))
    # ns: window [0, 12000], ops [1000, 9000] holding [3000, 6000]
    assert sc.window_s == pytest.approx(12e-6)
    assert sc.busy_s == pytest.approx(8e-6)
    assert sc.scope_seconds == pytest.approx(
        {"repro.round.fused": 5e-6, "repro.round.compact": 3e-6})
    # idle [0, 1000] outside every phase, [9000, 12000] in the readback
    assert sc.idle_by_phase == pytest.approx(
        {"none": 1e-6, "repro.readback": 3e-6})
    ms = xspace.per_enumeration_ms(sc, 2)
    assert ms["device"]["repro.round.compact"] == pytest.approx(1.5e-3)


def test_d2h_reader_reads_the_program_counter():
    import sys
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.core import CycleService, EngineConfig, build_graph
    from repro.core.graphs import grid_graph
    m = load_module(os.path.join(ROOT, "bench", "metrics",
                                 "d2h_reads_per_request.oneshot.py"))
    res = CycleService(EngineConfig(store=False)).enumerate(
        build_graph(*grid_graph(4, 4)))
    n = res.stats["n_d2h_arrays"]
    assert n > res.stats["n_host_syncs"]
    r = dict(counters=dict(enumerations=[res.stats, dict(res.stats)]))
    assert m.read(r) == n
    # a program without the counter gives the reader nothing to read
    assert m.read(dict(counters=dict(enumerations=[{"n_host_syncs": 3}]))) \
        is None
    assert m.read(dict(counters={})) is None
