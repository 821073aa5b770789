"""Trace reduction on a trace recorded on the chip.

``data/v5e_grid6x10_count.xplane.pb.gz`` is the traced window of
``python3 bench/run.py --workload oneshot_grid6x10_count --seed 23
--seconds 1 --trace 1 --keep-trace <file>`` on one TPU v5 lite: one whole
Grid_6x10 enumeration. That run printed ``busy_s`` 8.569694601,
``window_s`` 8.669249872 and ``device_idle_pct.oneshot`` 1.148372379039897.
"""
from __future__ import annotations

import os

import pytest

from bench import devtrace
from bench.run import load_module

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TRACE = os.path.join(ROOT, "bench", "tests", "data",
                     "v5e_grid6x10_count.xplane.pb.gz")


@pytest.fixture(scope="module")
def red():
    return devtrace.reduce(devtrace.load(TRACE))


def test_window_and_busy(red):
    assert red.n_chips == 1
    assert red.window_s == pytest.approx(8.669249872, abs=1e-9)
    assert red.busy_s == pytest.approx(8.569694601, abs=1e-9)
    # every op's self time is busy time, counted once
    assert sum(red.op_seconds.values()) == pytest.approx(red.busy_s)
    assert sum(red.idle_by_label.values()) == pytest.approx(
        red.window_s - red.busy_s)


def test_breakdown_names_the_split_path(red):
    ops = red.top_ops()
    assert len(ops) == 10
    assert ops[0][0] == "jit__traced/fusion.59 s32[4194304]"
    assert ops[0][1] == pytest.approx(2.751404179, rel=1e-9)
    assert all(a[1] >= b[1] for a, b in zip(ops, ops[1:]))
    idle = red.top_idle()
    assert idle[0][0] == "bench.enumerate>np.asarray(jax.Array)"
    assert all(label.startswith("bench.enumerate>") for label, _ in idle)


def test_metrics_from_the_trace(red):
    idle = load_module(os.path.join(ROOT, "bench", "metrics",
                                    "device_idle_pct.oneshot.py"))
    r = dict(counters=dict(enumerations=[{}], paths_extended=10_696_912,
                           n_vertices=60), trace=red,
             peaks={"hbm_bytes_per_s": 819e9})
    assert idle.read(r) == pytest.approx(1.148372379039897, rel=1e-9)
    roof = load_module(os.path.join(ROOT, "bench", "metrics",
                                    "wave_hbm_roofline_pct.py"))
    assert roof.read(r) == pytest.approx(0.006096339821220013, rel=1e-9)


def test_self_time_of_nested_ops():
    out = devtrace._self_times([(0, 10, "while"), (2, 4, "a"),
                                (5, 9, "b"), (6, 7, "c"), (12, 13, "d")])
    own = {name: t for _, _, name, t in out}
    assert own == {"while": 4, "a": 2, "b": 3, "c": 1, "d": 1}


def test_union_and_labels():
    assert devtrace._union([(0, 2), (1, 3), (5, 6)], 1, 10) == \
        [[1, 3], [5, 6]]
    thread = [(0, 100, "bench.window"), (10, 50, "bench.serve"),
              (20, 30, "DevicePut")]
    assert devtrace._labels(thread, [5, 15, 25, 60]) == [
        "bench.window>python", "bench.serve>python",
        "bench.serve>DevicePut", "bench.window>python"]


def test_a_trace_without_a_window_is_refused(tmp_path):
    with pytest.raises(FileNotFoundError):
        devtrace.load(str(tmp_path))
