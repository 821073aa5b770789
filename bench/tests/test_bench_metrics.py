"""Per-layer readers: the arithmetic, and nothing read where nothing is."""
from __future__ import annotations

import os

import pytest

from bench import devtrace
from bench.run import load_module

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
V5E = {"hbm_bytes_per_s": 819e9}


def reader(name):
    return load_module(os.path.join(ROOT, "bench", "metrics", name + ".py"))


def reduction(busy_s, window_s):
    return devtrace.Reduction(n_chips=1, window_s=window_s, busy_s=busy_s,
                              op_seconds={}, idle_by_label={})


def reading(counters=None, trace=None):
    return dict(counters=counters or {}, trace=trace, peaks=V5E,
                traffic={}, config={})


def test_roofline_counts_the_algorithms_bytes():
    m = reader("wave_hbm_roofline_pct")
    # Grid_6x10: P paths of nw = 2 words plus three endpoints, written
    # and read once each: 2 * 10,696,912 * 20 bytes
    assert m.wave_bytes(10_696_912, 60) == 427_876_480
    assert m.wave_bytes(730_015, 50, stored_cycles=52_620) == \
        2 * 730_015 * 20 + 8 * 52_620
    r = reading(dict(enumerations=[{}] * 4, paths_extended=10_696_912,
                     n_vertices=60, stored_cycles=None),
                reduction(busy_s=34.0, window_s=35.0))
    want = 100 * 4 * 427_876_480 / 819e9 / 34.0
    assert m.read(r) == pytest.approx(want)
    assert m.read(reading(dict(enumerations=[]), reduction(1, 1))) is None


def test_idle_share():
    for name in ("device_idle_pct.oneshot", "device_idle_pct.serve"):
        m = reader(name)
        assert m.read(reading(trace=reduction(3.0, 4.0))) == \
            pytest.approx(25.0)


def test_scheduler_readers():
    s = dict(queue_wait_ms=list(range(1, 101)), occupancy_sum=30.0,
             supersteps=40, n_host_syncs=900, completed=300)
    r = reading(dict(session=s))
    assert reader("queue_wait_p95_ms").read(r) == pytest.approx(95.05)
    assert reader("lane_occupancy_pct").read(r) == pytest.approx(75.0)
    assert reader("host_syncs_per_request.serve").read(r) == \
        pytest.approx(3.0)
    empty = reading(dict(session={}))
    for name in ("queue_wait_p95_ms", "lane_occupancy_pct",
                 "host_syncs_per_request.serve"):
        assert reader(name).read(empty) is None


def test_driver_and_round_readers():
    enums = [dict(n_host_syncs=17, fused_rounds=19, split_rounds=27),
             dict(n_host_syncs=19, fused_rounds=19, split_rounds=27)]
    r = reading(dict(enumerations=enums))
    assert reader("host_syncs_per_request.oneshot").read(r) == 18.0
    assert reader("split_round_share_pct").read(r) == \
        pytest.approx(100 * 54 / 92)
    none = reading(dict(enumerations=[{}]))
    assert reader("host_syncs_per_request.oneshot").read(none) is None
    assert reader("split_round_share_pct").read(none) is None
