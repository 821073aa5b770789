"""The traffic generator: every seed the same work in another order."""
from __future__ import annotations

import collections
import json
import os
import statistics

import numpy as np
import pytest

from bench import traffic

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def families():
    with open(os.path.join(ROOT, "bench", "configs",
                           "table1_tenants.json")) as f:
        return json.load(f)["families"]


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 17, 2**40 + 3])
def test_sequence_deterministic_per_seed(seed):
    a = traffic.request_sequence(families(), 500, seed)
    b = traffic.request_sequence(families(), 500, seed)
    assert a == b
    assert np.array_equal(traffic.arrivals(500, 80.0, seed),
                          traffic.arrivals(500, 80.0, seed))


def test_seeds_change_order_not_work():
    a = traffic.request_sequence(families(), 1000, 1)
    b = traffic.request_sequence(families(), 1000, 2)
    assert a != b
    assert collections.Counter(a) == collections.Counter(b)
    ga = np.diff(traffic.arrivals(1000, 50.0, 1))
    gb = np.diff(traffic.arrivals(1000, 50.0, 2))
    assert not np.array_equal(ga, gb)
    # the same gaps, less the one left after the last arrival
    assert abs(np.sort(ga)[:-1] - np.sort(gb)[:-1]).max() < 0.05


def test_shares_follow_the_catalogue():
    fam = families()
    seq = traffic.request_sequence(fam, 1000, 3)
    counts = collections.Counter(seq)
    for f in fam.values():
        got = sum(counts[g] for g in f["graphs"])
        assert got == round(1000 * f["share"])
        per = [counts[g] for g in f["graphs"]]
        assert max(per) - min(per) <= 1


def test_arrivals_offer_the_rate():
    a = traffic.arrivals(4000, 100.0, 5)
    assert a[0] == 0.0 and np.all(np.diff(a) > 0)
    gaps = np.diff(a)
    # 4000 arrivals span 40 s less one gap; exponential: sd == mean
    assert 39.9 < a[-1] < 40.0
    assert abs(gaps.mean() - 0.01) < 1e-4
    assert abs(gaps.std() / gaps.mean() - 1.0) < 0.05


def test_split_largest_remainder():
    assert traffic._split(10, [0.5, 0.2, 0.15, 0.15]) == [5, 2, 2, 1]
    assert traffic._split(7, [1, 1, 1]) == [3, 2, 2]
    assert sum(traffic._split(4001, [0.5, 0.2, 0.15, 0.15])) == 4001


def test_percentile_over_every_request():
    xs = list(range(1, 101))
    assert traffic.percentile(xs, 95) == pytest.approx(95.05)
    assert traffic.percentile([3.0], 95) == 3.0
    # one slow request in twenty moves the p95 of twenty
    assert traffic.percentile([1.0] * 19 + [100.0], 95) == pytest.approx(
        1.0 + 0.05 * 99.0)
    assert traffic.percentile(xs, 50) == statistics.median(xs)
    with pytest.raises(ValueError):
        traffic.percentile([], 95)
