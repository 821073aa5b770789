"""The one traffic generator: request sequences and arrival times from a seed.

Every seed gets the same work in another order. A mix of ``n`` requests
holds each family's share of ``n`` (largest remainders), split evenly
over the family's graphs, and the seed only permutes them. Open-loop
arrivals use ``n`` exponential gaps at the stratified quantiles
``(i + 1/2) / n``, scaled so that the ``n`` arrivals span exactly
``n / rate`` seconds, and the seed only permutes the gaps. So runs with
different seeds offer the same graphs at the same mean rate with the same
spread of gaps, and differ in which request meets which queue.
"""
from __future__ import annotations

import numpy as np


def rng(seed: int, stream: int) -> np.random.Generator:
    """An independent generator for each use of one run's seed."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


def _split(total: int, weights) -> list[int]:
    """``total`` split in proportion to ``weights``, largest remainders
    first and the earlier entry among equal remainders."""
    w = np.asarray(weights, np.float64)
    exact = total * w / w.sum()
    out = np.floor(exact).astype(np.int64)
    order = sorted(range(len(w)), key=lambda i: (-(exact[i] - out[i]), i))
    for i in order[:total - int(out.sum())]:
        out[i] += 1
    return [int(x) for x in out]


def request_sequence(families: dict, n: int, seed: int) -> list:
    """``n`` graph specs drawn from ``families`` (``{name: {"share": s,
    "graphs": [spec, ...]}}``), in the seed's order."""
    names = list(families)
    specs = []
    for name, k in zip(names, _split(n, [families[f]["share"]
                                         for f in names])):
        members = families[name]["graphs"]
        for spec, j in zip(members, _split(k, [1] * len(members))):
            specs.extend([spec] * j)
    order = rng(seed, 1).permutation(len(specs))
    return [specs[i] for i in order]


def arrivals(n: int, rate: float, seed: int) -> np.ndarray:
    """Offsets in seconds of ``n`` open-loop arrivals at ``rate`` per
    second: 0 for the first, ``n / rate`` minus one gap for the last."""
    if n <= 0:
        return np.zeros(0)
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps *= (n / rate) / gaps.sum()
    gaps = gaps[rng(seed, 2).permutation(n)]
    return np.concatenate([[0.0], np.cumsum(gaps[:-1])])


def percentile(values, q: float) -> float:
    """The ``q``-th percentile by linear interpolation, over every value."""
    if len(values) == 0:
        raise ValueError("percentile of no values")
    return float(np.percentile(np.asarray(values, np.float64), q))
