"""Traffic generators + latency summaries for sustained-serving scenarios.

Sustained-serving scenarios (DESIGN.md §6.9) need two ingredients the wave
engine's one-shot runs never model:

* an OPEN-LOOP arrival process — requests arrive on their own clock
  (Poisson at a fixed QPS), not when the previous one finishes, so queue
  wait is a real, measurable quantity;
* an imbalanced-LIFETIME queue — same shape class (so everything coalesces
  into one pool), wildly different wave lifetimes (so the wave-at-a-time
  scheduler drags dead lanes and recycling visibly wins).

``connectors_graph`` is the short-lived half of that queue: triangles hung
on a tree of bridge vertices. Every cycle is a triangle, so the wave dies
after ~2 expansion rounds, yet its (n, m, Δ) lands in the SAME pow2 shape
class as a 4×4 grid — whose wave runs the full |V|−3 = 13 rounds.
"""
from __future__ import annotations

import numpy as np


def poisson_arrivals(n: int, qps: float, seed: int = 0) -> list[float]:
    """Arrival offsets (seconds) of ``n`` requests from a Poisson process
    at rate ``qps`` (exponential inter-arrivals), starting at t=0."""
    rng = np.random.default_rng(seed)
    gaps = rng.exponential(1.0 / max(qps, 1e-9), size=max(n - 1, 0))
    return [0.0] + list(np.cumsum(gaps))


def percentiles(xs, *, points=(50, 99)) -> dict:
    """{'p50': ..., 'p99': ...} over a latency sample (ms); zeros when
    empty so stats dicts stay shape-stable."""
    if not xs:
        return {f"p{p}": 0.0 for p in points}
    arr = np.asarray(xs, np.float64)
    return {f"p{p}": round(float(np.percentile(arr, p)), 3) for p in points}


# ---------------------------------------------------------------------------
# The imbalanced-lifetime queue (class-matched long + short requests)
# ---------------------------------------------------------------------------

def connectors_graph(n_tris: int = 4):
    """(n, edges) of a short-lived wave: ``n_tris`` disjoint triangles whose
    corners hang off bridge vertices forming a TREE over the triangles —
    the bridges close no extra cycles, so the only chordless cycles are the
    triangles themselves and the wave dies in ~2 rounds.

    The default (4 triangles: n=15, m=18, Δ=3 → pow2 class n16-m32-d4) is
    the class partner of Grid_4x4 (n=16, m=24, Δ=4); ``n_tris=8``
    (n=31, m=38, Δ=3 → n32-m64-d4) partners Grid_5x6 (n=30, m=49, Δ=4).
    """
    edges = []
    for t in range(n_tris):
        a = 3 * t
        edges += [(a, a + 1), (a + 1, a + 2), (a, a + 2)]
    # bridge vertex t links triangle t to triangle t+1 (a path over the
    # triangles — a tree, so no new cycles); distinct corners keep Δ=3
    for t in range(n_tris - 1):
        b = 3 * n_tris + t
        edges += [(b, 3 * t + 1), (b, 3 * (t + 1))]
    n = 3 * n_tris + max(n_tris - 1, 0)
    return n, edges


def imbalanced_queue(n_long: int = 4, shorts_per_long: int = 3,
                     scale: str = "small"):
    """Class-matched queue of long-lived grids and short-lived connector
    graphs, interleaved L,S,S,S,… — the lane-lifetime imbalance the
    recycling A/B measures. All requests share ONE shape class, so the
    wave-at-a-time scheduler coalesces them into full batches (its best
    case) and still loses to recycling on the dead-lane rounds.

    ``scale='small'``: Grid_4x4 longs (13-round waves, class n16-m32-d4) —
    the test-suite size. ``scale='large'``: Grid_5x6 longs (27-round waves,
    class n32-m64-d4, frontier peaks in the hundreds) — the benchmark size,
    where per-round device work dominates dispatch overhead."""
    from ..core import build_graph
    from ..core.graphs import grid_graph

    if scale == "large":
        long_g = build_graph(*grid_graph(5, 6))
        short_g = build_graph(*connectors_graph(8))
    else:
        long_g = build_graph(*grid_graph(4, 4))
        short_g = build_graph(*connectors_graph())
    queue = []
    for _ in range(n_long):
        queue.append(long_g)
        queue.extend([short_g] * shorts_per_long)
    return queue
