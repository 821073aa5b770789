"""Graphs of the paper's Table 1 families, built from their names.

The benchmark keeps its own generators so that what it measures does not
move when the system under test changes its own. A graph is named as in
Jradi et al., Table 1: ``Grid_<rows>x<cols>``, ``K_<a>_<b>`` (complete
bipartite), ``C_<n>`` (cycle) and ``Wheel_<rim>`` (a rim cycle plus one
hub). A traffic or configuration file may also give a graph as
``{"n": <vertices>, "edges": [[u, v], ...]}``. Vertices are numbered
row-major for grids and as the paper's generators number them otherwise;
the numbering decides how much work the enumeration does, so it is part
of the graph.
"""
from __future__ import annotations

import re


def grid(rows: int, cols: int):
    edges = []
    for r in range(rows):
        for c in range(cols):
            v = r * cols + c
            if c + 1 < cols:
                edges.append((v, v + 1))
            if r + 1 < rows:
                edges.append((v, v + cols))
    return rows * cols, edges


def complete_bipartite(a: int, b: int):
    return a + b, [(i, a + j) for i in range(a) for j in range(b)]


def cycle(n: int):
    return n, [(i, (i + 1) % n) for i in range(n)]


def wheel(rim: int):
    edges = [(i, (i + 1) % rim) for i in range(rim)]
    edges += [(rim, i) for i in range(rim)]
    return rim + 1, edges


_FAMILIES = (
    (re.compile(r"Grid_(\d+)x(\d+)"), grid),
    (re.compile(r"K_(\d+)_(\d+)"), complete_bipartite),
    (re.compile(r"C_(\d+)"), cycle),
    (re.compile(r"Wheel_(\d+)"), wheel),
)


def from_spec(spec) -> tuple[int, list[tuple[int, int]]]:
    """``(n, edges)`` of a graph given by name or as an explicit edge list."""
    if isinstance(spec, dict):
        return int(spec["n"]), [(int(u), int(v)) for u, v in spec["edges"]]
    for pattern, build in _FAMILIES:
        m = pattern.fullmatch(spec)
        if m:
            return build(*(int(x) for x in m.groups()))
    raise ValueError(f"unknown graph {spec!r}: expected Grid_RxC, K_A_B, "
                     "C_N, Wheel_N or {'n': ..., 'edges': [...]}")

