"""The benchmark's plain reference against the paper's Table 1 and against
Algorithm 1 written out as a depth-first search."""
from __future__ import annotations

import json
import os

import numpy as np
import pytest

from bench import graphs, reference

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def dfs_chordless_cycles(n, edges):
    """Dias et al. / Jradi et al. Algorithm 1 as a stack of vertex tuples:
    (count, sorted vertex sets, paths extended)."""
    e = reference._clean(n, edges)
    labels = reference.degree_labels(n, e)
    adj = [set() for _ in range(n)]
    for a, b in e:
        adj[a].add(int(b))
        adj[b].add(int(a))
    cycles, stack, extended = [], [], 0
    for u in range(n):
        for x in adj[u]:
            for y in adj[u]:
                if labels[u] < labels[x] < labels[y]:
                    (cycles if y in adj[x] else stack).append((x, u, y))
    while stack:
        p = stack.pop()
        extended += 1
        for v in adj[p[-1]]:
            if labels[v] <= labels[p[1]]:
                continue
            if any(v in adj[w] for w in p[1:-1]):
                continue
            (cycles if v in adj[p[0]] else stack).append(p + (v,))
    return len(cycles), sorted(tuple(sorted(c)) for c in cycles), extended


def vertex_sets(masks: np.ndarray) -> list:
    out = []
    for row in masks:
        bits = [w * 64 + b for w, word in enumerate(row)
                for b in range(64) if int(word) >> b & 1]
        out.append(tuple(bits))
    return sorted(out)


@pytest.mark.parametrize("name,count", [
    ("C_100", 1), ("Wheel_100", 101), ("K_8_8", 784), ("Grid_4x10", 1823),
    ("Grid_5x6", 749), ("Grid_6x6", 3436), ("Grid_5x10", 52620)])
def test_table1_counts(name, count):
    r = reference.enumerate_cycles(*graphs.from_spec(name), store=True)
    assert r.count == count == len(r.cycles)
    assert len({row.tobytes() for row in r.cycles}) == count


@pytest.mark.parametrize("traffic", ["grid6x10_count", "grid5x10_store"])
def test_paths_extended_in_traffic_files(traffic):
    """The roofline reads P from the traffic file: the reference re-derives
    it (10,696,912 and 730,015 in row-major numbering)."""
    with open(os.path.join(ROOT, "bench", "traffic", traffic + ".json")) as f:
        t = json.load(f)
    r = reference.enumerate_cycles(*graphs.from_spec(t["graph"]))
    assert r.paths_extended == t["paths_extended"]


@pytest.mark.parametrize("n,p,seed", [(9, 0.4, 1), (11, 0.35, 2),
                                      (12, 0.5, 3), (13, 0.3, 4)])
def test_matches_depth_first_algorithm(n, p, seed):
    rng = np.random.default_rng(seed)
    edges = [(i, j) for i in range(n) for j in range(i + 1, n)
             if rng.random() < p]
    count, sets, extended = dfs_chordless_cycles(n, edges)
    r = reference.enumerate_cycles(n, edges, store=True)
    assert r.count == count
    assert vertex_sets(r.cycles) == sets
    assert r.paths_extended == extended


def test_small_grid_paths_and_levels():
    r = reference.enumerate_cycles(*graphs.grid(4, 4))
    _, _, extended = dfs_chordless_cycles(*graphs.grid(4, 4))
    assert r.paths_extended == extended == 133
    assert r.levels == [9, 12, 22, 24, 22, 14, 14, 7, 9]


def test_degree_labels_break_ties_by_id():
    n, edges = graphs.cycle(5)
    assert list(reference.degree_labels(n, np.asarray(edges))) == \
        [0, 1, 2, 3, 4]
    n, edges = graphs.complete_bipartite(1, 3)
    assert list(reference.degree_labels(n, np.asarray(edges))) == \
        [2, 0, 1, 3]


def test_words32_to_64_keeps_every_vertex():
    n = 70
    rng = np.random.default_rng(0)
    dense = rng.random((5, n)) < 0.5
    m32 = np.zeros((5, 3), np.uint32)
    m64 = np.zeros((5, 2), np.uint64)
    for i, row in enumerate(dense):
        for v in np.flatnonzero(row):
            m32[i, v // 32] |= np.uint32(1 << (v % 32))
            m64[i, v // 64] |= np.uint64(1) << np.uint64(v % 64)
    assert np.array_equal(reference.words32_to_64(m32, n), m64)


@pytest.mark.parametrize("name", ["Grid_4x4", "K_4_4", "C_24", "Wheel_24"])
def test_control_cap_changes_the_answer(name):
    """The control (a frontier that keeps only the largest power of two
    below its peak) loses cycles on every graph the cells serve."""
    n, edges = graphs.from_spec(name)
    r = reference.enumerate_cycles(n, edges)
    capped = reference.enumerate_cycles(
        n, edges, frontier_cap=reference.control_cap(r))
    assert capped.count < r.count


def test_graph_specs():
    assert graphs.from_spec("Grid_2x3") == (6, [(0, 1), (0, 3), (1, 2),
                                                (1, 4), (2, 5), (3, 4),
                                                (4, 5)])
    assert graphs.from_spec({"n": 3, "edges": [[0, 1]]}) == (3, [(0, 1)])
    assert graphs.from_spec("Wheel_4")[0] == 5
    with pytest.raises(ValueError):
        graphs.from_spec("Petersen")
