"""The plain reference: every chordless cycle of a graph, one level at a time.

This is the sequential algorithm of Dias et al. that Jradi et al. (arXiv
1410.4876, Algorithm 1) parallelise, written with NumPy and nothing of
the system under test:

* vertices get the degree labeling (repeatedly remove a vertex of least
  remaining degree, the smallest id first among equals);
* every path ``<x, u, y>`` with ``l(u) < l(x) < l(y)`` is an initial
  triplet, or a triangle where ``x`` and ``y`` are adjacent;
* a path ``<v1, v2, ..., vt>`` extends by each neighbour ``v`` of ``vt``
  with ``l(v) > l(v2)`` that is adjacent to no inner vertex
  ``v2 .. v(t-1)``; the extension closes a cycle where ``v`` is adjacent
  to ``v1`` and is a longer path otherwise.

Each chordless cycle is found exactly once. The levels are the paths that
enter each round; their sum is the number of paths the algorithm extends,
a property of the graph and its numbering that the roofline metric uses.

``frontier_cap`` keeps at most that many paths of each level and drops
the rest: it is the benchmark's control, a frontier buffer that silently
overflows, and no correct run has it.
"""
from __future__ import annotations

import dataclasses

import numpy as np

WORD = 64


@dataclasses.dataclass
class Enumeration:
    count: int                  # chordless cycles, triangles included
    levels: list[int]           # paths entering each round (0: triplets)
    cycles: np.ndarray | None   # (count, words) uint64 vertex sets, sorted

    @property
    def paths_extended(self) -> int:
        return int(sum(self.levels))


def control_cap(e: Enumeration) -> int:
    """The control's frontier: the largest power of two below the widest
    level of a correct run, so that the widest level overflows."""
    peak = max(e.levels, default=0)
    return (1 << (peak - 1).bit_length()) // 2 if peak else 0


def _clean(n: int, edges) -> np.ndarray:
    e = np.asarray(list(edges), dtype=np.int64).reshape(-1, 2)
    e = e[e[:, 0] != e[:, 1]]
    if not e.size:
        return e
    if e.min() < 0 or e.max() >= n:
        raise ValueError(f"edge endpoint outside 0..{n - 1}")
    return np.unique(np.sort(e, axis=1), axis=0)


def degree_labels(n: int, edges: np.ndarray) -> np.ndarray:
    """Label i goes to the i-th vertex removed, least remaining degree
    first and the smallest id among equals."""
    adj = [set() for _ in range(n)]
    for a, b in edges:
        adj[int(a)].add(int(b))
        adj[int(b)].add(int(a))
    deg = [len(s) for s in adj]
    alive = [True] * n
    labels = np.zeros(n, np.int64)
    for i in range(n):
        u = min((v for v in range(n) if alive[v]), key=lambda v: (deg[v], v))
        labels[u] = i
        alive[u] = False
        for w in adj[u]:
            if alive[w]:
                deg[w] -= 1
    return labels


def canonical(masks: np.ndarray) -> np.ndarray:
    """Rows of vertex-set words in lexicographic order."""
    masks = np.asarray(masks, np.uint64)
    if len(masks) == 0:
        return masks.reshape(0, masks.shape[-1] if masks.ndim == 2 else 1)
    order = np.lexsort(masks.T[::-1])
    return masks[order]


def words32_to_64(masks: np.ndarray, n: int) -> np.ndarray:
    """(k, ceil(n/32)) uint32 vertex sets, bit j of word w for vertex
    32w + j, as the (k, ceil(n/64)) uint64 words of this module."""
    m32 = np.asarray(masks, np.uint32)
    k = m32.shape[0]
    w64 = -(-n // WORD)
    padded = np.zeros((k, 2 * w64), np.uint64)
    padded[:, :m32.shape[1]] = m32
    return padded[:, 0::2] | (padded[:, 1::2] << np.uint64(32))


def enumerate_cycles(n: int, edges, *, store: bool = False,
                     frontier_cap: int | None = None) -> Enumeration:
    e = _clean(n, edges)
    labels = degree_labels(n, e)
    nbrs = [[] for _ in range(n)]
    for a, b in e:
        nbrs[int(a)].append(int(b))
        nbrs[int(b)].append(int(a))
    delta = max((len(x) for x in nbrs), default=0)
    words = max(1, -(-n // WORD))
    onehot = np.zeros((n, words), np.uint64)
    for v in range(n):
        onehot[v, v // WORD] = np.uint64(1) << np.uint64(v % WORD)
    adjm = np.zeros((n, words), np.uint64)
    for a, b in e:
        adjm[a] |= onehot[b]
        adjm[b] |= onehot[a]
    table = np.full((n, max(delta, 1)), -1, np.int64)
    for v in range(n):
        table[v, :len(nbrs[v])] = sorted(nbrs[v])

    def adjacent(a: np.ndarray, b: np.ndarray) -> np.ndarray:
        w = adjm[a, b // WORD]
        return ((w >> (b % WORD).astype(np.uint64)) & np.uint64(1)) == 1

    count = 0
    found: list[np.ndarray] = []
    v1, v2, vt = [], [], []
    for u in range(n):
        for x in nbrs[u]:
            for y in nbrs[u]:
                if labels[u] < labels[x] < labels[y]:
                    if adjm[x, y // WORD] >> np.uint64(y % WORD) & np.uint64(1):
                        count += 1
                        if store:
                            found.append((onehot[x] | onehot[u]
                                          | onehot[y])[None])
                    else:
                        v1.append(x)
                        v2.append(u)
                        vt.append(y)
    v1, v2, vt = (np.asarray(a, np.int64) for a in (v1, v2, vt))
    inner = onehot[v2] if len(v2) else np.zeros((0, words), np.uint64)

    levels = []
    while len(v1):
        if frontier_cap is not None and len(v1) > frontier_cap:
            v1, v2, vt, inner = (a[:frontier_cap] for a in (v1, v2, vt, inner))
        levels.append(len(v1))
        grown = []
        for j in range(delta):
            v = table[vt, j]
            ok = v >= 0
            v = np.where(ok, v, 0)
            ok &= labels[v] > labels[v2]
            ok &= ~(adjm[v] & inner).any(axis=1)
            closes = ok & adjacent(v1, v)
            count += int(closes.sum())
            if store and closes.any():
                c = closes
                found.append(inner[c] | onehot[v1[c]] | onehot[vt[c]]
                             | onehot[v[c]])
            g = ok & ~closes
            grown.append((v1[g], v2[g], v[g], inner[g] | onehot[vt[g]]))
        v1, v2, vt, inner = (np.concatenate(parts) for parts in zip(*grown))

    cycles = None
    if store:
        cycles = canonical(np.concatenate(found) if found
                           else np.zeros((0, words), np.uint64))
    return Enumeration(count=count, levels=levels, cycles=cycles)
