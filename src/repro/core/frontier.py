"""Frontier state for chordless-path expansion (paper's T / T' sets).

The paper stores each in-flight chordless path as a bitmap row of matrix S
plus auxiliary vectors V1, V2, VL (first / second / last vertex).  We keep the
same struct-of-arrays layout and add the incremental *blocked* bitset
B_p = ∪_{i=2..t-1} Adj(v_i) (DESIGN.md §2) that turns the paper's O(t·logΔ)
chord re-check into one word probe. We store ℓ(v₂) directly instead of v₂
since only the label is ever used.

Capacity changes (DESIGN.md §6.4) happen only between jit shapes:
``with_capacity`` pads/trims to a new power-of-two bucket at a superstep
boundary. Inside a superstep each round rebuilds the frontier at the same
capacity on device (``core.expand``'s compaction or the fused kernel).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Frontier:
    path: jnp.ndarray     # (cap, nw) uint32 — bitmap of path vertices (S row)
    blocked: jnp.ndarray  # (cap, nw) uint32 — ∪ Adj(internal vertices)
    v1: jnp.ndarray       # (cap,) int32 — first vertex (V1)
    l2: jnp.ndarray       # (cap,) int32 — label of second vertex (ℓ(V2))
    vlast: jnp.ndarray    # (cap,) int32 — last vertex (VL)
    count: jnp.ndarray    # () int32 — rows [0, count) are live

    def tree_flatten(self):
        return (self.path, self.blocked, self.v1, self.l2, self.vlast,
                self.count), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(*children)

    @property
    def capacity(self) -> int:
        return self.path.shape[0]

    @property
    def n_words(self) -> int:
        return self.path.shape[1]


def empty_frontier(capacity: int, n_words: int) -> Frontier:
    return Frontier(
        path=jnp.zeros((capacity, n_words), jnp.uint32),
        blocked=jnp.zeros((capacity, n_words), jnp.uint32),
        v1=jnp.full((capacity,), -1, jnp.int32),
        l2=jnp.zeros((capacity,), jnp.int32),
        vlast=jnp.zeros((capacity,), jnp.int32),
        count=jnp.zeros((), jnp.int32),
    )


def with_capacity(f: Frontier, capacity: int) -> Frontier:
    """Grow/shrink row capacity (host-side bucketing; keeps live rows)."""
    cap0 = f.capacity
    if capacity == cap0:
        return f
    if capacity > cap0:
        pad = capacity - cap0
        return Frontier(
            path=jnp.pad(f.path, ((0, pad), (0, 0))),
            blocked=jnp.pad(f.blocked, ((0, pad), (0, 0))),
            v1=jnp.pad(f.v1, (0, pad), constant_values=-1),
            l2=jnp.pad(f.l2, (0, pad)),
            vlast=jnp.pad(f.vlast, (0, pad)),
            count=f.count,
        )
    return Frontier(
        path=f.path[:capacity], blocked=f.blocked[:capacity],
        v1=f.v1[:capacity], l2=f.l2[:capacity], vlast=f.vlast[:capacity],
        count=jnp.minimum(f.count, capacity).astype(jnp.int32),
    )


def stack_frontiers(fs) -> Frontier:
    """Stack same-capacity frontiers on a new leading batch axis (the
    multi-graph batch path: leaves become (B, cap, nw) / (B, cap) / (B,))."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *fs)


def with_capacity_batched(f: Frontier, capacity: int) -> Frontier:
    """Batched ``with_capacity``: re-bucket every lane of a stacked frontier
    (leaves (B, cap, nw) / (B, cap); count stays (B,))."""
    cap0 = f.path.shape[1]
    if capacity == cap0:
        return f
    if capacity > cap0:
        pad = capacity - cap0
        return Frontier(
            path=jnp.pad(f.path, ((0, 0), (0, pad), (0, 0))),
            blocked=jnp.pad(f.blocked, ((0, 0), (0, pad), (0, 0))),
            v1=jnp.pad(f.v1, ((0, 0), (0, pad)), constant_values=-1),
            l2=jnp.pad(f.l2, ((0, 0), (0, pad))),
            vlast=jnp.pad(f.vlast, ((0, 0), (0, pad))),
            count=f.count,
        )
    return Frontier(
        path=f.path[:, :capacity], blocked=f.blocked[:, :capacity],
        v1=f.v1[:, :capacity], l2=f.l2[:, :capacity],
        vlast=f.vlast[:, :capacity],
        count=jnp.minimum(f.count, capacity).astype(jnp.int32),
    )


# ---------------------------------------------------------------------------
# Cycle ring buffer (the wave engine's device-resident slice of matrix S)
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class CycleBuffer:
    """Preallocated device buffer of discovered cycle bitmaps.

    Rows [0, count) hold cycles not yet drained to the host. The wave
    superstep appends to it each round; the host drains it at superstep
    boundaries only (DESIGN.md §6.4) — that is what turns O(iterations)
    device→host mask copies into O(bucket transitions).
    """
    masks: jnp.ndarray  # (cap, nw) uint32
    count: jnp.ndarray  # () int32

    def tree_flatten(self):
        return (self.masks, self.count), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(*children)

    @property
    def capacity(self) -> int:
        return self.masks.shape[0]

    @property
    def n_words(self) -> int:
        return self.masks.shape[1]


def empty_cycle_buffer(capacity: int, n_words: int,
                       batch: int = 0) -> CycleBuffer:
    """Fresh cycle ring. ``batch=B`` builds the stacked multi-graph variant:
    masks (B, cap, nw), count (B,)."""
    if batch:
        return CycleBuffer(
            masks=jnp.zeros((batch, max(capacity, 1), n_words), jnp.uint32),
            count=jnp.zeros((batch,), jnp.int32),
        )
    return CycleBuffer(
        masks=jnp.zeros((max(capacity, 1), n_words), jnp.uint32),
        count=jnp.zeros((), jnp.int32),
    )
