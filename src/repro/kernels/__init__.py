"""Pallas TPU kernels for the paper's two hot spots (+ TPU-native bitword).

Kernels target TPU (pl.pallas_call + BlockSpec VMEM tiling): they compile
for the chip (tests/test_tpu_compile.py compiles them for a described v5e)
and are validated in the Pallas interpreter on CPU against the pure-jnp
oracles in ref.py.

Every kernel runs on a ``grid=(B, …)`` LANE GRID (DESIGN.md §6.7) — the
single-graph entry points are the B=1 special case, and the ``ops``
wrappers carry ``custom_vmap`` rules mapping ``jax.vmap`` onto the lane
axis so a batched wave superstep is ONE kernel dispatch per round.
"""
from . import ops, ref  # noqa: F401
