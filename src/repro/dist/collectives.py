"""Compressed cross-replica gradient reduction (error-feedback int8).

At multi-pod scale the gradient all-reduce crosses the slow inter-pod links,
so we ship int8 + one fp32 scale per leaf (4×+ compression) and keep the
quantization residual *locally* as error feedback (Seide et al. '14 /
Karimireddy et al. '19): the residual is added back into the next step's
gradient, so the compression error telescopes instead of accumulating.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_QMAX = 127.0  # symmetric int8


def ef_quantize(x: jnp.ndarray, err: jnp.ndarray, scale: jnp.ndarray | None = None):
    """Error-feedback int8 quantization of one leaf.

    Returns ``(q, scale, new_err)`` with ``x + err == q * scale + new_err``
    and ``|new_err| ≤ scale / 2`` (round-to-nearest). Pass ``scale`` to
    quantize against an externally agreed (e.g. cross-replica) scale.
    """
    target = x + err
    if scale is None:
        scale = jnp.max(jnp.abs(target)) / _QMAX
    safe = jnp.maximum(scale, 1e-12)
    q = jnp.clip(jnp.round(target / safe), -_QMAX, _QMAX).astype(jnp.int8)
    recon = q.astype(jnp.float32) * scale
    return q, scale, target - recon


def ef_psum_tree(grads, errs, axis: str):
    """Compressed mean over mesh axis ``axis`` inside shard_map.

    The replicas first agree on a shared scale per leaf (one scalar pmax),
    each quantizes its local leaf against it with error feedback, and the
    *integer* payload is reduced — int8 on the wire, int32 accumulation
    (n·127 can't overflow), ONE dequantize at the end. Returns
    ``(mean_tree, new_err_tree)``.
    """
    n = jax.lax.axis_size(axis)

    def one(g, e):
        local_scale = jnp.max(jnp.abs(g + e)) / _QMAX
        scale = jax.lax.pmax(local_scale, axis)       # shared wire scale
        q, _, new_e = ef_quantize(g, e, scale=scale)
        total = jax.lax.psum(q.astype(jnp.int32), axis).astype(
            jnp.float32) * scale
        return total / n, new_e

    flat_g, treedef = jax.tree_util.tree_flatten(grads)
    flat_e = treedef.flatten_up_to(errs)
    out = [one(g, e) for g, e in zip(flat_g, flat_e)]
    mean = jax.tree_util.tree_unflatten(treedef, [o[0] for o in out])
    new_err = jax.tree_util.tree_unflatten(treedef, [o[1] for o in out])
    return mean, new_err
