"""Jaxpr dispatch accounting for the fused round (DESIGN.md §6.8).

The fused-round claim — "the whole guarded round is ONE kernel dispatch,
with no XLA cumsum/scatter/sort passes over the frontier" — is a property
of the traced program, so it is asserted on the jaxpr rather than timed:
count primitives OUTSIDE pallas kernels (descending into every sub-jaxpr —
cond branches, while bodies, custom_vmap calls — but never into a
``pallas_call``'s own body, whose internal cumsums run in VMEM and are
exactly the point).
"""
from __future__ import annotations

import jax

try:  # jax >= 0.4.x keeps these importable from jax.core
    from jax.core import ClosedJaxpr, Jaxpr
except ImportError:  # pragma: no cover - newer layouts
    from jax.extend.core import ClosedJaxpr, Jaxpr  # type: ignore

# the frontier-pass primitives the fused round must NOT issue outside the
# kernel (substring-matched: scatter, scatter-add, cumsum, sort, ...)
COMPACTION_PRIMS = ("scatter", "cumsum", "sort")


def _sub_jaxprs(v):
    if isinstance(v, (Jaxpr, ClosedJaxpr)):
        yield v if isinstance(v, Jaxpr) else v.jaxpr
    elif isinstance(v, (list, tuple)):
        for x in v:
            yield from _sub_jaxprs(x)


def primitive_counts(closed_jaxpr) -> dict:
    """Histogram of primitive names reachable from ``closed_jaxpr``,
    EXCLUDING everything inside pallas_call kernel bodies. A platform-
    dependent ``cond`` (``kernels.ops`` picks the compiled kernel on a TPU
    and the interpreted one elsewhere) contributes its first branch only:
    exactly one of its branches is ever lowered."""
    counts: dict[str, int] = {}

    def walk(jaxpr, inside_kernel):
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if not inside_kernel:
                counts[name] = counts.get(name, 0) + 1
            inner = inside_kernel or name == "pallas_call"
            params = eqn.params
            if name == "cond" and params.get("branches_platforms"):
                params = dict(branches=params["branches"][:1])
            for v in params.values():
                for sub in _sub_jaxprs(v):
                    walk(sub, inner)

    walk(closed_jaxpr.jaxpr, False)
    return counts


def compaction_prims_outside_kernel(counts: dict) -> dict:
    """The subset of ``counts`` that are frontier-compaction passes the
    fused round promises not to issue (empty dict == promise kept)."""
    return {k: v for k, v in counts.items()
            if any(tag in k for tag in COMPACTION_PRIMS)}


def assert_fused_round_program(fn, *args):
    """Trace ``fn(*args)`` and assert the fused-round dispatch contract:
    exactly ONE pallas_call, zero scatter/cumsum/sort outside it. Returns
    the primitive histogram for reporting."""
    return assert_superstep_dispatches(fn, *args, budget=1,
                                       rounds_per_launch=1)


def assert_superstep_dispatches(fn, *args, budget: int,
                                rounds_per_launch: int = 1):
    """Trace ``fn(*args)`` and assert the persistent-superstep dispatch
    contract (DESIGN.md §6.11): a ``budget``-round superstep traced with
    ``rounds_per_launch`` R must contain exactly ⌈budget/R⌉ pallas_calls —
    one persistent launch per R rounds — and zero scatter/cumsum/sort
    frontier passes outside the kernels. R=1 reproduces the PR-6 fused
    contract (one dispatch per round).

    ``fn`` must be an UNROLLED superstep (each launch traced inline): a
    ``lax.while_loop`` body traces its pallas_call once regardless of trip
    count, so the runtime contract is asserted on the unrolled composition
    instead. Returns the primitive histogram for reporting.
    """
    rpl = max(int(rounds_per_launch), 1)
    expect = -(-max(int(budget), 1) // rpl)
    counts = primitive_counts(jax.make_jaxpr(fn)(*args))
    n_kernels = counts.get("pallas_call", 0)
    assert n_kernels == expect, (
        f"a {budget}-round superstep at rounds_per_launch={rpl} must be "
        f"⌈{budget}/{rpl}⌉ = {expect} pallas dispatches, traced "
        f"{n_kernels}; primitives: {counts}")
    leaked = compaction_prims_outside_kernel(counts)
    assert not leaked, (
        f"superstep leaked compaction passes outside the kernel "
        f"(offending primitives): {leaked}")
    return counts
