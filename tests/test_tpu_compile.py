"""The main-path Pallas kernels compile for a TPU v5e.

Every test compiles for a described (not attached) ``v5e:2x2`` topology,
at Grid_6x10 widths (n=60, nw=2, Δ=4), and asserts that the program holds
a Mosaic kernel (``tpu_custom_call``) — so nothing fell back to the
interpreter. Interpret-mode tests cannot see what the chip's compiler
refuses (vector gathers, unaligned blocks, VMEM overflow); these can.

The topology is described inside a module fixture and never at import:
only one process may load the TPU library, and pytest-xdist workers all
import this file. This file is the only one that describes the chip.
"""
import functools

import pytest
import jax
import jax.numpy as jnp

from repro.core import expand as E
from repro.core import build_graph
from repro.core.frontier import empty_cycle_buffer, empty_frontier
from repro.core.graphs import grid_graph
from repro.kernels.bitword_expand import bitword_expand_lanes
from repro.kernels.frontier_expand import frontier_expand_lanes
from repro.kernels.fused_round import (fits_vmem, fused_round_lanes,
                                       persistent_round_lanes)
from repro.kernels.triplet_init import triplet_init_lanes

N, NW, DELTA, NBR = 60, 2, 4, 220      # Grid_6x10: n, words, Δ, 2m
CAP, CYC = 1024, 4096                  # frontier bucket, cycle ring rows


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a described chip's compiles are written to the persistent cache but
    # can never be read back without the chip: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def spec(topo):
    """``spec(shape, dtype)`` → a shape placed on one described chip."""
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype=jnp.int32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)


def _frontier(spec, B, cap=CAP, nw=NW):
    u = jnp.uint32
    return (spec((B, cap, nw), u), spec((B, cap, nw), u), spec((B, cap)),
            spec((B, cap)), spec((B, cap)), spec((B,)))


def _bitword_tables(spec, B, nw=NW):
    return (spec((B, N, nw), jnp.uint32), spec((B, N, nw), jnp.uint32))


def _slot_tables(spec, B):
    return (spec((B, N + 1)), spec((B, NBR)), spec((B, N)),
            spec((B, N, NW), jnp.uint32))


def _compile_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def _fused(spec, B, *, store, cap=CAP, formulation="bitword"):
    tabs = (_bitword_tables(spec, B) if formulation == "bitword"
            else _slot_tables(spec, B))
    fn = lambda *a: fused_round_lanes(
        *a[:8], a[8:], formulation=formulation, delta=DELTA, store=store,
        interpret=False)
    return _compile_text(fn, *_frontier(spec, B, cap),
                         spec((B, CYC, NW), jnp.uint32), spec((B,)), *tabs)


def _persistent(spec, B, *, store, cap=CAP, formulation="bitword"):
    tabs = (_bitword_tables(spec, B) if formulation == "bitword"
            else _slot_tables(spec, B))
    fn = lambda *a: persistent_round_lanes(
        *a[:9], a[9:], formulation=formulation, delta=DELTA, store=store,
        rounds=4, interpret=False)
    return _compile_text(fn, *_frontier(spec, B, cap),
                         spec((B, CYC, NW), jnp.uint32), spec((B,)),
                         spec((B,)), *tabs)


@pytest.mark.parametrize("B", [1, 4])
def test_bitword_expand_compiles(spec, B):
    fn = functools.partial(bitword_expand_lanes, interpret=False)
    text = _compile_text(fn, *_frontier(spec, B), *_bitword_tables(spec, B))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("B", [1, 4])
def test_bitword_expand_compiles_at_paper_scale_bucket(spec, B):
    """The split path's flag kernel sees the largest buckets: its planar
    operands stay lane-dense at 2^22 rows."""
    fn = functools.partial(bitword_expand_lanes, interpret=False)
    text = _compile_text(fn, *_frontier(spec, B, cap=1 << 22),
                         *_bitword_tables(spec, B))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("B", [1, 4])
def test_triplet_init_compiles(spec, B):
    fn = functools.partial(triplet_init_lanes, delta=DELTA, interpret=False)
    text = _compile_text(fn, spec((B, N + 1)), spec((B, NBR)),
                         spec((B, N)), spec((B, N, NW), jnp.uint32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("store", [False, True])
@pytest.mark.parametrize("B", [1, 4])
def test_fused_round_compiles(spec, B, store):
    assert "tpu_custom_call" in _fused(spec, B, store=store)


@pytest.mark.parametrize("store", [False, True])
@pytest.mark.parametrize("B", [1, 4])
def test_persistent_round_compiles(spec, B, store):
    assert "tpu_custom_call" in _persistent(spec, B, store=store)


@pytest.mark.parametrize("B", [1, 4])
def test_slot_flag_kernel_compiles(spec, B):
    fn = functools.partial(frontier_expand_lanes, delta=DELTA,
                           interpret=False)
    text = _compile_text(fn, *_frontier(spec, B), *_slot_tables(spec, B))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("persistent", [False, True])
def test_slot_fused_kernels_compile(spec, persistent):
    build = _persistent if persistent else _fused
    assert "tpu_custom_call" in build(spec, 4, store=True,
                                      formulation="slot")


def _largest_admitted(*, store: bool, persistent: bool) -> int:
    caps = [1 << k for k in range(4, 24)]
    fits = [c for c in caps if fits_vmem(
        cap=c, nw=NW, n=N, n_neighbors=NBR, cyc_cap=CYC if store else 1,
        formulation="bitword", store=store, persistent=persistent)]
    assert fits and fits[-1] < caps[-1]
    return fits[-1]


@pytest.mark.parametrize("store", [False, True])
@pytest.mark.parametrize("persistent", [False, True])
def test_largest_admitted_bucket_compiles(spec, persistent, store):
    """The bucket rule's largest admitted bucket (nw=2) compiles, at B=4
    where every block is double buffered."""
    cap = _largest_admitted(store=store, persistent=persistent)
    build = _persistent if persistent else _fused
    assert "tpu_custom_call" in build(spec, 4, store=store, cap=cap)


def _round_kernels(cap: int, *, persistent: bool) -> list[str]:
    """Names of the pallas kernels one traced round (or launch) of a
    Grid_6x10 count-only bitword/pallas superstep issues at ``cap``."""
    from repro.analysis.dispatch import _sub_jaxprs
    n, edges = grid_graph(6, 10)
    g = build_graph(n, edges)
    f, buf = empty_frontier(cap, NW), empty_cycle_buffer(1, NW)
    if persistent:
        fn = lambda g, f, buf: E.expand_count_compact_multi(
            g, f, buf, delta=DELTA, store=False, rounds=4,
            formulation="bitword", backend="pallas")
    else:
        fn = lambda g, f, buf: E.expand_count_compact(
            g, f, buf, delta=DELTA, store=False, formulation="bitword",
            backend="pallas")
    shapes = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), (g, f, buf))
    names = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                names.append(eqn.params["name"])
                continue
            for v in eqn.params.values():
                for sub in _sub_jaxprs(v):
                    walk(sub)

    walk(jax.make_jaxpr(fn)(*shapes).jaxpr)
    return names


@pytest.mark.parametrize("persistent", [False, True])
def test_next_bucket_routes_to_split_path(persistent):
    """By shapes alone (nothing compiles): the largest admitted bucket
    traces its fused kernel, the next bucket up does not. Past the
    persistent kernel's budget a launch loops over single-round fused
    kernels; past the single-round budget a round takes the split path's
    flag kernel."""
    cap = _largest_admitted(store=False, persistent=persistent)
    fused_name = "persistent_round" if persistent else "fused_round"
    inside = _round_kernels(cap, persistent=persistent)
    past = _round_kernels(2 * cap, persistent=persistent)
    assert inside and set(inside) == {fused_name}, inside
    assert past and fused_name not in past, past
    if not persistent:
        assert set(past) == {"bitword_expand"}, past
