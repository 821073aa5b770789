"""Output-slot → source-row mapping of the gather compaction (DESIGN.md §6.8).

``core.expand._source_rows`` merges the sorted output slots with the sorted
inclusive prefix of per-row survivor counts (a histogram and two prefix
scans). It must equal the binary-search definition in every slot, valid or
not — checked here against NumPy's ``searchsorted`` — and the compaction
programs built on it must carry no loop: ``jnp.searchsorted`` is a
``fori_loop`` (``scan`` in the jaxpr), which lowers to a ``while`` that runs
one dependent gather per step.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.analysis.dispatch import primitive_counts
from repro.core import build_graph
from repro.core import expand as E
from repro.core.frontier import empty_cycle_buffer
from repro.core.graphs import grid_graph
from repro.core.triplets import initial_frontier

CAP, OUT_CAP, LANES = 64, 128, 4

_source_rows = jax.jit(E._source_rows, static_argnums=1)


def _reference(counts: np.ndarray, out_cap: int):
    """(src, k, valid, total) by binary search over the inclusive prefix."""
    counts = counts.astype(np.int64)
    cap = counts.shape[0]
    incl = np.cumsum(counts)
    total = int(incl[-1])
    o = np.arange(out_cap)
    src = np.minimum(np.searchsorted(incl, o, side="right"), cap - 1)
    valid = o < min(total, out_cap)
    k = np.where(valid, o - (incl[src] - counts[src]), 0)
    return src, k, valid, total


def _assert_matches(got, counts, out_cap):
    src, k, valid, total = (np.asarray(a) for a in got)
    r_src, r_k, r_valid, r_total = _reference(counts, out_cap)
    assert int(total) == r_total
    assert np.array_equal(valid, r_valid)
    assert np.array_equal(src, r_src)
    assert np.array_equal(k, r_k)


def _counts(case: str, seed: int = 0) -> np.ndarray:
    rng = np.random.default_rng(seed)
    if case == "random_sparse":        # many empty rows, total < out_cap
        c = rng.integers(1, 4, CAP) * (rng.random(CAP) < 0.3)
    elif case == "random_dense":       # few empty rows, may overflow
        c = rng.integers(0, 5, CAP) * (rng.random(CAP) < 0.8)
    elif case == "all_zero":
        c = np.zeros(CAP)
    elif case == "total_eq_out_cap":   # every slot valid, none dropped
        c = np.full(CAP, OUT_CAP // CAP)
    elif case == "overflow":           # total > out_cap: the tail is cut
        c = np.full(CAP, 3)
    elif case == "zeros_at_both_ends":
        c = np.zeros(CAP)
        c[5:40] = rng.integers(0, 3, 35)
    elif case == "one_live_row_last":
        c = np.zeros(CAP)
        c[-1] = 7
    else:
        raise ValueError(case)
    return c.astype(np.int32)


@pytest.mark.parametrize("case,seed", [
    ("random_sparse", 0), ("random_sparse", 1), ("random_sparse", 2),
    ("random_dense", 0), ("random_dense", 1),
    ("all_zero", 0), ("total_eq_out_cap", 0), ("overflow", 0),
    ("zeros_at_both_ends", 3), ("one_live_row_last", 0),
])
def test_source_rows_matches_binary_search(case, seed):
    c = _counts(case, seed)
    _assert_matches(_source_rows(jnp.asarray(c), OUT_CAP), c, OUT_CAP)


@pytest.mark.parametrize("n", [0, 1, OUT_CAP - 1, OUT_CAP, OUT_CAP + 5])
def test_source_rows_one_row(n):
    c = np.array([n], np.int32)
    _assert_matches(_source_rows(jnp.asarray(c), OUT_CAP), c, OUT_CAP)


def test_source_rows_vmapped_lanes():
    cases = ("random_sparse", "all_zero", "overflow", "total_eq_out_cap")
    c = np.stack([_counts(case, i) for i, case in enumerate(cases)])
    assert c.shape == (LANES, CAP)
    got = jax.jit(jax.vmap(lambda x: E._source_rows(x, OUT_CAP)))(
        jnp.asarray(c))
    for lane in range(LANES):
        _assert_matches([a[lane] for a in got], c[lane], OUT_CAP)


# ---------------------------------------------------------------------------
# Structural guard: no binary search in the split-path compaction programs
# ---------------------------------------------------------------------------

SPLIT_BUCKET = 32_768     # past the fused kernels' VMEM budget at nw=2


@pytest.fixture(scope="module")
def split_operands():
    n, edges = grid_graph(6, 10)
    g = build_graph(n, edges)
    f, _, _ = initial_frontier(g, bucket=lambda c: SPLIT_BUCKET)
    buf = empty_cycle_buffer(256, g.adj_bits.shape[1])
    for formulation in ("slot", "bitword"):
        assert not E.fused_kernel_fits(formulation, g, f, buf, store=False,
                                       persistent=False)
    return g, f


@pytest.mark.parametrize("formulation", ["bitword", "slot"])
def test_split_compaction_has_no_while_loop(split_operands, formulation):
    g, f = split_operands
    _, ext_w = E.expand_words_bitword(g, f)
    if formulation == "bitword":
        def body(g, f, ext_w):
            return E.bitword_compact_gather(g, f, ext_w, SPLIT_BUCKET)
        args = (g, f, ext_w)
    else:
        def body(g, f, cand_v):
            return E.compact_extensions_gather(g, f, cand_v, cand_v >= 0,
                                               SPLIT_BUCKET)
        args = (g, f, E.bitword_to_slots(ext_w, int(g.max_degree)))
    counts = primitive_counts(jax.make_jaxpr(body)(*args))
    assert "scatter-add" in counts          # the histogram merge is there
    # a binary search is a fori_loop: `scan` in the jaxpr, `while` once lowered
    assert not {"while", "scan"} & set(counts), counts
    assert "stablehlo.while" not in jax.jit(body).lower(*args).as_text()


# ---------------------------------------------------------------------------
# The Pallas ops' split round, just past the fused kernels' VMEM budget
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def grown_frontier(split_operands):
    """Grid_6x10's frontier after nine rounds (7,951 live rows) at
    SPLIT_BUCKET, the first bucket above the 16,384 rows the fused kernels
    hold at nw = 2. Three more rounds reach 13,130, 20,693, then 33,692
    rows, which the bucket cannot hold."""
    g, f = split_operands
    op = E.expand_op("bitword", "jnp")
    step = jax.jit(lambda f, buf: E.expand_count_compact(
        g, f, buf, delta=int(g.max_degree), store=False, op=op))
    buf = empty_cycle_buffer(1, g.adj_bits.shape[1])
    for _ in range(9):
        f, buf, *_ = step(f, buf)
    assert int(f.count) == 7951
    return g, f


@pytest.mark.parametrize("store", [True, False])
@pytest.mark.parametrize("formulation", ["bitword", "slot"])
def test_pallas_split_round_matches_jnp_past_vmem_budget(grown_frontier,
                                                         formulation, store):
    """The split round of the Pallas op (its flag kernel, then the gather
    compaction and cycle append) == the jnp op's split round, leaf for leaf,
    through two applied rounds and the guard trip of the third."""
    g, f0 = grown_frontier
    delta = int(g.max_degree)
    buf0 = empty_cycle_buffer(1 << 14, g.adj_bits.shape[1])
    state = {}
    for backend in ("pallas", "jnp"):
        op = E.expand_op(formulation, backend)
        with E.record_round_paths() as paths:
            step = jax.jit(lambda f, buf: E.expand_count_compact(
                g, f, buf, delta=delta, store=store, op=op))
            f, buf, rounds = f0, buf0, []
            for _ in range(3):
                f, buf, n_cyc, n_new, ok_f, ok_c = step(f, buf)
                rounds.append((int(n_cyc), int(n_new), bool(ok_f),
                               bool(ok_c)))
        assert paths == ["split"], (backend, paths)
        state[backend] = (f, buf, rounds)
    (fp, bp, rp), (fj, bj, rj) = state["pallas"], state["jnp"]
    assert rp == rj
    assert [r[1] for r in rj] == [13130, 20693, 33692]
    assert [r[2] for r in rj] == [True, True, False]
    for name in ("path", "blocked", "v1", "l2", "vlast", "count"):
        assert np.array_equal(np.asarray(getattr(fp, name)),
                              np.asarray(getattr(fj, name))), name
    assert int(fj.count) == 20693
    assert int(bp.count) == int(bj.count)
    assert np.array_equal(np.asarray(bp.masks), np.asarray(bj.masks))
