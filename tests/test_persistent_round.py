"""Persistent multi-round wave kernel (DESIGN.md §6.11).

The acceptance surface of the rounds-per-launch fusion:

* ``expand_count_compact_multi`` — the persistent pallas kernel AND its
  ``fori_loop`` jnp twin — is bit-identical to composing single guarded
  rounds: every frontier leaf, the ring masks, the per-round |T|/|C|
  histories, ``rounds_done``, and both guard flags, including a guard trip
  at r < R inside one launch (the remaining grid rounds must degrade to
  identity copy-through) and a dynamic ``rlimit`` below R;
* end-to-end through ``CycleService``, any R produces bit-identical
  ``cycle_masks`` and |T| histories to R=1, across slot/bitword ×
  jnp/pallas, and mesh-routed enumeration matches on 1/2/4-device meshes;
* the traced superstep obeys the generalized dispatch contract: exactly
  ⌈K/R⌉ ``pallas_call``s for a K-round budget (R=1 reproduces the PR-6
  one-dispatch-per-round contract), zero compaction passes outside them;
* telemetry counts kernel launches as ⌈attempted/R⌉ per dispatch and the
  replay twin reproduces the real driver's launch/sync counts exactly;
* the tuner searches ``rounds_per_launch`` as a knob.
"""
import os
import subprocess
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import (CycleService, EngineConfig, build_graph,
                        sequential_chordless_cycles)
from repro.core import expand as E
from repro.core.frontier import empty_cycle_buffer
from repro.core.graphs import grid_graph, random_gnp
from repro.core.triplets import initial_frontier
from repro.analysis.dispatch import assert_superstep_dispatches

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _graph(r=4, c=4):
    n, edges = grid_graph(r, c)
    return build_graph(n, edges)


def _leaves(f):
    return [("path", f.path), ("blocked", f.blocked), ("v1", f.v1),
            ("l2", f.l2), ("vlast", f.vlast), ("count", f.count)]


def _compose_single(g, f, buf, *, delta, store, rounds, rlimit, op):
    """Reference: ``rounds`` guarded single rounds with the host applying
    the kernel's SMEM rules (guard trip latches, budget cap, death)."""
    ch, nh = [0] * rounds, [0] * rounds
    done, alive, okf, okc = 0, True, True, True
    for r in range(rounds):
        if not alive or done >= rlimit:
            continue
        f2, buf2, n_cyc, n_new, okf_r, okc_r = E.expand_count_compact(
            g, f, buf, delta=delta, store=store, op=op)
        nh[r], ch[r] = int(n_new), int(n_cyc)
        if not bool(okf_r & okc_r):
            alive, okf, okc = False, bool(okf_r), bool(okc_r)
            continue
        done += 1
        f, buf = f2, buf2
        alive = int(n_new) > 0
    return f, buf, ch, nh, done, okf, okc


@pytest.mark.parametrize("formulation", ["slot", "bitword"])
@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("store", [True, False])
@pytest.mark.parametrize("bucket,rlimit", [(64, 4), (16, 4), (64, 2)])
def test_multi_round_bit_identical(formulation, backend, store, bucket,
                                   rlimit):
    """One persistent R-round launch == R composed single rounds, on a
    healthy bucket (64), a bucket sized to trip the guard mid-launch (16),
    and a dynamic budget below R (rlimit=2)."""
    R = 4
    g = _graph()
    delta = int(g.max_degree)
    f0, _, _ = initial_frontier(g, bucket=lambda c: bucket)
    buf0 = empty_cycle_buffer(256, g.adj_bits.shape[1])
    ref = _compose_single(g, f0, buf0, delta=delta, store=store, rounds=R,
                          rlimit=rlimit, op=E.expand_op(formulation, "jnp"))
    f_r, buf_r, ch_r, nh_r, done_r, okf_r, okc_r = ref
    out = E.expand_count_compact_multi(
        g, f0, buf0, delta=delta, store=store, rounds=R,
        op=E.expand_op(formulation, backend), rlimit=jnp.int32(rlimit))
    f_p, buf_p, ch_p, nh_p, done_p, okf_p, okc_p = out
    assert int(done_p) == done_r
    assert list(np.asarray(nh_p)) == nh_r
    assert list(np.asarray(ch_p)) == ch_r
    assert (bool(okf_p), bool(okc_p)) == (okf_r, okc_r)
    if bucket == 16:  # the trip case must actually trip mid-launch
        assert done_r < rlimit and not (okf_r and okc_r)
    for name, leaf in _leaves(f_r):
        got = dict(_leaves(f_p))[name]
        assert np.array_equal(np.asarray(leaf), np.asarray(got)), name
    if store:
        assert np.array_equal(np.asarray(buf_r.masks),
                              np.asarray(buf_p.masks))
        assert int(buf_r.count) == int(buf_p.count)


# ---------------------------------------------------------------------------
# End-to-end: any R == R=1 in cycle_masks and |T| histories
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("formulation", ["slot", "bitword"])
@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_service_persistent_matches_r1_end_to_end(formulation, backend):
    for n, edges in [grid_graph(4, 4), random_gnp(14, 0.35, 7)]:
        g = build_graph(n, edges)
        ref, _ = sequential_chordless_cycles(n, edges)
        res = {}
        for rpl in (1, 4):
            svc = CycleService(EngineConfig(
                store=True, formulation=formulation, backend=backend,
                rounds_per_launch=rpl))
            res[rpl] = svc.enumerate(g)
        assert res[1].n_cycles == res[4].n_cycles == ref
        assert res[1].history == res[4].history
        assert np.array_equal(res[1].cycle_masks, res[4].cycle_masks)


def test_service_persistent_batched_matches_r1():
    specs = [grid_graph(3, 4), grid_graph(4, 5), random_gnp(12, 0.3, 3)]
    gs = [build_graph(n, e) for n, e in specs]
    out = {}
    for rpl in (1, 4):
        svc = CycleService(EngineConfig(store=True, formulation="bitword",
                                        backend="pallas",
                                        rounds_per_launch=rpl))
        out[rpl] = svc.enumerate_batch(gs)
    for a, b, (n, edges) in zip(out[1], out[4], specs):
        ref, _ = sequential_chordless_cycles(n, edges)
        assert a.n_cycles == b.n_cycles == ref
        assert a.history == b.history
        assert np.array_equal(a.cycle_masks, b.cycle_masks)


def test_mesh_persistent_matches_r1_1_2_4_devices():
    """Sharded multi-round body == R=1 histories and reference counts on
    1/2/4-device meshes (subprocess: forces multiple host devices)."""
    code = """
import jax, numpy as np
from jax.sharding import Mesh
from repro.core import (CycleService, EngineConfig, build_graph,
                        sequential_chordless_cycles)
from repro.core.graphs import grid_graph

n, edges = grid_graph(4, 6)
g = build_graph(n, edges)
ref, _ = sequential_chordless_cycles(n, edges)
for ndev in (1, 2, 4):
    mesh = Mesh(np.array(jax.devices())[:ndev].reshape(ndev,), ('data',))
    res = {}
    for rpl in (1, 4):
        cfg = EngineConfig(store=False, mesh=mesh, local_capacity=1<<13,
                           balance_block=64, rounds_per_launch=rpl)
        res[rpl] = CycleService(cfg).enumerate(g)
        assert res[rpl].n_cycles == ref, (ndev, rpl, res[rpl].n_cycles, ref)
        assert res[rpl].stats['dropped'] == 0 and res[rpl].stats['lost'] == 0
    assert res[1].history == res[4].history, ndev
print('OK')
"""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout


# ---------------------------------------------------------------------------
# Dispatch contract: ⌈K/R⌉ pallas_calls per traced superstep
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rpl,expect", [(1, 4), (2, 2), (4, 1)])
def test_superstep_dispatch_contract_ceil_k_over_r(rpl, expect):
    g = _graph()
    delta = int(g.max_degree)
    f, _, _ = initial_frontier(g, bucket=lambda c: 64)
    buf = empty_cycle_buffer(256, g.adj_bits.shape[1])
    op = E.expand_op("bitword", "pallas")
    K = 4

    def superstep(g, f, buf):
        for _ in range(-(-K // rpl)):
            f, buf, *_ = E.expand_count_compact_multi(
                g, f, buf, delta=delta, store=True, rounds=rpl, op=op)
        return f, buf

    counts = assert_superstep_dispatches(superstep, g, f, buf, budget=K,
                                         rounds_per_launch=rpl)
    assert counts.get("pallas_call", 0) == expect


def test_superstep_dispatch_contract_fails_loudly():
    """A superstep traced with the WRONG R must fail with the primitive
    histogram in the message (the offending-prim report)."""
    g = _graph()
    delta = int(g.max_degree)
    f, _, _ = initial_frontier(g, bucket=lambda c: 64)
    buf = empty_cycle_buffer(256, g.adj_bits.shape[1])
    op = E.expand_op("slot", "pallas")

    def one_launch(g, f, buf):
        return E.expand_count_compact_multi(
            g, f, buf, delta=delta, store=False, rounds=4, op=op)

    with pytest.raises(AssertionError, match="pallas"):
        assert_superstep_dispatches(one_launch, g, f, buf, budget=4,
                                    rounds_per_launch=1)


def test_persistent_kernel_build_counters_increment():
    from repro.kernels import ops as kops
    g = _graph()
    delta = int(g.max_degree)
    f, _, _ = initial_frontier(g, bucket=lambda c: 64)
    buf = empty_cycle_buffer(256, g.adj_bits.shape[1])
    op = E.expand_op("bitword", "pallas")
    before = dict(kops.FUSED_KERNEL_BUILDS)
    jax.make_jaxpr(lambda g, f, buf: E.expand_count_compact_multi(
        g, f, buf, delta=delta, store=False, rounds=4, op=op))(g, f, buf)
    assert (kops.FUSED_KERNEL_BUILDS["persistent_single"]
            > before["persistent_single"])


# ---------------------------------------------------------------------------
# Telemetry + replay twin: launches = ⌈attempted/R⌉ per dispatch, exactly
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rpl", [1, 2, 4])
def test_replay_matches_real_driver_persistent(rpl):
    n, edges = grid_graph(4, 5)
    g = build_graph(n, edges)
    base = CycleService(EngineConfig(store=True)).enumerate(g)
    from repro.tune import WaveProfile, replay
    prof = WaveProfile.from_history(base.history, n=g.n,
                                    nw=g.adj_bits.shape[1])
    cfg = EngineConfig(store=True, rounds_per_launch=rpl)
    real = CycleService(cfg).enumerate(g)
    rep = replay(prof, cfg)
    s = real.stats
    assert rep.n_kernel_launches == s["n_kernel_launches"] > 0
    assert rep.n_dispatches == s["n_dispatches"]
    assert rep.n_host_syncs == s["n_host_syncs"]
    assert rep.n_bucket_transitions == s["n_bucket_transitions"]
    assert rep.rounds == s["rounds"]
    assert rep.by_cause == s.get("exit_causes", {})


def test_replay_r1_reproduces_baseline_exactly():
    """rounds_per_launch=1 must leave EVERY replay column bit-identical to
    a config without the knob — the PR-6 numbers are the R=1 case."""
    import dataclasses
    from repro.tune import WaveProfile, replay
    g = build_graph(*grid_graph(4, 5))
    res = CycleService(EngineConfig(store=True)).enumerate(g)
    prof = WaveProfile.from_history(res.history, n=g.n,
                                    nw=g.adj_bits.shape[1])
    a = replay(prof, EngineConfig(store=True, rounds_per_launch=1))
    b = replay(prof, EngineConfig(store=True))
    da, db = dataclasses.asdict(a), dataclasses.asdict(b)
    assert da == db
    # R>1 amortizes launches and pays identity-round traffic for it
    c = replay(prof, EngineConfig(store=True, rounds_per_launch=4))
    assert c.n_kernel_launches < a.n_kernel_launches
    assert c.row_work >= a.row_work


def test_persistent_launches_amortize_in_stats():
    g = build_graph(*grid_graph(4, 5))
    s1 = CycleService(EngineConfig(store=False,
                                   rounds_per_launch=1)).enumerate(g).stats
    s4 = CycleService(EngineConfig(store=False,
                                   rounds_per_launch=4)).enumerate(g).stats
    assert s1["rounds"] == s4["rounds"]
    assert 0 < s4["n_kernel_launches"] < s1["n_kernel_launches"]
    # R=1 launches == attempted rounds (rounds + one per trip exit)
    causes = s1.get("exit_causes", {})
    att = s1["rounds"] + causes.get("GROW", 0) + causes.get("DRAIN", 0)
    assert s1["n_kernel_launches"] == att


# ---------------------------------------------------------------------------
# Tuner surface
# ---------------------------------------------------------------------------

def test_tuner_searches_rounds_per_launch_axis():
    from repro.tune import TUNED_KNOBS, AutoTuner
    from repro.tune.autotune import TuneSpace
    assert "rounds_per_launch" in TUNED_KNOBS
    sets = TuneSpace().knob_sets(EngineConfig())
    assert any(k.get("rounds_per_launch", 1) > 1 for k in sets)
    tuned = AutoTuner.apply({"rounds_per_launch": 4}, EngineConfig())
    assert tuned.rounds_per_launch == 4
