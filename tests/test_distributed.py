"""Sharded wave superstep + checkpointing tests.

Multi-device tests run in a subprocess with XLA_FLAGS forcing 8 host
devices (the main pytest process must keep seeing 1 device)."""
import os
import subprocess
import sys

import numpy as np
import jax
import jax.numpy as jnp
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)
from repro.launch.env import host_sim_env  # noqa: E402


def _run(code: str) -> str:
    out = subprocess.run([sys.executable, "-c", code],
                         env=host_sim_env(8, src_path=SRC),
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_sharded_superstep_matches_wave_and_reference():
    """Count-equivalence property across the (graph × mesh-size) matrix:
    sharded wave superstep == single-device wave engine == ref_sequential
    on 1/2/4-device meshes, with no dropped or lost rows."""
    print(_run("""
import jax, numpy as np
from jax.sharding import Mesh
from repro.core import (CycleService, EngineConfig, build_graph,
                        enumerate_chordless_cycles,
                        sequential_chordless_cycles)
from repro.core.graphs import grid_graph, random_gnp

cases = [grid_graph(4, 6), grid_graph(5, 5), random_gnp(30, 0.2, 11),
         random_gnp(24, 0.35, 2)]
for n, edges in cases:
    g = build_graph(n, edges)
    ref, _ = sequential_chordless_cycles(n, edges)
    wave = enumerate_chordless_cycles(g, store=False)
    assert wave.n_cycles == ref, (wave.n_cycles, ref)
    for ndev in (1, 2, 4):
        mesh = Mesh(np.array(jax.devices())[:ndev].reshape(ndev,), ('data',))
        cfg = EngineConfig(store=False, mesh=mesh, local_capacity=1<<13,
                           balance_block=64)
        res = CycleService(cfg).enumerate(g)
        assert res.n_cycles == ref, (ndev, n, res.n_cycles, ref)
        assert res.stats['dropped'] == 0 and res.stats['lost'] == 0
        # history carries the same per-round |T| wave as the wave engine
        assert [h['T'] for h in res.history] == \
            [h['T'] for h in wave.history], (ndev, n)
print('OK')
"""))


def test_bitword_pallas_sharded_matches_slot_wave_and_reference():
    """The fast path on a mesh: each device's round runs the bitword/Pallas
    ExpandOp (kernel interpreted on the CPU), and its count equals the
    sequential reference, the slot/jnp sharded count and the one-chip wave
    count on 1/2/4-device meshes, with the wave's per-round |T| history and
    no dropped or lost rows. K_8_8 (Δ = 8) gives wide candidate words. The
    other two pairs a mesh accepts, slot/pallas and bitword/jnp, count
    Grid_4x6 exactly on two devices."""
    print(_run("""
import jax, numpy as np
from jax.sharding import Mesh
from repro.core import (CycleService, EngineConfig, build_graph,
                        enumerate_chordless_cycles,
                        sequential_chordless_cycles)
from repro.core.graphs import complete_bipartite, grid_graph, random_gnp

cases = [grid_graph(4, 6), grid_graph(5, 5), random_gnp(30, 0.2, 11),
         complete_bipartite(8, 8)]
for n, edges in cases:
    g = build_graph(n, edges)
    ref, _ = sequential_chordless_cycles(n, edges)
    wave = enumerate_chordless_cycles(g, store=False, formulation='bitword',
                                      backend='pallas')
    assert wave.n_cycles == ref, (wave.n_cycles, ref)
    want_T = [h['T'] for h in wave.history]
    for ndev in (1, 2, 4):
        mesh = Mesh(np.array(jax.devices())[:ndev].reshape(ndev,), ('data',))
        got = {}
        for form, backend in (('bitword', 'pallas'), ('slot', 'jnp')):
            cfg = EngineConfig(store=False, mesh=mesh, local_capacity=1<<12,
                               balance_block=64, formulation=form,
                               backend=backend)
            res = CycleService(cfg).enumerate(g)
            s = res.stats
            assert s['dropped'] == 0 and s['lost'] == 0, (form, ndev, n)
            assert [h['T'] for h in res.history] == want_T, (form, ndev, n)
            assert len(s['per_device_peak_rows']) == ndev
            got[form] = res.n_cycles
        assert got == {'bitword': ref, 'slot': ref}, (ndev, n, got, ref)
# the other two pairs a mesh accepts, on one graph and one mesh size
n, edges = grid_graph(4, 6)
g = build_graph(n, edges)
ref, _ = sequential_chordless_cycles(n, edges)
mesh = Mesh(np.array(jax.devices())[:2], ('data',))
for form, backend in (('slot', 'pallas'), ('bitword', 'jnp')):
    cfg = EngineConfig(store=False, mesh=mesh, local_capacity=1<<12,
                       balance_block=64, formulation=form, backend=backend)
    res = CycleService(cfg).enumerate(g)
    assert res.n_cycles == ref, (form, backend, res.n_cycles, ref)
    assert res.stats['dropped'] == 0 and res.stats['lost'] == 0
print('OK')
"""))


def test_sharded_programs_name_their_stages():
    """The lowered sharded programs carry the stage scopes a device trace
    reads: ``repro.seed`` (the deal) and, in the superstep,
    ``repro.round.flags``, ``repro.round.compact`` and
    ``repro.round.balance``, with the Pallas flag kernel inside."""
    print(_run("""
import jax, numpy as np
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.core import EngineConfig, build_graph
from repro.core import distributed as D
from repro.core.frontier import Frontier
from repro.core.graphs import grid_graph

ndev, cap = 2, 1 << 10
mesh = Mesh(np.array(jax.devices())[:ndev], ('data',))
g = build_graph(*grid_graph(4, 5))
g_spec = jax.tree_util.tree_map(lambda _: P(), g)
cfg = EngineConfig(store=False, mesh=mesh, formulation='bitword',
                   backend='pallas', local_capacity=cap, balance_block=64)
rows = NamedSharding(mesh, P('data'))
S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=rows)
nw = g.adj_bits.shape[1]
f = Frontier(path=S((ndev * cap, nw), jnp.uint32),
             blocked=S((ndev * cap, nw), jnp.uint32),
             v1=S((ndev * cap,), jnp.int32), l2=S((ndev * cap,), jnp.int32),
             vlast=S((ndev * cap,), jnp.int32), count=S((ndev,), jnp.int32))
step = D.make_dist_superstep(mesh, 'data', g_spec, cfg, g.max_degree, 4,
                             cap)
text = jax.jit(step).lower(g, f, S((ndev, D._N_COUNTERS), jnp.int32),
                           jnp.int32(4), jnp.int32(0)).as_text(
                               debug_info=True)
for name in ('repro.round.flags', 'repro.round.compact',
             'repro.round.balance', 'bitword_expand'):
    assert name in text, name
deal = D.make_dist_deal(mesh, 'data', g_spec, cap, g.max_degree)
assert 'repro.seed' in jax.jit(deal).lower(g).as_text(debug_info=True)
print('OK')
"""))


def test_superstep_syncs_bounded_and_twin_exact():
    """The tentpole's accounting: host syncs are O(rounds / K) + O(1), the
    per-round arm (K=1) dispatches >= 2x more, the warm path re-traces
    nothing, and the sharded replay twin reproduces the driver's
    dispatch/sync/round counters exactly."""
    print(_run("""
import jax, numpy as np
from jax.sharding import Mesh
from repro.core import CycleService, EngineConfig, build_graph
from repro.core.graphs import grid_graph
from repro.tune import DistProfile, replay_dist

mesh = Mesh(np.array(jax.devices())[:4].reshape(4,), ('data',))
n, edges = grid_graph(5, 6)
g = build_graph(n, edges)

def run(k):
    cfg = EngineConfig(store=False, mesh=mesh, local_capacity=1<<13,
                       balance_block=64, superstep_rounds=k)
    svc = CycleService(cfg, trace=True)
    res = svc.enumerate(g)
    return svc, cfg, res

svc, cfg, res = run(8)
s = res.stats
R = s['iterations']
assert R > 8, R                       # multiple supersteps exercised
# one deal dispatch + ceil(R/8) supersteps, plus one superstep per early
# exit (each GROW abort and each SHRINK re-buckets); one sync each + the
# final fetch
extra = s['bucket_transitions'] + s['grow_aborts']
assert s['n_dispatches'] <= -(-R // 8) + 1 + extra, s
assert s['n_host_syncs'] <= -(-R // 8) + 2 + extra, s
ev = res.trace.events
assert [e.kind for e in ev] == ['deal'] + ['dist'] * (len(ev) - 1)
assert all(e.ndev == 4 for e in ev)
assert any(e.per_device and max(e.per_device) > 0 for e in ev[1:])
assert sum(e.rounds for e in ev) == R
# balance counters are plumbed per dispatch and sum to the run totals
assert sum(e.moved for e in ev) == s['moved']
assert sum(e.lost for e in ev) == s['lost'] == 0

# sharded replay twin: exact dispatch/sync/round accounting
prof = DistProfile.from_run(res.history, n=g.n, nw=g.adj_bits.shape[1],
                            ndev=4, cfg=cfg, traces=(res.trace,))
rep = replay_dist(prof, cfg)
assert rep.n_dispatches == s['n_dispatches'], (rep, s)
assert rep.n_host_syncs == s['n_host_syncs'], (rep, s)
assert rep.rounds == R and rep.feasible
# ... and the same GROW/SHRINK exits and re-buckets, through one policy
assert rep.by_cause == s['exit_causes'], (rep.by_cause, s['exit_causes'])
assert rep.n_bucket_transitions == s['bucket_transitions'] > 0, (rep, s)

# per-round arm (K=1): the old dispatch-per-round pattern
_, _, res1 = run(1)
s1 = res1.stats
assert res1.n_cycles == res.n_cycles
assert s1['n_dispatches'] >= 2 * s['n_dispatches'], (s1, s)
assert s1['n_host_syncs'] >= 2 * s['n_host_syncs'], (s1, s)

# warm path: a second request through the same service re-traces nothing
t0 = svc.stats['n_traces']
res2 = svc.enumerate(g)
assert res2.n_cycles == res.n_cycles
assert svc.stats['n_traces'] == t0, 'warm sharded path retraced'
print('OK', R, s['n_dispatches'], s1['n_dispatches'])
"""))


# the fixed-capacity run: the bucket policy's floor raised to the ceiling,
# so the deal and every superstep run at ``local_capacity`` and no
# superstep exits GROW or SHRINK. The driver and the superstep builder
# look the policy up in ``core.distributed`` when they run, so patching the
# module reaches both.
_FIXED = """
import repro.core.distributed as D
D.dist_bucket_range = lambda cfg: (int(cfg.local_capacity),) * 2
"""


@pytest.mark.parametrize("rpl", [1, 2])
def test_bucketed_superstep_matches_fixed_capacity(rpl):
    """Per-device bucketing changes no answer and no placement: against
    the fixed-capacity run (every superstep at ``local_capacity``, no
    GROW or SHRINK exit), the bucketed run's count, history,
    per-device peaks and final live rows, balance moves and live-row sum
    are equal, no row is dropped or lost, the mechanism engaged (it
    re-bucketed, aborted a round to GROW, and ran fewer row slots), and
    the replay twin reproduces its dispatches, syncs and exits."""
    print(_run("""
import jax, numpy as np
from jax.sharding import Mesh
from repro.core import (CycleService, EngineConfig, build_graph,
                        sequential_chordless_cycles)
from repro.core.graphs import grid_graph, random_gnp
from repro.tune import DistProfile, replay_dist
import repro.core.distributed as D

mesh = Mesh(np.array(jax.devices())[:4], ('data',))
cap = 1 << 13
policy = D.dist_bucket_range
for n, edges in (grid_graph(5, 6), random_gnp(30, 0.2, 11)):
    g = build_graph(n, edges)
    ref, _ = sequential_chordless_cycles(n, edges)
    cfg = EngineConfig(store=False, mesh=mesh, local_capacity=cap,
                       balance_block=64, rounds_per_launch=%d)
    out = {}
    for arm in ('bucketed', 'fixed'):
        if arm == 'fixed':
%s
        res = CycleService(cfg, trace=True).enumerate(g)
        D.dist_bucket_range = policy
        s = res.stats
        assert res.n_cycles == ref, (arm, n, res.n_cycles, ref)
        assert s['dropped'] == 0 and s['lost'] == 0, (arm, s)
        out[arm] = res
    b, f = out['bucketed'], out['fixed']
    # the replay twin chops the bucketed run exactly
    rep = replay_dist(DistProfile.from_run(
        b.history, n=g.n, nw=g.adj_bits.shape[1], ndev=4, cfg=cfg,
        traces=(b.trace,)), cfg)
    assert (rep.n_dispatches, rep.n_host_syncs, rep.by_cause) == (
        b.stats['n_dispatches'], b.stats['n_host_syncs'],
        b.stats['exit_causes']), (rep, b.stats)
    assert b.history == f.history, n
    for key in ('per_device_peak_rows', 'per_device_live', 'moved',
                'live_rows_sum', 'iterations'):
        assert b.stats[key] == f.stats[key], (key, b.stats[key],
                                              f.stats[key])
    assert f.stats['slot_rows_sum'] == f.stats['iterations'] * 4 * cap
    assert f.stats['bucket_transitions'] == f.stats['grow_aborts'] == 0
    assert set(f.stats['exit_causes']) <= {'RUN', 'DONE'}, f.stats
    assert b.stats['bucket_transitions'] > 0 and b.stats['grow_aborts'] > 0
    assert b.stats['slot_rows_sum'] < f.stats['slot_rows_sum'], b.stats
print('OK')
""" % (rpl, "\n".join("            " + line
                       for line in _FIXED.strip().splitlines()))))


def test_grow_abort_leaves_the_round_untouched():
    """A superstep whose busiest device's extensions outgrow its bucket
    aborts that round on every device: its rows and counters are those of
    the same superstep stopped by its budget just before the round, the
    status is GROW, and the need it hands back exceeds the bucket."""
    print(_run("""
import jax, numpy as np
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from repro.core import EngineConfig, build_graph
from repro.core import distributed as D
from repro.core.engine import STATUS_NAMES
from repro.core.graphs import grid_graph

ndev, b = 4, 128
mesh = Mesh(np.array(jax.devices())[:ndev], ('data',))
g = build_graph(*grid_graph(5, 6))
g_spec = jax.tree_util.tree_map(lambda _: P(), g)
cfg = EngineConfig(store=False, mesh=mesh, local_capacity=1 << 13,
                   balance_block=64)          # floor 128: no SHRINK at b
deal = jax.jit(D.make_dist_deal(mesh, 'data', g_spec, b, g.max_degree))
step = jax.jit(D.make_dist_superstep(mesh, 'data', g_spec, cfg,
                                     g.max_degree, 16, b))
zeros = jnp.zeros((ndev, D._N_COUNTERS), jnp.int32)

f, _ = deal(g)
fa, ca, r, status, nh, ch, lh = step(g, f, zeros, jnp.int32(16),
                                     jnp.int32(0))
r = int(r)
assert STATUS_NAMES[int(status)] == 'GROW', int(status)
assert int(nh[r]) > b, (r, np.asarray(nh))
assert 0 < r < 16, r

f, _ = deal(g)
fb, cb, rb, sb, _, _, lhb = step(g, f, zeros, jnp.int32(r), jnp.int32(0))
assert int(rb) == r and STATUS_NAMES[int(sb)] == 'RUN'
assert np.array_equal(np.asarray(ca), np.asarray(cb))
assert np.array_equal(np.asarray(lh)[:, :r], np.asarray(lhb)[:, :r])
cnt = np.asarray(fa.count)
assert np.array_equal(cnt, np.asarray(fb.count))
for name in ('path', 'blocked', 'v1', 'l2', 'vlast'):
    xa = np.asarray(getattr(fa, name)).reshape(ndev, b, -1)
    xb = np.asarray(getattr(fb, name)).reshape(ndev, b, -1)
    for d in range(ndev):
        assert np.array_equal(xa[d, :cnt[d]], xb[d, :cnt[d]]), (name, d)
print('OK', r, int(nh[r]))
"""))


def test_slot_rows_fall_below_the_ceiling_as_the_wave_rises_and_falls():
    """Over a wave that rises and falls, the rounds run at buckets that
    sum to less than every round at ``local_capacity`` on every device,
    and still hold every live row."""
    print(_run("""
import jax, numpy as np
from jax.sharding import Mesh
from repro.core import CycleService, EngineConfig, build_graph
from repro.core.graphs import grid_graph

mesh = Mesh(np.array(jax.devices())[:4], ('data',))
cap = 1 << 13
res = CycleService(EngineConfig(store=False, mesh=mesh, local_capacity=cap,
                                balance_block=32)).enumerate(
    build_graph(*grid_graph(5, 8)))
s = res.stats
T = [h['T'] for h in res.history]
assert max(T) > T[0] and T[-1] < max(T)          # the wave rose and fell
assert s['live_rows_sum'] <= s['slot_rows_sum']
assert s['slot_rows_sum'] < s['iterations'] * 4 * cap, s
assert s['dropped'] == 0 and s['lost'] == 0
print('OK', s['slot_rows_sum'], s['iterations'] * 4 * cap)
"""))


def test_overflow_at_the_ceiling_still_raises():
    """Bucketing grows no bucket past ``local_capacity``: a wave whose
    busiest device outgrows the ceiling still drops rows there, and the
    driver refuses the count."""
    print(_run("""
import jax, numpy as np
from jax.sharding import Mesh
from repro.core import CycleService, EngineConfig, build_graph
from repro.core.graphs import grid_graph

mesh = Mesh(np.array(jax.devices())[:4], ('data',))
cfg = EngineConfig(store=False, mesh=mesh, local_capacity=256,
                   balance_block=64)
try:
    CycleService(cfg).enumerate(build_graph(*grid_graph(5, 8)))
    raise SystemExit('expected the overflow RuntimeError')
except RuntimeError as e:
    assert 'sharded frontier overflow' in str(e), e
    assert 'local_capacity=256' in str(e), e
print('OK')
"""))


def test_a_grow_exit_the_policy_cannot_serve_raises():
    """A superstep that aborts a round to GROW hands the host a need its
    bucket cannot hold; a policy that gives back the same bucket would
    relaunch the same abort forever, so the driver refuses it."""
    print(_run("""
import jax, numpy as np
from jax.sharding import Mesh
from repro.core import CycleService, EngineConfig, build_graph
from repro.core.graphs import grid_graph
import repro.core.distributed as D

mesh = Mesh(np.array(jax.devices())[:4], ('data',))
cfg = EngineConfig(store=False, mesh=mesh, local_capacity=1 << 13,
                   balance_block=64)
D.dist_next_bucket = lambda cfg, status, cur, **kw: cur
try:
    CycleService(cfg).enumerate(build_graph(*grid_graph(5, 8)))
    raise SystemExit('expected the RuntimeError')
except RuntimeError as e:
    assert 'no larger bucket is left' in str(e), e
print('OK')
"""))


def test_one_service_keeps_programs_apart_by_local_capacity():
    """The superstep's GROW and SHRINK exits depend on ``local_capacity``,
    so one service enumerating with two configs that differ only there
    builds separate programs: the small ceiling still refuses the wave
    that overflows it, whichever config ran first, and the large one
    still counts it exactly."""
    print(_run("""
import dataclasses
import jax, numpy as np
from jax.sharding import Mesh
from repro.core import (CycleService, EngineConfig, build_graph,
                        sequential_chordless_cycles)
from repro.core.graphs import grid_graph

mesh = Mesh(np.array(jax.devices())[:4], ('data',))
n, edges = grid_graph(5, 8)
g = build_graph(n, edges)
ref, _ = sequential_chordless_cycles(n, edges)
small = EngineConfig(store=False, mesh=mesh, local_capacity=2048,
                     balance_block=64)          # the busiest device peaks at 2640
large = dataclasses.replace(small, local_capacity=1 << 13)


def refused(svc):
    try:
        svc.enumerate(g, config=small)
    except RuntimeError as e:
        assert 'sharded frontier overflow' in str(e), e
        return
    raise SystemExit('expected the overflow RuntimeError')


def exact(svc):
    res = svc.enumerate(g, config=large)
    assert res.n_cycles == ref, (res.n_cycles, ref)
    assert res.stats['dropped'] == res.stats['lost'] == 0, res.stats
    return {e.bucket for e in res.trace.events if e.kind == 'dist'}


svc = CycleService(small, trace=True)
refused(svc)                       # then the capacity raised, as told
buckets = exact(svc)
assert 2048 in buckets, buckets    # a bucket both configs run at
svc = CycleService(small, trace=True)
exact(svc)
refused(svc)
print('OK', sorted(buckets))
"""))


def test_diffusion_balancing_spreads_load():
    print(_run("""
import jax, numpy as np
from jax.sharding import Mesh
from repro.core import CycleService, EngineConfig, build_graph
from repro.core.graphs import grid_graph

# run only a few rounds of a frontier-heavy graph; live rows must appear on
# several devices even though work trees are lopsided
mesh = Mesh(np.array(jax.devices()).reshape(8,), ('data',))
n, edges = grid_graph(5, 8)
g = build_graph(n, edges)
cfg = EngineConfig(store=False, mesh=mesh, local_capacity=1<<13,
                   balance_block=32, max_iters=8)
res = CycleService(cfg).enumerate(g)
live = np.array(res.stats['per_device_live'])
assert (live > 0).sum() >= 4, live
assert res.stats['moved'] > 0
print('OK', live.tolist())
"""))


def test_balance_conserves_rows_and_backpressures():
    """Diffusion balancing conserves the live-row multiset when no device
    is at capacity, and a full receiver refuses donation (give=0 via the
    reverse permute) instead of dropping rows."""
    print(_run("""
import jax, numpy as np
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.core.distributed import make_balance_step
from repro.core.frontier import Frontier

ndev, cap, block, nw = 4, 64, 8, 2
mesh = Mesh(np.array(jax.devices())[:ndev].reshape(ndev,), ('data',))
sh = NamedSharding(mesh, P('data'))

def frontier(counts):
    v1 = np.full((ndev, cap), -1, np.int32)
    for d, c in enumerate(counts):
        v1[d, :c] = np.arange(c) + 1000 * d   # distinguishable rows
    return Frontier(
        path=jax.device_put(jnp.zeros((ndev * cap, nw), jnp.uint32), sh),
        blocked=jax.device_put(jnp.zeros((ndev * cap, nw), jnp.uint32), sh),
        v1=jax.device_put(jnp.asarray(v1.reshape(-1)), sh),
        l2=jax.device_put(jnp.zeros((ndev * cap,), jnp.int32), sh),
        vlast=jax.device_put(jnp.zeros((ndev * cap,), jnp.int32), sh),
        count=jax.device_put(jnp.asarray(counts, jnp.int32), sh))

def live_rows(f):
    v1 = np.asarray(f.v1).reshape(ndev, cap)
    cnt = np.asarray(f.count)
    return sorted(x for d in range(ndev) for x in v1[d, :cnt[d]])

step = make_balance_step(mesh, 'data', cap, block)

# conservation: lopsided but nobody full -> rows move, none lost
f = frontier([60, 0, 0, 0])
before = live_rows(f)
moved_total = 0
for _ in range(10):
    f, moved, lost = step(f)
    assert int(np.asarray(lost).sum()) == 0
    moved_total += int(np.asarray(moved).sum())
    assert int(np.asarray(f.count).sum()) == 60
assert moved_total > 0
assert live_rows(f) == before, 'row multiset changed'
assert (np.asarray(f.count) > 0).sum() >= 2, np.asarray(f.count)

# backpressure: the right neighbor is FULL -> donation refused, no loss
f = frontier([cap, cap, 0, 0])
f2, moved, lost = step(f)
cnt = np.asarray(f2.count)
assert int(np.asarray(lost).sum()) == 0, 'receiver dropped live rows'
assert int(cnt.sum()) == 2 * cap
assert cnt[1] <= cap, cnt    # never above capacity
print('OK', cnt.tolist())
"""))


def test_balance_cadence_is_global_across_supersteps():
    """balance_every(6) > superstep_rounds(4): the cadence must run on the
    GLOBAL round index — an in-dispatch counter (which resets to 0 every
    superstep) would never fire a balance step at all."""
    print(_run("""
import jax, numpy as np
from jax.sharding import Mesh
from repro.core import (CycleService, EngineConfig, build_graph,
                        enumerate_chordless_cycles)
from repro.core.graphs import grid_graph

mesh = Mesh(np.array(jax.devices()).reshape(8,), ('data',))
n, edges = grid_graph(5, 8)
g = build_graph(n, edges)
ref = enumerate_chordless_cycles(g, store=False).n_cycles
cfg = EngineConfig(store=False, mesh=mesh, local_capacity=1<<13,
                   balance_block=32, balance_every=6, superstep_rounds=4)
res = CycleService(cfg).enumerate(g)
assert res.n_cycles == ref, (res.n_cycles, ref)
assert res.stats['moved'] > 0, res.stats
assert res.stats['lost'] == 0
print('OK', res.stats['moved'])
"""))


def test_sharded_requests_resolve_through_tuner():
    """CycleService(auto_tune=True) on a mesh config: first visit records a
    trace and searches the sharded knob space; the second request is a warm
    hit — tuned knobs applied, no new search, no re-trace."""
    print(_run("""
import jax, numpy as np
from jax.sharding import Mesh
from repro.core import CycleService, EngineConfig, build_graph
from repro.core.graphs import grid_graph
from repro.tune import DIST_TUNED_KNOBS

mesh = Mesh(np.array(jax.devices())[:4].reshape(4,), ('data',))
g = build_graph(*grid_graph(4, 6))
cfg = EngineConfig(store=False, mesh=mesh, local_capacity=1<<13,
                   balance_block=64)
svc = CycleService(cfg, auto_tune=True)
r1 = svc.enumerate(g)
ts = svc.stats['tune']
assert ts['searches'] == 1 and ts['observations'] == 1, ts
assert svc.stats['traces_recorded'] == 1
keys = svc._tuner.store.keys()
assert len(keys) == 1 and '|dist|' in keys[0] and keys[0].endswith('x4'), keys
knobs = svc._tuner.store.get(keys[0])
# flat meshes search the base sharded axes; the cross-host knobs (the
# DIST_TUNED_KNOBS tail) only join the grid when a host_axis is set
assert set(knobs) == {'superstep_rounds', 'local_capacity',
                      'balance_every'}, knobs
assert set(knobs) < set(DIST_TUNED_KNOBS), knobs

r2 = svc.enumerate(g)
ts = svc.stats['tune']
assert r2.n_cycles == r1.n_cycles
assert ts['searches'] == 1 and ts['warm_hits'] >= 1, ts
assert svc.stats['traces_recorded'] == 1, 'warm hit re-traced'
assert svc.stats['tuned_requests'] == 1
assert r2.stats['dropped'] == 0 and r2.stats['lost'] == 0
print('OK', knobs)
"""))


def test_checkpoint_roundtrip(tmp_path):
    from repro import checkpoint as ckpt
    tree = {"a": jnp.arange(12).reshape(3, 4), "b": [jnp.float32(3.5),
            jnp.ones((2, 2), jnp.bfloat16)]}
    ckpt.save_pytree(str(tmp_path), 7, tree)
    assert ckpt.latest_step(str(tmp_path)) == 7
    like = jax.tree_util.tree_map(lambda x: jnp.zeros_like(x), tree)
    back = ckpt.restore_pytree(str(tmp_path), 7, like)
    flat_a, _ = jax.tree_util.tree_flatten(tree)
    flat_b, _ = jax.tree_util.tree_flatten(back)
    for x, y in zip(flat_a, flat_b):
        assert x.dtype == y.dtype
        assert np.array_equal(np.asarray(x, np.float32), np.asarray(y, np.float32))


def test_checkpoint_retention_and_atomicity(tmp_path):
    from repro import checkpoint as ckpt
    for s in range(6):
        ckpt.save_pytree(str(tmp_path), s, {"x": jnp.full((4,), s)}, keep=3)
    assert ckpt.list_steps(str(tmp_path)) == [3, 4, 5]
    assert not [f for f in os.listdir(tmp_path) if f.endswith(".tmp")]


def test_enum_checkpoint_written_at_superstep_boundaries():
    """Sharded runs snapshot the frontier pytree at superstep boundaries."""
    print(_run("""
import jax, numpy as np
from jax.sharding import Mesh
from repro.core import build_graph, enumerate_chordless_cycles, EngineConfig
from repro.core.distributed import enumerate_distributed
from repro.core.graphs import grid_graph
import tempfile

mesh = Mesh(np.array(jax.devices()).reshape(8,), ('data',))
n, edges = grid_graph(4, 7)
g = build_graph(n, edges)
ref = enumerate_chordless_cycles(g, store=False)
d = tempfile.mkdtemp()
cfg = EngineConfig(store=False, local_capacity=1<<13, balance_block=32,
                   superstep_rounds=4, checkpoint_every=3, checkpoint_dir=d)
out = enumerate_distributed(g, mesh, cfg=cfg)
assert out['n_cycles'] == ref.n_cycles
from repro import checkpoint as ckpt
assert ckpt.list_steps(d), 'checkpoints written'
print('OK')
"""))


def test_dist_enum_config_shim_removed():
    from repro.core import distributed
    assert not hasattr(distributed, "DistEnumConfig")
    with pytest.raises(TypeError, match="DistEnumConfig was removed"):
        distributed.as_engine_config(None, "data", object())
