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
step = D.make_dist_superstep(mesh, 'data', g_spec, cfg, g.max_degree, 4)
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
# one deal dispatch + ceil(R/8) supersteps; one sync each + final fetch
assert s['n_dispatches'] <= -(-R // 8) + 1, s
assert s['n_host_syncs'] <= -(-R // 8) + 2, s
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
