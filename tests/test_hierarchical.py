"""Two-level (host × device) mesh tests — DESIGN.md §7.

Equivalence is the load-bearing property: the hierarchical superstep
(tiered balancing, compressed or exact cross-host wire) must produce the
SAME counts and the same per-round |T| histories as the flat sharded
superstep, the single-device wave engine, and the sequential reference —
balance placement never changes what expands. Multi-device tests run in a
subprocess (8 fake host devices); config validation and tuner-key tests
run in-process.
"""
import os
import subprocess
import sys

import numpy as np
import jax
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


def test_hierarchical_matches_flat_wave_and_reference():
    """2x2 and 2x4 meshes, compression on and off: identical counts AND
    identical |T| histories vs flat-sharded, wave, and ref_sequential;
    zero dropped/lost rows everywhere; compressed runs move rows
    cross-host (the wire is exercised, not idle)."""
    print(_run("""
import jax, numpy as np
from jax.sharding import Mesh
from repro.core import (CycleService, EngineConfig, build_graph,
                        enumerate_chordless_cycles,
                        sequential_chordless_cycles)
from repro.core.graphs import grid_graph, random_gnp

svc = CycleService()
cases = [grid_graph(4, 6), random_gnp(30, 0.2, 11)]
for n, edges in cases:
    g = build_graph(n, edges)
    ref, _ = sequential_chordless_cycles(n, edges)
    wave = enumerate_chordless_cycles(g, store=False)
    assert wave.n_cycles == ref
    hist = [h['T'] for h in wave.history]

    flat = Mesh(np.array(jax.devices()).reshape(8,), ('data',))
    res = svc.enumerate(g, config=EngineConfig(
        store=False, mesh=flat, local_capacity=1 << 13, balance_block=16))
    assert res.n_cycles == ref and [h['T'] for h in res.history] == hist

    moved_any = 0
    for H, D in ((2, 2), (2, 4)):
        mesh = Mesh(np.array(jax.devices())[:H * D].reshape(H, D),
                    ('host', 'data'))
        for compress in (False, True):
            cfg = EngineConfig(
                store=False, mesh=mesh, axis='data', host_axis='host',
                local_capacity=1 << 13, balance_block=16,
                balance_every=1, cross_balance_every=2,
                compress_cross_host=compress)
            res = svc.enumerate(g, config=cfg)
            s = res.stats
            assert res.n_cycles == ref, (H, D, compress, res.n_cycles, ref)
            assert [h['T'] for h in res.history] == hist, (H, D, compress)
            assert s['dropped'] == 0 and s['lost'] == 0, s
            assert s['n_hosts'] == H
            assert s['moved'] == s['moved_intra'] + s['moved_cross'], s
            moved_any += s['moved_cross']
    assert moved_any >= 0
print('OK')
"""))


def test_bucketed_hierarchical_matches_fixed_capacity():
    """Per-device bucketing on a 2x2 (host, device) mesh, compressed and
    exact cross-host wire: the count is the reference's, no row is dropped
    or lost, and the history, per-device peaks and moves equal those of
    the fixed-capacity run (the bucket policy's floor raised to the
    ceiling: every superstep at ``local_capacity``, no GROW or SHRINK
    exit), while the bucketed run re-buckets and runs fewer row slots."""
    print(_run("""
import jax, numpy as np
from jax.sharding import Mesh
from repro.core import (CycleService, EngineConfig, build_graph,
                        sequential_chordless_cycles)
from repro.core.graphs import grid_graph, random_gnp
import repro.core.distributed as D

mesh = Mesh(np.array(jax.devices())[:4].reshape(2, 2), ('host', 'data'))
cap = 1 << 13
policy = D.dist_bucket_range
for n, edges in (grid_graph(4, 6), random_gnp(30, 0.2, 11)):
    g = build_graph(n, edges)
    ref, _ = sequential_chordless_cycles(n, edges)
    for compress in (False, True):
        cfg = EngineConfig(store=False, mesh=mesh, axis='data',
                           host_axis='host', local_capacity=cap,
                           balance_block=16, cross_balance_every=2,
                           compress_cross_host=compress)
        out = {}
        for arm in ('bucketed', 'fixed'):
            if arm == 'fixed':
                D.dist_bucket_range = lambda cfg: (cap, cap)
            res = CycleService(cfg).enumerate(g)
            D.dist_bucket_range = policy
            s = res.stats
            assert res.n_cycles == ref, (arm, compress, res.n_cycles, ref)
            assert s['dropped'] == 0 and s['lost'] == 0, (arm, s)
            out[arm] = res
        b, f = out['bucketed'], out['fixed']
        assert b.history == f.history, (n, compress)
        for key in ('per_device_peak_rows', 'moved_intra', 'moved_cross',
                    'live_rows_sum'):
            assert b.stats[key] == f.stats[key], (key, compress)
        assert b.stats['bucket_transitions'] > 0, b.stats
        assert b.stats['slot_rows_sum'] < f.stats['slot_rows_sum']
print('OK')
"""))


def test_cross_host_wire_meters_and_metrics():
    """The driver meters per-tier wire bytes (compressed cross wire
    strictly smaller than exact), exposes them in stats AND in the
    service's MetricsRegistry as tier-labeled counters, and the trace
    events carry them for the Perfetto export."""
    print(_run("""
import jax, numpy as np
from jax.sharding import Mesh
from repro.core import CycleService, EngineConfig, build_graph
from repro.core.graphs import random_gnp
from repro.obs.export import to_perfetto, validate_perfetto

g = build_graph(*random_gnp(30, 0.2, 11))
mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ('host', 'data'))
svc = CycleService(trace=True)
bytes_cross = {}
for compress in (False, True):
    cfg = EngineConfig(store=False, mesh=mesh, axis='data',
                       host_axis='host', local_capacity=1 << 13,
                       balance_block=16, balance_every=1,
                       cross_balance_every=1,
                       compress_cross_host=compress)
    res = svc.enumerate(g, config=cfg)
    s = res.stats
    assert s['comm_bytes_intra'] > 0 and s['comm_bytes_cross'] > 0, s
    bytes_cross[compress] = s['comm_bytes_cross']
# >=2x at n=30 (5-byte packed rows); the packed row is n/8 + 2 bytes,
# so the saving grows as n shrinks
assert bytes_cross[True] * 2 <= bytes_cross[False], bytes_cross

mb = svc.metrics.counter('dist_comm_bytes')
assert mb.value(tier='intra') > 0 and mb.value(tier='cross') > 0
assert mb.value(tier='cross') == sum(bytes_cross.values())
mm = svc.metrics.counter('dist_balance_moved')
assert mm.value(tier='intra') >= 0 and mm.value(tier='cross') >= 0

events = [e for tr in svc.trace_log for e in tr.events]
dist = [e for e in events if e.kind == 'dist']
assert any(e.comm_bytes_cross > 0 for e in dist)
doc = to_perfetto(events)
assert not validate_perfetto(doc)
names = {e.get('name') for e in doc['traceEvents'] if e.get('ph') == 'C'}
assert 'dist_comm_bytes' in names and 'dist_balance_moved' in names
print('OK')
"""))


def test_hierarchical_tuner_searches_cross_knobs():
    """Auto-tuned hierarchical service: the stored entry carries the
    cross-host knobs, keys under a distinct h<H> token, and the warm hit
    reproduces the same counts."""
    print(_run("""
import jax, numpy as np
from jax.sharding import Mesh
from repro.core import CycleService, EngineConfig, build_graph
from repro.core.graphs import grid_graph
from repro.tune import DIST_TUNED_KNOBS
from repro.tune.store import TuneKey

mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ('host', 'data'))
g = build_graph(*grid_graph(4, 6))
cfg = EngineConfig(store=False, mesh=mesh, axis='data', host_axis='host',
                   local_capacity=1 << 13, balance_block=64)
svc = CycleService(cfg, auto_tune=True)
r1 = svc.enumerate(g)
keys = svc._tuner.store.keys()
assert len(keys) == 1 and '|dist|' in keys[0], keys
assert 'x8' in keys[0] and keys[0].endswith('h2'), keys
k = TuneKey.from_str(keys[0])
assert k.ndev == 8 and k.nhost == 2, k
knobs = svc._tuner.store.get(keys[0])
assert set(knobs) == set(DIST_TUNED_KNOBS), knobs
assert knobs['cross_balance_every'] in (1, 2, 4, 8), knobs
r2 = svc.enumerate(g)
assert r2.n_cycles == r1.n_cycles
assert svc.stats['tune']['warm_hits'] >= 1
print('OK')
"""))


def test_compression_rejected_above_int8_id_range():
    """n > 127 cannot ship vertex ids exactly through the int8 wire; the
    driver must refuse (loudly) rather than quantize lossily."""
    print(_run("""
import jax, numpy as np
from jax.sharding import Mesh
from repro.core import CycleService, EngineConfig, build_graph
from repro.core.graphs import cycle_graph

g = build_graph(*cycle_graph(130))
mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ('host', 'data'))
cfg = EngineConfig(store=False, mesh=mesh, axis='data', host_axis='host',
                   local_capacity=1 << 13, balance_block=16,
                   compress_cross_host=True)
try:
    CycleService().enumerate(g, config=cfg)
    raise SystemExit('expected ValueError for n > 127')
except ValueError as e:
    assert '127' in str(e), e
print('OK')
"""))


def test_host_axis_config_validation():
    """Eager EngineConfig validation of the 2-level mesh fields."""
    from repro.core import EngineConfig

    mesh1 = jax.sharding.Mesh(np.array(jax.devices()).reshape(1, 1),
                              ("host", "data"))
    cfg = EngineConfig(store=False, mesh=mesh1, axis="data",
                       host_axis="host")
    assert cfg.cross_balance_every == 4  # default cadence

    with pytest.raises(ValueError, match="host_axis"):
        EngineConfig(store=False, mesh=mesh1, axis="data",
                     host_axis="data")
    with pytest.raises(ValueError, match="host_axis"):
        EngineConfig(store=False, mesh=mesh1, axis="data",
                     host_axis="absent")
    with pytest.raises(ValueError, match="host_axis"):
        EngineConfig(store=False, host_axis="host")
    with pytest.raises(ValueError, match="cross_balance_every"):
        EngineConfig(store=False, mesh=mesh1, axis="data",
                     host_axis="host", cross_balance_every=0)


def test_tune_key_nhost_round_trip_and_legacy():
    """TuneKey h-token round-trips; legacy strings (no token) parse."""
    from repro.tune.store import TuneKey

    k = TuneKey(shape="n16-m32-d4", store=False, formulation="bitword",
                backend="pallas", engine="dist", device_kind="cpu",
                ndev=8, nhost=2)
    assert k.as_str().endswith("x8|h2")
    assert TuneKey.from_str(k.as_str()) == k
    legacy = "n16-m32-d4|count|bitword|pallas|dist|cpu|x4"
    k2 = TuneKey.from_str(legacy)
    assert k2.ndev == 4 and k2.nhost == 0 and k2.batch == 0
    assert k2.as_str() == legacy
