"""The four-chip cell ``sharded_grid7x10_count``: its entries resolve, its
readers read hand-built readings (and nothing where the program has no
counter), and its driver runs a tiny cell on four virtual CPU devices,
exact when the system is sound and not correct when it drops rows, alters
an answer or is replaced by the control."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import types

import pytest

from bench import run as harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "sharded_grid7x10_count"
NEW_METRICS = ("shard_fill_pct.sharded", "shard_imbalance_pct.sharded",
               "collective_share_pct.sharded",
               "wave_hbm_roofline_pct.sharded")


def reader(name):
    return harness.load_module(os.path.join(ROOT, "bench", "metrics",
                                            name + ".py"))


def test_cell_resolves_to_its_files():
    cell = harness.load_cell(ROOT, CELL)
    assert cell.chips == 4 and cell.config["chips"] == 4
    assert cell.config["mesh"] == {"axis": "data"}
    assert cell.config["engine"] == {
        "formulation": "bitword", "backend": "pallas",
        "local_capacity": 1 << 23, "balance_block": 1 << 15}
    assert cell.config["reduced"] == ["graph"]
    assert cell.traffic == dict(cell.traffic, driver="sharded_oneshot",
                                graph="Grid_7x10", store=False,
                                paths_extended=140095471)
    assert {"setup_s", "oneshot_s"} <= {m["name"] for m in cell.end_to_end}
    assert {m["name"] for m in cell.per_layer} >= {
        "host_syncs_per_request.oneshot", "d2h_reads_per_request.oneshot",
        "device_idle_pct.oneshot", *NEW_METRICS}


def test_new_entries_only_add():
    """The cell is in the lists of the one-shot metrics it reports and of
    the new metrics, which move ``oneshot_s`` and sit in layers the
    benchmark already names."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    metrics = {m["name"]: m for m in spec["end_to_end"] + spec["per_layer"]}
    for name in ("oneshot_s", "host_syncs_per_request.oneshot",
                 "d2h_reads_per_request.oneshot", "device_idle_pct.oneshot"):
        assert CELL in metrics[name]["workloads"], name
    layers = {m["layer"] for m in spec["per_layer"]
              if m["name"] not in NEW_METRICS}
    for name in NEW_METRICS:
        m = metrics[name]
        assert CELL in m["workloads"] and m["moves"] == "oneshot_s", name
        assert m["layer"] in layers, name


def _stats(**kw):
    return dict(dict(rounds=4, n_devices=2, local_capacity=100,
                     live_rows_sum=80, per_device_peak_rows=[30, 10]), **kw)


def _trace(**kw):
    return types.SimpleNamespace(**dict(dict(
        busy_s=2.0, window_s=2.5, n_chips=4,
        op_seconds={"jit__traced/fusion.3 u32[8388608]": 6.0,
                    "jit__traced/all-reduce.1 s32[]": 1.0,
                    "jit__traced/collective-permute-start.2 u32[32768,3]":
                        0.5,
                    "jit__traced/collective-permute-done.2 u32[32768,3]":
                        0.5}), **kw))


READINGS = {
    # 80 live rows over 4 rounds x 2 devices x 100 rows, twice
    "shard_fill_pct.sharded": (
        dict(counters=dict(enumerations=[_stats(), _stats()])), 10.0),
    # peaks 30 and 10: the busiest holds 1.5x the mean, and 1x
    "shard_imbalance_pct.sharded": (
        dict(counters=dict(enumerations=[
            _stats(), _stats(per_device_peak_rows=[5, 5])])), 25.0),
    # 2 of the window's 8 device seconds in collectives
    "collective_share_pct.sharded": (dict(trace=_trace()), 25.0),
    # 2 x 1000 paths x (4 x 3 + 12) bytes x 2 enumerations over 4 chips x
    # 819 GB/s x 2 s busy
    "wave_hbm_roofline_pct.sharded": (
        dict(counters=dict(enumerations=[{}, {}], paths_extended=1000,
                           n_vertices=70),
             trace=_trace(), config=dict(chips=4),
             peaks=dict(hbm_bytes_per_s=819e9)),
        100.0 * 2 * 2 * 1000 * 24 / (4 * 819e9) / 2.0),
}

ABSENT = {
    # the parent's sharded stats have none of the counters
    "shard_fill_pct.sharded": dict(counters=dict(enumerations=[
        dict(rounds=4, n_devices=2)])),
    "shard_imbalance_pct.sharded": dict(counters=dict(enumerations=[
        dict(rounds=4)])),
    "collective_share_pct.sharded": dict(trace=_trace(op_seconds={})),
    "wave_hbm_roofline_pct.sharded": dict(
        counters=dict(enumerations=[], paths_extended=1, n_vertices=70),
        trace=_trace(busy_s=0.0), config=dict(chips=4),
        peaks=dict(hbm_bytes_per_s=819e9)),
}


@pytest.mark.parametrize("name", NEW_METRICS)
def test_reader_reads_a_hand_built_reading(name):
    reading, want = READINGS[name]
    assert reader(name).read(reading) == pytest.approx(want)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_reader_finds_nothing_where_the_counter_is_absent(name):
    assert reader(name).read(ABSENT[name]) is None


def test_roofline_reader_shares_the_one_chip_work_count():
    base = reader("wave_hbm_roofline_pct")
    sharded = reader("wave_hbm_roofline_pct.sharded")
    reading, _ = READINGS["wave_hbm_roofline_pct.sharded"]
    one = base.read(dict(reading, counters=dict(reading["counters"],
                                                stored_cycles=None)))
    assert sharded.read(reading) == pytest.approx(one / 4)


# -- the driver on four virtual CPU devices ----------------------------------

TINY = "tiny_sharded_count"
CHILD = r"""
import json, sys
sys.path[:0] = [sys.argv[1], sys.argv[2]]
import repro.core as core
from bench import control, run as harness

root = sys.argv[3]
Real = core.CycleService


class Faulty(Real):
    fault = None

    def enumerate(self, g, **kw):
        res = super().enumerate(g, **kw)
        if self.fault == "rows_dropped":
            res.stats["dropped"] = 3
        elif self.fault == "answer_altered":
            res.n_cycles += 1
        return res


for case in ("sound", "rows_dropped", "answer_altered", "control"):
    Faulty.fault = case
    core.CycleService = Faulty
    if case == "control":
        control.install()
    cell = harness.load_cell(root, %r)
    res = harness.run_cell(cell, root, seed=2**31 + 17, seconds=0.3,
                           trace=False)
    res.pop("window")
    print(json.dumps(dict(case=case, **res)), flush=True)
""" % TINY


@pytest.fixture(scope="module")
def tiny_runs(tmp_path_factory):
    """The cell's driver and configuration at a tiny size (Grid_4x6, 2^10
    rows per device) in a copy of the benchmark, run in a child that has
    four CPU devices: a sound run, two planted faults and the control."""
    from repro.launch.env import host_sim_env
    root = str(tmp_path_factory.mktemp("sharded_root"))
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    with open(os.path.join(root, "bench", "configs",
                           "table1_sharded4.json")) as f:
        config = json.load(f)
    config.update(name="tiny_sharded4")
    config["engine"].update(local_capacity=1 << 10, balance_block=64)
    with open(os.path.join(root, "bench", "configs",
                           "tiny_sharded4.json"), "w") as f:
        json.dump(config, f)
    with open(os.path.join(root, "bench", "traffic", TINY + ".json"),
              "w") as f:
        json.dump({"driver": "sharded_oneshot", "graph": "Grid_4x6",
                   "store": False, "paths_extended": 972}, f)
    spec["configs"].append({"name": "tiny_sharded4", "source": "test",
                            "file": "bench/configs/tiny_sharded4.json",
                            "reduced": ["graph"], "why": "test"})
    spec["workloads"].append({"name": TINY, "config": "tiny_sharded4",
                              "traffic": TINY, "chips": 4, "why": "test"})
    for m in spec["end_to_end"]:
        if m["name"] == "oneshot_s":
            m["workloads"].append(TINY)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    env = host_sim_env(4)
    env.update(JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    done = subprocess.run(
        [sys.executable, "-c", CHILD, ROOT, os.path.join(ROOT, "src"), root],
        env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    lines = [json.loads(x) for x in done.stdout.splitlines()
             if x.startswith("{")]
    return {r.pop("case"): r for r in lines}


def test_driver_is_exact_on_a_sound_system(tiny_runs):
    res = tiny_runs["sound"]
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert res["checks"] == {name: {"value": 0, "limit": 0} for name in
                             ("count_error_max", "dropped_max", "lost_max")}
    assert res["metrics"]["oneshot_s"]["value"] > 0
    assert res["metrics"]["setup_s"]["value"] > 0


@pytest.mark.parametrize("case,check", [
    ("rows_dropped", "dropped_max"), ("answer_altered", "count_error_max"),
    ("control", "count_error_max")])
def test_driver_refuses_a_fault(tiny_runs, case, check):
    res = tiny_runs[case]
    assert res["correct"] is False
    assert res["failed"] > 0
    assert res["checks"][check]["value"] > 0
