"""The reader of ``shard_slot_fill_pct.sharded``: its entry resolves, it
reads hand-built sharded stats, and it finds nothing where the program
does not count ``slot_rows_sum``."""
from __future__ import annotations

import json
import os

import pytest

from bench import run as harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "sharded_grid7x10_count"
NAME = "shard_slot_fill_pct.sharded"


def reader():
    return harness.load_module(os.path.join(ROOT, "bench", "metrics",
                                            NAME + ".py"))


def _stats(**kw):
    return dict(dict(rounds=4, n_devices=2, local_capacity=100,
                     live_rows_sum=80, per_device_peak_rows=[30, 10]), **kw)


def test_entry_reads_the_round_layer_of_the_sharded_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    m = {m["name"]: m for m in spec["per_layer"]}[NAME]
    assert m["workloads"] == [CELL] and m["moves"] == "oneshot_s"
    assert m["layer"] == "round" and m["source"] == "program_counter"
    assert NAME in {m["name"] for m in harness.load_cell(ROOT, CELL).per_layer}


@pytest.mark.parametrize("enums,want", [
    # 80 and 80 live rows over buckets summing to 200 and 600 slots
    ([_stats(slot_rows_sum=200), _stats(slot_rows_sum=600)], 20.0),
    # every round at a full bucket
    ([_stats(slot_rows_sum=80)], 100.0),
])
def test_reader_reads_a_hand_built_reading(enums, want):
    reading = dict(counters=dict(enumerations=enums))
    assert reader().read(reading) == pytest.approx(want)


@pytest.mark.parametrize("enums", [
    # a driver without per-device bucketing counts no slot_rows_sum
    [_stats(), _stats()],
    # one enumeration of several lacks it
    [_stats(slot_rows_sum=200), _stats()],
    # no enumeration in the window, or no slot counted
    [],
    [_stats(slot_rows_sum=0)],
])
def test_reader_finds_nothing_where_the_counter_is_absent(enums):
    assert reader().read(dict(counters=dict(enumerations=enums))) is None
