"""BENCHMARK.json: every entry resolves to its files, within the limits the
benchmark's contract sets, and a later cell, mix or metric is added by
files and entries alone."""
from __future__ import annotations

import json
import os
import re

import pytest

from bench import run as harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")
KEYS = {
    "top": {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"},
    "config": {"name", "source", "file", "reduced", "why"},
    "workload": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def one_line(text):
    return isinstance(text, str) and 1 <= len(text) <= 200 and \
        "\n" not in text and "\t" not in text


def test_shape_and_names(spec):
    assert set(spec) == KEYS["top"]
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert spec["paths"] == ["bench"]
    assert spec["command"] == ["python3", "bench/run.py"]
    assert 1 <= spec["run_seconds"] <= 51
    for kind, entries in (("config", spec["configs"]),
                          ("workload", spec["workloads"]),
                          ("end_to_end", spec["end_to_end"]),
                          ("per_layer", spec["per_layer"])):
        names = [e["name"] for e in entries]
        assert len(names) == len(set(names)), kind
        for e in entries:
            assert set(e) - {"workloads"} == KEYS[kind], e
            assert NAME.fullmatch(e["name"]), e["name"]
            if "unit" in e:
                assert UNIT.fullmatch(e["unit"]), e["unit"]
                assert e["better"] in ("lower", "higher")
            for key in ("why", "layer", "source"):
                if key in e:
                    assert one_line(e[key]), (e["name"], key)


def test_metrics_follow_the_contract(spec):
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert set(e2e) == {"setup_s", "oneshot_s"}
    assert e2e["setup_s"]["bound"] == 0.25
    for m in e2e.values():
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    cells = {w["name"] for w in spec["workloads"]}
    reported = {c: {n for n, m in e2e.items()
                    if c in m.get("workloads", [c])} for c in cells}
    for c in cells:
        assert "setup_s" in reported[c] and len(reported[c]) >= 2, c
    layers = set()
    for m in spec["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["moves"] in e2e
        for c in m["workloads"]:
            assert m["moves"] in reported[c], (m["name"], c)
        layers.add(m["layer"])
    assert layers == {"driver", "round", "kernels", "device"}
    for c in cells:
        assert any(c in m["workloads"] for m in spec["per_layer"]), c


def test_every_entry_resolves(spec):
    configs = {c["name"]: c for c in spec["configs"]}
    used = set()
    for w in spec["workloads"]:
        assert w["chips"] in (1, 4)
        cell = harness.load_cell(ROOT, w["name"])
        used.add(w["config"])
        assert os.path.isfile(os.path.join(
            ROOT, "bench", "drivers", cell.traffic["driver"] + ".py"))
        for m in cell.per_layer:
            assert os.path.isfile(os.path.join(ROOT, "bench", "metrics",
                                               m["name"] + ".py"))
    assert used == set(configs)
    files = [c["file"] for c in configs.values()]
    assert len(files) == len(set(files))
    for c in configs.values():
        assert c["file"].startswith("bench/")
        with open(os.path.join(ROOT, c["file"])) as f:
            body = json.load(f)
        assert body["name"] == c["name"] and body["reduced"] == c["reduced"]
    sources = [c["source"] for c in configs.values()]
    assert len(sources) == len(set(sources))


def test_four_chip_cells_and_budget(spec):
    cells = spec["workloads"]
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 2)
    # a full check of 24 cells fits in the 43,200 s a check may take
    runs = 2 + 14 * 24
    assert runs * (spec["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_paths_hold_only_allowed_file_names():
    for base, _, files in os.walk(os.path.join(ROOT, "bench")):
        if "__pycache__" in base:
            continue
        for name in files:
            rel = os.path.relpath(os.path.join(base, name), ROOT)
            assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", rel), rel


def test_later_cell_mix_and_metric_are_files_and_entries(tiny_root):
    """The fixture adds a configuration, three traffic mixes, three cells
    and a per-layer metric without editing a file: the harness finds
    them all by name."""
    cell = harness.load_cell(tiny_root, "tiny_mix")
    assert cell.config["families"]["cycles"]["graphs"][0] == "C_8"
    assert cell.traffic["driver"] == "open_loop"
    assert [m["name"] for m in cell.end_to_end] == \
        ["setup_s", "graphs_per_s", "e2e_p95_ms"]
    assert "tiny_dummy" in [m["name"] for m in cell.per_layer]
    reader = harness.load_module(os.path.join(
        tiny_root, "bench", "metrics", "tiny_dummy.py"))
    assert reader.read(dict(counters=dict(completed=3))) == 1.0
    with pytest.raises(KeyError):
        harness.load_cell(tiny_root, "no_such_cell")
