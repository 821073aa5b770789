"""Both drivers through tiny cells on the CPU, with the system sound, with
a fault planted under the timed path, and with the control in its place;
and the harness's refusal to print a result without a chip."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench import control

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.mark.parametrize("name", ["tiny_count", "tiny_store", "tiny_mix"])
def test_sound_run_is_correct(run_tiny, name):
    res = run_tiny(name)
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert all(c["value"] == 0 == c["limit"] for c in res["checks"].values())
    assert list(res)[-2:] == ["checks", "window"]
    if name != "tiny_mix":
        # one whole enumeration in set-up compiles all the window runs
        assert res["window"]["loads"] == {}
    metrics = res["metrics"]
    assert metrics["setup_s"]["value"] > 0
    if name == "tiny_mix":
        assert metrics["e2e_p95_ms"]["unit"] == "ms"
        assert metrics["graphs_per_s"]["value"] > 0
    else:
        assert metrics["oneshot_s"]["value"] > 0


def test_seed_sets_the_requests(run_tiny):
    """The same seed offers the same requests; the count is the rate times
    the window whatever the seed."""
    a = run_tiny("tiny_mix", seed=5)
    b = run_tiny("tiny_mix", seed=2**33 + 1)
    assert a["attempted"] == b["attempted"] == 20


class _Faulty:
    """The system's service with one fault planted where answers come out."""

    @staticmethod
    def make(fault: str):
        from repro.core import CycleService

        class Service(CycleService):
            def enumerate(self, g, **kw):
                res = super().enumerate(g, **kw)
                return _Faulty.alter(fault, res)

            def serve_stream(self, graphs, **kw):
                for i, res in super().serve_stream(graphs, **kw):
                    if fault == "half_left_out" and i % 2:
                        continue
                    yield i, _Faulty.alter(fault, res)
        return Service

    @staticmethod
    def alter(fault, res):
        if fault == "state_unchanged":
            # as if no round ever ran: only the triangles of stage 1
            res.n_cycles = res.n_triangles
            if res.cycle_masks is not None:
                res.cycle_masks = res.cycle_masks[:res.n_triangles]
        elif fault == "answer_altered":
            res.n_cycles += 1
        elif fault == "half_left_out" and res.cycle_masks is not None:
            res.cycle_masks = res.cycle_masks[::2]
        elif fault == "vertex_set_altered" and res.cycle_masks is not None:
            res.cycle_masks = res.cycle_masks.copy()
            res.cycle_masks[0, 0] ^= np.uint32(1)
        return res


@pytest.mark.parametrize("name,fault", [
    ("tiny_count", "state_unchanged"), ("tiny_count", "answer_altered"),
    ("tiny_store", "state_unchanged"), ("tiny_store", "half_left_out"),
    ("tiny_store", "vertex_set_altered"),
    ("tiny_mix", "state_unchanged"), ("tiny_mix", "half_left_out"),
    ("tiny_mix", "answer_altered")])
def test_planted_fault_is_not_correct(run_tiny, monkeypatch, name, fault):
    import repro.core as core
    monkeypatch.setattr(core, "CycleService", _Faulty.make(fault))
    res = run_tiny(name)
    assert res["correct"] is False
    assert res["failed"] > 0
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


@pytest.mark.parametrize("name", ["tiny_count", "tiny_store", "tiny_mix"])
def test_control_is_not_correct(run_tiny, monkeypatch, name):
    import repro.core as core
    for attr, obj in control.REPLACEMENTS.items():
        monkeypatch.setattr(core, attr, obj)
    res = run_tiny(name)
    assert res["correct"] is False
    assert res["checks"]["count_error_max"]["value"] > 0


def _run_main(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "oneshot_grid6x10_count", "--seed", str(2**31 + 3),
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _has_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if "correct" in json.loads(line):
                return True
        except (ValueError, TypeError):
            continue
    return False


def test_no_chip_no_result(tmp_path):
    done = _run_main(ROOT, {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert done.returncode == 2, done.stderr[-2000:]
    assert not _has_result(done.stdout)
    assert "needs 1 TPU chip" in done.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    """A directory with BENCHMARK.json and the benchmark's own files, and
    no system to measure, exits non-zero with no result line."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    done = _run_main(str(tmp_path))
    assert done.returncode != 0
    assert not _has_result(done.stdout)
