"""``chip_smoke.py`` on the CPU: its phases at tiny size, its refusal to run
without a TPU, and where the compile cache goes."""
import importlib.util
import os

import pytest
import jax

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def cache_config():
    """Restore the compile-cache settings a test changes."""
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


PHASES = {
    "one_shot_pallas": lambda m: m.one_shot("Grid_4x4", "pallas", "cpu"),
    "one_shot_jnp": lambda m: m.one_shot("Grid_4x4", "jnp", "cpu"),
    "store": lambda m: m.store_mode("Grid_4x4", "cpu", rounds_per_launch=4),
    "serve": lambda m: m.served(6, "cpu", recycle=False),
    "serve_recycled": lambda m: m.served(6, "cpu", recycle=True),
    "sharded": lambda m: m.sharded("Grid_4x4", "cpu", n_devices=1,
                                   local_capacity=1 << 10,
                                   balance_block=64),
}


@pytest.mark.parametrize("phase", sorted(PHASES))
def test_phase_runs_at_tiny_size(smoke, phase, capsys):
    out = PHASES[phase](smoke)
    line = capsys.readouterr().out.strip().splitlines()[-1]
    assert line.startswith("[cpu] ")
    assert out["cycles"] == 24 or phase.startswith("serve")
    assert "peak_bytes_in_use" in out


def test_main_refuses_cpu(smoke, capsys, cache_config):
    rc = smoke.main([])
    out = capsys.readouterr()
    assert rc != 0
    assert '"ok"' not in out.out
    assert "needs a TPU" in out.err


def test_compile_cache_honours_env(monkeypatch, tmp_path, cache_config):
    from repro.launch.env import enable_compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_ignored_checkout_dir(monkeypatch,
                                                        cache_config):
    from repro.launch.env import REPO_CACHE_DIR, enable_compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert enable_compile_cache() == REPO_CACHE_DIR
    assert jax.config.jax_compilation_cache_dir == REPO_CACHE_DIR
    assert os.path.dirname(REPO_CACHE_DIR) == ROOT
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()

