"""CPU checks of the chip entry point and of the compile-cache helper.

``chip_smoke.py`` must refuse to run anywhere but on a TPU and print no
result line there; the cache helper must honour ``JAX_COMPILATION_CACHE_DIR``,
fall back to ``<checkout>/.jax_cache``, and set nothing when imported.
"""
import os
import subprocess
import sys
import types

import jax
import pytest

import chip_smoke
from repro.launch import cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_refuses_cpu_backend(capsys):
    assert jax.devices()[0].platform == "cpu"
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert exc.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out


@pytest.mark.parametrize("count", [1, 4])
def test_device_record_on_tpu(count, capsys):
    dev = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    fake_jax = types.SimpleNamespace(devices=lambda: [dev] * count)
    assert chip_smoke.device_check(fake_jax) == {
        "platform": "tpu", "kind": "TPU v5 lite", "count": count}
    assert "TPU v5 lite" in capsys.readouterr().out


@pytest.fixture
def cache_config():
    """Restore JAX's cache directory after a test changes it."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_cache_honours_env(monkeypatch, tmp_path, cache_config):
    monkeypatch.setenv(cache.ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_default_is_checkout(monkeypatch, cache_config):
    monkeypatch.delenv(cache.ENV, raising=False)
    assert cache.DEFAULT_DIR == os.path.join(ROOT, ".jax_cache")
    assert cache.enable_compile_cache() == cache.DEFAULT_DIR
    assert jax.config.jax_compilation_cache_dir == cache.DEFAULT_DIR


def test_cache_not_set_at_import():
    env = {k: v for k, v in os.environ.items() if k != cache.ENV}
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run(
        [sys.executable, "-c",
         "import jax, repro.launch.cache, repro.launch.train, "
         "repro.launch.serve; print(jax.config.jax_compilation_cache_dir)"],
        capture_output=True, text=True, env=env, cwd=ROOT)
    assert r.returncode == 0, r.stderr
    assert r.stdout.strip().splitlines()[-1] == "None"
