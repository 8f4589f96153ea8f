"""Guards that keep the chip honest: the persistent compile cache lands at a
fixed place, nothing starts a second process on a TPU, and `chip_smoke.py`
never reports success without one."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro import utils

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)
    compilation_cache.reset_cache()


def test_compile_cache_env_var_wins(monkeypatch, tmp_path,
                                    restore_compile_cache):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert utils.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_compile_cache_default_is_fixed_inside_checkout(
        monkeypatch, restore_compile_cache):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = utils.enable_compile_cache()
    assert utils.enable_compile_cache() == first
    assert Path(first) == REPO / ".jax_cache"
    assert jax.config.jax_compilation_cache_dir == first


def test_multihost_refuses_workers_on_tpu(monkeypatch):
    from repro.runtime.multihost import MultiHostCoordinator
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="belongs to one process"):
        MultiHostCoordinator(n_hosts=2, start=False)


def _smoke(script: Path, cwd: Path, env: dict):
    return subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_chip_smoke_fails_without_a_tpu():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    r = _smoke(REPO / "chip_smoke.py", REPO, env)
    assert r.returncode != 0 and r.stdout == ""
    assert "no TPU" in r.stderr


def test_chip_smoke_fails_alone_in_a_directory(tmp_path):
    shutil.copy(REPO / "chip_smoke.py", tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = _smoke(tmp_path / "chip_smoke.py", tmp_path, env)
    assert r.returncode != 0 and r.stdout == ""
