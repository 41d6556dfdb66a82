"""chip_smoke.py refuses to run without a TPU, and the compile cache is
placed from outside or at a fixed path in the checkout."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.utils import compile_cache

ROOT = Path(__file__).resolve().parents[1]


def _run_smoke(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_chip_smoke_refuses_the_cpu():
    res = _run_smoke(ROOT)
    assert res.returncode != 0
    assert "needs a TPU" in res.stderr
    assert '"ok"' not in res.stdout


def test_chip_smoke_refuses_without_the_repository(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    res = _run_smoke(tmp_path)
    assert res.returncode != 0
    assert '"ok"' not in res.stdout


@pytest.fixture
def config_updates(monkeypatch):
    """Record jax.config.update calls instead of making them: tests
    never turn the persistent cache on."""
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_compile_cache_leaves_a_placed_dir_alone(monkeypatch,
                                                 config_updates, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert config_updates == []


def test_compile_cache_defaults_to_the_checkout(monkeypatch,
                                                config_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = str(ROOT / ".jax_cache")
    assert compile_cache.enable_compile_cache() == path
    assert config_updates == [("jax_compilation_cache_dir", path)]
    # the same path on every call: no pid, time or temporary name in it
    assert compile_cache.enable_compile_cache() == path
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text()
