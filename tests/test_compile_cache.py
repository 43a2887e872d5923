"""Where the persistent compilation cache goes: JAX_COMPILATION_CACHE_DIR
when it is set, else the fixed in-checkout directory git ignores."""

import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro import compile_cache

ROOT = Path(__file__).resolve().parents[1]

_PROBE = """
import os, jax, jax.numpy as jnp
from repro.compile_cache import enable_compile_cache
print(enable_compile_cache())
jax.jit(lambda x: x * 3 + 1)(jnp.arange(8)).block_until_ready()
"""


def _run_probe(env: dict) -> str:
    out = subprocess.run([sys.executable, "-c", _PROBE], env=env,
                         capture_output=True, text=True, timeout=120,
                         check=True)
    return out.stdout.strip().splitlines()[-1]


def _env(**extra) -> dict:
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"), **extra)
    return env


def test_env_dir_is_the_only_cache(tmp_path):
    """With the variable set, JAX reads it itself and the entries land
    there and nowhere else the code chose."""
    where = _run_probe(_env(JAX_COMPILATION_CACHE_DIR=str(tmp_path)))
    assert where == str(tmp_path)
    assert any(p.name.endswith("-cache") for p in tmp_path.iterdir())


def test_default_dir_is_fixed_inside_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    prev = (jax.config.jax_compilation_cache_dir,
            jax.config.jax_persistent_cache_min_compile_time_secs)
    try:
        where = compile_cache.enable_compile_cache()
        assert where == str(ROOT / ".jax_cache")
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    finally:
        jax.config.update("jax_compilation_cache_dir", prev[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          prev[1])
    ignored = (ROOT / ".gitignore").read_text().splitlines()
    assert ".jax_cache/" in ignored


@pytest.mark.parametrize("name", ["chip_smoke.py", "benchmarks/run.py",
                                  "examples/quickstart.py",
                                  "examples/serve_cpq.py"])
def test_entry_points_place_the_cache(name):
    """The entry points call it; importing ``repro`` does not."""
    assert "enable_compile_cache()" in (ROOT / name).read_text()
    for path in (ROOT / "src" / "repro").rglob("*.py"):
        if path.name != "compile_cache.py":
            assert "enable_compile_cache" not in path.read_text(), path
