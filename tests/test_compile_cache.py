"""repro.launch.compile_cache: a fixed per-checkout cache directory unless
JAX_COMPILATION_CACHE_DIR names one.  Each case runs fresh processes,
since the cache is process-wide JAX configuration."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SCRIPT = """
import pathlib, sys
from repro.launch import compile_cache
compile_cache.DEFAULT_DIR = pathlib.Path(sys.argv[1])
print(compile_cache.enable_compile_cache())
import jax, jax.numpy as jnp
jax.jit(lambda x: jnp.sin(x) * 2 + 1)(jnp.ones(3)).block_until_ready()
"""


def _run(default_dir, cache_env=None):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=os.path.join(ROOT, "src"))
    if cache_env is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(cache_env)
    proc = subprocess.run([sys.executable, "-c", _SCRIPT, str(default_dir)],
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return proc.stdout.strip().splitlines()[-1]


def _entries(path):
    return sorted(os.listdir(path)) if os.path.isdir(path) else []


@pytest.mark.parametrize("variable", ["unset", "set"])
def test_second_run_hits_one_fixed_dir(tmp_path, variable):
    default_dir = tmp_path / ".jax_cache"
    cache_env = tmp_path / "env_cache" if variable == "set" else None
    used = cache_env or default_dir
    assert _run(default_dir, cache_env) == str(used)
    first = _entries(used)
    assert first
    assert _run(default_dir, cache_env) == str(used)
    assert _entries(used) == first          # all hits, nothing recompiled
    if cache_env is not None:
        assert not default_dir.exists()     # nothing written elsewhere
