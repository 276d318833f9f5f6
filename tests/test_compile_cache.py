"""Where ``repro.compile_cache.enable_compile_cache`` puts JAX's persistent
compilation cache.  Each case runs in a fresh interpreter: JAX fixes its
cache at the first compile of a process."""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

from repro.compile_cache import REPO_CACHE_DIR

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD = textwrap.dedent("""
    import sys, time
    import jax, jax.numpy as jnp
    from repro.compile_cache import enable_compile_cache
    path = enable_compile_cache()
    # a program no other process compiles, so it cannot be a cache hit
    salt = time.time_ns()
    jax.jit(lambda x: jnp.cumsum(x * 3.0) + salt % 997)(
        jnp.arange(17.0)).block_until_ready()
    print(path)
""")


def _run(env_dir):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=os.path.join(ROOT, "src"))
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run([sys.executable, "-c", _CHILD], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.strip().splitlines()[-1]


def _entries(path):
    return {f for _, _, files in os.walk(path) for f in files}


def test_cache_lands_in_the_env_dir(tmp_path):
    before = _entries(REPO_CACHE_DIR)
    assert _run(tmp_path) == str(tmp_path)
    assert _entries(tmp_path), "no cache entry was written"
    assert _entries(REPO_CACHE_DIR) <= before  # and nowhere else


def test_cache_defaults_to_the_repo_dir():
    before = _entries(REPO_CACHE_DIR)
    assert _run(None) == str(REPO_CACHE_DIR)
    assert REPO_CACHE_DIR == Path(ROOT) / ".jax_cache"
    assert _entries(REPO_CACHE_DIR) - before, "no cache entry was written"
