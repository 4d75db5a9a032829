"""The entry points' compile cache: ``JAX_COMPILATION_CACHE_DIR`` wins and
nothing is set in code; otherwise ``<checkout>/.jax_cache``; importing the
helper sets nothing."""

import os
import subprocess
import sys
from pathlib import Path

SRC = Path(__file__).resolve().parents[1] / "src"

PROBE = """
import jax, jax.numpy as jnp
from repro.launch import compile_cache
before = jax.config.jax_compilation_cache_dir
used = compile_cache.enable()
jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((64, 64))).block_until_ready()
print(repr(before), used, jax.config.jax_compilation_cache_dir)
"""


def _probe(tmp_path, **env):
    base = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    r = subprocess.run(
        [sys.executable, "-c", PROBE], capture_output=True, text=True,
        timeout=300, cwd=tmp_path,
        env={**base, "PYTHONPATH": str(SRC), "JAX_PLATFORMS": "cpu",
             "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0", **env})
    assert r.returncode == 0, r.stderr
    return r.stdout.split()


def test_env_dir_is_used_and_nothing_is_set(tmp_path):
    cache = tmp_path / "cache"
    before, used, after = _probe(tmp_path,
                                 JAX_COMPILATION_CACHE_DIR=str(cache))
    assert before == repr(str(cache)) and used == after == str(cache)
    assert any(cache.iterdir()), "compile wrote no cache entry"
    assert sorted(p.name for p in tmp_path.iterdir()) == ["cache"]


def test_default_is_the_checkout_cache():
    from repro.launch import compile_cache

    assert compile_cache.CHECKOUT_CACHE == SRC.parent / ".jax_cache"
    probe = ("import jax; from repro.launch import compile_cache; "
             "print(jax.config.jax_compilation_cache_dir)")
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    r = subprocess.run([sys.executable, "-c", probe], capture_output=True,
                       text=True, timeout=120,
                       env={**env, "PYTHONPATH": str(SRC)})
    assert r.stdout.split() == ["None"], "importing must not set the cache"
    assert ".jax_cache/" in (SRC.parent / ".gitignore").read_text().split()
