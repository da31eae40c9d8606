"""The persistent compile-cache helper: the environment wins, otherwise one
fixed directory inside the checkout."""
import os
import subprocess
import sys

import jax

from repro.launch import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_env_var_sets_the_cache_dir(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, JAX's configured directory is that
    one and the helper reports it (fresh process: JAX reads the variable at
    start-up)."""
    prog = ("import jax\n"
            "from repro.launch.compile_cache import configure_compile_cache\n"
            "print(configure_compile_cache())\n"
            "print(jax.config.jax_compilation_cache_dir)\n")
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PLATFORMS="cpu", PYTHONPATH=os.path.join(ROOT, "src"))
    r = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                       text=True, env=env, timeout=300)
    assert r.returncode == 0, r.stderr
    assert r.stdout.split() == [str(tmp_path), str(tmp_path)]


def test_default_is_one_fixed_in_repo_dir(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        first = compile_cache.configure_compile_cache()
        second = compile_cache.configure_compile_cache()
        configured = jax.config.jax_compilation_cache_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert first == second == configured
    assert first == os.path.join(ROOT, ".jax_cache")
