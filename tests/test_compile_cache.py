"""The entry points' persistent compilation cache helper."""

import os
import shutil
import subprocess
import sys
import textwrap

import pytest

from repro.launch import compile_cache


def test_default_cache_dir_is_in_the_checkout():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert compile_cache.default_cache_dir() == os.path.join(root,
                                                             ".jax_cache")


@pytest.mark.parametrize("env_set", [True, False], ids=["env", "checkout"])
def test_cache_entries_land_in_one_directory(tmp_path, env_set):
    """Cache entries appear under ``$JAX_COMPILATION_CACHE_DIR`` when it is
    set and under ``<checkout>/.jax_cache`` otherwise — never in both. The
    helper runs from a copy laid out as a checkout under ``tmp_path``, in a
    subprocess, because the cache is process-global."""
    checkout = tmp_path / "checkout"
    launch = checkout / "src" / "repro" / "launch"
    launch.mkdir(parents=True)
    shutil.copy(compile_cache.__file__, launch / "compile_cache.py")
    env_dir = tmp_path / "env_cache"
    home_dir = checkout / ".jax_cache"
    (tmp_path / "home").mkdir()
    code = textwrap.dedent("""
        import importlib.util, sys
        import jax, jax.numpy as jnp
        spec = importlib.util.spec_from_file_location("cc", sys.argv[1])
        cc = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(cc)
        print(cc.enable_compile_cache())
        jax.jit(lambda x: jnp.sin(x) * 2)(jnp.ones(8)).block_until_ready()
    """)
    env = dict(os.environ, JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               HOME=str(tmp_path / "home"), TMPDIR=str(tmp_path))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_set:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    r = subprocess.run([sys.executable, "-c", code,
                        str(launch / "compile_cache.py")],
                       capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode == 0, r.stderr
    want, other = (env_dir, home_dir) if env_set else (home_dir, env_dir)
    assert r.stdout.strip().splitlines()[-1] == str(want)
    assert any(want.iterdir())
    assert not other.exists()
