"""Where the persistent compilation cache lands.

Each case runs in a child process: turning the cache on is process-wide,
and the test process itself never turns it on.
"""

import os
import pathlib
import subprocess
import sys

_SRC = str(pathlib.Path(__file__).resolve().parents[1] / "src")

_SCRIPT = r"""
import pathlib, sys
import jax, jax.numpy as jnp
from repro.launch import compile_cache
compile_cache.DEFAULT_DIR = pathlib.Path(sys.argv[1])
print("DIR", compile_cache.enable_compile_cache())
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.jit(lambda x: jnp.sin(x) * 3)(jnp.ones(8)).block_until_ready()
"""


def _run(default_dir, env_dir=None):
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [_SRC] + os.environ.get("PYTHONPATH", "").split(os.pathsep)))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    proc = subprocess.run([sys.executable, "-c", _SCRIPT, str(default_dir)],
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return proc.stdout.split("DIR ", 1)[1].split()[0]


def test_cache_goes_to_the_fixed_directory(tmp_path):
    fixed = tmp_path / "fixed"
    assert _run(fixed) == str(fixed)
    assert any(p.name.endswith("-cache") for p in fixed.iterdir())


def test_cache_follows_the_environment(tmp_path):
    fixed, chosen = tmp_path / "fixed", tmp_path / "chosen"
    assert _run(fixed, env_dir=chosen) == str(chosen)
    assert any(p.name.endswith("-cache") for p in chosen.iterdir())
    assert not fixed.exists()


def test_default_directory_is_inside_the_checkout():
    from repro.launch import compile_cache
    root = pathlib.Path(__file__).resolve().parents[1]
    assert compile_cache.DEFAULT_DIR == root / ".jax_cache"
