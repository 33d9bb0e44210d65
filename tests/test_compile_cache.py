"""Where the persistent compilation cache lands (``repro.compile_cache``)."""

import os
import pathlib
import subprocess
import sys

import pytest

_REPO = pathlib.Path(__file__).resolve().parents[1]

_SCRIPT = """
import pathlib, sys
import repro.compile_cache as cc
cc.DEFAULT_DIR = pathlib.Path(sys.argv[1])
cc.use_compile_cache()
import jax, jax.numpy as jnp
jax.jit(lambda x: x * 2 + 1)(jnp.arange(8.0)).block_until_ready()
"""


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_dir(tmp_path, from_env):
    """With ``JAX_COMPILATION_CACHE_DIR`` set, entries land there and the
    default directory stays empty; without it, they land in the default."""
    env_dir, default_dir = tmp_path / "env", tmp_path / "default"
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(_REPO / "src"))
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    r = subprocess.run([sys.executable, "-c", _SCRIPT, str(default_dir)],
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    used, unused = (env_dir, default_dir) if from_env else (default_dir, env_dir)
    assert any(used.iterdir())
    assert not unused.exists() or not any(unused.iterdir())
