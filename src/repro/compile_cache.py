"""Where JAX keeps its persistent compilation cache.

One rule for every entry point (``chip_smoke.py``, ``python -m
repro.service.serve``, ``python -m repro.launch.train``, ``python -m
benchmarks.run``): when ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and nothing here names another path; otherwise the cache lives in
``<checkout>/.jax_cache``.  That path is fixed: it is part of the cache key,
so a path built from a temp name, a pid or the time would never hit.

Each module calls it from its ``__main__`` block, not from ``main()``: a
test that calls ``main()`` in-process keeps JAX's own cache settings.
"""

from __future__ import annotations

import os
import pathlib

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> pathlib.Path:
    """Turn the persistent cache on before the first compile; returns its
    directory.  Every program is cached, however fast it compiled: a cold
    run otherwise recompiles each descent bucket and kernel shape."""
    import jax

    env = os.environ.get(ENV_VAR)
    path = pathlib.Path(env) if env else DEFAULT_DIR
    if not env:
        jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
