"""Persistent XLA compile cache, placed from outside the program.

Every fresh machine compiles the trainer's rollout/update programs and
the serve engine's bucket lattice cold; JAX's persistent compilation
cache turns the second process on the same machine into a disk read.
Where the cache lives is the operator's call, not the program's:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself, so
  this module sets NO directory at all — whatever mounted that path
  (a volume that outlives the machine, a per-job scratch) keeps control.
- unset: ``<checkout>/.jax_cache``, resolved from this package's own
  location — the cache directory is part of the cache key, so it must
  not move between runs (no tempfile, pid or time in the path) and must
  not depend on the working directory. ``.gitignore`` lists it.

Called by the entry points only (``chip_smoke.py``,
``benchmarks/run.py``, ``python -m trlx_tpu.serve``, ``examples/*.py``,
``__graft_entry__.py``) and never on ``import trlx_tpu``: a library import must not start
writing files. Tests stay cache-less (tests/conftest.py).
"""

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def default_cache_dir() -> str:
    """``<checkout>/.jax_cache`` — beside the ``trlx_tpu`` package."""
    package_dir = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(package_dir), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns the directory it
    lives in (for the entry point's start-up log line)."""
    from_env = os.environ.get(ENV_VAR)
    if from_env:
        return from_env
    import jax

    path = default_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
