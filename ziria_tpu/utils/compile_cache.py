"""The ONE placement of JAX's persistent compilation cache.

Cold, the served receiver's two programs cost minutes of XLA time at
MTU geometry, so every entry point that compiles them shares one
on-disk cache — and the cache's directory is part of its key, so a
directory that moves never hits. The rule, decided here and nowhere
else:

- ``JAX_COMPILATION_CACHE_DIR`` set: the operator has placed the
  cache. JAX reads the variable itself; this code sets no directory.
- unset: ``<checkout>/.jax_cache`` (git-ignored) — a fixed path,
  never a temporary name, a pid or a time.

``serve.main``, ``cli.main``, ``programs.main``, ``chip_smoke.py`` and
``tests/conftest.py`` call :func:`place` before their first compile.
"""

from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"


def env_dir() -> str:
    """The ONE reading of ``JAX_COMPILATION_CACHE_DIR`` ("" if unset)."""
    return os.environ.get(ENV, "")


def checkout_dir() -> str:
    """``<checkout>/.jax_cache``: the parent of the package directory."""
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(pkg), ".jax_cache")


def place() -> str:
    """Place the persistent compile cache by the module's rule and
    return the directory in use. Call before the first compile (JAX
    initializes its cache once per process)."""
    import jax

    # the receive path is many sub-second programs on the CPU suite
    # and two multi-minute ones on the chip: cache both kinds
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    env = env_dir()
    if env:
        return env      # JAX reads the variable itself
    path = checkout_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
