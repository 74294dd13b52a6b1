"""Test configuration.

Tests run on CPU with 8 virtual XLA devices so multi-chip sharding paths
(mesh/pjit/shard_map) are exercised without TPU hardware. A CPU run
gives results and counts, never speeds. The platform is forced through
jax.config as well as the environment, before any backend
initialization.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# Persistent XLA compilation cache: the tier-1 suite is COMPILE-
# dominated on CPU (per-geometry jits + interpret-mode Pallas), and
# the cache is keyed on the lowered program + compile flags, so repeat
# suite runs on one box reload executables instead of re-invoking XLA.
# Placed by the package's one rule (JAX_COMPILATION_CACHE_DIR, else
# the git-ignored <checkout>/.jax_cache).
from ziria_tpu.utils import compile_cache  # noqa: E402

compile_cache.place()
