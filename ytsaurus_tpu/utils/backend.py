"""Backend selection + compile-cache placement for driver entry points.

Entry points (chip_smoke.py, __graft_entry__.py) call these
explicitly; nothing here runs at import time.  There is no fallback: a
process that was not told `JAX_PLATFORMS=cpu` by its caller either finds a
TPU in this process or raises.
"""

from __future__ import annotations

import os

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def place_compile_cache() -> str:
    """Decide where JAX's persistent compilation cache lives; call before
    the first backend use.  `JAX_COMPILATION_CACHE_DIR`, when set, is
    left alone (JAX reads it itself); otherwise the cache goes to the
    fixed `<checkout>/.jax_cache` — the path is part of the cache key,
    so it must not move.  Returns the directory in use."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax
    cache_dir = os.path.join(_REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir


def ensure_backend():
    """Returns the jax module with its backend initialized in THIS
    process: the CPU when the caller exported `JAX_PLATFORMS=cpu` (tests,
    rehearsals), otherwise the TPU — anything else raises."""
    import jax

    if os.environ.get("JAX_PLATFORMS", "") == "cpu":
        jax.config.update("jax_platforms", "cpu")
        jax.devices()
        return jax
    platform = jax.devices()[0].platform
    if platform != "tpu":
        raise RuntimeError(
            f"no TPU in this process (jax.devices()[0].platform == "
            f"{platform!r}); export JAX_PLATFORMS=cpu to run on the CPU "
            f"on purpose")
    return jax
