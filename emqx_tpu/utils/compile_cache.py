"""Where JAX's persistent compilation cache lives.

Every entry point that traces a route program (chip_smoke.py, bench.py,
tools/run_node.py, tests/conftest.py) calls `configure_compile_cache()`
before its first trace, so repeat runs in one checkout — or in one
`JAX_COMPILATION_CACHE_DIR` — skip the XLA compile of the standard
classes and their cached / compact / delta variants.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", ".."))


def configure_compile_cache() -> str:
    """Place the persistent compile cache; returns the directory in effect.

    `JAX_COMPILATION_CACHE_DIR` set: JAX reads it itself and no directory
    is set in code. Unset: `<checkout>/.jax_cache` as an absolute,
    normalised path — never a temp name, pid or timestamp, since a cache
    that moves between runs never hits."""
    import jax
    # an executable carries its operations' names (the route programs'
    # `jax.named_scope`s) as metadata, and a device trace is read by
    # them: by default the cache key leaves metadata out, and a hit then
    # hands back an executable with the names it was first built with
    jax.config.update("jax_compilation_cache_include_metadata_in_key",
                      True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def compile_cache_entries(path: str) -> int:
    """Number of entries in a cache directory (0 when it does not exist)."""
    try:
        return len(os.listdir(path))
    except FileNotFoundError:
        return 0
