"""The process's one persistent-compilation-cache setting.

Every entry point that wants compiled executables to survive the process
(tests, the benchmark, the scripts, ``ExpertConfig.compile_cache``)
calls ``enable_compile_cache`` — nothing else in the tree names a cache
directory.  The directory is placeable from outside: where
``JAX_COMPILATION_CACHE_DIR`` is set jax reads it itself and this module
sets no directory in code; otherwise it is the fixed ``.jax_cache`` next
to the package (the path is part of the cache key, so it must not move).
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: the jax option this module owns; nothing else in the tree names it
CACHE_DIR_OPTION = "jax_compilation_cache_dir"


def compile_cache_dir() -> str | None:
    """Where the cache lives, or None under the
    ``DRAGONBOAT_TPU_COMPILE_CACHE=0`` veto.  Touches no jax state."""
    if os.environ.get("DRAGONBOAT_TPU_COMPILE_CACHE", "1") == "0":
        return None
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(_CHECKOUT, ".jax_cache"))


def cache_entry_count() -> int:
    """Executables persisted so far (0 when vetoed or not yet created)."""
    d = compile_cache_dir()
    if d is None or not os.path.isdir(d):
        return 0
    return sum(1 for n in os.listdir(d) if n.endswith("-cache"))


def enable_compile_cache(min_compile_secs: float = 1.0) -> str | None:
    """Turn the persistent compilation cache on; returns its directory,
    or None when vetoed (then nothing is set)."""
    cache_dir = compile_cache_dir()
    if cache_dir is None:
        return None
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update(CACHE_DIR_OPTION, cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      float(min_compile_secs))
    return cache_dir
