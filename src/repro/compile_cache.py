"""JAX's persistent compilation cache for the repo's entry points.

Scripts call :func:`use_compile_cache` from their ``main()``; no library
module touches the cache when it is imported.
"""

from __future__ import annotations

import os
import pathlib

__all__ = ["use_compile_cache"]


def use_compile_cache(default_dir: str | os.PathLike) -> str:
    """Keep compiled programs on disk and return the directory used.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    other path is set here.  Otherwise the cache goes to ``default_dir``,
    a fixed path, so that later runs of the same program find it again.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = str(pathlib.Path(default_dir).resolve())
    jax.config.update("jax_compilation_cache_dir", path)
    return path
