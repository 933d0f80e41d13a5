"""Persistent XLA compilation cache for the launchers.

Every site engine builds its own ``jax.jit`` closures, so a server with
four sites compiles the same prefill and decode programs four times; the
persistent cache turns the repeats into hits, and a later process on the
same checkout starts warm. Call :func:`enable_compile_cache` from a
launcher's entry point, never at import time.
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Optional, Union


def source_checkout() -> Optional[Path]:
    """The checkout this package runs from (src/repro/launch/ -> three
    levels up), or None for an installed package with no checkout."""
    root = Path(__file__).resolve().parents[3]
    return root if (root / "pyproject.toml").is_file() else None


def enable_compile_cache(root: Union[str, Path, None] = None
                         ) -> Optional[str]:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here. Otherwise the cache lives at the fixed, git-ignored
    ``<root>/.jax_cache``, ``root`` defaulting to the source checkout: the
    directory is part of what a later run must find again, so it is never
    built from a temp name, a pid or the time. With neither a root nor a
    checkout, no cache is set and None is returned."""
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    root = Path(root) if root is not None else source_checkout()
    if root is None:
        return None
    path = str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
