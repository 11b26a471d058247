"""Where JAX keeps its persistent compilation cache for this repository's
entry points (the launchers and ``chip_smoke.py``; never on import).

A cache directory that moves never hits, so the place is fixed: the one
``JAX_COMPILATION_CACHE_DIR`` names when it is set (JAX reads it itself),
else ``<repo root>/.jax_cache`` (gitignored).
"""

from __future__ import annotations

import os
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[3]


def use_persistent_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = str(REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
