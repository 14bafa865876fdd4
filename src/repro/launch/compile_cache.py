"""JAX's persistent compilation cache, at one fixed place per checkout.

Call ``enable()`` before the first compile of a process.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
set here.  Otherwise the cache lives in ``<checkout>/.jax_cache``: a
fixed path (the path is part of each entry's key, so a directory that
moves never hits), listed in ``.gitignore``.
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable() -> Optional[Path]:
    """Point the persistent compilation cache at its directory; returns
    the directory this code chose, or None when the environment chose."""
    if os.environ.get(ENV):
        return None
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return DEFAULT_DIR
