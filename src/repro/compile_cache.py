"""Where JAX's persistent compilation cache lives.

The cache's directory is part of what makes an entry hit, so it is fixed:
``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX reads the
variable itself, and nothing else is set), otherwise ``.jax_cache`` at the
root of this checkout (listed in ``.gitignore``). Entry points call
:func:`enable` from their ``main``; importing the package never does.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = Path(__file__).resolve().parents[2]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
