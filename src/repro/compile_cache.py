"""Where JAX's persistent compilation cache lives.

One directory per checkout, chosen from outside the program: the
``JAX_COMPILATION_CACHE_DIR`` environment variable when it is set,
otherwise ``.jax_cache/`` at the checkout root.  The path is fixed — no
temporary name, process id or time — so the next run of the same
checkout finds what this one compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: ``<checkout>/.jax_cache`` (this file is ``<checkout>/src/repro/...``).
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory.

    Call before the first compilation.  Sets no directory but the one
    returned: ``$JAX_COMPILATION_CACHE_DIR`` if set, else
    :data:`DEFAULT_DIR`.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
