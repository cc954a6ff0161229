"""Where JAX's persistent compilation cache lives.

Entry points (``repro.launch.serve``, ``benchmarks/run.py``,
``chip_smoke.py``) call ``configure_compile_cache`` before their first
compile.  A cache is found again only at the same path, so the default is
one fixed directory inside the checkout — never a temp name, a pid or a
time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_compile_cache() -> str:
    """Place the persistent compilation cache and return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already reads it and
    nothing is set here; otherwise the cache goes to ``<checkout>/.jax_cache``
    (listed in ``.gitignore``)."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
