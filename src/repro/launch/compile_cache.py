"""JAX persistent compilation cache for the entry points.

``enable_compile_cache()`` is called from each entry point's ``main()``
(never at import): ``repro.launch.train``, ``repro.campaign.run``,
``benchmarks.run`` and ``chip_smoke.py``.  ``JAX_COMPILATION_CACHE_DIR``,
when set, wins and nothing else is touched (JAX reads that variable
itself); otherwise the cache lives at the fixed ``<checkout>/.jax_cache``
— a stable path, since the path is part of what makes a later process hit.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
