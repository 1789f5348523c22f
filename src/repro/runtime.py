"""Process set-up for programs that drive the device.

Called by the entry points that compile device programs (``chip_smoke.py``,
``benchmarks/run.py``, ``scripts/dedupe_estimate.py``) — never at import,
so a library user keeps full control of JAX's configuration.
"""
from __future__ import annotations

import os
from pathlib import Path

#: the checkout this package was loaded from
CHECKOUT = Path(__file__).resolve().parents[2]
#: where compiled programs are kept when nothing else says: a fixed path
#: inside the checkout (ignored by git), so reruns from the same checkout
#: find what earlier runs compiled
DEFAULT_CACHE_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is left to JAX, which reads it
    itself; no other directory is set.  Otherwise the cache goes to
    ``<checkout>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
