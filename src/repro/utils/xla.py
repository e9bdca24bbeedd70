"""XLA compiled-artifact helpers: cost introspection and the persistent
compilation cache shared by the entry points."""
from __future__ import annotations

import os
from pathlib import Path
from typing import Any, Dict

import jax

#: the checkout-local cache directory used when the environment names none.
#: A fixed path, so a second run of the same command finds the first one's
#: entries.
CHECKOUT_CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def cost_analysis_dict(compiled) -> Dict[str, Any]:
    """``Compiled.cost_analysis()`` as a plain dict, empty on backends
    that report none, so callers can ``.get`` keys."""
    return compiled.cost_analysis() or {}


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point and
    return its directory. When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
    already reads it and nothing is set here; otherwise the cache lives in
    the fixed ``<checkout>/.jax_cache``, so a second run of the same
    command reuses the first one's compiled programs."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
