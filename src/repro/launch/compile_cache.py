"""Persistent XLA compilation cache for the entry points.

``launch/serve.py``, ``launch/train.py`` and ``chip_smoke.py`` call
``enable_compile_cache()`` at start-up; importing this module changes
nothing.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses that
directory and no other is set.  Otherwise the cache goes to one fixed path
inside the checkout, so a later process that compiles the same program on
the same device finds it there.

Either way the checkout's own path is kept out of the cache key.  A Pallas
TPU kernel is embedded in the program as serialized Mosaic code that
carries the absolute path of its source file, so without this every
checkout directory would compile its kernels afresh.
"""

from __future__ import annotations

import os
import re
from pathlib import Path

import jax

SRC_DIR = Path(__file__).resolve().parents[2]
CHECKOUT_CACHE_DIR = SRC_DIR.parent / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    jax.config.update(
        "jax_hlo_source_file_canonicalization_regex",
        "^" + re.escape(str(SRC_DIR) + os.sep),
    )
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
