"""Where JAX keeps its persistent compilation cache.

Called once by each entry point (launch/train.py, benchmarks/run.py,
benchmarks/chip/run.py, chip_smoke.py), never at import time:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and this module
  sets nothing, so the cache lands only where the environment says.
* unset: the cache goes to ``<checkout>/.jax_cache`` — a fixed path, since
  the path is part of what a later run must find again (it is listed in
  ``.gitignore``).
"""

from __future__ import annotations

import os
import pathlib

CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
