"""Where JAX's persistent compilation cache lives — decided in ONE place
for the tests, the cluster workers, the chaos dryrun and ``chip_smoke.py``.

The cache directory is part of every entry's key, so a directory that
moves (a pid, a timestamp, a temp dir) never hits. Rule:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; this repo sets
  no directory and no threshold on top of it.
- unset: ``<checkout>/.jax_cache`` (git-ignored), with the size/time
  thresholds at zero so every program of a short run is kept.
"""
from __future__ import annotations

import os
from typing import Optional

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def enable() -> Optional[str]:
    """Turn the persistent compile cache on for this process; returns the
    directory this helper chose, or None when the environment's
    ``JAX_COMPILATION_CACHE_DIR`` decides."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return DEFAULT_DIR
