"""Persistent XLA compilation cache for the entry points.

Called by ``launch/serve.py``, ``launch/train.py`` and ``chip_smoke.py``
when they start -- never on import and never from tests -- so that a
second run of the same program on the same checkout skips compilation.
"""
from __future__ import annotations

import os

import jax

# <checkout>/.jax_cache: a fixed path (git ignored), never a temp, pid or
# time path, so a later run of the same tree finds what an earlier run
# cached
DEFAULT_DIR = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..",
    ".jax_cache"))


def enable() -> str:
    """Turn the cache on and return its directory.  Where
    ``$JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps the cache
    there and nothing else is set."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
