"""Where JAX keeps its persistent compilation cache for the entry points.

A BERT-large train step takes tens of seconds to compile; the cache lets
a second process (or a second run on the same checkout) skip that.  The
cache directory is part of every entry's key, so it is a fixed path,
never one derived from a temporary name, a process id or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: ``<checkout>/.jax_cache`` (this file is ``<checkout>/src/repro/launch/``)
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it into
    ``jax_compilation_cache_dir`` by itself, so nothing is changed.
    Otherwise the cache goes to :data:`CHECKOUT_CACHE_DIR`.  Call it from
    a script's ``__main__`` block, before the first compile — never from a
    function that tests call in-process.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
