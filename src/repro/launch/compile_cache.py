"""Where JAX keeps its persistent compilation cache for the entry points.

A BERT-large train step takes tens of seconds to compile; the cache lets
a second process (or a second run on the same checkout) skip that.  The
cache directory is part of every entry's key, so it is a fixed path,
never one derived from a temporary name, a process id or the time.
"""

from __future__ import annotations

import os
import re
from pathlib import Path

import jax

#: the checkout (this file is ``<checkout>/src/repro/launch/``)
CHECKOUT = Path(__file__).resolve().parents[3]
#: ``<checkout>/.jax_cache``
CHECKOUT_CACHE_DIR = CHECKOUT / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it into
    ``jax_compilation_cache_dir`` by itself, so nothing is changed.
    Otherwise the cache goes to :data:`CHECKOUT_CACHE_DIR`.  Call it from
    a script's ``__main__`` block, before the first compile — never from a
    function that tests call in-process.

    Each entry's key includes the ops' metadata: their ``op_name`` (the
    ``jax.named_scope`` names a profiler trace attributes device time to)
    and source lines.  Without it a program that differs from a cached one
    only in its scopes loads the cached executable, and runs under the old
    names.  Source files are named from the checkout's root, so that the
    same program in another checkout finds its entries.
    """
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(str(CHECKOUT) + os.sep))
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
