"""JAX's persistent compilation cache at one fixed place.

A cold compile of the paper-width step is a large share of a short run, and
a cache only hits if the next run looks in the same directory.  So the
directory is `JAX_COMPILATION_CACHE_DIR` when that is set (jax reads it
itself), and otherwise `<checkout>/.jax_cache` — never a temporary name.

The key must not depend on where the checkout lies either.  JAX strips
locations from the program it hashes, but not from the serialized Mosaic
module inside each Pallas kernel call, whose locations name the kernel's
source file.  So source paths are made relative to the checkout.
"""
from __future__ import annotations

import os
import re

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CHECKOUT_CACHE = os.path.join(CHECKOUT, ".jax_cache")


def enable() -> str:
    """Turn the cache on; call before the first compile.  Returns the
    directory in use."""
    import jax
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(CHECKOUT + os.sep))
    path = os.environ.get(ENV)
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE)
    return CHECKOUT_CACHE
