"""Where JAX's persistent compilation cache lives.

Entry points (benchmarks/run.py, chip_smoke.py, the examples' mains) call
`enable_compile_cache()` first thing. It is never called at package
import: the tests run several workers, and a compile for a described
(not attached) TPU writes entries a chipless process cannot read back.
"""
from __future__ import annotations

import os

#: the checkout this file sits in (paddle_tpu/utils/ -> two levels up);
#: the path is part of the cache key, so it is fixed — never a temp dir,
#: a pid or a time
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on and return its directory.

    With JAX_COMPILATION_CACHE_DIR set, JAX reads it itself and nothing
    is set in code; otherwise the cache goes to `.jax_cache/` in the
    checkout."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
