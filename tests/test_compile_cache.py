"""Where the persistent compile cache is placed (utils/compile_cache.py)."""
import os

import pytest

from paddle_tpu.utils import compile_cache


@pytest.mark.parametrize("placed", ["/some/dir", None],
                         ids=["from_outside", "in_the_checkout"])
def test_compile_cache_placement(monkeypatch, placed):
    """With JAX_COMPILATION_CACHE_DIR set, JAX reads it and nothing is set
    in code; unset, the cache is the fixed .jax_cache/ of the checkout.
    jax.config.update is intercepted: tests never turn the cache on."""
    import jax
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append((k, v)))
    if placed:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
        assert compile_cache.enable_compile_cache() == placed
        assert updates == []
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        want = os.path.join(root, ".jax_cache")
        assert compile_cache.enable_compile_cache() == want
        assert updates == [("jax_compilation_cache_dir", want)]
