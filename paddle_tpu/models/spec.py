"""ModelSpec: what the serving engine needs to know of a decoder family.

`LLMEngine`, `fused_decode_chunk`, `paged_decode_step` and
`PagedKVCache` own the scheduler, the scan (carry, prompt feed, sampling,
termination, the packed upload and the one fetch), the allocator, the
block tables and the pools' format; a family owns its layers and says
what one position caches. The spec is the seam between the two: one
frozen (hashable, so usable as a jit static argument) object per (family,
configuration). Every family builds its own, `serving_spec(...)` beside
the functions it wraps (`models.generation.serving_spec`, the first, and
`models.pangu_moe.serving_spec`); both are cached, so the same
configuration always gives the same object and the same compiled
programs. The serving layer imports this module and no family.

Cache layouts (`PagedKVCache` builds the pools from `cache_shape` and
`cache_dtype`; a decode layer writes and reads them through
`paged_cache.write_rows` / `gather_rows`, a prefill hands its dense rows
to `write_prefill_scatter`):
- "heads":  two pools a layer, (k, v) [num_blocks, block_size, H, D];
            `cache_shape` is (H, D); dense prefill rows are [B, H, S, D];
- "latent": ONE pool a layer, [num_blocks, block_size, W] (multi-head
            latent attention: the normed compressed key-value and the
            rotated shared key); `cache_shape` is (W,); dense prefill rows
            are [B, S, W].
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Hashable, Tuple

__all__ = ["ModelSpec", "merge_counts"]


def merge_counts(total, counts):
    """Fold one layer's (or one program's) counts into a running total:
    sums, and the last a maximum (`ModelSpec.counters`)."""
    import jax.numpy as jnp
    return jnp.concatenate([total[:-1] + counts[:-1],
                            jnp.maximum(total[-1:], counts[-1:])])


@dataclass(frozen=True)
class ModelSpec:
    family: str
    num_layers: int
    max_seq_len: int
    cache_layout: str                 # "heads" | "latent"
    cache_shape: Tuple[int, ...]      # per position and pool
    cache_dtype: str                  # numpy/jax dtype name of the pools
    #: (params, tokens [N], positions [N]) -> x [N, 1, h]
    embed: Callable
    #: (params, i, x, layer_pool, slot_blocks [N], slot_offsets [N],
    #:  tables [N, MB], positions [N], att_lens [N], live [N] bool,
    #:  ragged: bool) -> (x, layer_pool, counts): writes the new token's
    #: cache row at (slot_block, slot_offset) (out-of-range blocks are
    #: dropped), attends row n to its first att_lens[n] positions through
    #: its block table, runs the rest of layer i. `layer_pool` is the
    #: layout's per-layer leaf: a (k, v) pair or one array. `counts` is an
    #: int32 vector of len(counters), or None.
    decode_layer: Callable
    #: (params, x [N, 1, h]) -> logits [N, V]
    head: Callable
    #: (params, ids [B, T]) -> (last-position logits [B, V], dense
    #: per-layer cache rows for `write_prefill_scatter`, counts or None)
    prefill: Callable
    #: names of the int32 counts `decode_layer` and `prefill` return:
    #: summed over layers and trips, but for the last, a maximum
    counters: Tuple[str, ...] = ()
    #: what the functions above were built from (a config, a geometry)
    config: Hashable = None

    @property
    def pools_per_layer(self) -> int:
        return 2 if self.cache_layout == "heads" else 1

    @property
    def cache_bytes_per_token(self) -> int:
        import math
        import jax.numpy as jnp      # bfloat16 is jax's, not numpy's
        return (self.num_layers * self.pools_per_layer
                * math.prod(self.cache_shape)
                * jnp.dtype(self.cache_dtype).itemsize)
