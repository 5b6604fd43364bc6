"""ModelSpec: what the serving engine needs to know of a decoder family.

`LLMEngine`, `fused_decode_chunk`, `paged_decode_step` and
`PagedKVCache` own the scheduler, the scan (carry, prompt feed, sampling,
termination, the packed upload and the one fetch), the allocator, the
block tables and the pools' format; a family owns its layers and says
what one position caches. The spec is the seam between the two: one
frozen (hashable, so usable as a jit static argument) object per (family,
configuration). Every family builds its own, `serving_spec(...)` beside
the functions it wraps (`models.generation.serving_spec`, the first, and
`models.pangu_moe.serving_spec`, `models.qwen3_next.serving_spec`,
`models.mellum.serving_spec`, `models.ling_flash.serving_spec`); all are
cached, so the same configuration
always gives the same object and the same compiled programs. The serving layer imports this module and no family.

Cache layouts (`PagedKVCache` builds the pools from `cache_shape` and
`cache_dtype`; a decode layer writes and reads them through
`paged_cache.write_rows` / `gather_rows`, a prefill hands its dense rows
to `write_prefill_scatter`):
- "heads":  two pools a layer, (k, v) [num_blocks, block_size, H, D];
            `cache_shape` is (H, D); dense prefill rows are [B, H, S, D];
- "latent": ONE pool a layer, [num_blocks, block_size, W] (multi-head
            latent attention: the normed compressed key-value and the
            rotated shared key); `cache_shape` is (W,); dense prefill rows
            are [B, S, W];
- "hybrid": `layer_caches` says per LAYER what it caches. A "rows" layer
            is a "heads" layer, a (k, v) pair of pools of `cache_shape`
            (H, D), or, where `cache_shape` is (W,), a "latent" layer, ONE
            pool (dense prefill rows [B, S, W]). A "state" layer caches no position: it keeps ONE
            fixed-size entry a SEQUENCE (a recurrent state), the leaves
            `state_shapes` names, stored [num_state_slots, ...shape] as a
            `paged_cache.SeqState`; the cache manager gives a sequence a
            slot with its blocks, `decode_layer` reads and writes the
            entries at `state_slots`, and a prefill hands back each
            sequence's final entry [B, ...shape] in the place of dense
            rows. A "window" layer is a rows layer that attends only to
            the last `window` positions (sliding-window attention): its
            (k, v) pools are a GROUP of their own, [num_window_blocks,
            block_size, ...], with a free list and a table a sequence of
            their own; the table holds only the blocks of the window
            (the cache manager returns a block when the window has moved
            past it), so a sequence costs such a layer at most
            `window_bytes_per_seq` however long it grows. A prefill hands
            over, for such a layer, the rows of the prompt's last
            `window` positions alone: [B, H, window, D], row r the
            position max(0, T - window) + r.
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
    cache_layout: str                 # "heads" | "latent" | "hybrid"
    cache_shape: Tuple[int, ...]      # per position and pool
    cache_dtype: str                  # numpy/jax dtype name of the pools
    #: (params, tokens [N], positions [N]) -> x [N, 1, h]
    embed: Callable
    #: (params, i, x, layer_pool, slot_blocks [N], slot_offsets [N],
    #:  tables [N, MB], positions [N], att_lens [N], live [N] bool,
    #:  ragged: bool, state_slots [N] or None[, att_starts [N],
    #:  table_starts [N]]) -> (x, layer_pool, counts):
    #: writes the new token's cache row at (slot_block, slot_offset)
    #: (out-of-range blocks are dropped), attends row n to its first
    #: att_lens[n] positions through its block table, runs the rest of
    #: layer i. A "window" layer, and no other, is called with the two
    #: trailing arguments: `tables` [N, WB] is then ITS table (the blocks
    #: that hold the window, slot_blocks ids of ITS pools), row w of a
    #: sequence's gathered context is position table_starts[n] + w, and
    #: row n attends to positions att_starts[n] .. att_lens[n] - 1.
    #: `layer_pool` is the layout's per-layer leaf: a (k, v) pair, one array, or a state layer's `SeqState`, whose entry of row n is at
    #: `state_slots[n]` (None where the spec has no state layer; a row at
    #: position 0 starts from a zero entry whatever its slot held, a row
    #: not `live` leaves its entry as it is). `counts` is an int32 vector
    #: of len(counters), or None.
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
    #: per layer "rows" (a cache row a position), "state" (one entry a
    #: sequence) or "window" (a cache row a position of the last `window`);
    #: () = rows in every layer
    layer_caches: Tuple[str, ...] = ()
    #: the leaves of a state layer's entry, per sequence: ((shape, dtype
    #: name), ...)
    state_shapes: Tuple[Tuple[Tuple[int, ...], str], ...] = ()
    #: positions a "window" layer attends to, the query's own included
    #: (0 where no layer has a window)
    window: int = 0
    #: a family with expert layers: the shape of one of an expert layer's
    #: stacked weights here, (held experts, hidden, expert width); the
    #: engine puts it on `serving.prefill` as `moe_shape` ("64x2304x896"),
    #: and a trace's reader finds a prompt's expert products by it (() = no
    #: expert layer)
    expert_shape: Tuple[int, ...] = ()

    @property
    def pools_per_layer(self) -> int:
        """Pools a rows layer: one of (W,) latent rows, a (k, v) pair of
        (H, D) heads."""
        return 1 if len(self.cache_shape) == 1 else 2

    @property
    def state_layers(self) -> int:
        return sum(c == "state" for c in self.layer_caches)

    @property
    def window_layers(self) -> int:
        return sum(c == "window" for c in self.layer_caches)

    def _row_bytes(self) -> int:
        """Bytes of one position in one layer that caches rows."""
        import math
        import jax.numpy as jnp      # bfloat16 is jax's, not numpy's
        return (self.pools_per_layer * math.prod(self.cache_shape)
                * jnp.dtype(self.cache_dtype).itemsize)

    @property
    def cache_bytes_per_token(self) -> int:
        """Bytes one more position costs: the layers that cache a row for
        EVERY position (a window layer's cost stops growing at `window`)."""
        return (self.num_layers - self.state_layers - self.window_layers) \
            * self._row_bytes()

    @property
    def window_bytes_per_seq(self) -> int:
        """The most a sequence's window layers have to hold: `window`
        positions each (whole blocks, as stored, hold a little more)."""
        return self.window_layers * self.window * self._row_bytes()

    @property
    def state_bytes_per_seq(self) -> int:
        """Bytes a sequence costs whatever its length: the state layers."""
        import math
        import jax.numpy as jnp
        return self.state_layers * sum(
            math.prod(shape) * jnp.dtype(dtype).itemsize
            for shape, dtype in self.state_shapes)
