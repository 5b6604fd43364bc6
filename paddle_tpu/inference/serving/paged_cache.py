"""Paged KV cache: a fixed block pool + per-sequence block tables.

The dense decode cache in models/generation.py is [B, H, max_seq, D] per
layer — every sequence pays for max_seq_len positions and a batch slot,
so a serving mix of short and long requests wastes most of HBM. Here KV
lives in a per-layer block pool, logically [num_blocks, block_size, H,
D]; a sequence owns an ordered list of block ids (its *block table*) and
only ever holds ceil(len/block_size) blocks. This is the TPU-native
shape of the Ragged Paged Attention kernel (PAPERS.md, arxiv 2604.15464)
and of vLLM's PagedAttention, with the pool as one jnp array per layer
so the ragged decode step (serving/attention.py) gathers it with one
block-table index per layer. [.., H, D] is the LOGICAL view, what a
family writes and reads; the array is stored as [num_blocks, block_size,
...physical_shape((H, D))], a shape the device keeps block-major
(`physical_shape`), and `write_rows` / `gather_rows` /
`write_prefill_scatter` and the ragged kernel are all that look inside.

Prefix caching (docs/serving.md "Prefix caching"): with
`enable_prefix_cache=True` blocks become REFCOUNTED and content-
addressed through a radix-trie index (serving/prefix_cache.py) at
full-block granularity. Admission attaches the longest cached prefix
of a prompt to the new sequence's table (the same physical blocks,
refcount += 1), forks a private copy-on-write block when the prompt
diverges mid-block, and only the uncached suffix is ever prefilled.
A freed block returns to the free list only at refcount 0; blocks the
trie still indexes are RETAINED at refcount 0 (evictable LRU-leaf-
first under pool pressure) instead of freed. Scrub is refcount-aware:
a quarantined sequence scrubs only blocks it was the LAST holder of,
and distrusts (trie-evicts + taints) anything it shared — a tainted
block is scrubbed the moment its final reference drops.

Hierarchical tiering (docs/serving.md "Hierarchical KV-cache
tiering"): with `host_tier_blocks > 0` LRU eviction becomes
demote-instead-of-free — the victim block's payload is spilled to a
host-RAM HostTierStore (per-block numpy copy + sha256 digest) and the
trie node is retagged host-resident instead of destroyed. A later
match promotes the payload back into a fresh device block
(`ensure_promoted`), re-verifying the digest on fill; a promotion
that is killed, times out, races a store-side eviction or fails the
integrity check degrades to ordinary re-prefill of the missing
suffix. The zero-leak, refcount and scrub-taint invariants span both
tiers (`check_integrity` cross-tier keys; a distrusted subtree's
host copies are poisoned, never promoted).

int8 pool mode (docs/serving.md "int8 KV blocks"): with
`kv_cache_dtype="int8"` the pools are STORED as int8 codes plus
per-(block, head) f32 scales (serving/kv_quant.py), cutting resident
KV bytes ~4x. `pools` stays the logical f32 interface — the property
getter dequantizes, the setter re-encodes with MONOTONE scales so a
block whose content didn't change round-trips bit-identically — and
every consumer (attention gather, write_prefill scatter, migration,
scrub, promotion) is oblivious. The worst-case dequantization error
is not folklore: analysis/jaxnum.py derives it from the codec's
jaxpr (`serving.kv_block_codec`) and numplan.json pins it against
the declared `KV_INT8_REL_ERR` budget. Host-tier spill in this mode
stores the QUANTIZED payload (codes + scale rows under one sha256),
so the spill tier gets the same ~4x and the integrity contract is
unchanged.

Host/device split: block accounting (free list, tables, lengths,
refcounts, trie, counters) is plain Python — it feeds the scheduler
and never traces. The pools themselves are jax arrays; `write_prefill`
scatters a dense prefill cache into a sequence's blocks with ONE
jitted program over all layers (`write_prefill_scatter`, pools
donated, one compilation per pool geometry and dense-cache shape —
the host uploads the block ids and dispatches once per prefill), and
the decode step returns updated pools that the engine assigns back.
"""
from __future__ import annotations

import functools
import hashlib
import time
from collections import Counter
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ... import obs
from . import kv_quant
from .host_tier import HostTierStore
from .prefix_cache import PrefixCacheIndex, PrefixNode

__all__ = ["PagedKVCache", "CacheExhausted", "SeqState", "write_rows",
           "gather_rows", "pool_geometry", "physical_shape",
           "window_blocks_per_seq"]


#: len(cache_shape) -> the layout (models/spec.py): (H, D) is a (k, v) pair
#: of pools a layer, (W,) one pool
_LAYOUTS = {2: "heads", 1: "latent"}


@jax.tree_util.register_pytree_node_class
class SeqState:
    """The leaf of a layer that caches ONE fixed-size entry a SEQUENCE (a
    recurrent state) where the others cache a row a position: its arrays,
    each [num_state_slots, ...shape] (`ModelSpec.state_shapes`); a
    sequence's entry is index `state_slot(seq)` of each. A pytree node, so
    the pools of a hybrid cache go through `jax.jit`, donation and the scan
    as the others do; a class of its own, so the block operations, which
    index axis 0 by BLOCK, can tell it from a pool and leave it alone."""

    def __init__(self, *arrays):
        self.arrays = tuple(arrays)

    def __iter__(self):
        return iter(self.arrays)

    def tree_flatten(self):
        return self.arrays, None

    @classmethod
    def tree_unflatten(cls, aux, arrays):
        return cls(*arrays)


def _map_kind(fn, pools, kinds, kind):
    """`fn` over every block pool of the layers of `kind` ("rows" or
    "window": two groups, each with block ids of its own); the others as
    they are."""
    return tuple(jax.tree_util.tree_map(fn, p) if k == kind else p
                 for p, k in zip(pools, kinds))


def _of_kind(layers, kinds, kind):
    """The leaves of the layers of `kind` ("rows", "window", "state") of an
    L-tuple."""
    return tuple(p for p, k in zip(layers, kinds) if k == kind)


def pool_geometry(pools):
    """(num_blocks, block_size) of the pools of any layout (of the first
    layer that caches rows)."""
    rows = next(p for p in pools if not isinstance(p, SeqState))
    return jax.tree_util.tree_leaves(rows)[0].shape[:2]


def window_blocks_per_seq(window: int, block_size: int,
                          lookahead: int) -> int:
    """The most window blocks a sequence holds at once, when
    `PagedKVCache.release_behind` follows every reservation of at most
    `lookahead` positions: the blocks of window - 1 + lookahead positions
    that start on a block's last slot (window // block_size + 2 where the
    block divides the window and lookahead <= block_size + 1). The width of
    the window table in the decode chunk's upload; 0 without a window."""
    return 1 + (window + lookahead + block_size - 3) // block_size \
        if window else 0


_LANES, _SUBLANES = 128, 8


def physical_shape(cache_shape):
    """The per-position shape a pool is STORED in, from the logical one
    (`ModelSpec.cache_shape`). A TPU tiles the two minor dimensions of an
    array as 8 sublanes x 128 lanes, and keeps [num_blocks, block_size,
    ...] block-major only when those two fill whole tiles: otherwise its
    compiler moves the block id (or block_size) into the tile and every
    program that indexes the pool by block copies the whole pool out of
    that layout and back (PERF.md section 6, PR 32). So:

    - heads (H, D), D a divisor of 128 below it and H * D whole lane rows,
      more than half a sublane tile of them (GPT-2 medium: 16 x 64 -> 8
      rows, the whole tile that keeps the pool block-major; GPT-2 small:
      12 x 64 -> 6 rows, padded to 8 where [12, 64] is padded to [16,
      128]): (H * D // 128, 128), that is 128 // D heads side by side on
      the lanes. Whole lane rows are also what the ragged kernel's block
      copies need (ops/pallas/ragged_paged_attention.supported);
    - latent (W,), W at least a lane row: W rounded up to 128 lanes
      (576 -> 640), the padding zero and never read;
    - heads (H, D), D whole lane rows and H at most half a sublane tile
      (grouped query attention: 2 KV heads x 256): ONE row of H * D lanes a
      position, (H * D,). Stored as [.., 2, 256] the 2 heads would sit on
      the sublanes and be padded to a tile of 8 (float32) or 16 (bfloat16):
      4 or 8 times the bytes (from 5 heads on the padding stays under
      double, and the shape is left alone). The row's minor dimensions are
      then (block_size, H * D), as the latent layout's; the ragged kernel,
      whose tile is a four-dimensional stored block, does not read such a
      pool: `generation.decode_layer` gathers it;
    - anything else (the toy sizes of the CPU tests, 6 or 8 heads of 128):
      the logical shape. A pool whose two minor dimensions fill no whole
      tile keeps whatever copies the compiler makes for it."""
    cache_shape = tuple(cache_shape)
    if len(cache_shape) == 1:
        (w,) = cache_shape
        return (-(-w // _LANES) * _LANES,) if w > _LANES else cache_shape
    h, d = cache_shape
    if d < _LANES and _LANES % d == 0 and (h * d) % _LANES == 0 \
            and 2 * (h * d // _LANES) > _SUBLANES:
        return (h * d // _LANES, _LANES)
    if d % _LANES == 0 and 2 * h <= _SUBLANES:
        return (h * d,)
    return cache_shape


def _to_physical(x, stored, rank):
    """Values whose `rank` trailing dimensions are a logical per-position
    shape, in the pool's stored one `stored` (`pool.shape[2:]`): heads
    regroup onto the lanes (a reshape), a latent row is zero-padded. The
    same values to the bit."""
    if rank == 1:
        pad = stored[0] - x.shape[-1]
        return jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, pad),)) \
            if pad else x
    return x.reshape(x.shape[:-2] + tuple(stored))


def _to_logical(x, cache_shape, stored_rank):
    """The inverse of `_to_physical`: values whose `stored_rank` trailing
    dimensions are a stored shape, as `cache_shape` (a row of heads may also
    be asked for flat, (H * D,): no reshape where it is stored so)."""
    if len(cache_shape) == 1 and stored_rank == 1:
        return x[..., :cache_shape[0]]
    return x.reshape(x.shape[:x.ndim - stored_rank] + tuple(cache_shape))


def write_rows(pool, rows, slot_blocks, slot_offsets):
    """Write rows [N, H, D] or [N, W] (the LOGICAL per-position shape)
    into pool [num_blocks, block_size, ...stored] at (slot_blocks[n],
    slot_offsets[n]): the decode layer's cache write. Out-of-range block
    ids (num_blocks: padded and frozen rows) are dropped."""
    rows = _to_physical(rows.astype(pool.dtype), pool.shape[2:],
                        rows.ndim - 1)
    return pool.at[slot_blocks, slot_offsets].set(rows, mode="drop")


def gather_rows(pool, tables, cache_shape=None):
    """pool [num_blocks, block_size, ...stored] through block tables
    [N, MB] -> [N, MB * block_size, ...cache_shape]: each sequence's
    context in the LOGICAL per-position shape (`cache_shape`; None: the
    pool is stored as it is read; (H * D,) for a pool of (H, D) heads: each
    position's heads as one row), positions in block-table order
    (position p = block p // block_size, slot p % block_size), live or
    not."""
    n, mb = tables.shape
    ctx = pool[tables].reshape((n, mb * pool.shape[1]) + pool.shape[2:])
    return ctx if cache_shape is None \
        else _to_logical(ctx, cache_shape, pool.ndim - 2)


# ptlint: disable=PT-T009  agrees with the committed plan entry
# serving.write_prefill (donate=[0]); the jaxplan donation gate pins it
@functools.partial(jax.jit, donate_argnums=(0,))
def write_prefill_scatter(pools, dense_cache, block_ids, batch_index):
    """Scatter row `batch_index` of a dense prefill cache (L-tuple of
    (k, v) [B, H, S, D]) into `pools` (L-tuple of (k, v) [num_blocks,
    block_size, ...stored]) at `block_ids`, for every layer in one
    program. The latent layout is the same program over one pool a layer:
    dense rows [B, S, W] into [num_blocks, block_size, ...stored], no
    transpose.

    Nothing the traffic varies is in the program's key: the WHOLE dense
    row is cut into ceil(S / block_size) blocks (zero-padded past S),
    `block_ids` always has that many entries — the sequence's table
    padded with the out-of-range id num_blocks, whose updates the
    scatter drops, the frozen-row idiom of the decode scan — and
    `batch_index` is a traced scalar. The pools are donated (the caller
    rebinds them from the return value), so the scatter is in place;
    the dense cache is NOT: batched callers read it again."""
    n_slots = block_ids.shape[0]

    def scatter(pool, dense):
        row = jax.lax.dynamic_index_in_dim(dense, batch_index, 0,
                                           keepdims=False)
        if row.ndim == 3:                  # heads: [H, S, D] -> [S, H, D]
            row = row.transpose(1, 0, 2)
        bs, stored = pool.shape[1], pool.shape[2:]
        blk = _to_physical(row.astype(pool.dtype), stored, row.ndim - 1)
        blk = jnp.pad(blk, ((0, n_slots * bs - blk.shape[0]),)
                      + ((0, 0),) * len(stored))
        return pool.at[block_ids].set(
            blk.reshape((n_slots, bs) + stored), mode="drop")

    return jax.tree_util.tree_map(scatter, pools, dense_cache)


# ptlint: disable=PT-T009  the leaves are rebound from the return value by
# the one caller (write_prefill), as write_prefill_scatter's pools are; the
# committed plan's serving entries are traced at a geometry without state
# layers, so there is no plan entry to consume
@functools.partial(jax.jit, donate_argnums=(0,))
def write_state_scatter(states, final, slot, batch_index):
    """Row `batch_index` of a prefill's final entries (a tuple of
    `SeqState`, arrays [B, ...shape]) into slot `slot` of the state layers'
    leaves `states` (arrays [num_state_slots, ...shape]), every layer in one
    program, the leaves donated. The WHOLE entry is written: whatever the
    slot held of its last owner is gone."""
    return jax.tree_util.tree_map(
        lambda leaf, new: leaf.at[slot].set(jax.lax.dynamic_index_in_dim(
            new, batch_index, 0, keepdims=False).astype(leaf.dtype)),
        states, final)


# ptlint: disable=PT-T009  the pools are rebound from the return value by
# the one caller (write_prefill); the committed plan's serving entries are
# traced at a geometry without window layers
@functools.partial(jax.jit, donate_argnums=(0,))
def write_window_scatter(pools, dense, slot_blocks, slot_offsets,
                         batch_index):
    """Row `batch_index` of a prefill's window rows (a tuple of (k, v)
    [B, H, window, D], the prompt's last `window` positions) into the
    window layers' pools, ROW BY ROW: row r goes to (slot_blocks[r],
    slot_offsets[r]), because the window does not start on a block's
    border; rows the prompt does not have carry the out-of-range block id
    and are dropped. One program whatever the prompt's length."""
    def scatter(pool, rows):
        row = jax.lax.dynamic_index_in_dim(rows, batch_index, 0,
                                           keepdims=False)
        return write_rows(pool, row.transpose(1, 0, 2), slot_blocks,
                          slot_offsets)

    return jax.tree_util.tree_map(scatter, pools, dense)


class CacheExhausted(RuntimeError):
    """Block pool exhaustion report: who needed how much vs. what's free.

    The scheduler catches this to preempt; anyone else sees a precise
    message instead of a silent mis-allocation."""

    def __init__(self, seq_id, needed: int, free: int, total: int,
                 what: str = "block"):
        self.seq_id = seq_id
        self.needed = needed
        self.free = free
        self.total = total
        super().__init__(
            f"KV {what} pool exhausted: seq {seq_id!r} needs {needed} "
            f"{what}(s), {free}/{total} free")


class PagedKVCache:
    """Fixed-size per-layer KV block pools with alloc/free accounting.

    Pools: an L-tuple of the layer's cached leaf, built from
    `cache_shape`, the LOGICAL per-position shape of ONE pool (what
    `ModelSpec.cache_shape` says): (H, D) gives a (k_pool, v_pool) pair
    (`layout` "heads"); (W,) gives one pool (`layout` "latent"). Each
    pool is stored as [num_blocks, block_size, ...stored_shape], where
    `stored_shape = physical_shape(cache_shape)`: (H, D) or (W,) itself,
    or the packed / padded form the device keeps block-major. This module
    is the format's one owner: a decode layer writes and reads a pool
    through `write_rows` / `gather_rows`, a prefill through
    `write_prefill_scatter`, all in the logical shape; the block
    operations below index whole blocks on axis 0 and carry whatever
    trails. Token position p of a sequence lives in its
    block table entry p // block_size at slot offset p % block_size — the
    identity layout that makes the gathered context bitwise-match the
    dense cache.

    Block lifecycle: free list -> owned (refcount = number of tables
    holding the block) -> either back to the free list at refcount 0,
    or — when the prefix trie indexes it — retained at refcount 0 as
    an evictable cached block. `blocks_allocated`/`blocks_freed` count
    free-list crossings only, so attaching a shared block is not an
    allocation and retaining a cached block is not (yet) a free; with
    the prefix cache disabled this reduces exactly to the historical
    allocated == freed zero-leak reconciliation.

    State slots (`layout` "hybrid"): `layer_caches` says per layer "rows"
    or "state" (`ModelSpec.layer_caches`). A rows layer is a (k, v) pair as
    above, or, where `cache_shape` is (W,), ONE latent pool (multi-head
    latent attention beside recurrent state; window layers still need
    (k, v) pairs). A state layer's leaf is a `SeqState` of arrays
    [num_state_slots, ...shape] (`state_shapes`): one fixed-size entry a
    SEQUENCE, whatever its length. The two kinds have ONE owner: `allocate`
    takes a sequence's blocks and its slot, `free` returns both (a
    preemption by recompute needs nothing more), `check_integrity` audits
    both. A slot carries nothing from one owner to the next: a prefill
    writes the whole entry (`write_prefill`), and a decode layer starts a
    row at position 0 from zeros (`ModelSpec.decode_layer`). What the
    hybrid layout cannot do yet raises by name, as on the latent layout:
    here for int8 pools, the prefix cache and the host tier (a block of
    rows is not the whole of a prefix: the state after it would have to be
    kept too), at the call for block migration.

    Window blocks (`layout` "hybrid" too): a "window" layer
    (`ModelSpec.layer_caches`, sliding-window attention over the last
    `window` positions) keeps its (k, v) in a SECOND group of pools,
    [num_window_blocks, block_size, ...stored_shape], with a free list of
    its own and a second table a sequence: the blocks that hold the
    window, and the logical index of the first (`window_table`). Position
    p lives in entry p // block_size - first at offset p % block_size.
    `allocate(seq, n)` takes the blocks of the last `window` positions of
    n, `append_slot` / `reserve_slots` grow both tables, and
    `release_behind(seq)` returns every block the window has moved past:
    one whose last position is more than `window - 1` behind the
    sequence's next position, which no later query can attend to. So a
    sequence holds at most `window_blocks_per_seq(..., lookahead)` of them
    however long it grows, when the caller releases after every
    reservation of `lookahead` positions (the engine: where it drains a
    chunk). `free` returns both groups; either group's exhaustion raises
    `CacheExhausted` with no side effect; `check_integrity` audits both. A
    cache may hold window layers and state layers at once. The refusals of
    the hybrid layout hold here for the same reason turned round: a block
    of the full layers is not the whole of a prefix when the window layers
    have given theirs back.
    """

    def __init__(self, num_layers: int, cache_shape: Tuple[int, ...],
                 num_blocks: int, block_size: int, dtype=jnp.float32,
                 enable_prefix_cache: bool = False,
                 host_tier_blocks: int = 0,
                 promote_timeout_s: Optional[float] = None,
                 kv_cache_dtype: str = "float32",
                 layer_caches: Tuple[str, ...] = (),
                 state_shapes: Tuple = (), num_state_slots: int = 0,
                 window: int = 0, num_window_blocks: int = 0):
        if num_blocks <= 0 or block_size <= 0:
            raise ValueError("num_blocks and block_size must be positive")
        cache_shape = tuple(cache_shape)
        if len(cache_shape) not in _LAYOUTS:
            raise ValueError(
                f"cache_shape must be (num_heads, head_dim) or "
                f"(latent_width,), got {cache_shape!r}")
        #: the layout's name as models/spec.py has it (`cache_layout`)
        self.layout = _LAYOUTS[len(cache_shape)]
        layer_caches = tuple(layer_caches) or ("rows",) * num_layers
        if len(layer_caches) != num_layers \
                or set(layer_caches) - {"rows", "state", "window"} \
                or "rows" not in layer_caches:
            raise ValueError(
                f"layer_caches must name 'rows', 'state' or 'window' for "
                f"each of the {num_layers} layers, 'rows' at least once, "
                f"got {layer_caches!r}")
        heads = self.layout == "heads"
        if "window" in layer_caches:
            if not heads or window <= 0 or num_window_blocks <= 0:
                raise ValueError(
                    "window layers need (num_heads, head_dim) rows layers, "
                    "window > 0 and num_window_blocks > 0")
            self.layout = "hybrid"
        if "state" in layer_caches:
            if not state_shapes or num_state_slots <= 0:
                raise ValueError(
                    "state layers need state_shapes and num_state_slots > 0")
            self.layout = "hybrid"
        if self.layout != "heads":
            # what still assumes (k, v) pairs of heads in every layer
            # refuses here, by name, and never falls back
            for feature, asked in (
                    ("int8 KV pools (kv_cache_dtype='int8')",
                     kv_cache_dtype != "float32"),
                    ("the prefix cache (enable_prefix_cache)",
                     enable_prefix_cache),
                    ("the host tier (host_tier_blocks)",
                     host_tier_blocks > 0)):
                if asked:
                    raise NotImplementedError(
                        f"the {self.layout} cache layout does not support "
                        f"{feature} yet")
        if kv_cache_dtype not in ("float32", "int8"):
            raise ValueError(
                f"kv_cache_dtype must be 'float32' or 'int8', got "
                f"{kv_cache_dtype!r}")
        self.num_layers = num_layers
        self.cache_shape = cache_shape
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.kv_cache_dtype = kv_cache_dtype
        #: the per-position shape the pools are stored in
        self.stored_shape = physical_shape(cache_shape)
        shape = (num_blocks, block_size) + self.stored_shape
        if self.layout == "latent":
            self._qpools = None
            self._pools = tuple(jnp.zeros(shape, dtype)
                                for _ in range(num_layers))
        elif kv_cache_dtype == "int8":
            # quantized pool mode (module docstring): int8 codes +
            # per-(block, head) scales, the codes in the LOGICAL shape
            # the scales are per head of; the `pools` property is the
            # dequantized f32 view every consumer reads and writes
            codes = (num_blocks, block_size) + cache_shape
            self._qpools = tuple(
                (jnp.zeros(codes, jnp.int8), jnp.zeros(codes, jnp.int8))
                for _ in range(num_layers))
            scales = (num_blocks, cache_shape[0])      # per (block, head)
            self._scales = tuple(
                (jnp.zeros(scales, jnp.float32),
                 jnp.zeros(scales, jnp.float32))
                for _ in range(num_layers))
        else:
            self._qpools = None
            # a rows layer of (W,) rows is one latent pool, else a pair
            rows = (lambda: jnp.zeros(shape, dtype)) if not heads \
                else (lambda: (jnp.zeros(shape, dtype),
                               jnp.zeros(shape, dtype)))
            self._pools: Tuple[Tuple[jnp.ndarray, jnp.ndarray], ...] = \
                tuple(rows() if kind == "rows" else SeqState(*(
                          jnp.zeros((num_state_slots,) + tuple(sh), dt)
                          for sh, dt in state_shapes))
                      if kind == "state" else tuple(
                          jnp.zeros((num_window_blocks,) + shape[1:], dtype)
                          for _ in range(2))
                      for kind in layer_caches)
        # ----------------------------------------------- host accounting
        self.layer_caches = layer_caches
        self.num_state_slots = num_state_slots \
            if "state" in layer_caches else 0
        # window blocks: a free list, seq -> the blocks of its window and
        # seq -> the logical index of the first of them, beside the blocks'
        self.window = window if "window" in layer_caches else 0
        self.num_window_blocks = num_window_blocks if self.window else 0
        self._wfree: List[int] = list(
            range(self.num_window_blocks - 1, -1, -1))
        self._wtables: Dict[object, List[int]] = {}
        self._wfirst: Dict[object, int] = {}
        self.window_blocks_allocated = 0
        self.window_blocks_freed = 0
        self.window_high_water = 0
        # state slots: a free list and seq -> slot, beside the blocks'
        self._state_free: List[int] = list(
            range(self.num_state_slots - 1, -1, -1))
        self._state_slots: Dict[object, int] = {}
        self._free: List[int] = list(range(num_blocks - 1, -1, -1))
        self._tables: Dict[object, List[int]] = {}
        self._lens: Dict[object, int] = {}
        # refcount[b] = number of block tables containing b; an entry
        # exists exactly while b is OFF the free list (0 only for
        # trie-cached, currently-unreferenced blocks)
        self._refcount: Dict[int, int] = {}
        # blocks whose content is distrusted (shared at scrub time):
        # never re-indexed, scrubbed when their last reference drops
        self._tainted: set = set()
        self.prefix_index: Optional[PrefixCacheIndex] = \
            PrefixCacheIndex(block_size) if enable_prefix_cache else None
        # host-RAM spill tier behind the trie: eviction demotes into it
        # instead of destroying (meaningless without the trie, so gated
        # on enable_prefix_cache)
        self.host_tier: Optional[HostTierStore] = \
            HostTierStore(host_tier_blocks) \
            if (enable_prefix_cache and host_tier_blocks > 0) else None
        self.promote_timeout_s = promote_timeout_s
        # tiering counters + promote-latency samples (the engine drains
        # the samples into its serving_tier_promote_seconds histogram)
        self.tier_demotions = 0
        self.tier_promotions = {"hit": 0, "timeout": 0,
                                "integrity": 0, "raced": 0}
        self._promote_seconds: List[float] = []
        # fault-injection hooks, armed per step by the owning engine
        # (inert when never armed); the promote guard excludes the
        # in-progress promotion path from demotion victim selection
        self._tier_faults = None
        self._tier_step = 0
        self._promote_guard: set = set()
        # lifetime counters (the zero-leak invariant is
        # blocks_allocated == blocks_freed once every sequence is freed
        # and, with prefix caching, the trie is cleared)
        self.blocks_allocated = 0
        self.blocks_freed = 0
        self.blocks_attached = 0             # shared-prefix attaches
        self.alloc_failures = 0
        self.high_water = 0
        # multi-tenant accounting (serving/tenancy.py; inert until the
        # scheduler feeds it): seq_id -> tenant so register_prefix can
        # stamp trie nodes, and the prefix-share weights arbitrating
        # weighted eviction (None = historical global LRU)
        self._seq_tenant: Dict[object, str] = {}
        self._tenant_weights: Optional[Dict[str, float]] = None

    # ------------------------------------------------ pool storage view
    @property
    def pools(self) -> Tuple[Tuple[jnp.ndarray, jnp.ndarray], ...]:
        """L-tuple of (k, v) [num_blocks, block_size, ...stored_shape]
        (latent layout: of one such array) in the pool dtype — what the
        attention gather, write_prefill scatter, migration and scrub
        paths all read and assign; `stored_shape` is `physical_shape`
        of the logical per-position shape, and only `write_rows` /
        `gather_rows` / `write_prefill_scatter` and the ragged kernel
        look inside it. In f32 mode this is the storage itself. In int8
        mode the getter dequantizes the code/scale storage and the
        setter re-encodes through kv_quant.requantize_blocks, whose
        monotone scales make an unchanged block's round-trip bit-stable
        (kv_quant docstring), so repeated decode-chunk rebinds never
        walk stored values."""
        if self._qpools is None:
            return self._pools
        return tuple(
            tuple(_to_physical(kv_quant.dequantize_blocks(q, sc),
                               self.stored_shape, 2)
                  for q, sc in zip(qkv, scales))
            for qkv, scales in zip(self._qpools, self._scales))

    @pools.setter
    def pools(self, new_pools) -> None:
        if self._qpools is None:
            self._pools = tuple(new_pools)
            return
        qpools, scales = [], []
        for (k, v), (sk, sv) in zip(new_pools, self._scales):
            qk, nsk = kv_quant.requantize_blocks(
                _to_logical(k, self.cache_shape, len(self.stored_shape)),
                sk)
            qv, nsv = kv_quant.requantize_blocks(
                _to_logical(v, self.cache_shape, len(self.stored_shape)),
                sv)
            qpools.append((qk, qv))
            scales.append((nsk, nsv))
        self._qpools = tuple(qpools)
        self._scales = tuple(scales)

    @property
    def physical_bytes_per_token(self) -> int:
        """Bytes one position holds in the pools as stored, over all
        layers: `ModelSpec.cache_bytes_per_token` plus what
        `physical_shape` pads (int8 mode: the codes, scales aside)."""
        stored = self._pools if self._qpools is None else self._qpools
        return sum(int(np.prod(p.shape[2:])) * p.dtype.itemsize
                   for layer in _of_kind(stored, self.layer_caches, "rows")
                   for p in jax.tree_util.tree_leaves(layer))

    @property
    def window_bytes_per_seq(self) -> int:
        """Bytes `window` positions hold in the window layers' pools as
        stored (0 without any): `ModelSpec.window_bytes_per_seq`, from the
        arrays."""
        return self.window * sum(
            int(np.prod(p.shape[2:])) * p.dtype.itemsize
            for layer in _of_kind(self._pools, self.layer_caches, "window")
            for p in layer) if self.window else 0

    @property
    def state_bytes_per_seq(self) -> int:
        """Bytes one sequence's entries hold over all state layers (0
        without any): `ModelSpec.state_bytes_per_seq`, from the arrays."""
        if not self.num_state_slots:
            return 0
        return sum(int(np.prod(a.shape[1:])) * a.dtype.itemsize
                   for layer in self._pools
                   if isinstance(layer, SeqState) for a in layer)

    def num_state_slots_used(self) -> int:
        return len(self._state_slots)

    def state_slot(self, seq_id) -> int:
        """The slot of the sequence's entries in every state layer."""
        return self._state_slots[seq_id]

    def _reset_block_scales(self, ids) -> None:
        """Zero freshly-claimed blocks' scale rows (int8 mode): stale
        codes dequantize against scale 0 to exact zeros — the
        fresh-block invariant — and the next write derives its scale
        from the new content alone. A surviving (larger) scale from the
        block's previous tenant would inflate the quantization step
        past the committed relative-error bound (numplan.json)."""
        at = jnp.asarray(list(ids), jnp.int32)
        self._scales = tuple(
            (sk.at[at].set(0.0), sv.at[at].set(0.0))
            for sk, sv in self._scales)

    def arm_tier_faults(self, faults: "ServingFaultInjector",
                        step: int) -> None:
        """Point the demote/promote fault hooks (kill_demotion /
        kill_promotion) at the engine's injector for this step."""
        self._tier_faults = faults
        self._tier_step = step

    # -------------------------------------------------- tenant plumbing
    def note_seq_tenant(self, seq_id, tenant: str) -> None:
        """Tag the tenant whose fair share seq_id spends; the tag rides
        into the trie when the sequence's prefix registers and is
        dropped with the sequence's table."""
        self._seq_tenant[seq_id] = tenant

    def set_tenant_weights(self, weights: Optional[Dict[str, float]]
                           ) -> None:
        """Install the prefix-share weights (TenantRegistry snapshot;
        the scheduler refreshes on registry-version change). None
        restores the historical unweighted global-LRU eviction."""
        self._tenant_weights = dict(weights) if weights else None

    def _over_share_tenants(self) -> Optional[set]:
        """Tenants holding MORE device-resident cached blocks than
        their prefix_share-weighted proportion of the current cached
        pool — the victims weighted eviction charges first. None when
        weighting cannot discriminate (no weights installed, or zero/
        one tenant holding blocks): the caller falls back to the
        historical global LRU sweep, which keeps single-tenant stacks
        on the exact pre-tenancy path."""
        w = self._tenant_weights
        idx = self.prefix_index
        if not w or idx is None:
            return None
        census = idx.tenant_device_blocks()
        if len(census) <= 1:
            return None
        total = sum(census.values())
        total_w = sum(w.get(t, 1.0) for t in census)
        over = {t for t, n in census.items()
                if n > total * w.get(t, 1.0) / total_w}
        return over or None

    # ------------------------------------------------------------ queries
    def num_free(self) -> int:
        return len(self._free)

    def num_used(self) -> int:
        return self.num_blocks - len(self._free)

    def num_evictable(self) -> int:
        """Trie-cached blocks no table references — reclaimable on
        demand, so admission watermarks treat them as headroom."""
        if self.prefix_index is None:
            return 0
        return sum(1 for b in self.prefix_index.blocks()
                   if self._refcount.get(b, 0) == 0)

    def utilization(self) -> float:
        return self.num_used() / self.num_blocks

    def blocks_needed(self, num_tokens: int) -> int:
        return -(-num_tokens // self.block_size)

    # ------------------------------------------------------ window blocks
    def num_window_free(self) -> int:
        return len(self._wfree)

    def num_window_used(self) -> int:
        return self.num_window_blocks - len(self._wfree)

    def _window_first(self, next_pos: int) -> int:
        """The logical index of the first block a sequence has to keep
        whose next position is `next_pos`: that query attends to
        next_pos - window + 1 .. next_pos, and every later one starts
        later."""
        return max(0, next_pos - self.window + 1) // self.block_size

    def window_blocks_needed(self, num_tokens: int, ahead: int = 0) -> int:
        """Window blocks a sequence of num_tokens cached tokens holds once
        `ahead` more positions are reserved for it (0 without window
        layers)."""
        if not self.window:
            return 0
        return self.blocks_needed(num_tokens + ahead) \
            - self._window_first(num_tokens)

    def window_table(self, seq_id) -> Tuple[List[int], int]:
        """(the sequence's window blocks, the logical index of the first):
        position p is in entry p // block_size - first."""
        return list(self._wtables[seq_id]), self._wfirst[seq_id]

    def _window_short(self, seq_id, upto: int, held_upto: int = None) -> int:
        """Window blocks still to take so that the sequence's window table
        (which reaches logical block `held_upto`, exclusive; default: as it
        stands) reaches position upto - 1; raises CacheExhausted, nothing
        taken, where the group has not that many free."""
        if not self.window:
            return 0
        if held_upto is None:
            held_upto = self._wfirst[seq_id] + len(self._wtables[seq_id])
        need = self.blocks_needed(upto) - held_upto
        if need > len(self._wfree):
            self.alloc_failures += 1
            raise CacheExhausted(seq_id, need, len(self._wfree),
                                 self.num_window_blocks, what="window block")
        return max(0, need)

    def _take_window(self, n: int) -> List[int]:
        got = [self._wfree.pop() for _ in range(n)]
        self.window_blocks_allocated += n
        self.window_high_water = max(self.window_high_water,
                                     self.num_window_used())
        return got

    def release_behind(self, seq_id) -> int:
        """Return to the window group every block of the sequence whose
        last position is more than window - 1 behind its next position (no
        later query attends to it). Host arithmetic only; the rows stay in
        the pool until the block's next owner overwrites them, unread: a
        window layer's mask starts at the query's own window. Returns the
        number of blocks freed."""
        if seq_id not in self._wtables:
            return 0
        table = self._wtables[seq_id]
        drop = min(len(table), self._window_first(self._lens[seq_id])
                   - self._wfirst[seq_id])
        if drop <= 0:
            return 0
        self._wfree.extend(reversed(table[:drop]))
        del table[:drop]
        self._wfirst[seq_id] += drop
        self.window_blocks_freed += drop
        return drop

    def has_seq(self, seq_id) -> bool:
        return seq_id in self._tables

    def seq_len(self, seq_id) -> int:
        return self._lens[seq_id]

    def block_table(self, seq_id) -> List[int]:
        return list(self._tables[seq_id])

    # ------------------------------------------------------- alloc / free
    def _take_blocks(self, seq_id, n: int) -> List[int]:
        if n > len(self._free) and self.prefix_index is not None:
            self._evict_cached(n - len(self._free))
        if n > len(self._free):
            self.alloc_failures += 1
            raise CacheExhausted(seq_id, n, len(self._free),
                                 self.num_blocks)
        got = [self._free.pop() for _ in range(n)]
        for b in got:
            self._refcount[b] = 1
        if self._qpools is not None and got:
            self._reset_block_scales(got)
        self.blocks_allocated += n
        self.high_water = max(self.high_water, self.num_used())
        return got

    def _evict_cached(self, n: int) -> int:
        """Reclaim up to n unreferenced cached blocks, LRU leaf first
        (leaf-only removal keeps the trie rooted; clocks are monotone
        root-ward so the coldest extremity goes first). Evicted blocks
        are NOT scrubbed — finite stale KV is erased exactly by the
        attention length mask, the same contract as a non-scrub free.

        With a host tier, eviction is demote-instead-of-free: the LRU
        node on the demotion frontier spills its payload to host RAM
        and keeps its trie position (`_flush_demotions`); the device
        block is reclaimed either way, so each iteration makes
        progress."""
        idx = self.prefix_index
        evicted = 0
        if self.host_tier is not None:
            # batched demotion: select every victim first (pending
            # nodes count as demoted for frontier eligibility, so the
            # selection sequence matches the one-at-a-time loop), then
            # spill all payloads with ONE gather per pool tensor (on
            # TPU: one DMA per tensor instead of one per block; the
            # dispatch-bound CPU path gains the same way). A victim
            # the demote path refuses (tainted / injected
            # kill_demotion) flushes what is staged — its children
            # must be host-resident before _plain_evict drops them —
            # and plain-evicts
            pending: List[PrefixNode] = []
            pset: set = set()
            faults = self._tier_faults
            # share-weighted victim selection: tenants over their
            # prefix_share go first; once they are drained back under
            # share (among exhausts) the sweep widens to the global LRU
            among = self._over_share_tenants()
            while evicted < n:
                node = idx.lru_demotable(
                    lambda b: self._refcount.get(b, 0) == 0,
                    skip=self._promote_guard, pending=pset, among=among)
                if node is None and among is not None:
                    among = None
                    continue
                if node is None:
                    break
                evicted += 1
                if node.block in self._tainted or (
                        faults is not None
                        and faults.kill_demotion(self._tier_step)):  # ptlint: disable=PT-C004
                    self._flush_demotions(pending)
                    pending, pset = [], set()
                    self._plain_evict(node)
                    continue
                pending.append(node)
                pset.add(node)
            self._flush_demotions(pending)
            return evicted
        among = self._over_share_tenants()
        while evicted < n:
            node = idx.pop_lru_leaf(
                lambda b: self._refcount.get(b, 0) == 0, among=among)
            if node is None and among is not None:
                among = None                 # widen to the global LRU
                continue
            if node is None:
                break
            del self._refcount[node.block]
            self._free.append(node.block)
            self.blocks_freed += 1
            idx.evictions += 1
            evicted += 1
        return evicted

    # ---------------------------------------------------- host tiering
    def _payload_digest(self, payload) -> str:
        """sha256 over a per-block payload (L-tuple of (k, v) numpy
        arrays), taken at spill time and re-checked on every fill —
        the tier's end-to-end integrity contract."""
        h = hashlib.sha256()
        for k, v in payload:
            h.update(np.ascontiguousarray(k).tobytes())
            h.update(np.ascontiguousarray(v).tobytes())
        return h.hexdigest()

    def _dequant_payload(self, payload) -> tuple:
        """Decode a QUANTIZED spill payload (L int8 code pairs + the
        trailing (k_scales [L, H], v_scales [L, H]) pair) back to the
        L-pair f32 blocks, in the pools' stored shape, that the
        scatter/wire paths expect. Only meaningful in int8 mode; called
        after the stored digest has verified."""
        scales = payload[self.num_layers]
        block = (self.block_size,) + self.stored_shape
        return tuple(
            tuple((codes.astype(np.float32)
                   * sc[li][None, :, None]).reshape(block)
                  for codes, sc in zip(payload[li], scales))
            for li in range(self.num_layers))

    def _flush_demotions(self, nodes: List[PrefixNode]) -> None:
        """Spill the staged victims' payloads to the host tier and free
        their device blocks (demote-instead-of-free). The payload read
        is ONE gather per pool tensor for the whole batch; blocks stay
        valid until here because nothing reclaims the free list inside
        `_evict_cached`. Victims that must not be spilled (tainted,
        kill_demotion) never reach this path — the selection loop
        routes them to `_plain_evict` before anything of theirs is
        read, so nothing hits the host tier half-written. `nodes` is
        leaf-ward (children before parents, the selection order), so
        each `demote` sees its device children already host-resident."""
        if not nodes:
            return
        ids = jnp.asarray([n.block for n in nodes], dtype=jnp.int32)
        if self._qpools is not None:
            # spill QUANTIZED (module docstring): the codes gather per
            # pool tensor, plus every layer's scale rows appended as ONE
            # extra (L+1)-th pair — the pair-iterating digest therefore
            # covers codes AND scales, and the store's byte accounting /
            # corrupt_oldest chaos hook work unchanged
            per_layer = [(np.asarray(qk[ids]), np.asarray(qv[ids]))
                         for qk, qv in self._qpools]
            sc = [(np.asarray(sk[ids]), np.asarray(sv[ids]))
                  for sk, sv in self._scales]
        else:
            per_layer = [(np.asarray(kp[ids]), np.asarray(vp[ids]))
                         for kp, vp in self.pools]
            sc = None
        for i, node in enumerate(nodes):
            b = node.block
            payload = tuple((np.array(pk[i]), np.array(pv[i]))
                            for pk, pv in per_layer)
            if sc is not None:
                payload += ((np.stack([k[i] for k, _ in sc]),
                             np.stack([v[i] for _, v in sc])),)
            hid, dropped = self.host_tier.put(
                payload, self._payload_digest(payload))
            self.prefix_index.demote(node, hid)
            for dh in dropped:
                # store-side LRU eviction: unlink the orphaned trie
                # subtrees (host nodes hang below the frontier, so the
                # subtree is all host-resident)
                dn = self.prefix_index.node_of_host(dh)
                if dn is not None:
                    self._drop_host_subtree(dn)
            del self._refcount[b]
            self._free.append(b)
            self.blocks_freed += 1
            self.tier_demotions += 1

    def _plain_evict(self, node: PrefixNode) -> None:
        """Destroy a frontier node the demote path refused: its host
        children (if any) are dropped with it — an unlinked host
        subtree is unreachable — and the device block returns to the
        free list, scrubbed if tainted."""
        for child in list(node.children.values()):
            self._drop_host_subtree(child)
        idx = self.prefix_index
        idx.remove(node)
        del self._refcount[node.block]
        self._free.append(node.block)
        self.blocks_freed += 1
        idx.evictions += 1
        if node.block in self._tainted:
            self._tainted.discard(node.block)
            self.scrub_blocks([node.block])

    def _drop_host_subtree(self, node: PrefixNode,
                           poison: bool = False) -> int:
        """Unlink a subtree rooted at a HOST node and drop its store
        entries (raced store eviction, failed integrity, distrust).
        `poison=True` marks the drops as taint-driven. Returns the
        number of host entries dropped."""
        dropped = 0
        for n in self.prefix_index.remove_subtree(node):
            if n.tier == "host":
                if self.host_tier is not None:
                    if poison:
                        self.host_tier.poison(n.host_id)
                    else:
                        self.host_tier.drop(n.host_id)
                dropped += 1
            elif self._refcount.get(n.block, 0) == 0:
                # defensive: device below host cannot exist (insert
                # stops at host nodes), but never strand a block
                del self._refcount[n.block]
                self._free.append(n.block)
                self.blocks_freed += 1
        return dropped

    def host_match_len(self, tokens) -> int:
        """Tier-aware pricing probe companion to `match_len`: how many
        ADDITIONAL leading tokens are host-resident behind the device
        match — promotable before prefill, so the scheduler prices the
        prompt at its true uncached cost at enqueue."""
        if self.host_tier is None or len(tokens) < 2:
            return 0
        toks = [int(t) for t in tokens[:len(tokens) - 1]]
        _dev, host_path = self.prefix_index.match_tiered(toks)
        return len(host_path) * self.block_size

    def ensure_promoted(self, tokens) -> Optional[dict]:
        """Fill the host-resident run extending `tokens`' device match
        back into fresh device blocks, root-ward, stopping at the
        first failure. Outcomes per node: "hit" (digest verified,
        scattered, trie retagged), "timeout" (injected kill_promotion,
        promote_timeout_s exceeded, or no device block free — entry
        stays host-resident and retryable), "raced" (store evicted the
        payload first) or "integrity" (sha256 mismatch) — the last two
        drop the subtree so the suffix re-prefills. Returns None when
        tiering is off or nothing host-resident matches, else
        {"promoted_blocks", "promoted_tokens", "outcomes", "seconds"}.
        Never raises: a misbehaving tier degrades to re-prefill."""
        if self.host_tier is None or len(tokens) < 2:
            return None
        toks = [int(t) for t in tokens[:len(tokens) - 1]]
        dev_path, host_path = self.prefix_index.match_tiered(toks)
        if not host_path:
            return None
        t0 = time.perf_counter()
        outcomes: List[str] = []
        staged: List[Tuple[PrefixNode, int, tuple]] = []
        # guard the active path: _take_blocks inside _promote_stage may
        # recurse into _evict_cached, which must not demote the parent
        # of the node being promoted
        self._promote_guard = set(dev_path)
        try:
            tail: List[str] = []
            for node in host_path:
                out, b, payload = self._promote_stage(node, t0)
                if out != "hit":
                    tail.append(out)
                    break
                staged.append((node, b, payload))
                self._promote_guard.add(node)
            # commit. Staging verified each node in hand, but a LATER
            # stage's _take_blocks may have demoted into a full host
            # store whose LRU eviction dropped an EARLIER staged entry
            # and unlinked its subtree — that node and everything
            # staged below it raced; give their blocks back
            live: List[Tuple[PrefixNode, int, tuple]] = []
            raced = False
            for node, b, payload in staged:
                if not raced and self.prefix_index.node_of_host(
                        node.host_id) is node:
                    live.append((node, b, payload))
                else:
                    raced = True
                    del self._refcount[b]
                    self._free.append(b)
                    self.blocks_freed += 1
            if raced:
                tail = ["raced"]
            outcomes = ["hit"] * len(live) + tail
            if live:
                # ONE batched scatter per pool tensor for the whole
                # chain (on TPU: one DMA per tensor instead of one per
                # block; the dispatch-bound CPU path gains the same
                # way — promote latency is the tail of revisit TTFT)
                ids = jnp.asarray([b for _n, b, _p in live],
                                  dtype=jnp.int32)
                self.pools = tuple(
                    (kp.at[ids].set(jnp.asarray(np.stack(
                        [p[li][0] for _n, _b, p in live]))),
                     vp.at[ids].set(jnp.asarray(np.stack(
                         [p[li][1] for _n, _b, p in live]))))
                    for li, (kp, vp) in enumerate(self.pools))
                for node, b, _p in live:
                    hid = node.host_id       # promote() clears it
                    self._refcount[b] = 0    # trie-cached, unreferenced
                    self.prefix_index.promote(node, b)
                    self.host_tier.drop(hid)
        finally:
            self._promote_guard = set()
        for out in outcomes:
            self.tier_promotions[out] += 1
        seconds = time.perf_counter() - t0
        if live:
            self._promote_seconds.append(seconds)
        return {"promoted_blocks": len(live),
                "promoted_tokens": len(live) * self.block_size,
                "outcomes": outcomes, "seconds": seconds}

    def _promote_stage(self, node: PrefixNode, t0: float
                       ) -> Tuple[str, Optional[int], Optional[tuple]]:
        """Verify + claim for one host->device fill; the caller
        batch-scatters every staged payload in one op. Returns
        (outcome, block, payload); block/payload are None unless the
        outcome is "hit". See ensure_promoted for outcome semantics."""
        faults = self._tier_faults
        if faults is not None \
                and faults.kill_promotion(self._tier_step):  # ptlint: disable=PT-C004
            return "timeout", None, None    # in-flight promotion cut
            # short: entry stays resident, the schedule-time retry
            # picks it up
        if self.promote_timeout_s is not None \
                and time.perf_counter() - t0 > self.promote_timeout_s:
            return "timeout", None, None
        entry = self.host_tier.get(node.host_id)
        if entry is None:
            # the store LRU-dropped the payload between match and fill
            self._drop_host_subtree(node)
            return "raced", None, None
        if self._payload_digest(entry["payload"]) != entry["digest"]:
            # torn host copy (corrupt_host_block chaos fault, bad DMA):
            # drop it — the request re-prefills this suffix
            self._drop_host_subtree(node)
            return "integrity", None, None
        try:
            b = self._take_blocks("_promote", 1)[0]
        except CacheExhausted:
            self.alloc_failures -= 1     # not an admission failure
            return "timeout", None, None    # pool too hot; stays
            # resident
        if self.prefix_index.node_of_host(node.host_id) is not node:
            # _take_blocks recursed into demotion, whose host-store put
            # LRU-evicted this very entry and unlinked the node — give
            # the block back and let the suffix re-prefill
            del self._refcount[b]
            self._free.append(b)
            self.blocks_freed += 1
            return "raced", None, None
        payload = entry["payload"]
        if self._qpools is not None:
            # the batched commit scatters through the f32 `pools` view;
            # decode the verified quantized payload here so the commit
            # path is mode-oblivious
            payload = self._dequant_payload(payload)
        return "hit", b, payload

    def drain_promote_seconds(self) -> List[float]:
        """Hand accumulated promote-latency samples to the engine's
        histogram (cleared on read)."""
        out, self._promote_seconds = self._promote_seconds, []
        return out

    def allocate(self, seq_id, num_tokens: int) -> List[int]:
        """Claim blocks for a new sequence of num_tokens cached tokens
        (prefill). Raises CacheExhausted without side effects."""
        if seq_id in self._tables:
            raise ValueError(f"seq {seq_id!r} already allocated")
        if self.num_state_slots and not self._state_free:
            self.alloc_failures += 1
            raise CacheExhausted(seq_id, 1, 0, self.num_state_slots,
                                 what="state slot")
        wneed = self._window_short(seq_id, num_tokens,
                                   self._window_first(num_tokens))
        ids = self._take_blocks(seq_id, self.blocks_needed(num_tokens))
        if self.num_state_slots:
            self._state_slots[seq_id] = self._state_free.pop()
        if self.window:
            # the blocks of the last `window` positions of num_tokens
            self._wtables[seq_id] = self._take_window(wneed)
            self._wfirst[seq_id] = self._window_first(num_tokens)
        self._tables[seq_id] = ids
        self._lens[seq_id] = num_tokens
        return ids

    def append_slot(self, seq_id) -> Tuple[int, int, int]:
        """Reserve the slot for the sequence's next token; grows the
        block table by one block on a block boundary. Returns
        (block_id, offset, position); raises CacheExhausted (leaving the
        sequence untouched) when a new block is needed but none is free.
        """
        pos = self._lens[seq_id]
        table = self._tables[seq_id]
        wneed = self._window_short(seq_id, pos + 1)
        if pos % self.block_size == 0 and len(table) * self.block_size \
                <= pos:
            table.extend(self._take_blocks(seq_id, 1))
        if wneed:
            self._wtables[seq_id].extend(self._take_window(wneed))
        self._lens[seq_id] = pos + 1
        block = table[pos // self.block_size]
        return block, pos % self.block_size, pos

    def reserve_slots(self, seq_id, n: int) -> Tuple[int, int, int]:
        """Reserve the slots for the sequence's next n tokens at once —
        the chunk-granular twin of append_slot for the fused k-token
        decode (serving/attention.py fused_decode_chunk). Grows the
        block table by however many blocks the n tokens need in ONE
        atomic _take_blocks claim (CacheExhausted leaves the sequence
        untouched), and advances the length by n. Returns the FIRST
        reserved slot (block_id, offset, position); the device scan
        derives slot j's location as position+j through the identity
        layout. A sequence that finishes mid-chunk simply leaves its
        tail reservation unwritten — the whole table is freed with the
        request, so over-reservation can never leak blocks."""
        if n <= 0:
            raise ValueError(f"reserve_slots needs n >= 1, got {n}")
        pos = self._lens[seq_id]
        table = self._tables[seq_id]
        need = self.blocks_needed(pos + n) - len(table)
        wneed = self._window_short(seq_id, pos + n)
        if need > 0:
            table.extend(self._take_blocks(seq_id, need))
        if wneed:
            self._wtables[seq_id].extend(self._take_window(wneed))
        self._lens[seq_id] = pos + n
        return table[pos // self.block_size], pos % self.block_size, pos

    # -------------------------------------------------- prefix caching
    def match_len(self, tokens) -> int:
        """Pricing probe (no LRU side effects): how many leading tokens
        of `tokens` the cache could serve at admission. Capped at
        len(tokens) - 1 — at least one prompt token must run through
        the model so the first output has logits to sample from (and so
        the last prompt token's KV is written at its own position,
        never double-written)."""
        if self.prefix_index is None or len(tokens) < 2:
            return 0
        toks = [int(t) for t in tokens[:len(tokens) - 1]]
        path, partial = self.prefix_index.match(toks, touch=False)
        return len(path) * self.block_size + \
            (partial[1] if partial is not None else 0)

    def allocate_with_prefix(self, seq_id, tokens) -> int:
        """Admission with prefix reuse: start seq_id's table with the
        longest cached prefix of `tokens` — full-block trie hits attach
        the SHARED physical blocks (refcount += 1), a mid-block
        divergence forks a private copy-on-write duplicate of the
        partially-agreeing cached block (the sequence overwrites slots
        past the matched m as it prefills). Returns the number of
        prompt tokens served from cache (the sequence's initial length;
        prefill resumes there). With the prefix cache disabled this is
        exactly `allocate(seq_id, 0)` returning 0 — the chunked-prefill
        empty-table admission."""
        if seq_id in self._tables:
            raise ValueError(f"seq {seq_id!r} already allocated")
        idx = self.prefix_index
        if idx is None:
            self.allocate(seq_id, 0)
            return 0
        toks = [int(t) for t in tokens]
        path, partial = idx.match(toks[:len(toks) - 1], touch=True)
        table = [node.block for node in path]
        for b in table:
            self._refcount[b] += 1
        self.blocks_attached += len(table)
        cached = len(table) * self.block_size
        if partial is not None:
            donor, m = partial
            try:
                fork = self._take_blocks(seq_id, 1)[0]
            except CacheExhausted:
                # the fork is an optimisation; under pressure fall back
                # to recomputing the partial block from tokens. The
                # attached full blocks stay attached — roll nothing back
                self.alloc_failures -= 1     # not an admission failure
            else:
                self._copy_block(donor.block, fork)
                table.append(fork)
                cached += m
                idx.cow_forks += 1
        self._tables[seq_id] = table
        self._lens[seq_id] = cached
        if cached > 0:
            idx.hits += 1
        else:
            idx.misses += 1
        idx.cached_tokens_total += cached
        idx.prompt_tokens_total += len(toks)
        return cached

    def note_prefix_miss(self, num_tokens: int) -> None:
        """Hit-rate accounting for admissions that bypass
        allocate_with_prefix (the dense prefill path — taken exactly
        when nothing matched): without this, dense misses would never
        enter the cached-token ratio's denominator."""
        if self.prefix_index is not None:
            self.prefix_index.misses += 1
            self.prefix_index.prompt_tokens_total += num_tokens

    def register_prefix(self, seq_id, tokens) -> int:
        """Index seq_id's full blocks under `tokens` — the tokens whose
        KV the sequence has actually WRITTEN (prefill progress, or the
        full log minus the never-fed-back last sampled token). Only
        whole blocks are indexed (partial blocks are still being
        written); first-wins dedupe keeps an existing node's physical
        block; tainted blocks are never indexed. Idempotent. Returns
        the number of newly indexed blocks."""
        idx = self.prefix_index
        if idx is None:
            return 0
        table = self._tables[seq_id]
        toks = [int(t) for t in tokens]
        full = min(len(toks) // self.block_size, len(table))
        if full <= 0:
            return 0
        return idx.insert(toks, table[:full],
                          skip=lambda b: b in self._tainted,
                          tenant=self._seq_tenant.get(seq_id, "default"))

    def clear_prefix_cache(self) -> int:
        """Drop the entire trie, returning unreferenced cached blocks
        to the free list (tainted ones scrubbed). Blocks still held by
        live tables just lose their index entry. The reconciliation
        hook: after clearing, a drained cache is back to the
        allocated == freed zero-leak identity. Returns the number of
        blocks released."""
        idx = self.prefix_index
        if idx is None:
            return 0
        if self.host_tier is not None:
            self.host_tier.clear()
        released: List[int] = []
        for b in idx.clear():
            if self._refcount.get(b, 0) == 0:
                del self._refcount[b]
                self._free.append(b)
                released.append(b)
        self.blocks_freed += len(released)
        dirty = [b for b in released if b in self._tainted]
        if dirty:
            self._tainted.difference_update(dirty)
            self.scrub_blocks(dirty)
        return len(released)

    def _copy_block(self, src: int, dst: int) -> None:
        """Device-side block duplication for copy-on-write forks: one
        gather + scatter per layer pool, no host sync."""
        self.pools = tuple(
            (kp.at[dst].set(kp[src]), vp.at[dst].set(vp[src]))
            for kp, vp in self.pools)

    # ---------------------------------------------------- block migration
    def export_blocks(self, seq_id) -> Tuple[tuple, int]:
        """Snapshot one sequence's KV payload for migration to another
        pool (serving/migration.py): an L-tuple of (k, v) arrays, each
        [len(table), block_size, H, D] — a device-side gather per layer
        pool, so the snapshot is a COPY and the source's table,
        refcounts and trie entries are untouched. Shared (refcount >= 2)
        and trie-cached blocks are therefore copied out, never stolen:
        the source keeps serving its other holders, and frees this
        sequence normally after the migration commits. Returns
        (payload, num_tokens); num_tokens is the sequence's current
        length — at a clean step boundary every one of those positions
        holds written KV."""
        self._heads_layout_only("block migration (export_blocks)")
        table = self._tables[seq_id]
        if not table:
            return tuple((None, None) for _ in self.pools), \
                self._lens[seq_id]
        idx = jnp.asarray(table, jnp.int32)
        return tuple((kp[idx], vp[idx]) for kp, vp in self.pools), \
            self._lens[seq_id]

    def import_blocks(self, seq_id, payload, num_tokens: int) -> List[int]:
        """Admit a migrated sequence's KV payload (export_blocks from a
        SOURCE pool of identical geometry): allocate fresh private
        blocks, scatter the payload into them (one scatter per layer
        pool), and install the rewritten block table at `num_tokens`.
        Raises CacheExhausted with no side effects when the pool can't
        hold the table — migration aborts and the request keeps running
        at the source. The caller registers clean prefixes afterwards
        (register_prefix) so cached-prefix hit rates survive the hop."""
        self._heads_layout_only("block migration (import_blocks)")
        if seq_id in self._tables:
            raise ValueError(f"seq {seq_id!r} already allocated")
        n = 0 if payload[0][0] is None else int(payload[0][0].shape[0])
        if n < self.blocks_needed(num_tokens):
            raise ValueError(
                f"migration payload holds {n} block(s) but {num_tokens} "
                f"tokens need {self.blocks_needed(num_tokens)}")
        ids = self._take_blocks(seq_id, n) if n else []
        if n:
            idx = jnp.asarray(ids, jnp.int32)
            self.pools = tuple(
                (kp.at[idx].set(pk), vp.at[idx].set(pv))
                for (kp, vp), (pk, pv) in zip(self.pools, payload))
        self._tables[seq_id] = ids
        self._lens[seq_id] = num_tokens
        return ids

    def _heads_layout_only(self, feature: str) -> None:
        if self.layout != "heads":
            raise NotImplementedError(
                f"the {self.layout} cache layout does not support "
                f"{feature} yet")

    def payload_bytes(self, payload) -> int:
        """Wire size of an export_blocks payload (obs histogram food)."""
        return sum(int(a.size) * a.dtype.itemsize
                   for pair in payload for a in pair if a is not None)

    # ------------------------------------------------------- peer fetch
    def export_prefix(self, tokens) -> Optional[dict]:
        """Snapshot the longest cached full-block prefix of `tokens`
        for a peer replica (serving/migration.py fetch_prefix) — the
        fleet-level twin of export_blocks, walking BOTH tiers: device
        blocks are gathered out (digest taken now), host entries ship
        their stored payload after re-verifying the spill digest (a
        torn entry truncates the export and drops its subtree; the
        peer prefills the rest). Read-only on the device tier. Returns
        None when nothing matches, else {"blocks": [(payload, digest),
        ...] in root-ward order, "tokens": the tokens those blocks
        cover, "bytes": wire size}."""
        idx = self.prefix_index
        if idx is None or len(tokens) < 2:
            return None
        toks = [int(t) for t in tokens[:len(tokens) - 1]]
        dev_path, host_path = idx.match_tiered(toks)
        blocks: List[tuple] = []
        total = 0
        for node in dev_path:
            b = node.block
            payload = tuple((np.array(kp[b]), np.array(vp[b]))
                            for kp, vp in self.pools)
            blocks.append((payload, self._payload_digest(payload)))
        for node in host_path:
            entry = self.host_tier.get(node.host_id) \
                if self.host_tier is not None else None
            if entry is None:
                self._drop_host_subtree(node)
                break
            if self._payload_digest(entry["payload"]) != entry["digest"]:
                self._drop_host_subtree(node)
                break
            payload, digest = entry["payload"], entry["digest"]
            if self._qpools is not None:
                # peers admit uniform f32 payloads (admit_prefix stacks
                # per-layer pairs across blocks): decode the verified
                # quantized spill and digest the decoded wire form fresh
                payload = self._dequant_payload(payload)
                digest = self._payload_digest(payload)
            blocks.append((payload, digest))
        if not blocks:
            return None
        for payload, _ in blocks:
            total += sum(k.nbytes + v.nbytes for k, v in payload)
        return {"blocks": blocks,
                "tokens": toks[:len(blocks) * self.block_size],
                "bytes": total}

    def admit_prefix(self, tokens, blocks) -> int:
        """Install a peer's export_prefix snapshot into THIS pool's
        trie as device-resident cached blocks (refcount 0, evictable)
        so the next admission of `tokens` hits locally. Atomic-abort
        semantics mirror admit_migrated: every digest is verified
        BEFORE any block is claimed (ValueError on mismatch, nothing
        mutated), and CacheExhausted propagates with no side effects.
        First-wins insert dedupes against blocks cached meanwhile; a
        snapshot block the trie didn't take is returned to the free
        list immediately. Returns the number of newly indexed blocks."""
        idx = self.prefix_index
        if idx is None:
            raise ValueError("admit_prefix needs the prefix cache enabled")
        blocks = list(blocks)
        if not blocks:
            return 0
        for i, (payload, digest) in enumerate(blocks):
            if self._payload_digest(payload) != digest:
                raise ValueError(
                    f"peer prefix block {i} failed integrity check")
        ids = self._take_blocks("_peer_fetch", len(blocks))
        stacked = tuple(
            (jnp.asarray(np.stack([p[layer][0] for p, _ in blocks])),
             jnp.asarray(np.stack([p[layer][1] for p, _ in blocks])))
            for layer in range(self.num_layers))
        at = jnp.asarray(ids, jnp.int32)
        self.pools = tuple(
            (kp.at[at].set(pk), vp.at[at].set(pv))
            for (kp, vp), (pk, pv) in zip(self.pools, stacked))
        toks = [int(t) for t in tokens[:len(blocks) * self.block_size]]
        added = idx.insert(toks, ids,
                           skip=lambda b: b in self._tainted)
        for b in ids:
            if idx.node_of(b) is None:
                # first-wins dedupe kept an existing block instead
                del self._refcount[b]
                self._free.append(b)
                self.blocks_freed += 1
            else:
                self._refcount[b] = 0    # trie-cached, unreferenced
        return added

    def _distrust(self, b: int, to_scrub: List[int]) -> None:
        """Scrub-path hygiene for block b's trie entry: remove its
        whole subtree from the index (a removed parent orphans its
        children, and content downstream of a distrusted block must
        not be re-matched). Subtree blocks nobody references are
        released scrubbed; still-referenced ones are tainted — their
        final free scrubs them. HOST-resident descendants are POISONED:
        the spilled copy is dropped from the store immediately, never
        promoted (the satellite taint-across-tiers contract). b itself
        is left to the caller."""
        idx = self.prefix_index
        if idx is None:
            return
        node = idx.node_of(b)
        if node is None:
            return
        for n in idx.remove_subtree(node):
            if n.tier == "host":
                if self.host_tier is not None:
                    self.host_tier.poison(n.host_id)
                continue
            blk = n.block
            if blk == b:
                continue
            if self._refcount.get(blk, 0) == 0:
                del self._refcount[blk]
                self._free.append(blk)
                self.blocks_freed += 1
                self._tainted.discard(blk)
                to_scrub.append(blk)
            else:
                self._tainted.add(blk)

    def free(self, seq_id, scrub: bool = False, cache_tokens=None) -> int:
        """Drop seq_id's table (completion, preemption, cancellation),
        decrementing refcounts; blocks return to the pool only at
        refcount 0, and blocks the prefix trie indexes are RETAINED at
        refcount 0 (evictable) instead of freed. `cache_tokens` — the
        sequence's tokens with valid written KV — indexes its full
        blocks first, so finished/preempted work stays matchable.

        `scrub=True` (quarantine/recovery) zeroes the device contents
        of every block this call actually releases — finite stale
        garbage is erased exactly by the attention length mask (masked
        probs are exact zeros), but NaN survives it (0 * NaN = NaN), so
        a poisoned block must not re-enter the free list carrying NaN.
        Scrub is REFCOUNT-AWARE: a block other sequences still hold is
        never zeroed under them; it is evicted from the trie, tainted,
        and scrubbed when its final reference drops."""
        idx = self.prefix_index
        if idx is not None and cache_tokens is not None and not scrub \
                and len(cache_tokens):
            self.register_prefix(seq_id, cache_tokens)
        ids = self._tables.pop(seq_id)
        self._lens.pop(seq_id)
        self._seq_tenant.pop(seq_id, None)
        if seq_id in self._state_slots:
            # the entry stays as it is: the slot's next owner overwrites
            # it whole (write_prefill) or starts from zeros at position 0
            self._state_free.append(self._state_slots.pop(seq_id))
        if seq_id in self._wtables:
            wids = self._wtables.pop(seq_id)
            del self._wfirst[seq_id]
            self._wfree.extend(reversed(wids))
            self.window_blocks_freed += len(wids)
            if scrub and wids:
                at = jnp.asarray(wids, jnp.int32)
                self.pools = _map_kind(lambda pool: pool.at[at].set(0),
                                       self.pools, self.layer_caches,
                                       "window")
        to_scrub: List[int] = []
        for b in reversed(ids):
            self._refcount[b] -= 1
            if scrub:
                self._distrust(b, to_scrub)
            if self._refcount[b] > 0:
                if scrub:
                    self._tainted.add(b)
                continue
            if not scrub and idx is not None \
                    and idx.node_of(b) is not None:
                continue                     # retained: cached, evictable
            del self._refcount[b]
            self._free.append(b)
            self.blocks_freed += 1
            if scrub or b in self._tainted:
                self._tainted.discard(b)
                to_scrub.append(b)
        if to_scrub:
            self.scrub_blocks(to_scrub)
        return len(ids)

    def scrub_blocks(self, block_ids) -> None:
        """Zero the given blocks in every layer's pools, restoring the
        fresh-block invariant the bitwise-parity contract relies on."""
        if not block_ids:
            return
        idx = jnp.asarray(list(block_ids), jnp.int32)
        self.pools = _map_kind(lambda pool: pool.at[idx].set(0), self.pools,
                               self.layer_caches, "rows")

    def check_integrity(self) -> dict:
        """Invariant audit for the chaos harness: the free list and the
        LIVE blocks (table-owned plus trie-cached) must exactly
        partition the pool, refcounts must equal table multiplicity,
        unreferenced live blocks must be trie-cached, taints must point
        at owned blocks, the trie must be structurally sound, and the
        lifetime counters must account for every off-free-list block.
        Returns the audit dict; raises RuntimeError on any violation.
        With the prefix cache disabled this reduces to the historical
        free-list/table partition check."""
        in_tables = [b for ids in self._tables.values() for b in ids]
        owned = set(in_tables)
        free = set(self._free)
        idx = self.prefix_index
        cached = set(idx.blocks()) if idx is not None else set()
        live = owned | cached
        mult = Counter(in_tables)
        report = {
            "leaked": self.num_blocks - len(live | free),
            "double_owned": sum(
                1 for b in set(self._refcount) | owned
                if self._refcount.get(b, 0) != mult.get(b, 0)),
            "free_and_owned": len(live & free),
            "counter_drift": (self.blocks_allocated - self.blocks_freed)
            - (self.num_blocks - len(self._free)),
            "unreachable_zero_ref": sum(
                1 for b, rc in self._refcount.items()
                if rc == 0 and b not in cached),
            "stale_tainted": len(self._tainted - owned),
            "trie_defects": idx.audit() if idx is not None else 0,
        }
        # state slots: the free list and the owned slots partition them,
        # no slot has two owners, and exactly the sequences that hold a
        # table hold one
        held = list(self._state_slots.values())
        report["state_slots_leaked"] = self.num_state_slots \
            - len(set(held) | set(self._state_free))
        report["state_slots_double_owned"] = \
            len(held) - len(set(held)) \
            + len(set(held) & set(self._state_free))
        report["state_slots_without_table"] = len(
            set(self._state_slots) ^ set(self._tables)) \
            if self.num_state_slots else 0
        # window blocks: the free list and the tables partition the group,
        # no block has two owners, exactly the sequences that hold a table
        # hold a window table, and the counters account for the blocks out
        wheld = [b for ids in self._wtables.values() for b in ids]
        report["window_blocks_leaked"] = self.num_window_blocks \
            - len(set(wheld) | set(self._wfree))
        report["window_blocks_double_owned"] = \
            len(wheld) - len(set(wheld)) \
            + len(set(wheld) & set(self._wfree))
        report["window_blocks_without_table"] = len(
            set(self._wtables) ^ set(self._tables)) if self.window else 0
        report["window_counter_drift"] = \
            (self.window_blocks_allocated - self.window_blocks_freed) \
            - (self.num_window_blocks - len(self._wfree))
        # cross-tier keys: every trie host node must point at a live
        # store entry (orphan = promoted-from-under-us bug) and every
        # store entry must be reachable from the trie (leaked = host-
        # side block leak). Payload digests are deliberately NOT
        # re-verified here — a corrupted-but-never-promoted entry is
        # harmless until a fill checks it (that is the fill's job).
        if self.host_tier is not None and idx is not None:
            trie_hids = set(idx.host_ids())
            store_hids = set(self.host_tier.ids())
            report["host_orphans"] = len(trie_hids - store_hids)
            report["host_leaked"] = len(store_hids - trie_hids)
        else:
            report["host_orphans"] = 0
            report["host_leaked"] = 0
        # per-tenant reconciliation (multi-tenant accounting): each
        # tenant's lifetime inserted − removed counters must equal its
        # live trie census (both tiers) — a drift means a removal path
        # skipped attribution and the per-tenant gauges are lying
        if idx is not None:
            census = idx.tenant_census()
            names = set(idx.tenant_inserted) | set(idx.tenant_removed) \
                | set(census)
            report["tenant_drift"] = sum(
                abs(idx.tenant_inserted.get(t, 0)
                    - idx.tenant_removed.get(t, 0) - census.get(t, 0))
                for t in names)
        else:
            report["tenant_drift"] = 0
        if any(report.values()):
            # flight recorder (obs/reqtrace.py): an integrity violation
            # is a postmortem trigger — when armed, ship the full ring
            # + registry snapshot before raising. Lazy import keeps the
            # cache importable without the obs package loaded first.
            from ...obs import reqtrace
            reqtrace.maybe_flight("check_integrity",
                                  extra={"report": dict(report)})
            raise RuntimeError(f"paged cache integrity violated: {report} "
                               f"(tables={len(self._tables)}, "
                               f"cached={len(cached)}, "
                               f"free={len(free)}/{self.num_blocks})")
        return report

    # ------------------------------------------------------- device side
    def write_prefill(self, seq_id, dense_cache, num_tokens: int,
                      batch_index: int = 0):
        """Scatter one sequence's dense prefill cache (the L-tuple of
        (k [B, H, S, D], v) from models.generation.prefill) into its
        allocated blocks: one dispatch of `write_prefill_scatter` for
        all layers, whatever the prompt length. Positions past
        num_tokens inside the last block stay zero (prefill zero-fills
        past the prompt), matching a fresh pool block bit-for-bit. Must
        only run on PRIVATE tables (dense admission never attaches
        shared blocks — any prefix hit is admitted through the chunked
        path, which writes only the uncached suffix positions).

        On the hybrid layout `dense_cache` holds, in the place of a state
        layer's rows, the sequence's FINAL entry (a `SeqState` of arrays
        [B, ...shape]): a second dispatch (`write_state_scatter`, span
        `serving.prefill.write_state`) writes it whole into the
        sequence's slot of every state layer. In the place of a window
        layer's rows it holds the rows of the prompt's last `window`
        positions alone ((k, v) [B, H, window, D], row r the position
        max(0, num_tokens - window) + r): a third dispatch
        (`write_window_scatter`, span `serving.prefill.write_window`) puts
        them row by row into the sequence's window blocks."""
        ids = self._tables[seq_id]
        kinds, pools = self.layer_caches, self.pools
        dense_cache, final, last = (_of_kind(dense_cache, kinds, kind)
                                    for kind in ("rows", "state", "window"))
        rows, states, windows = (_of_kind(pools, kinds, kind)
                                 for kind in ("rows", "state", "window"))
        # [B, H, S, D] a (k, v) leaf, [B, S, W] a latent one
        leaf = jax.tree_util.tree_leaves(dense_cache)[0]
        batch, seq = leaf.shape[0], leaf.shape[-2]
        n_slots = self.blocks_needed(seq)
        if len(ids) > n_slots:
            raise ValueError(
                f"sequence {seq_id!r} holds {len(ids)} blocks but a dense "
                f"cache of {seq} positions fills at most {n_slots}")
        if not 0 <= batch_index < batch:
            raise IndexError(
                f"batch_index {batch_index} out of range for a dense "
                f"cache of batch {batch}")
        with obs.span("serving.prefill.write_cache", cat="prefill",
                      args={"blocks": len(ids)}):
            padded = np.full((n_slots,), self.num_blocks, np.int32)
            padded[:len(ids)] = ids
            rows = write_prefill_scatter(rows, dense_cache, padded,
                                         np.int32(batch_index))
        if states:
            slot = self._state_slots[seq_id]
            with obs.span("serving.prefill.write_state", cat="prefill",
                          args={"slot": slot}):
                states = write_state_scatter(
                    states, final, np.int32(slot), np.int32(batch_index))
        if windows:
            # the prompt's last `window` positions, row by row: dense row r
            # is position lo + r, which lives in the window table's entry
            # (lo + r) // block_size - first
            table, first = self._wtables[seq_id], self._wfirst[seq_id]
            at = max(0, num_tokens - self.window) + np.arange(self.window)
            entry = at // self.block_size - first
            held = (at < num_tokens) & (entry >= 0)
            blocks = np.full((self.window,), self.num_window_blocks,
                             np.int32)
            blocks[held] = np.asarray(table, np.int32)[entry[held]]
            with obs.span("serving.prefill.write_window", cat="prefill",
                          args={"blocks": len(table)}):
                windows = write_window_scatter(
                    windows, last, blocks,
                    (at % self.block_size).astype(np.int32),
                    np.int32(batch_index))
        leaves = {"rows": iter(rows), "state": iter(states),
                  "window": iter(windows)}
        self.pools = tuple(next(leaves[kind]) for kind in kinds)

    def prefix_stats(self) -> dict:
        """Prefix-cache telemetry snapshot (engine gauges + load suite
        hit-rate reporting read this)."""
        idx = self.prefix_index
        if idx is None:
            return {"enabled": False, "cached_blocks": 0,
                    "shared_blocks": 0, "evictable_blocks": 0,
                    "hits": 0, "misses": 0, "evictions": 0,
                    "cow_forks": 0, "inserted_blocks": 0,
                    "cached_tokens_total": 0, "prompt_tokens_total": 0,
                    "cached_tokens_ratio": 0.0, "attached_blocks": 0,
                    "host_blocks": 0, "tier_demotions": 0,
                    "promote_hit": 0, "promote_timeout": 0,
                    "promote_integrity": 0, "promote_raced": 0,
                    "tenant_blocks": {}}
        out = {"enabled": True}
        out.update(idx.stats())
        out["tenant_blocks"] = idx.tenant_census()
        out["shared_blocks"] = sum(
            1 for rc in self._refcount.values() if rc >= 2)
        out["evictable_blocks"] = self.num_evictable()
        out["attached_blocks"] = self.blocks_attached
        out["tier_demotions"] = self.tier_demotions
        for k, v in self.tier_promotions.items():
            out[f"promote_{k}"] = v
        return out

    def stats(self) -> dict:
        """Both groups' accounting; the window group's keys only where the
        cache has window layers."""
        window = {
            "window_blocks": self.num_window_blocks,
            "window_free": self.num_window_free(),
            "window_used": self.num_window_used(),
            "window_blocks_allocated": self.window_blocks_allocated,
            "window_blocks_freed": self.window_blocks_freed,
            "window_high_water": self.window_high_water,
        } if self.window else {}
        return {
            "num_blocks": self.num_blocks,
            "block_size": self.block_size,
            "kv_cache_dtype": self.kv_cache_dtype,
            "free": self.num_free(),
            "used": self.num_used(),
            "utilization": self.utilization(),
            "blocks_allocated": self.blocks_allocated,
            "blocks_freed": self.blocks_freed,
            "blocks_attached": self.blocks_attached,
            "alloc_failures": self.alloc_failures,
            "high_water": self.high_water,
            **window,
        }
