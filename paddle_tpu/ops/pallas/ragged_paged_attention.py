"""Ragged paged attention for TPU decode.

One program for a whole mixed batch: each row attends over exactly
``lengths[i]`` KV positions read straight from the paged-cache block
table — no power-of-2 bucket padding, no per-bucket recompile, and
rows that are mid-prefill (chunked prefill feeds one prompt token per
scan trip) ride the same kernel as decode rows. The kernel is a
flash-style streaming softmax over a row's blocks. Its work follows the
row's LIVE blocks: the grid is one step a row, the block tables and
per-row lengths are *scalar-prefetched*, the pools stay in HBM
(``memory_space=pl.ANY``), and inside a step a loop over
``cdiv(lengths[i], block_size)`` blocks copies them into VMEM itself, a
group of several blocks at a time (``blocks_per_group``: from the stored
block's bytes), group g + 1 in flight while group g is multiplied, and
the next live row's first group in flight during a row's last. A block
past a row's length costs nothing (no step, no copy, its table entry
never read), and a padded/dead row (length 0) costs one empty grid step,
which is what lets the engine pad every batch to one fixed width
(``max_num_seqs``). The kernel has no MXU work at all: one query token a
row, so both products are VPU multiplies with a reduce. The tile is the
pool's block AS STORED (serving/paged_cache.physical_shape):
``[block_size, H, D]``, or ``[block_size, H * D // 128, 128]`` with
``128 // D`` heads side by side on the lanes, read in place either way;
on the chip it has to be whole lane rows (``supported``).

Reference parity: ``ragged_attention_reference`` is a ``lax.scan``
over the same block axis performing the *identical* flash update, a
block at a time, so the kernel (run under ``interpret=True`` on CPU in
tier-1) is pinned against it with bounded error (bitwise on a pool stored
one head a row); the bucketed gather path remains the bitwise oracle at
the engine level (see tests/test_serving_ragged.py).

Blueprint: "Ragged Paged Attention: A High-Performance and Flexible
LLM Inference Kernel for TPU" (PAPERS.md); built on the flash /
packed-flash foundation in this directory.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30  # matches the serving masks: exact erase, no NaN from inf-inf


def supported(head_dim: int, num_heads: int, block) -> bool:
    """Kernel scope: TPU backend only (CPU tier-1 exercises it through
    ``interpret=True``), and a stored block ``[block_size, G, L]`` that the
    kernel can copy and multiply as it is: whole lane rows (a DMA out of
    HBM cannot cut a lane row), a sublane-aligned block_size, and two
    slots of k and v with the products' block-sized temporaries inside the
    default scoped VMEM (``_MAX_BLOCK_VMEM_BYTES``)."""
    if jax.default_backend() != "tpu":
        return False
    bs, g, lanes = block
    return lanes % 128 == 0 and lanes % head_dim == 0 \
        and g * lanes == num_heads * head_dim and bs % 8 == 0 \
        and _block_vmem_bytes(block, jnp.float32) <= _MAX_BLOCK_VMEM_BYTES


def route_gate(head_dim: int, num_heads: int, block) -> bool:
    """Serving-side routing gate: the ragged kernel applies whenever the
    engine selected ``kernel="ragged"`` (the default) and the pool's
    stored block is in scope. Off-TPU the caller keeps the block-table
    gather + composed attention — same jitted sub-programs as the dense
    path, preserving the engine's structural bitwise-parity contract."""
    return supported(head_dim, num_heads, block)


# what one DMA buffer (k or v of one group) may hold in VMEM: four of them
# (two pools, double-buffered) stay an eighth of the v5e's default scoped
# limit of 16 MiB, and a group is long enough that the fixed cost of a
# copy hides behind the bytes of the group before it
_GROUP_VMEM_BYTES = 512 * 1024
# the largest stored block (in VMEM, as float32) the gate admits: four
# buffers of it and the block-sized float32 temporaries of the two products
# stay under the 16 MiB
_MAX_BLOCK_VMEM_BYTES = 1024 * 1024


def _block_vmem_bytes(block_shape, dtype) -> int:
    """Bytes a stored block [bs, G, L] takes in VMEM: G padded to the
    dtype's sublane tile (8 rows of 32 bits), L to 128 lanes."""
    bs, g, lanes = block_shape
    itemsize = jnp.dtype(dtype).itemsize
    sublanes = 8 * max(1, 4 // itemsize)
    return bs * -(-g // sublanes) * sublanes * -(-lanes // 128) * 128 \
        * itemsize


def blocks_per_group(block_shape, dtype, max_blocks: int) -> int:
    """Blocks one DMA group fetches: as many stored blocks as fit
    `_GROUP_VMEM_BYTES`, at least one, at most a row's table."""
    return int(max(1, min(max_blocks, _GROUP_VMEM_BYTES
                          // _block_vmem_bytes(block_shape, dtype))))


def _kernel(tables_ref, lengths_ref, q_ref, k_hbm, v_hbm, o_ref,
            k_buf, v_buf, sems, state_ref, m_ref, l_ref, acc_ref, *,
            block_size, group, scale, head_dim):
    """One grid step a row: the row's live blocks
    cdiv(lengths[i], block_size), and only those, in a loop over DMA groups
    of `group` blocks, group g + 1 in flight while group g is multiplied.
    The loop's bounds are per-row scalars (it starts at block 0 today).
    A group's blocks are loops the KERNEL runs (their trip count is the
    group's live blocks), so the program is as long at group 4 as at
    group 1: the copy's start, its wait and the flash update are traced
    once each per site. Tracing and lowering this body is host time in
    every serving run's set-up (PERF.md section 6, PR 39)."""
    i = pl.program_id(0)
    num_rows = pl.num_programs(0)

    def live_blocks(row):
        return (jnp.maximum(lengths_ref[row], 0) + (block_size - 1)) \
            // block_size

    def group_copies(row, grp, slot, act):
        # k and v of the row's live blocks [grp * group, (grp + 1) * group):
        # one contiguous copy a block and pool, all on the slot's semaphore.
        # A last group that is not full starts (and waits on) fewer copies;
        # table entries past the row's live blocks (the engine's sentinel)
        # are never read. Returns the group's live blocks.
        def one_block(b, _):
            # a fault in a live entry must not become a copy from outside
            # the pool: the DMA needs a legal address
            idx = jnp.clip(tables_ref[row, grp * group + b], 0,
                           k_hbm.shape[0] - 1)
            for hbm, buf in ((k_hbm, k_buf), (v_hbm, v_buf)):
                act(pltpu.make_async_copy(
                    hbm.at[idx], buf.at[slot, b], sems.at[slot]))

        count = jnp.clip(live_blocks(row) - grp * group, 0, group)
        jax.lax.fori_loop(0, count, one_block, None)
        return count

    def start(row, grp, slot):
        group_copies(row, grp, slot, lambda dma: dma.start())

    # state_ref (SMEM, outlives a grid step like the buffers and the
    # semaphores): [slot the row's first group is in, row whose first group
    # is already in flight]
    @pl.when(i == 0)
    def _first_row():
        state_ref[0] = 0
        state_ref[1] = -1

    length = lengths_ref[i]
    nblk = live_blocks(i)
    ngrp = (nblk + (group - 1)) // group
    first_slot = state_ref[0]
    # the next live row, whose first group this row's last group starts
    nxt = jax.lax.while_loop(
        lambda r: (ngrp > 0) & (r < num_rows)
        & (lengths_ref[jnp.minimum(r, num_rows - 1)] <= 0),
        lambda r: r + 1, i + 1)

    @pl.when((ngrp > 0) & (state_ref[1] != i))
    def _start_own_first_group():
        start(i, 0, first_slot)

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    # the tile as the pool stores it: [bs, G, L] with L // head_dim heads
    # side by side on the L lanes of each of G rows (one head a row when
    # the pool is stored in its logical [H, D] shape)
    q = q_ref[0].astype(jnp.float32)          # [G, L]

    def accumulate(j, k, v):
        # One query token per row leaves the MXU nothing to tile, and
        # Mosaic refuses a dot_general batched over the middle dim of the
        # tile (no non-contracting lhs dim). So both products are VPU
        # multiplies with a reduce, kept 3-D in the tile's own layout:
        # bs untiled, G on sublanes, L on lanes. A head's score is the
        # sum of its own head_dim lanes, held on every one of them, so
        # m, l and the probabilities live lane for lane beside acc.
        # s[s, g, lane] = scale * sum_{d in lane's head} q[g, d] k[s, g, d]
        s = _sum_each_head(q[None] * k, head_dim) * jnp.float32(scale)
        # mask positions at/past the row length
        pos = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 0)
        s = jnp.where(pos < length, s, jnp.float32(NEG_INF))

        m_prev = m_ref[...]                                  # [G, L]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[None])                         # [bs, G, L]
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=0)
        # out[g, lane] = sum_s p[s, g, lane] v[s, g, lane]
        acc_ref[...] = acc_ref[...] * alpha + jnp.sum(p * v, axis=0)
        m_ref[...] = m_new

    def one_group(grp, _):
        slot = (first_slot + grp) % 2
        # the group after this one is in flight while this one is
        # multiplied: the row's next, or after its last the first group of
        # the next live row (whose grid step then finds it started)
        more = grp + 1 < ngrp

        @pl.when(more | (nxt < num_rows))
        def _start_next_group():
            start(jnp.where(more, i, nxt), jnp.where(more, grp + 1, 0),
                  1 - slot)

        landed = group_copies(i, grp, slot, lambda dma: dma.wait())

        def one_block(b, _):
            accumulate(grp * group + b, k_buf[slot, b].astype(jnp.float32),
                       v_buf[slot, b].astype(jnp.float32))

        jax.lax.fori_loop(0, landed, one_block, None)

    jax.lax.fori_loop(0, ngrp, one_group, None)

    @pl.when(ngrp > 0)
    def _hand_over():
        state_ref[0] = (first_slot + ngrp) % 2
        state_ref[1] = nxt

    l_fin = l_ref[...]
    denom = jnp.where(l_fin == jnp.float32(0.0), jnp.float32(1.0),
                      l_fin)                         # dead row -> zeros
    o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def _sum_each_head(x, head_dim):
    """x [bs, G, L] summed over each head's head_dim lanes, the sum held
    on all of them: one lane reduce a head of the row (L // head_dim of
    them, masked apart), broadcast back."""
    lanes = x.shape[-1]
    if lanes == head_dim:
        return jnp.broadcast_to(jnp.sum(x, axis=2, keepdims=True), x.shape)
    head = jax.lax.broadcasted_iota(jnp.int32, x.shape, 2) // head_dim
    out = jnp.zeros_like(x)
    for h in range(lanes // head_dim):
        mine = head == h
        out = jnp.where(mine, jnp.sum(jnp.where(mine, x, 0.0), axis=2,
                                      keepdims=True), out)
    return out


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def ragged_decode_attention(q, k_pool, v_pool, block_tables, lengths,
                            scale=None, interpret=False):
    """One attention step over ragged paged KV state.

    q:            [N, H, D]  one query token per row
    k_pool/v_pool:[num_blocks, block_size, G, L] paged-cache pools AS
                  STORED (serving/paged_cache.physical_shape): [H, D]
                  itself, or L // D heads side by side on L = 128 lanes
                  (G * L == H * D). The kernel's tile is the stored
                  block; q and the result are viewed the same way.
    block_tables: [N, MB] int32 pool indices (row-major positions); the
                  first cdiv(lengths[i], block_size) entries of row i are
                  pool blocks, the rest are never read
    lengths:      [N] int32 live KV positions per row (0 = dead row)
    returns       [N, H, D]; dead rows return zeros.
    """
    n, h, d = q.shape
    num_blocks_kv, bs, g, lanes = k_pool.shape
    if g * lanes != h * d or lanes % d:
        raise ValueError(
            f"a pool stored as [.., {g}, {lanes}] holds no [{h}, {d}] heads")
    mb = block_tables.shape[1]
    if scale is None:
        scale = 1.0 / float(np.sqrt(d))
    group = blocks_per_group((bs, g, lanes), k_pool.dtype, mb)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n,),
        in_specs=[
            pl.BlockSpec((1, g, lanes), lambda i, t, le: (i, 0, 0)),
            # the pools stay in HBM; the kernel copies a row's live blocks
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, g, lanes), lambda i, t, le: (i, 0, 0)),
        scratch_shapes=[
            # two slots of one group of k and of v, a semaphore a slot
            pltpu.VMEM((2, group, bs, g, lanes), k_pool.dtype),
            pltpu.VMEM((2, group, bs, g, lanes), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((2,), jnp.int32),
            # m, l (each head's value on all of its lanes) and acc
        ] + [pltpu.VMEM((g, lanes), jnp.float32)] * 3,
    )
    kernel = functools.partial(_kernel, block_size=bs, group=group,
                               scale=float(scale), head_dim=d)
    # int32 grid arithmetic (same reason flash_attention scopes x64 off)
    with jax.enable_x64(False):
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((n, g, lanes), q.dtype),
            # rows in order: a row starts the next live row's first group
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret,
        )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32),
          q.reshape(n, g, lanes), k_pool, v_pool).reshape(n, h, d)


@functools.partial(jax.jit, static_argnames=("scale",))
def ragged_attention_reference(q, k_pool, v_pool, block_tables, lengths,
                               scale=None):
    """lax.scan reference: the *same* flash update as the kernel, one
    scan trip per table block, so CPU tier-1 pins the kernel's
    accumulation order (not just its mathematical value). Dead rows
    (length 0) return zeros, matching the kernel's finalize guard. Takes
    the pools as stored, like the kernel, and reads them as [H, D]."""
    n, h, d = q.shape
    bs = k_pool.shape[1]
    mb = block_tables.shape[1]
    if scale is None:
        scale = 1.0 / float(np.sqrt(d))
    tables = block_tables.astype(jnp.int32)
    lens = lengths.astype(jnp.int32)
    num_blocks_kv = k_pool.shape[0]
    qf = q.astype(jnp.float32)

    def step(carry, j):
        m, l, acc = carry
        idx = tables[:, j]
        live_blk = (j * bs < lens) & (idx >= 0) & (idx < num_blocks_kv)
        safe = jnp.where(live_blk, idx, 0)
        k = k_pool[safe].astype(jnp.float32).reshape(n, bs, h, d)
        v = v_pool[safe].astype(jnp.float32).reshape(n, bs, h, d)
        # a head's score held on all of its D lanes, as in the kernel
        s = jnp.broadcast_to(
            jnp.sum(qf[:, None] * k, axis=3, keepdims=True), k.shape) \
            * jnp.float32(scale)                      # [N, bs, H, D]
        pos = j * bs + jnp.arange(bs, dtype=jnp.int32)[None, :, None, None]
        s = jnp.where(pos < lens[:, None, None, None], s,
                      jnp.float32(NEG_INF))
        m_new = jnp.maximum(m, jnp.max(s, axis=1))    # [N, H, D]
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = alpha * l + jnp.sum(p, axis=1)
        acc_new = acc * alpha + jnp.sum(p * v, axis=1)
        # skipped blocks leave the carry untouched, exactly like @pl.when
        keep = live_blk[:, None, None]
        return (jnp.where(keep, m_new, m), jnp.where(keep, l_new, l),
                jnp.where(keep, acc_new, acc)), None

    m0 = jnp.full((n, h, d), NEG_INF, jnp.float32)
    l0 = jnp.zeros((n, h, d), jnp.float32)
    a0 = jnp.zeros((n, h, d), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, a0), jnp.arange(mb, dtype=jnp.int32))
    denom = jnp.where(l == 0.0, 1.0, l)
    return (acc / denom).astype(q.dtype)
