"""Ragged paged attention for TPU decode.

One program for a whole mixed batch: each row attends over exactly
``lengths[i]`` KV positions read straight from the paged-cache block
table — no power-of-2 bucket padding, no per-bucket recompile, and
rows that are mid-prefill (chunked prefill feeds one prompt token per
scan trip) ride the same kernel as decode rows. The kernel is a
flash-style streaming softmax over the block axis with the block
tables and per-row lengths passed as *scalar-prefetched* operands, so
the index maps pick the next KV block to DMA and the compute of blocks
past a row's length is skipped: a padded/dead row (length 0) multiplies
nothing (the kernel has no MXU work at all: one query token a row, so
both products are VPU multiplies with a reduce; what a dead row still
pays is its grid steps), which is what lets the engine pad every batch
to one fixed width (``max_num_seqs``). The tile is the pool's block AS
STORED (serving/paged_cache.physical_shape): ``[block_size, H, D]``, or
``[block_size, H * D // 128, 128]`` with ``128 // D`` heads side by side
on the lanes, read in place either way.

Reference parity: ``ragged_attention_reference`` is a ``lax.scan``
over the same block axis performing the *identical* flash update, so
the kernel (run under ``interpret=True`` on CPU in tier-1) is pinned
against it with bounded error; the bucketed gather path remains the
bitwise oracle at the engine level (see tests/test_serving_ragged.py).

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


def supported(head_dim: int, num_heads: int, block_size: int) -> bool:
    """Kernel scope: TPU backend only (CPU tier-1 exercises it through
    ``interpret=True``); sublane-aligned head_dim and block_size so the
    stored block is a legal VMEM tile."""
    if jax.default_backend() != "tpu":
        return False
    return head_dim % 8 == 0 and block_size % 8 == 0 and num_heads >= 1


def route_gate(head_dim: int, num_heads: int, block_size: int) -> bool:
    """Serving-side routing gate: the ragged kernel applies whenever the
    engine selected ``kernel="ragged"`` (the default) and the geometry is
    in scope. Off-TPU the caller keeps the block-table gather + composed
    attention — same jitted sub-programs as the dense path, preserving
    the engine's structural bitwise-parity contract."""
    return supported(head_dim, num_heads, block_size)


def _kernel(tables_ref, lengths_ref, q_ref, k_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref, *, block_size, num_blocks_kv, scale,
            head_dim):
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = lengths_ref[i]

    # Block j covers KV positions [j*bs, (j+1)*bs); skip its compute
    # unless some position is live. Dead rows (length 0) skip every
    # block.
    @pl.when(j * block_size < length)
    def _accumulate():
        # the tile as the pool stores it: [bs, G, L] with L // head_dim
        # heads side by side on the L lanes of each of G rows (one head a
        # row when the pool is stored in its logical [H, D] shape)
        q = q_ref[0].astype(jnp.float32)      # [G, L]
        k = k_ref[0].astype(jnp.float32)      # [bs, G, L]
        v = v_ref[0].astype(jnp.float32)      # [bs, G, L]
        # One query token per row leaves the MXU nothing to tile, and
        # Mosaic refuses a dot_general batched over the middle dim of the
        # tile (no non-contracting lhs dim). So both products are VPU
        # multiplies with a reduce, kept 3-D in the tile's own layout:
        # bs untiled, G on sublanes, L on lanes. A head's score is the
        # sum of its own head_dim lanes, held on every one of them, so
        # m, l and the probabilities live lane for lane beside acc.
        # s[s, g, lane] = scale * sum_{d in lane's head} q[g, d] k[s, g, d]
        qk = q[None] * k
        s = _sum_each_head(qk, head_dim) * jnp.float32(scale)
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

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        l_fin = l_ref[...]
        denom = jnp.where(l_fin == jnp.float32(0.0), jnp.float32(1.0),
                          l_fin)                     # dead row -> zeros
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


def _kv_index_map(i, j, tables_ref, lengths_ref, *, block_size,
                  num_blocks_kv):
    # Scalar-prefetched table pick: the DMA for grid step (i, j) fetches
    # pool block tables[i, j]. Clamp dead/beyond-length entries (the
    # engine packs the out-of-range sentinel there) to block 0 — the
    # compute for those steps is @pl.when-ed off, the DMA just needs a
    # legal address.
    idx = tables_ref[i, j].astype(jnp.int32)
    live = (j * block_size < lengths_ref[i]) & (idx >= 0) \
        & (idx < num_blocks_kv)
    return jnp.where(live, idx, jnp.int32(0)), 0, 0, 0


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
    block_tables: [N, MB] int32 pool indices (row-major positions)
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
    kv_map = functools.partial(_kv_index_map, block_size=bs,
                               num_blocks_kv=num_blocks_kv)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n, mb),
        in_specs=[
            pl.BlockSpec((1, g, lanes), lambda i, j, t, le: (i, 0, 0)),
            pl.BlockSpec((1, bs, g, lanes), kv_map),
            pl.BlockSpec((1, bs, g, lanes), kv_map),
        ],
        out_specs=pl.BlockSpec((1, g, lanes),
                               lambda i, j, t, le: (i, 0, 0)),
        # m, l (each head's value on all of its lanes) and acc
        scratch_shapes=[pltpu.VMEM((g, lanes), jnp.float32)] * 3,
    )
    kernel = functools.partial(_kernel, block_size=bs,
                               num_blocks_kv=num_blocks_kv,
                               scale=float(scale), head_dim=d)
    # int32 grid arithmetic (same reason flash_attention scopes x64 off)
    with jax.enable_x64(False):
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((n, g, lanes), q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
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
