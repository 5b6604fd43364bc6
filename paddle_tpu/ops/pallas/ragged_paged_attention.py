"""Ragged paged attention for TPU decode.

One program for a whole mixed batch: each row attends over exactly
``lengths[i]`` KV positions read straight from the paged-cache block
table — no power-of-2 bucket padding, no per-bucket recompile, and
rows that are mid-prefill (chunked prefill feeds one prompt token per
scan trip) ride the same kernel as decode rows. The kernel is a
flash-style streaming softmax over the block axis with the block
tables and per-row lengths passed as *scalar-prefetched* operands, so
the index maps pick the next KV block to DMA and blocks past a row's
length are skipped entirely: a padded/dead row (length 0) costs zero
MXU work, which is what lets the engine pad every batch to one fixed
width (``max_num_seqs``) and still claim zero padding waste.

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
    ``interpret=True``); lane-aligned head_dim so the [H, D] accumulator
    tiles cleanly; block_size at least sublane width so the [H, bs]
    score tile is a legal VMEM shape."""
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
            m_ref, l_ref, acc_ref, *, block_size, num_blocks_kv, scale):
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = lengths_ref[i]

    # Block j covers KV positions [j*bs, (j+1)*bs); skip it (no DMA use,
    # no MXU work) unless some position is live. Dead rows (length 0)
    # skip every block — the zero-padding-waste claim is this line.
    @pl.when(j * block_size < length)
    def _accumulate():
        q = q_ref[0].astype(jnp.float32)      # [H, D]
        k = k_ref[0].astype(jnp.float32)      # [bs, H, D]
        v = v_ref[0].astype(jnp.float32)      # [bs, H, D]
        # One query token per row leaves the MXU nothing to tile, and
        # Mosaic refuses a dot_general batched over the middle dim of the
        # [bs, H, D] tile (no non-contracting lhs dim). So both products
        # are VPU multiplies with a reduce, kept 3-D in the tile's own
        # layout: bs untiled, H on sublanes, D (or the keepdims 1) on lanes.
        # scores[s, h, 0] = scale * sum_d q[h, d] k[s, h, d]
        s = jnp.sum(q[None] * k, axis=2, keepdims=True) \
            * jnp.float32(scale)                             # [bs, H, 1]
        # mask positions at/past the row length
        pos = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 0)
        s = jnp.where(pos < length, s, jnp.float32(NEG_INF))

        m_prev = m_ref[:, :1]                                # [H, 1]
        l_prev = l_ref[:, :1]
        m_cur = jnp.max(s, axis=0)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[None])                         # [bs, H, 1]
        l_new = alpha * l_prev + jnp.sum(p, axis=0)
        # out[h, d] = sum_s p[s, h, 0] v[s, h, d]
        pv = jnp.sum(p * v, axis=0)                          # [H, D]
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        l_fin = l_ref[:, :1]
        denom = jnp.where(l_fin == jnp.float32(0.0), jnp.float32(1.0),
                          l_fin)                     # dead row -> zeros
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


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
    k_pool/v_pool:[num_blocks, block_size, H, D] paged-cache pools
    block_tables: [N, MB] int32 pool indices (row-major positions)
    lengths:      [N] int32 live KV positions per row (0 = dead row)
    returns       [N, H, D]; dead rows return zeros.
    """
    n, h, d = q.shape
    num_blocks_kv, bs, _, _ = k_pool.shape
    mb = block_tables.shape[1]
    if scale is None:
        scale = 1.0 / float(np.sqrt(d))
    kv_map = functools.partial(_kv_index_map, block_size=bs,
                               num_blocks_kv=num_blocks_kv)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n, mb),
        in_specs=[
            pl.BlockSpec((1, h, d), lambda i, j, t, le: (i, 0, 0)),
            pl.BlockSpec((1, bs, h, d), kv_map),
            pl.BlockSpec((1, bs, h, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, h, d), lambda i, j, t, le: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, 128), jnp.float32),   # m (lane-replicated)
            pltpu.VMEM((h, 128), jnp.float32),   # l (lane-replicated)
            pltpu.VMEM((h, d), jnp.float32),     # acc
        ],
    )
    kernel = functools.partial(_kernel, block_size=bs,
                               num_blocks_kv=num_blocks_kv,
                               scale=float(scale))
    # int32 grid arithmetic (same reason flash_attention scopes x64 off)
    with jax.enable_x64(False):
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((n, h, d), q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=interpret,
        )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32),
          q, k_pool, v_pool)


@functools.partial(jax.jit, static_argnames=("scale",))
def ragged_attention_reference(q, k_pool, v_pool, block_tables, lengths,
                               scale=None):
    """lax.scan reference: the *same* flash update as the kernel, one
    scan trip per table block, so CPU tier-1 pins the kernel's
    accumulation order (not just its mathematical value). Dead rows
    (length 0) return zeros, matching the kernel's finalize guard."""
    n, h, d = q.shape
    _, bs, _, _ = k_pool.shape
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
        k = k_pool[safe].astype(jnp.float32)          # [N, bs, H, D]
        v = v_pool[safe].astype(jnp.float32)
        s = jnp.sum(qf[:, None] * k, axis=3, keepdims=True) \
            * jnp.float32(scale)                      # [N, bs, H, 1]
        pos = j * bs + jnp.arange(bs, dtype=jnp.int32)[None, :, None, None]
        s = jnp.where(pos < lens[:, None, None, None], s,
                      jnp.float32(NEG_INF))
        m_cur = jnp.max(s, axis=1)                    # [N, H, 1]
        m_new = jnp.maximum(m, m_cur)
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = alpha * l + jnp.sum(p, axis=1)
        pv = jnp.sum(p * v, axis=1)                   # [N, H, D]
        acc_new = acc * alpha + pv
        # skipped blocks leave the carry untouched, exactly like @pl.when
        keep = live_blk[:, None, None]
        return (jnp.where(keep, m_new, m), jnp.where(keep, l_new, l),
                jnp.where(keep, acc_new, acc)), None

    m0 = jnp.full((n, h, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((n, h, 1), jnp.float32)
    a0 = jnp.zeros((n, h, d), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, a0), jnp.arange(mb, dtype=jnp.int32))
    denom = jnp.where(l == 0.0, 1.0, l)
    return (acc / denom).astype(q.dtype)
