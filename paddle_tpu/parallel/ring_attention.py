"""Ring attention: context parallelism for long sequences.

Reference capability: the reference scales sequence length via its
pipeline/megatron hybrid (fleet meta-optimizers) — it has no ring
attention (2020-era snapshot); this is the TPU-native long-context
mechanism (Liu et al. 2023, "Ring Attention with Blockwise Transformers")
SURVEY.md §2.3 flags as the long-context enabler.

Design: Q stays resident per device (sequence sharded over a mesh axis);
K/V chunks ROTATE around the ring via `ppermute` (one ICI hop per step,
overlapping the blockwise attention compute), and softmax is accumulated
online flash-style (running max / denominator / weighted accumulator in
fp32), so no device ever materialises more than its [T_local, T_local]
score block. Causal masking is chunk-aware: a device attends fully to
earlier chunks, triangularly to its own, and not at all to later ones.

Use inside `shard_map` over the sequence axis (tests show the pattern);
`ring_attention` is differentiable (pure lax, jax.grad works through the
rotation) — the backward pass re-runs the ring in reverse via autodiff
of ppermute.
"""
from __future__ import annotations

from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

from jax import shard_map  # noqa: F401  (re-export: what callers pair
# with ring/ulysses attention)

__all__ = ["ring_attention", "shard_map", "ulysses_attention"]


def ring_attention(q, k, v, axis_name: str, causal: bool = True,
                   scale: Optional[float] = None):
    """Blockwise ring attention inside shard_map.

    q, k, v: [B, H, T_local, D] — this device's sequence chunk (chunk
    index == its coordinate along `axis_name`).
    Returns [B, H, T_local, D].
    """
    n = jax.lax.psum(1, axis_name)          # ring size (static under jit)
    idx = jax.lax.axis_index(axis_name)
    tl = q.shape[2]
    if scale is None:
        scale = 1.0 / float(np.sqrt(q.shape[-1]))
    qf = q.astype(jnp.float32) * scale
    neg = jnp.asarray(-1e30, jnp.float32)
    iota_q = jnp.arange(tl)[:, None]
    iota_k = jnp.arange(tl)[None, :]

    def body(s, carry):
        k_cur, v_cur, m, l, acc = carry
        j = (idx - s) % n                     # chunk id currently held
        scores = jnp.einsum("bhtd,bhsd->bhts", qf,
                            k_cur.astype(jnp.float32))
        if causal:
            # global positions: q row = idx*tl + t, k col = j*tl + s
            allow = (idx * tl + iota_q) >= (j * tl + iota_k)
            scores = jnp.where(allow[None, None], scores, neg)
        m_blk = jnp.max(scores, axis=-1)
        m_new = jnp.maximum(m, m_blk)
        # renormalise the running accumulator to the new max
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(scores - m_new[..., None])
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhts,bhsd->bhtd", p, v_cur.astype(jnp.float32))
        # rotate K/V one hop around the ring (r -> r+1, so after s steps
        # device i holds chunk (i - s) mod n)
        rot = [(r, (r + 1) % n) for r in range(n)]
        k_nxt = jax.lax.ppermute(k_cur, axis_name, rot)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, rot)
        return (k_nxt, v_nxt, m_new, l_new, acc_new)

    # the carry leaves the loop varying over the ring axis (it mixes in
    # q and the rotating k/v), so it has to enter varying too:
    # shard_map's type check rejects a fori_loop whose carry changes type
    def varying(x):
        return jax.lax.pcast(x, (axis_name,), to="varying")

    m0 = varying(jnp.full(q.shape[:3], -jnp.inf, jnp.float32))
    l0 = varying(jnp.zeros(q.shape[:3], jnp.float32))
    acc0 = varying(jnp.zeros(q.shape, jnp.float32))
    _, _, m, l, acc = jax.lax.fori_loop(
        0, n, body, (k, v, m0, l0, acc0))
    # fully-masked rows (can't happen with causal self-attention over own
    # chunk, but guard the division anyway)
    safe_l = jnp.maximum(l, 1e-30)
    return (acc / safe_l[..., None]).astype(q.dtype)


def ulysses_attention(q, k, v, axis_name: str, causal: bool = True,
                      scale: Optional[float] = None):
    """All-to-all sequence parallelism (DeepSpeed-Ulysses style): the
    OTHER long-context form SURVEY.md §2.3 names. Instead of rotating
    K/V, one all_to_all re-shards [B, H, T_local, D] → [B, H/n, T, D]
    (heads scatter, sequence gathers), each device runs FULL attention
    over its head subset, and a second all_to_all restores the sequence
    sharding. Two collectives total per call vs the ring's n hops —
    cheaper when H >= ring size and the full [T, T] score block fits;
    the ring wins when T is too long for any single chip.

    Use inside shard_map over `axis_name`; requires H % ring_size == 0.
    """
    n = jax.lax.psum(1, axis_name)
    if q.shape[1] % n:
        raise ValueError(
            f"ulysses_attention: heads {q.shape[1]} must divide by the "
            f"'{axis_name}' axis size {n} (use ring_attention otherwise)")
    if scale is None:
        scale = 1.0 / float(np.sqrt(q.shape[-1]))

    def a2a_in(x):   # [3, B, H, Tl, D] -> [3, B, H/n, T, D] (one launch)
        return jax.lax.all_to_all(x, axis_name, split_axis=2,
                                  concat_axis=3, tiled=True)

    def a2a_out(x):  # [B, H/n, T, D] -> [B, H, Tl, D]
        return jax.lax.all_to_all(x, axis_name, split_axis=2,
                                  concat_axis=1, tiled=True)

    qg, kg, vg = a2a_in(jnp.stack([q, k, v]))
    s = jnp.einsum("bhtd,bhsd->bhts", qg.astype(jnp.float32),
                   kg.astype(jnp.float32)) * scale
    if causal:
        T = s.shape[-1]
        allow = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
        s = jnp.where(allow[None, None], s, jnp.asarray(-1e30, jnp.float32))
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhts,bhsd->bhtd", p, vg.astype(jnp.float32))
    return a2a_out(out.astype(q.dtype))
