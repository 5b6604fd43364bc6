"""Packed-pair flash attention prototype (d=64 boundary-copy fix).

Hypothesis (an earlier builder's 12-head attribution): at head_dim 64, ~40% of
the 12-head geometry's gap is [B,T,H,64]<->[B,H,T,64] transposes that XLA
materialises around the pallas custom call (they fuse at d=128). Fix: keep
the HBM arrays PACKED as [B, H/2, T, 128] (head 2i in lanes 0:64, head
2i+1 in 64:128 — the natural reshape order) and run the UNCHANGED upstream
d=64 kernel body over them via index maps (b, h) -> (b, h//2, t, h%2):
the BlockSpec's 64-wide last-dim block selects the lane half. All
boundary tensors are then 128-minor, so the surrounding transposes fuse.

This file: FORWARD only — numerics check vs composed attention + slope
timing of (proj -> attention fwd -> out-proj) packed vs unpacked. If the
win shows, the bwd (dq/dkv kernels) gets the same index-map treatment.

Run: python tools/packed_flash_proto.py

VERDICT (v5e, 2026-07-31): the index-map route is REJECTED by the Mosaic
lowering — "the last two dimensions of your block shape [must be]
divisible by 8 and 128 respectively, or be equal to the respective
dimensions of the overall array". A 64-lane half-block over a 128-wide
packed array is exactly the disallowed case (the existing d=64 kernel is
legal only because its ARRAY last dim is 64). The surviving design is a
custom kernel whose blocks are the full 128 lanes and which splits the
halves in-register (two QK^T dots, two running softmaxes, two PV dots per
tile) — requires new fwd AND bwd kernel bodies, not index maps. That
design was then BUILT and SHIPPED as paddle_tpu/ops/pallas/packed_flash.py
(this harness now measures the shipped kernels): 12-head GPT step went
121.3k -> 153.3k tok/s (+26%, MFU 0.476 -> 0.602), far past the ~+9%
projected from the copy bytes alone — the simple full-block bwd also
outruns upstream's blocked bwd at this geometry.
"""
from __future__ import annotations

import functools
import time

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# The production kernels live in paddle_tpu/ops/pallas/packed_flash.py —
# the harness measures THOSE (an earlier revision carried drifting copies
# here; only the rejected BlockSpec route above stays local as a receipt).
from paddle_tpu.ops.pallas.packed_flash import (  # noqa: E402
    packed_flash_attention, _fwd_call as packed_flash_fwd_v2_call)


def packed_flash_fwd_v2(q, k, v, causal, sm_scale, block_q=512):
    return packed_flash_fwd_v2_call(q, k, v, causal, sm_scale,
                                    block_q=block_q)


# ---------------------------------------------------------------- harness
def attention_block_unpacked(x, wq, wk, wv, wo, H, D, causal=True):
    """Current path: [B,T,C] -> heads-major [B,H,T,D] -> flash -> out."""
    from paddle_tpu.ops.pallas.flash_attention import _fa_core
    B, T, C = x.shape
    q = jnp.swapaxes((x @ wq).reshape(B, T, H, D), 1, 2)
    k = jnp.swapaxes((x @ wk).reshape(B, T, H, D), 1, 2)
    v = jnp.swapaxes((x @ wv).reshape(B, T, H, D), 1, 2)
    o = _fa_core(q, k, v, causal, 1.0 / np.sqrt(D))
    return jnp.swapaxes(o, 1, 2).reshape(B, T, C) @ wo


def attention_block_packed(x, wq, wk, wv, wo, H, D, causal=True):
    """Packed path: [B,T,C] -> [B,H/2,T,2D] (128-minor; transpose fuses)
    -> packed kernel -> back."""
    B, T, C = x.shape
    q = jnp.swapaxes((x @ wq).reshape(B, T, H // 2, 2 * D), 1, 2)
    k = jnp.swapaxes((x @ wk).reshape(B, T, H // 2, 2 * D), 1, 2)
    v = jnp.swapaxes((x @ wv).reshape(B, T, H // 2, 2 * D), 1, 2)
    o = packed_flash_fwd_v2(q, k, v, causal, 1.0 / np.sqrt(D))
    return jnp.swapaxes(o, 1, 2).reshape(B, T, C) @ wo


def slope_time(fn, args, n1=5, n2=30):
    def make(n):
        @jax.jit
        def loop(*a):
            def body(i, carry):
                scale = 1.0 + 0.001 * i.astype(jnp.float32)
                o = fn(a[0] * scale.astype(a[0].dtype), *a[1:])
                of = o.astype(jnp.float32)
                return carry + jnp.sum(of * of)
            return lax.fori_loop(0, n, body, jnp.float32(0))
        return loop
    l1, l2 = make(n1), make(n2)
    float(np.asarray(l1(*args)))
    float(np.asarray(l2(*args)))
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        float(np.asarray(l1(*args)))
        t1 = time.perf_counter()
        float(np.asarray(l2(*args)))
        t2 = time.perf_counter()
        best = min(best, ((t2 - t1) - (t1 - t0)) / (n2 - n1))
    return best * 1e3


def main():
    B, T, H, D = 32, 1024, 12, 64
    C = H * D
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(B, T, C) * 0.05, jnp.bfloat16)
    ws = [jnp.asarray(rng.randn(C, C) / np.sqrt(C), jnp.bfloat16)
          for _ in range(4)]

    a = jax.jit(functools.partial(attention_block_unpacked, H=H, D=D))(
        x, *ws)
    b = jax.jit(functools.partial(attention_block_packed, H=H, D=D))(
        x, *ws)
    err = float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                - b.astype(jnp.float32))))
    ref = float(jnp.max(jnp.abs(a.astype(jnp.float32))))
    print(f"max|unpacked - packed| = {err:.4g} (scale {ref:.3g})")
    assert err <= 0.02 * max(ref, 1.0), "numerics mismatch"

    t_un = slope_time(functools.partial(attention_block_unpacked, H=H, D=D),
                      (x, *ws))
    t_pk = slope_time(functools.partial(attention_block_packed, H=H, D=D),
                      (x, *ws))
    print(f"fwd attention block (proj+attn+out, B{B} T{T} H{H} D{D}): "
          f"unpacked {t_un:.3f} ms   packed {t_pk:.3f} ms   "
          f"({t_un / t_pk:.2f}x)")

    # ---- fwd+bwd: grads wrt x and all four weights, packed vs current
    def loss_un(x, *ws):
        o = attention_block_unpacked(x, *ws, H=H, D=D)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    def block_packed_vjp(x, wq, wk, wv, wo, causal=True):
        q = jnp.swapaxes((x @ wq).reshape(B, T, H // 2, 2 * D), 1, 2)
        k = jnp.swapaxes((x @ wk).reshape(B, T, H // 2, 2 * D), 1, 2)
        v = jnp.swapaxes((x @ wv).reshape(B, T, H // 2, 2 * D), 1, 2)
        o = packed_flash_attention(q, k, v, causal, 1.0 / np.sqrt(D))
        return jnp.swapaxes(o, 1, 2).reshape(B, T, H * D) @ wo

    def loss_pk(x, *ws):
        o = block_packed_vjp(x, *ws)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    g_un = jax.jit(jax.grad(loss_un, argnums=(0, 1, 4)))(x, *ws)
    g_pk = jax.jit(jax.grad(loss_pk, argnums=(0, 1, 4)))(x, *ws)
    for name, a_, b_ in zip(("dx", "dwq", "dwo"), g_un, g_pk):
        aerr = float(jnp.max(jnp.abs(a_.astype(jnp.float32)
                                     - b_.astype(jnp.float32))))
        ascale = float(jnp.max(jnp.abs(a_.astype(jnp.float32)))) + 1e-9
        print(f"  bwd {name}: max|diff| {aerr:.4g} (scale {ascale:.3g})")
        assert aerr <= 0.03 * ascale, f"bwd {name} mismatch"

    t_un_b = slope_time(
        lambda x, *ws: jax.grad(loss_un, argnums=0)(x, *ws), (x, *ws),
        n1=4, n2=16)
    t_pk_b = slope_time(
        lambda x, *ws: jax.grad(loss_pk, argnums=0)(x, *ws), (x, *ws),
        n1=4, n2=16)
    print(f"fwd+bwd(dx) attention block: unpacked {t_un_b:.3f} ms   "
          f"packed {t_pk_b:.3f} ms   ({t_un_b / t_pk_b:.2f}x)")


if __name__ == "__main__":
    main()
