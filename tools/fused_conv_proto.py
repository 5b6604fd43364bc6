"""Prototype: pallas fused (affine+relu[+residual]) -> 1x1-conv matmul.

Question to answer BEFORE investing in an MLPerf-style fused-bottleneck
path: can a Mosaic matmul with the BN normalize+relu folded into its input
transform beat XLA's (normalize fusion -> conv custom-call) sequence at
ResNet-50's block-boundary geometries?  The fused kernel skips one full
write+read of the activation (the materialised relu output), worth ~7% of
step bytes if it holds the conv's MXU efficiency.

Run on the real chip:
    python tools/fused_conv_proto.py

Prints per-geometry times: xla_ref (normalize fusion + conv1x1) vs
pallas_fused, plus a correctness check.

VERDICT (v5e, 2026-07-31, slope-timed inside one jit with a
non-reassociable consumer):
    layer1 56x56 256->64:   xla 0.544 ms   pallas 0.656 ms
    layer2 28x28 512->128:  xla 0.253 ms   pallas 0.331 ms
    layer3 14x14 1024->256: xla 0.107 ms   pallas 0.109 ms
    layer4 7x7 2048->512:   xla 0.066 ms   pallas 0.696 ms
    bn2    56x56 64->256:   xla 0.230 ms   pallas 0.919 ms
XLA's (normalize fusion -> conv custom-call) sequence beats or ties the
fused Mosaic matmul at every ResNet-50 geometry — the input-transform
fusion saves bytes but Mosaic's matmul pipeline gives the advantage
straight back (and loses badly at small spatial dims). Conclusion: the
MLPerf-style fused-bottleneck path is a pessimization on this toolchain;
ResNet-50 stays on the XLA conv path (an earlier builder's ~2.6k imgs/s
at the HBM roofline; not re-measured on today's code). Same finding as the splash-attention comparison
(r4): hand kernels only beat XLA here when they change the ALGORITHM
(flash attention's O(T) HBM), not the schedule.
"""
from __future__ import annotations

import functools
import time

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _fused_kernel(x_ref, z_ref, w_ref, scale_ref, shift_ref, o_ref, acc_ref,
                  *, k_steps, with_res):
    """One (bm, bn) output tile; grid = (M/bm, N/bn, K/bk).

    x: [bm, bk] bf16 conv output (pre-BN), z: optional [bm, bk] residual,
    w: [bk, bn] bf16, scale/shift: [1, bk] f32 per-channel affine.
    Input transform: relu(x*scale + shift (+z)) in f32, cast to bf16,
    then MXU dot with f32 accumulation.
    """
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)
    t = x * scale_ref[...] + shift_ref[...]
    if with_res:
        t = t + z_ref[...].astype(jnp.float32)
    t = jnp.maximum(t, 0.0).astype(jnp.bfloat16)
    acc_ref[...] += jax.lax.dot_general(
        t, w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k == k_steps - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk"))
def fused_scale_relu_matmul(x, z, w, scale, shift, bm=512, bn=128, bk=256):
    """y = relu(x*scale + shift + z) @ w  — x:[M,K] bf16, w:[K,N] bf16."""
    M, K = x.shape
    N = w.shape[1]
    bn = min(bn, N)
    bk = min(bk, K)
    while M % bm:
        bm //= 2
    k_steps = K // bk
    with_res = z is not None
    args = [x] + ([z] if with_res else []) + [
        w, scale.reshape(1, K), shift.reshape(1, K)]
    in_specs = [pl.BlockSpec((bm, bk), lambda i, j, k: (i, k))]
    if with_res:
        in_specs.append(pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)))
    in_specs += [
        pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        pl.BlockSpec((1, bk), lambda i, j, k: (0, k)),
        pl.BlockSpec((1, bk), lambda i, j, k: (0, k)),
    ]
    kern = functools.partial(_fused_kernel if with_res else _fused_nores,
                             k_steps=k_steps, with_res=with_res)
    return pl.pallas_call(
        kern,
        grid=(M // bm, N // bn, k_steps),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(*args)


def _fused_nores(x_ref, w_ref, scale_ref, shift_ref, o_ref, acc_ref, *,
                 k_steps, with_res):
    _fused_kernel(x_ref, None, w_ref, scale_ref, shift_ref, o_ref, acc_ref,
                  k_steps=k_steps, with_res=False)


@jax.jit
def xla_ref(x, z, w, scale, shift):
    t = x.astype(jnp.float32) * scale + shift
    if z is not None:
        t = t + z.astype(jnp.float32)
    t = jnp.maximum(t, 0.0).astype(jnp.bfloat16)
    return jax.lax.dot_general(t, w, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32
                               ).astype(jnp.bfloat16)


def bench(f, x, z, w, scale, shift, iters=200):
    """Per-application time via a two-point slope: run the op n1 and n2
    times inside jitted fori_loops and divide the time DIFFERENCE by
    (n2-n1). Lessons encoded here (each produced a phantom measurement):
      - a dispatch costs ~0.2 ms and a synchronous scalar fetch ~0.9 ms
        on a local v5e (chip run, PR 23) — comparable to a sub-ms
        kernel, hence in-loop timing and the slope (which cancels the
        fixed cost);
      - the per-iteration perturbation must survive f32 rounding
        (1+1e-12*i == 1.0 exactly → whole body hoisted loop-invariant);
      - the window is closed by a host fetch of the result (same as
        bench.py). On a local v5e block_until_ready is as good: it
        returned 2.7 ms before the fetch on a 119 ms program (chip run,
        PR 23)."""

    def make(n):
        @jax.jit
        def loop(x, z, w, scale, shift):
            def body(i, carry):
                s = scale * (1.0 + 0.001 * i.astype(jnp.float32))
                o = f(x, z, w, s, shift)
                # non-reassociable full-output reduction: o[0,0] lets XLA
                # slice through the dot and DCE everything; sum(o) gets
                # reassociated into dot(sum(t), sum(w)) which also kills
                # the matmul. sum(o*o) forces the real computation; its
                # extra read of o is identical for both paths.
                of = o.astype(jnp.float32)
                return carry + jnp.sum(of * of)
            return jax.lax.fori_loop(0, n, body, jnp.float32(0))
        return loop

    n1, n2 = max(iters // 10, 5), iters
    l1, l2 = make(n1), make(n2)
    float(np.asarray(l1(x, z, w, scale, shift)))
    float(np.asarray(l2(x, z, w, scale, shift)))
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        float(np.asarray(l1(x, z, w, scale, shift)))
        t1 = time.perf_counter()
        float(np.asarray(l2(x, z, w, scale, shift)))
        t2 = time.perf_counter()
        best = min(best, ((t2 - t1) - (t1 - t0)) / (n2 - n1))
    return best * 1e3  # ms


def main():
    rng = np.random.RandomState(0)
    bs = 128
    # block-boundary sites: (H*W, C_in, C_out) with residual add
    geoms = [
        ("layer1->conv1 56x56 256->64", 56 * 56, 256, 64, True),
        ("layer2->conv1 28x28 512->128", 28 * 28, 512, 128, True),
        ("layer3->conv1 14x14 1024->256", 14 * 14, 1024, 256, True),
        ("layer4->conv1 7x7 2048->512", 7 * 7, 2048, 512, True),
        ("bn2->conv3 56x56 64->256", 56 * 56, 64, 256, False),
    ]
    for name, hw, cin, cout, with_res in geoms:
        M = bs * hw
        x = jnp.asarray(rng.randn(M, cin), jnp.bfloat16)
        z = jnp.asarray(rng.randn(M, cin), jnp.bfloat16) if with_res else None
        w = jnp.asarray(rng.randn(cin, cout) / np.sqrt(cin), jnp.bfloat16)
        scale = jnp.asarray(rng.rand(cin) + 0.5, jnp.float32)
        shift = jnp.asarray(rng.randn(cin) * 0.1, jnp.float32)
        ref = xla_ref(x, z, w, scale, shift)
        try:
            got = fused_scale_relu_matmul(x, z, w, scale, shift)
            err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                        - ref.astype(jnp.float32))))
            t_p = bench(lambda *a: fused_scale_relu_matmul(*a),
                        x, z, w, scale, shift)
        except Exception as e:  # noqa: BLE001 - prototype survey
            print(f"{name}: pallas FAILED: {type(e).__name__}: {e}")
            continue
        t_x = bench(lambda *a: xla_ref.__wrapped__(*a), x, z, w, scale,
                    shift)
        flops = 2 * M * cin * cout
        print(f"{name}: xla {t_x:.3f} ms  pallas {t_p:.3f} ms  "
              f"(pallas {flops/t_p/1e9:.0f} GF/s, max|err| {err:.3g})")


if __name__ == "__main__":
    main()
