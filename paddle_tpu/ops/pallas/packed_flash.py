"""Packed-pair flash attention for head_dim 64 (TPU lane-padding fix).

At head_dim 64 the standard flash path pays twice: (a) d=64 tiles fill
half the 128-lane MXU (unavoidable — a real kernel floor), and (b) XLA
materialises the [B,T,H,64]<->[B,H,T,64] transposes around the pallas
custom call because 64-minor layouts don't fuse (an earlier builder profiled
18.8 GB/step of extra traffic on the 12-head GPT bench geometry). This
module removes (b): adjacent head PAIRS stay packed on
the 128-lane minor dimension end to end — [B, H/2, T, 128], a pure
reshape of the projection output, whose transpose to heads-major fuses —
and the kernels split the two 64-wide halves IN REGISTERS (BlockSpec
lane-half selection is rejected by the Mosaic lowering: the last block
dim must be divisible by 128 or equal the array dim;
tools/packed_flash_proto.py has the receipts).

Measured on v5e at the 12-head bench geometry (B32 T1024 H12 D64): the
full GPT train step went 121.3k -> 153.3k tok/s (+26%, MFU 0.476 ->
0.602) with these kernels replacing the upstream flash path — the fwd
block alone measured 1.28x, and this single-kv-block backward (softmax
recomputed from q/k, full T x T rectangle) outruns upstream's blocked
bwd at this geometry despite no causal block-skipping.

Scope gate (see `supported`): head_dim 64, even head count, no mask/
dropout, T <= MAX_SEQ (8192 — the longest length MEASURED as a win;
see the MAX_SEQ comment). Up to 1024 the backward runs as one
program per (batch, pair) holding the full [T, T] f32 rectangle in VMEM
(~4 MB each at 1024 — fewer passes win at short T); above that it runs
FA2-style (`_dq_kernel`/`_dkv_kernel`): the forward stages each row's
logsumexp, delta = rowsum(do*o) replaces the in-kernel correction, and
2D q-block x kv-block grids SKIP fully-masked causal blocks. 12-head
GPT vs the upstream padded path: T=2048 MFU 0.459 -> 0.511; T=4096
0.458 -> 0.4907; T=8192 0.4617 -> 0.4780.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

MAX_SEQ = 8192
# Backward dispatch (all boundaries MEASURED on the 12-head GPT A/B,
# v5e, not VMEM limits):
# - T <= BWD_SINGLE_MAX: one program per (batch, pair) holding the full
#   [T, T] rectangle -- fewer passes win at short T (MFU 0.607 vs 0.537
#   for the FA2 kernels at T=1024).
# - BWD_SINGLE_MAX < T <= MAX_SEQ: FA2-style kernels (fwd-saved lse,
#   2D q-block x kv-block grids, causal block skipping, delta =
#   rowsum(do*o)) at FA2_BLOCK=1024 (block sweep: 256 -> MFU 0.431,
#   512 -> 0.511, 1024 -> 0.511 at T=2048; 1024 beats 512 outright at
#   4096, 0.4907 vs 0.4771, and flips T=8192 from a loss to a win,
#   0.4780 vs 0.4529). A/B vs upstream padded flash: T=2048 0.511 vs
#   0.459; T=4096 0.4907 vs 0.458; T=8192 0.4780 vs 0.4617. (An
#   intermediate full-kv q-blocked bwd without lse measured 0.5013 @
#   2048 but collapsed to 0.291 @ 4096 -- the full causal rectangle's
#   2x flop waste -- and was removed once FA2 dominated it.)
# - T > MAX_SEQ: upstream flash. 8192 is the longest length A/B'd,
#   not a measured loss boundary -- the trend at 8192 still favours
#   FA2 (+3.5%), so a 16k-context d=64 model should re-run the A/B
#   before assuming either path.
BWD_SINGLE_MAX = 1024


def supported(head_dim: int, num_heads: int, q_seq: int, kv_seq: int) -> bool:
    if jax.default_backend() != "tpu":
        return False
    return (head_dim == 64 and num_heads % 2 == 0
            and q_seq == kv_seq and q_seq % 128 == 0 and q_seq <= MAX_SEQ)


def route_gate(head_dim: int, num_heads: int, q_seq: int, kv_seq: int,
               dropout_active: bool = False, masked: bool = False) -> bool:
    """Model-side routing gate shared by GPTAttention/BertSelfAttention:
    packed-pair kernels apply under the same conditions as the flash path
    (no mask/dropout, seq past the flash threshold), on one device (GSPMD
    cannot partition a Mosaic kernel, and under tp sliced_qkv takes the
    unpacked path; several devices get the standard flash kernel per
    shard, flash_attention._fa_sharded), and within this kernel's scope
    (`supported`)."""
    if masked or dropout_active:
        return False
    from ...core import flags as _flags
    from ...parallel.mesh import get_global_mesh
    mesh = get_global_mesh()
    if mesh is not None and mesh.size > 1:
        return False
    return (_flags.flag("use_flash_attention")
            and q_seq >= _flags.flag("flash_attention_min_seq")
            and supported(head_dim, num_heads, q_seq, kv_seq))


def _half_fwd(qh, kh, vh, sm_scale, causal, row_offset):
    """Forward for ONE 64-wide half against the full kv: exact per-row
    softmax (every program sees full rows). Returns (normalized output
    [bq, 64] f32, lse [bq] f32 — the logsumexp the FA2 backward
    re-exponentiates against)."""
    s = lax.dot_general(qh, kh, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32,
                        precision=lax.Precision.DEFAULT) * sm_scale
    if causal:
        row = row_offset + lax.broadcasted_iota(jnp.int32, s.shape, 0)
        col = lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(row >= col, s, jnp.float32(-1e30))
    m = jnp.max(s, axis=1, keepdims=True)
    e = jnp.exp(s - m)
    l = jnp.sum(e, axis=1, keepdims=True)
    oh = lax.dot_general(e.astype(qh.dtype), vh, (((1,), (0,)), ((), ())),
                         preferred_element_type=jnp.float32,
                         precision=lax.Precision.DEFAULT)
    return oh / l, (m + jnp.log(l))[:, 0]


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref=None, *, causal,
                sm_scale, block_q, head_dim):
    """One (batch, pair, q-block): full-lane 128 blocks; the two 64-wide
    heads are sliced as values, each gets its own scores/softmax/PV, and
    the halves concat back for a single 128-lane store. With a second
    output bound (with_lse), also stages each half's row logsumexp for
    the FA2 backward (lse_ref block [1, 1, 2, bq] f32)."""
    qi = pl.program_id(2)
    q = q_ref[0, 0]                                   # [bq, 128]
    k = k_ref[0, 0]                                   # [T, 128]
    v = v_ref[0, 0]
    halves, lses = [], []
    for h in (0, 1):
        sl = slice(h * head_dim, (h + 1) * head_dim)
        oh, lse = _half_fwd(q[:, sl], k[:, sl], v[:, sl], sm_scale, causal,
                            qi * block_q)
        halves.append(oh)
        lses.append(lse)
    o_ref[0, 0] = jnp.concatenate(halves, axis=-1).astype(o_ref.dtype)
    if lse_ref is not None:
        lse_ref[0, 0] = jnp.stack(lses)


def _half_bwd(qh, kh, vh, doh, sm_scale, causal, row_offset):
    """Flash backward algebra for ONE 64-wide half, q rows starting at
    global row `row_offset` against the full kv: recompute the softmax
    from q/k (exact — every program sees full rows), then
    dv = P^T do;  ds = P*(dp - rowsum(dp*P))*scale;  dq = ds k;
    dk = ds^T q. Returns (dq_h, dk_h, dv_h) as f32. Used by the
    single-program (T <= BWD_SINGLE_MAX) backward; the FA2 kernels use
    the saved-lse form of the same algebra."""
    s = lax.dot_general(qh, kh, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32,
                        precision=lax.Precision.DEFAULT) * sm_scale
    if causal:
        row = row_offset + lax.broadcasted_iota(jnp.int32, s.shape, 0)
        col = lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(row >= col, s, jnp.float32(-1e30))
    m = jnp.max(s, axis=1, keepdims=True)
    e = jnp.exp(s - m)
    p = e / jnp.sum(e, axis=1, keepdims=True)
    pb = p.astype(qh.dtype)
    dv = lax.dot_general(pb, doh, (((0,), (0,)), ((), ())),
                         preferred_element_type=jnp.float32,
                         precision=lax.Precision.DEFAULT)
    dp = lax.dot_general(doh, vh, (((1,), (1,)), ((), ())),
                         preferred_element_type=jnp.float32,
                         precision=lax.Precision.DEFAULT)
    dvec = jnp.sum(dp * p, axis=1, keepdims=True)
    ds = (p * (dp - dvec) * sm_scale).astype(qh.dtype)
    dq = lax.dot_general(ds, kh, (((1,), (0,)), ((), ())),
                         preferred_element_type=jnp.float32,
                         precision=lax.Precision.DEFAULT)
    dk = lax.dot_general(ds, qh, (((0,), (0,)), ((), ())),
                         preferred_element_type=jnp.float32,
                         precision=lax.Precision.DEFAULT)
    return dq, dk, dv


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, dq_ref, dk_ref, dv_ref, *,
                causal, sm_scale, head_dim):
    """One (batch, pair), full T (see _half_bwd for the algebra)."""
    q = q_ref[0, 0]
    k = k_ref[0, 0]
    v = v_ref[0, 0]
    do = do_ref[0, 0]
    dqs, dks, dvs = [], [], []
    for h in (0, 1):
        sl = slice(h * head_dim, (h + 1) * head_dim)
        dq, dk, dv = _half_bwd(q[:, sl], k[:, sl], v[:, sl], do[:, sl],
                               sm_scale, causal, 0)
        dqs.append(dq)
        dks.append(dk)
        dvs.append(dv)
    dq_ref[0, 0] = jnp.concatenate(dqs, axis=-1).astype(dq_ref.dtype)
    dk_ref[0, 0] = jnp.concatenate(dks, axis=-1).astype(dk_ref.dtype)
    dv_ref[0, 0] = jnp.concatenate(dvs, axis=-1).astype(dv_ref.dtype)


def _choose_block_q(T: int, block_q: int = 512) -> int:
    """Forward q-block: bound the in-VMEM [block_q, T] f32 score/prob
    matrices to ~2 MB as T grows (T=1024 keeps the tuned 512;
    2048 -> 256), FLOORED to a power of two — the divisor-halving
    assumes it (a raw bound like 341 at T=1536 would halve to a
    degenerate block of 2). The result must DIVIDE T: floor-div grids
    silently skip the tail rows (supported() admits any T % 128 == 0,
    e.g. 640/768/896)."""
    bound = max(128, (1 << 21) // (4 * T))
    bound = 1 << (bound.bit_length() - 1)
    block_q = min(block_q, T, bound)
    while T % block_q:
        block_q //= 2
    return block_q


def _fwd_call(q, k, v, causal, sm_scale, with_lse=False):
    """Packed forward; with_lse also returns lse [B, Hp, 2, T] f32 for
    the FA2 backward."""
    B, Hp, T, d2 = q.shape
    block_q = _choose_block_q(T)
    spec_q = pl.BlockSpec((1, 1, block_q, d2), lambda b, h, i: (b, h, i, 0))
    spec_kv = pl.BlockSpec((1, 1, T, d2), lambda b, h, i: (b, h, 0, 0))
    kern = functools.partial(_fwd_kernel, causal=causal, sm_scale=sm_scale,
                             block_q=block_q, head_dim=d2 // 2)
    out_specs = spec_q
    out_shape = jax.ShapeDtypeStruct(q.shape, q.dtype)
    if with_lse:
        spec_lse = pl.BlockSpec((1, 1, 2, block_q),
                                lambda b, h, i: (b, h, 0, i))
        out_specs = [spec_q, spec_lse]
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct((B, Hp, 2, T), jnp.float32)]
    with jax.enable_x64(False):
        return pl.pallas_call(
            kern,
            grid=(B, Hp, T // block_q),
            in_specs=[spec_q, spec_kv, spec_kv],
            out_specs=out_specs,
            out_shape=out_shape,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            name="packed_flash_fwd",
        )(q, k, v)


def _bwd_call(q, k, v, do, causal, sm_scale):
    B, Hp, T, d2 = q.shape
    spec = pl.BlockSpec((1, 1, T, d2), lambda b, h: (b, h, 0, 0))
    kern = functools.partial(_bwd_kernel, causal=causal, sm_scale=sm_scale,
                             head_dim=d2 // 2)
    shp = jax.ShapeDtypeStruct(q.shape, q.dtype)
    with jax.enable_x64(False):
        return pl.pallas_call(
            kern,
            grid=(B, Hp),
            in_specs=[spec, spec, spec, spec],
            out_specs=[spec, spec, spec],
            out_shape=[shp, shp, shp],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel")),
            name="packed_flash_bwd",
        )(q, k, v, do)


def _half_bwd_lse(qh, kh, vh, doh, lse_h, delta_h, sm_scale, causal,
                  row0, col0):
    """Saved-lse flash backward algebra for ONE 64-wide half of one
    q-block x kv-block tile: p = exp(s - lse) is the TRUE softmax prob
    (no in-tile max/denominator), and delta = rowsum(do*o) replaces the
    in-kernel rowsum(dp*p) correction. Returns (p_cast, ds) — shared by
    _dq_kernel and _dkv_kernel so the algebra cannot drift."""
    s = lax.dot_general(qh, kh, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32,
                        precision=lax.Precision.DEFAULT) * sm_scale
    if causal:
        row = row0 + lax.broadcasted_iota(jnp.int32, s.shape, 0)
        col = col0 + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(row >= col, s, jnp.float32(-1e30))
    p = jnp.exp(s - lse_h[:, None])
    dp = lax.dot_general(doh, vh, (((1,), (1,)), ((), ())),
                         preferred_element_type=jnp.float32,
                         precision=lax.Precision.DEFAULT)
    ds = (p * (dp - delta_h[:, None]) * sm_scale).astype(qh.dtype)
    return p.astype(qh.dtype), ds


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *,
               causal, sm_scale, block_q, block_k, head_dim):
    """FA2 dq: one (batch, pair, q-block, kv-block); kv innermost
    sequential, dq accumulates in its f32 ref across kv blocks. Fully
    masked kv blocks are SKIPPED (the causal flop saving the full-kv
    kernels cannot have)."""
    qi = pl.program_id(2)
    kj = pl.program_id(3)

    @pl.when(kj == 0)
    def _init():
        dq_ref[...] = jnp.zeros_like(dq_ref)

    def compute():
        q = q_ref[0, 0]                               # [bq, 128]
        k = k_ref[0, 0]                               # [bk, 128]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0]                           # [2, bq]
        delta = delta_ref[0, 0]
        dqs = []
        for h in (0, 1):
            sl = slice(h * head_dim, (h + 1) * head_dim)
            _, ds = _half_bwd_lse(q[:, sl], k[:, sl], v[:, sl], do[:, sl],
                                  lse[h], delta[h], sm_scale, causal,
                                  qi * block_q, kj * block_k)
            dqs.append(lax.dot_general(
                ds, k[:, sl], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=lax.Precision.DEFAULT))
        dq_ref[0, 0] += jnp.concatenate(dqs, axis=-1)

    if causal:
        # block live iff some col <= some row: kj*bk <= qi*bq + bq - 1
        @pl.when(kj * block_k <= qi * block_q + block_q - 1)
        def _():
            compute()
    else:
        compute()


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
                dv_ref, *, causal, sm_scale, block_q, block_k, head_dim):
    """FA2 dk/dv: one (batch, pair, kv-block, q-block); q innermost
    sequential, dk/dv accumulate in their f32 refs across q blocks, with
    fully masked q blocks skipped."""
    kj = pl.program_id(2)
    qi = pl.program_id(3)

    @pl.when(qi == 0)
    def _init():
        dk_ref[...] = jnp.zeros_like(dk_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)

    def compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        for h in (0, 1):
            sl = slice(h * head_dim, (h + 1) * head_dim)
            pb, ds = _half_bwd_lse(q[:, sl], k[:, sl], v[:, sl],
                                   do[:, sl], lse[h], delta[h], sm_scale,
                                   causal, qi * block_q, kj * block_k)
            dv_ref[0, 0, :, sl] += lax.dot_general(
                pb, do[:, sl], (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=lax.Precision.DEFAULT)
            dk_ref[0, 0, :, sl] += lax.dot_general(
                ds, q[:, sl], (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=lax.Precision.DEFAULT)

    if causal:
        # block live iff some row >= some col: qi*bq + bq - 1 >= kj*bk
        @pl.when(qi * block_q + block_q - 1 >= kj * block_k)
        def _():
            compute()
    else:
        compute()


FA2_BLOCK = 1024


def _bwd_call_fa2(q, k, v, do, o, lse, causal, sm_scale):
    """FA2-style backward: saved-lse 2D-grid kernels with causal block
    skipping. delta = rowsum(do*o) per half is computed OUTSIDE pallas
    (XLA fuses it into one cheap pass over do/o)."""
    B, Hp, T, d2 = q.shape
    hd = d2 // 2
    # blocks must DIVIDE T (supported() admits any T % 128 == 0, e.g.
    # 1152/1280/2176): a floor-divided grid would silently never visit
    # the tail rows/cols — uninitialized dq tail, missing dk/dv blocks
    bq = bk = min(FA2_BLOCK, T)
    while T % bq:
        bq //= 2
    bk = bq
    dof = do.astype(jnp.float32)
    of = o.astype(jnp.float32)
    delta = jnp.stack(
        [jnp.sum(dof[..., :hd] * of[..., :hd], axis=-1),
         jnp.sum(dof[..., hd:] * of[..., hd:], axis=-1)],
        axis=2)                                       # [B, Hp, 2, T]
    spec_q = pl.BlockSpec((1, 1, bq, d2), lambda b, h, i, j: (b, h, i, 0))
    spec_kv = pl.BlockSpec((1, 1, bk, d2), lambda b, h, i, j: (b, h, j, 0))
    spec_row = pl.BlockSpec((1, 1, 2, bq), lambda b, h, i, j: (b, h, 0, i))
    kw = dict(causal=causal, sm_scale=sm_scale, block_q=bq, block_k=bk,
              head_dim=hd)
    f32 = jnp.float32
    with jax.enable_x64(False):
        dq = pl.pallas_call(
            functools.partial(_dq_kernel, **kw),
            grid=(B, Hp, T // bq, T // bk),
            in_specs=[spec_q, spec_kv, spec_kv, spec_q, spec_row,
                      spec_row],
            out_specs=spec_q,
            out_shape=jax.ShapeDtypeStruct(q.shape, f32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "parallel",
                                     "arbitrary")),
            name="packed_flash_bwd_dq",
        )(q, k, v, do, lse, delta)
        # dkv: swap grid roles — kv blocks parallel, q blocks innermost
        spec_q2 = pl.BlockSpec((1, 1, bq, d2),
                               lambda b, h, j, i: (b, h, i, 0))
        spec_kv2 = pl.BlockSpec((1, 1, bk, d2),
                                lambda b, h, j, i: (b, h, j, 0))
        spec_row2 = pl.BlockSpec((1, 1, 2, bq),
                                 lambda b, h, j, i: (b, h, 0, i))
        dk, dv = pl.pallas_call(
            functools.partial(_dkv_kernel, **kw),
            grid=(B, Hp, T // bk, T // bq),
            in_specs=[spec_q2, spec_kv2, spec_kv2, spec_q2, spec_row2,
                      spec_row2],
            out_specs=[spec_kv2, spec_kv2],
            out_shape=[jax.ShapeDtypeStruct(q.shape, f32),
                       jax.ShapeDtypeStruct(q.shape, f32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "parallel",
                                     "arbitrary")),
            name="packed_flash_bwd_dkv",
        )(q, k, v, do, lse, delta)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def packed_flash_attention(q, k, v, causal, scale):
    """q/k/v: [B, H/2, T, 128] — head 2i in lanes 0:64, head 2i+1 in
    64:128 (the natural [B,T,H,64] -> [B,T,H/2,128] reshape order).
    `scale` is the TRUE per-head scale (1/sqrt(64)). Returns the packed
    output, same shape."""
    return _fwd_call(q, k, v, causal, scale)


def _pf_fwd(q, k, v, causal, scale):
    if q.shape[2] <= BWD_SINGLE_MAX:
        return _fwd_call(q, k, v, causal, scale), (q, k, v, None, None)
    out, lse = _fwd_call(q, k, v, causal, scale, with_lse=True)
    return out, (q, k, v, out, lse)


def _pf_bwd(causal, scale, res, do):
    q, k, v, o, lse = res
    if q.shape[2] <= BWD_SINGLE_MAX:
        return _bwd_call(q, k, v, do, causal, scale)
    return _bwd_call_fa2(q, k, v, do, o, lse, causal, scale)


packed_flash_attention.defvjp(_pf_fwd, _pf_bwd)
