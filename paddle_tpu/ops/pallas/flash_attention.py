"""Flash attention for TPU.

Memory-efficient attention with O(T) HBM traffic: never materialises the
[T, S] score matrix in HBM. Wraps jax's pallas TPU flash kernel (a Mosaic
kernel tiled for the MXU/VMEM hierarchy) behind this framework's op dispatch
so it participates in the eager autograd tape and in jitted train steps.

Reference parity note: the reference snapshot has no flash attention (its
transformer uses composed matmul+softmax, python/paddle/nn/layer/transformer.py
:372-436); this is a beyond-reference TPU-native addition, flagged in
SURVEY.md §2.3 as the long-context enabler.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ...core.dispatch import op
from ...core.tensor import Tensor


@functools.lru_cache(maxsize=1)
def _kernel():
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        flash_attention as fa, BlockSizes)
    if not _patch_lmdi_width1():
        import warnings
        warnings.warn(
            "flash attention: the width-1 l/m/di rewrite no longer matches "
            "the installed jax.experimental.pallas.ops.tpu.flash_attention "
            "source; the backward runs UNPATCHED and materialises three "
            "[B, H, T, 128] f32 broadcasts per layer", RuntimeWarning,
            stacklevel=2)
    return fa, BlockSizes


def applied_patch() -> str:
    """Which source rewrite of the upstream kernel is live in this
    process: "lmdi_width1" or "none" (chip_smoke.py prints it)."""
    return "lmdi_width1" if _patch_lmdi_width1() else "none"


@functools.lru_cache(maxsize=1)
def _patch_lmdi_width1():
    """Stop materialising the softmax residuals broadcast: upstream's bwd
    wrappers expand l, m and di ([B, H, T] f32) to [B, H, T, 128] before
    pallas_call — 3 × 100 MB HBM round-trips per layer at the flagship
    geometry, profiled at 7.6 ms/step (3.7% of the step) with the tensors
    CSE-shared between the dq and dkv passes. The kernel bodies only ever
    use the values replicated across lanes (`jnp.tile(x, (1, block_k //
    MIN_BLOCK_SIZE))` right before use), so pass them as width-1 blocks
    ([..., 1] is a reshape, not a copy) and lane-splat in VMEM instead
    (`jnp.broadcast_to(x, capped_logits.shape)` — a register splat, no
    HBM traffic). Result-identical; verified against composed attention
    on TPU. Applied by guarded source rewrite; any drift in the upstream
    text → return False, and `_kernel` warns that the backward runs
    unpatched."""
    import inspect
    import re
    import jax.experimental.pallas.ops.tpu.flash_attention as m

    fns = ["_flash_attention_bwd_dkv", "_flash_attention_bwd_dq",
           "_flash_attention_dkv_kernel", "_flash_attention_dq_kernel"]
    srcs = {}
    try:
        for fn in fns:
            srcs[fn] = inspect.getsource(getattr(m, fn))
    except (OSError, AttributeError):
        return False

    bcast = re.compile(
        r"jnp\.broadcast_to\((l|m|di)\[\.\.\., None\], "
        r"\(\*\1\.shape, (?:MIN_BLOCK_SIZE|block_k_major)\)\)")
    spec = re.compile(r"pl\.BlockSpec\(\n?\s*\(1, 1, block_q_major, "
                      r"MIN_BLOCK_SIZE\),")
    tile = re.compile(r"jnp\.tile\(\n?\s*(m|1 / l|di),"
                      r" \(1, block_k // MIN_BLOCK_SIZE\)\n?\s*\)")
    patched = {}
    for fn in fns[:2]:   # wrappers
        src, n1 = bcast.subn(
            lambda g: f"jnp.broadcast_to({g.group(1)}[..., None], "
                      f"(*{g.group(1)}.shape, 1))", srcs[fn])
        src, n2 = spec.subn("pl.BlockSpec((1, 1, block_q_major, 1),", src)
        if n1 != 3 or n2 != 2:   # l/m/di bcasts; lm_spec + di_spec
            return False
        patched[fn] = src
    for fn in fns[2:]:   # kernel bodies
        src, n = tile.subn(
            lambda g: f"jnp.broadcast_to({g.group(1)}, "
                      "capped_logits.shape)", srcs[fn])
        if n != 3:       # m, 1/l, di
            return False
        # the q_segment_ids jnp.tile(..., (1, repeats)) uses a different
        # pattern and must remain untouched
        if "jnp.tile(m," in src or "jnp.tile(di," in src:
            return False
        patched[fn] = src
    for fn, src in patched.items():
        exec(src, m.__dict__)  # noqa: S102 - vendored jax fix
    return True


def _mesh_ways():
    """(mesh, batch axes, batch ways, head ways) of the global mesh when it
    spans several devices, else None. Batch shards over (dp, sharding) and
    heads over tp — the layouts ShardedTrainStep and the TP layers give
    q/k/v — and every other axis has to be trivial."""
    from ...parallel.mesh import get_global_mesh
    mesh = get_global_mesh()
    if mesh is None or mesh.size == 1:
        return None
    batch_axes = tuple(a for a in ("dp", "sharding") if a in mesh.shape)
    b_ways = int(np.prod([mesh.shape[a] for a in batch_axes], dtype=int))
    return mesh, batch_axes, b_ways, mesh.shape.get("tp", 1)


def supported(q_shape, heads_major: bool = False) -> bool:
    """Kernel gate. pallas TPU kernel: seq must tile into the (≥128) q/k
    blocks; head_dim needs lane alignment only (verified on v5e: d=64 and
    d=96 both run and match composed attention to bf16 tolerance). Under
    a multi-device mesh the kernel runs per shard (`_fa_sharded`), so
    batch and heads must divide over it. Other backends are routed to
    composed attention; a backend that fails to initialise raises out of
    `jax.default_backend()`."""
    if jax.default_backend() != "tpu":
        return False
    b, d = q_shape[0], q_shape[3]
    h, t = (q_shape[1], q_shape[2]) if heads_major else \
        (q_shape[2], q_shape[1])
    ways = _mesh_ways()
    if ways is not None:
        mesh, _, b_ways, h_ways = ways
        if b_ways * h_ways != mesh.size or b % b_ways or h % h_ways:
            return False
    return t % 128 == 0 and d % 8 == 0 and d >= 32


def _largest_block(t):
    # largest power-of-two block ≤1024 that divides the sequence (the
    # kernel requires seq % block == 0; `supported` guarantees t % 128 == 0).
    # 1024-wide measured +2.4% over 512 at T=1024/hd=128 on v5e (r2); a
    # 1024×128 bf16 q tile is 256KiB — comfortably inside VMEM.
    for b in (1024, 512, 256, 128):
        if t % b == 0:
            return b
    return 128


def _block_sizes(t, s, d=128):
    """Tuned for v5e: 512-wide q/k blocks keep the MXU fed at head_dim
    64-128 (measured 3× over the kernel defaults at T=2048, bench r2);
    shorter/odd sequences (768, 1152, ...) drop to the largest dividing
    power-of-two block. head_dim < 128 (lane-padded tiles): narrow the
    dq k-major block to 512 — measured ~10% off the d=64 fwd+bwd (r4);
    wider dq majors only grow the di/l/m staging with no MXU upside at
    half-depth contractions."""
    _, BlockSizes = _kernel()
    bq = _largest_block(t)
    bk = _largest_block(s)
    if d < 128:
        bkm_dq = min(bk, 512)
        return BlockSizes(
            block_q=bq, block_k_major=bk, block_k=bk, block_b=1,
            block_q_major_dkv=bq, block_k_major_dkv=bk, block_k_dkv=bk,
            block_q_dkv=bq, block_k_major_dq=bkm_dq, block_k_dq=bkm_dq,
            block_q_dq=bq)
    return BlockSizes(
        block_q=bq, block_k_major=bk, block_k=bk, block_b=1,
        block_q_major_dkv=bq, block_k_major_dkv=bk, block_k_dkv=bk,
        block_q_dkv=bq, block_k_major_dq=bk, block_k_dq=bk, block_q_dq=bq)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _fa_core(qh, kh, vh, causal, scale):
    # primal (no-grad forward): skip the l/m softmax residuals entirely —
    # the custom_vjp fwd below only runs under differentiation
    import jax.experimental.pallas.ops.tpu.flash_attention as m
    with jax.enable_x64(False):
        return m._flash_attention(
            qh, kh, vh, None, None, False, causal, scale,
            _block_sizes(qh.shape[2], kh.shape[2], qh.shape[3]), False)


def _fa_fwd(qh, kh, vh, causal, scale):
    """Both kernel traces run with x64 scoped OFF: the pallas index maps
    build int32 grid arithmetic, and this package's global jax_enable_x64
    (paddle's int64 default) would promote python ints to int64 inside
    lax.select. The bwd trace happens later (under jax.grad), so the scope
    lives in each rule rather than around the caller."""
    import jax.experimental.pallas.ops.tpu.flash_attention as m
    with jax.enable_x64(False):
        out, res = m._flash_attention_fwd(
            qh, kh, vh, None, None, save_residuals=False, causal=causal,
            sm_scale=scale,
            block_sizes=_block_sizes(qh.shape[2], kh.shape[2],
                                     qh.shape[3]),
            debug=False)
    return out, res


def _fa_bwd(causal, scale, res, do):
    import jax.experimental.pallas.ops.tpu.flash_attention as m
    q = res[0]
    with jax.enable_x64(False):
        grads = m._flash_attention_bwd(
            save_residuals=False, causal=causal, sm_scale=scale,
            block_sizes=_block_sizes(q.shape[2], res[1].shape[2],
                                     q.shape[3]),
            debug=False, residuals=res, do=do)
    dq, dk, dv = grads[:3]
    return dq, dk, dv


_fa_core.defvjp(_fa_fwd, _fa_bwd)


def _fa_sharded(qh, kh, vh, causal, scale):
    """`_fa_core` under the global mesh. GSPMD cannot partition a Mosaic
    kernel ("wrap the call in a shard_map"), and attention is independent
    across batch and heads, so on several devices each runs the kernel on
    its own [B/ways, H/tp, T, D] shard inside a full-manual shard_map.
    check_vma is off because upstream's pallas_call out_shapes carry no
    vma, which the check demands."""
    ways = _mesh_ways()
    if ways is None:
        return _fa_core(qh, kh, vh, causal, scale)
    mesh, batch_axes, _, _ = ways
    # ptlint: disable=PT-S001  not a layout decision: it restates the
    # layout q/k/v already have (batch over dp x sharding, heads over tp)
    # so the kernel can run per shard; `supported` declines anything else
    spec = P(batch_axes, "tp" if "tp" in mesh.shape else None, None, None)
    return jax.shard_map(
        lambda q, k, v: _fa_core(q, k, v, causal, scale), mesh=mesh,
        in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)(qh, kh, vh)


@op("flash_attention")
def _flash(q, k, v, causal, scale):
    # paddle layout [B, T, H, D] -> kernel layout [B, H, T, D]
    qh = jnp.swapaxes(q, 1, 2)
    kh = jnp.swapaxes(k, 1, 2)
    vh = jnp.swapaxes(v, 1, 2)
    out = _fa_sharded(qh, kh, vh, causal, scale)
    return jnp.swapaxes(out, 1, 2)


@op("flash_attention_hm")
def _flash_hm(q, k, v, causal, scale):
    # already in kernel layout [B, H, T, D]; output stays heads-major
    return _fa_sharded(q, k, v, causal, scale)


@op("packed_flash_attention")
def _packed_flash(q, k, v, causal, scale):
    # [B, H/2, T, 128] packed head pairs (ops/pallas/packed_flash.py);
    # scale is the TRUE per-head scale (1/sqrt(head_dim), not 1/sqrt(128))
    from .packed_flash import packed_flash_attention as pf
    return pf(q, k, v, causal, scale)


def flash_attention(q, k, v, causal=False, scale=None, heads_major=False):
    """q/k/v: [batch, seq, heads, head_dim] Tensors (paddle layout), or
    [batch, heads, seq, head_dim] when heads_major=True (kernel-native —
    skips the swapaxes copies the custom-call boundary would force)."""
    if scale is None:
        scale = 1.0 / float(np.sqrt(q.shape[-1]))
    if not supported(q.shape, heads_major):
        raise NotImplementedError(
            f"flash_attention: unsupported shape {q.shape} or non-TPU "
            "backend; caller should route to composed attention")
    if heads_major:
        return _flash_hm(q, k, v, causal, scale)
    return _flash(q, k, v, causal, scale)
