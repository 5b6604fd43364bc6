"""Normalization functionals.

TPU-native analogue of /root/reference/paddle/fluid/operators/batch_norm_op.cc
(+ .cu cudnnBatchNorm), layer_norm_op.cc (hand-tuned CUDA welford kernels),
instance_norm_op.cc, group_norm_op.cc, norm_op.cc;
python/paddle/nn/functional/norm.py. Pure-JAX reductions — XLA fuses the
normalize+scale+shift into neighbours, replacing the reference's
fuse_bn_act/fused_bn_add_act passes.

Running-stat updates are returned functionally AND applied in-place on the
passed stat tensors when executing eagerly (paddle mutates them in place).
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

from ...core.dispatch import op
from ...core.tensor import Tensor, to_tensor
from ...core import flags as _flags

_flags.define_flag(
    "fuse_bn_act", True,
    "Use the fused bn+(add+)relu op (residual-light backward) in models "
    "that call batch_norm_act — the fuse_bn_act_pass.cc analogue.")


def _wrap(x):
    return x if isinstance(x, Tensor) else to_tensor(x)


def _channel_axis(x, data_format):
    """Channel axis under a paddle data_format string; 2-D inputs are
    always [N, C] regardless of the format tag."""
    if x.ndim == 2:
        return 1
    return x.ndim - 1 if data_format in ("NHWC", "NLC", "NDHWC") else 1


def _apply_scale_shift(x, mean, var, weight, bias, eps, c_axis):
    """Fold (mean, var, weight, bias) into per-channel scale/shift computed
    in fp32, then apply in x's own dtype. For bf16 activations this keeps
    the full-tensor elementwise in bf16 (HBM-bandwidth bound) while the
    tiny per-channel math stays fp32 — the cuDNN BN recipe
    (batch_norm_op.cu keeps saved stats fp32 for __half inputs). f64
    inputs (FD-grad harness) keep f64 stats — f32 rounding of the
    per-channel scale quantizes the stats-derivative path."""
    f32 = jnp.float64 if x.dtype == jnp.float64 else jnp.float32
    inv = jax.lax.rsqrt(var.astype(f32) + eps)
    scale = inv if weight is None else inv * weight.astype(f32)
    shift = -mean.astype(f32) * scale
    if bias is not None:
        shift = shift + bias.astype(f32)
    shape = [1] * x.ndim
    shape[c_axis] = x.shape[c_axis]
    return (x * scale.astype(x.dtype).reshape(shape)
            + shift.astype(x.dtype).reshape(shape))


@op("batch_norm_infer")
def _bn_infer(x, mean, var, weight, bias, eps, c_axis):
    return _apply_scale_shift(x, mean, var, weight, bias, eps, c_axis)


def _bn_stats(x, axes):
    if x.dtype in (jnp.bfloat16, jnp.float16):
        # single-pass E[x^2]-E[x]^2: elementwise stays in bf16, only the
        # reduction ACCUMULATES in fp32 (dtype=). Materializing an fp32
        # upcast of x instead (x.astype(f32) shared by both reductions)
        # makes XLA write a full fp32 copy of every activation — measured
        # +13 GB/step HBM traffic on ResNet-50 bs=128.
        mean = jnp.mean(x, axis=axes, dtype=jnp.float32)
        mean_sq = jnp.mean(jnp.square(x), axis=axes, dtype=jnp.float32)
        var = jnp.maximum(mean_sq - mean * mean, 0.0)
    else:
        mean = jnp.mean(x, axis=axes)
        var = jnp.var(x, axis=axes)
    return mean, var


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _bn_core(x, weight, bias, eps, c_axis):
    axes = tuple(i for i in range(x.ndim) if i != c_axis)
    mean, var = _bn_stats(x, axes)
    out = _apply_scale_shift(x, mean, var, weight, bias, eps, c_axis)
    return out, mean, var


def _bn_core_fwd(x, weight, bias, eps, c_axis):
    out, mean, var = _bn_core(x, weight, bias, eps, c_axis)
    return (out, mean, var), (x, weight, bias, mean, var)


def _bn_core_bwd(eps, c_axis, res, cts):
    """Fused BN backward (the cuDNN/batch_norm_grad recipe, reference
    batch_norm_op.cu BNBackward): per-channel reductions in fp32, the big
    elementwise pass kept affine in x so bf16 activations stream at bf16
    bandwidth:  dx = a*gy + k*x + m  with per-channel a, k, m. The autodiff
    of the stats formula instead materializes several fp32 copies of the
    activation — measured 16 ms/step on ResNet-50 bs=128 (v5e) vs ~4 ms for
    this form."""
    gy, g_mean, g_var = cts
    x, weight, bias, mean, var = res
    f32 = jnp.float64 if x.dtype == jnp.float64 else jnp.float32
    axes = tuple(i for i in range(x.ndim) if i != c_axis)
    n = 1
    for i in axes:
        n *= x.shape[i]
    shape = [1] * x.ndim
    shape[c_axis] = x.shape[c_axis]

    inv = jax.lax.rsqrt(var.astype(f32) + eps)            # [C] fp32
    # products in the activation dtype, fp32 ACCUMULATORS only — an
    # astype(f32) on gy/x here materializes fp32 activation copies (see
    # _bn_stats)
    gy32sum = jnp.sum(gy, axis=axes, dtype=f32)           # dbeta
    gxsum = jnp.sum(gy * x, axis=axes, dtype=f32)
    # dgamma = sum(gy * xhat) = (sum(gy*x) - mean*sum(gy)) * inv
    dgamma = (gxsum - mean.astype(f32) * gy32sum) * inv
    dbeta = gy32sum

    gamma = jnp.ones_like(inv) if weight is None else weight.astype(f32)
    a = gamma * inv
    # dx from out-cotangent: a*gy - a*dbeta/N - xhat * a*dgamma/N, folded
    # affine in x:  dx = a*gy + k*x + m
    k = -a * dgamma * inv / n
    m = -a * dbeta / n - k * mean.astype(f32)
    # cotangents flowing into the mean/var outputs (running-stat EMAs are
    # buffers, so these are normally zero, but stay correct if used)
    if g_var is not None:
        k = k + 2.0 * g_var.astype(f32) / n
        m = m - 2.0 * g_var.astype(f32) * mean.astype(f32) / n
    if g_mean is not None:
        m = m + g_mean.astype(f32) / n
    dx = (gy * a.astype(gy.dtype).reshape(shape)
          + x * k.astype(x.dtype).reshape(shape)
          + m.astype(x.dtype).reshape(shape)).astype(x.dtype)
    dw = None if weight is None else dgamma.astype(weight.dtype)
    db = None if bias is None else dbeta.astype(bias.dtype)
    return dx, dw, db


_bn_core.defvjp(_bn_core_fwd, _bn_core_bwd)


@op("batch_norm_train")
def _bn_train(x, weight, bias, eps, c_axis):
    return _bn_core(x, weight, bias, eps, c_axis)


# ---- fused BN + (add +) ReLU with residual-light backward -------------
#
# The reference fuses conv→bn→relu chains at the graph level
# (framework/ir/fuse_bn_act_pass.cc, fused_bn_add_activation_op.cc). On
# TPU, XLA already fuses the *elementwise* chain; what it does NOT do is
# dedup the autodiff residuals: composed bn→relu saves BOTH the conv
# output (BN's custom-vjp residual) and the BN output (relu's vjp mask
# input), materialising an extra full activation tensor per BN site in
# fwd and reading it back in bwd. ResNet-50 was profiled HBM-bound by an
# earlier builder, so those bytes would be the step time.
#
# This fused op saves ONLY the conv output: the relu mask is recomputed
# in bwd as the affine test  x*scale + shift (+z) > 0  (per-channel fp32
# scale/shift folded, one bf16-bandwidth pass that XLA fuses into the
# dx epilogue). Forward never materialises the pre-relu BN output at all.
#
# An earlier round read it throughput NEUTRAL vs the composed path on a
# v5e (ResNet-50 bs128 O2; no cell of the benchmark measures it) — XLA's
# scheduler already avoids double-storing the elementwise chain. The op is
# kept for (a) reference op parity and (b) the smaller residual set
# (peak-memory headroom at larger batches).


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _bn_act_core(x, z, weight, bias, eps, c_axis):
    """relu(bn(x) + z); z=None → plain bn+relu. Returns (out, mean, var)."""
    axes = tuple(i for i in range(x.ndim) if i != c_axis)
    mean, var = _bn_stats(x, axes)
    out = _apply_scale_shift(x, mean, var, weight, bias, eps, c_axis)
    if z is not None:
        out = out + z
    return jnp.maximum(out, jnp.zeros((), out.dtype)), mean, var


def _bn_act_fwd(x, z, weight, bias, eps, c_axis):
    out, mean, var = _bn_act_core(x, z, weight, bias, eps, c_axis)
    return (out, mean, var), (x, z, weight, bias, mean, var)


def _bn_act_bwd(eps, c_axis, res, cts):
    gy, g_mean, g_var = cts
    x, z, weight, bias, mean, var = res
    # relu_grad semantics: out > 0 (reference activation_op.h ReluGradFunctor
    # masks on out). pre-relu value recomputed affine from the saved conv
    # output — never stored; same fold as forward, so the mask is
    # bitwise-consistent.
    pre = _apply_scale_shift(x, mean, var, weight, bias, eps, c_axis)
    if z is not None:
        pre = pre + z
    gym = jnp.where(pre > 0, gy, jnp.zeros((), gy.dtype))
    if z is None:
        dz = None
    else:
        # z may be broadcastable (e.g. [1, C, 1, 1]): reduce the cotangent
        # back to z's shape like lax's broadcast transpose does
        lead = gym.ndim - z.ndim
        bcast = tuple(range(lead)) + tuple(
            lead + i for i, d in enumerate(z.shape)
            if d == 1 and gym.shape[lead + i] != 1)
        dz = jnp.sum(gym, axis=bcast, keepdims=False).reshape(z.shape) \
            if bcast else gym
    dx, dw, db = _bn_core_bwd(eps, c_axis, (x, weight, bias, mean, var),
                              (gym, g_mean, g_var))
    return dx, dz, dw, db


_bn_act_core.defvjp(_bn_act_fwd, _bn_act_bwd)


@op("fused_bn_add_act_train")
def _bn_act_train(x, z, weight, bias, eps, c_axis):
    return _bn_act_core(x, z, weight, bias, eps, c_axis)


def batch_norm(x, running_mean, running_var, weight=None, bias=None,
               training=False, momentum=0.9, epsilon=1e-5,
               data_format="NCHW", use_global_stats=None, name=None):
    """reference: operators/batch_norm_op.cc (momentum semantics:
    running = momentum*running + (1-momentum)*batch, batch_norm_op.cc
    attr 'momentum' default 0.9)."""
    x = _wrap(x)
    c_axis = _channel_axis(x, data_format)
    use_stats = (not training) if use_global_stats is None else use_global_stats
    if use_stats:
        return _bn_infer(x, _wrap(running_mean), _wrap(running_var),
                         None if weight is None else _wrap(weight),
                         None if bias is None else _wrap(bias),
                         epsilon, c_axis)
    out, mean, var = _bn_train(x, None if weight is None else _wrap(weight),
                               None if bias is None else _wrap(bias),
                               epsilon, c_axis)
    _update_running_stats(running_mean, running_var, mean, var, momentum)
    return out


def _update_running_stats(running_mean, running_var, mean, var, momentum):
    """update running stats in place. Under a jit trace the assigned values
    are tracers; paddle_tpu.jit reads the buffers back after tracing and
    returns them as extra outputs, making the update functional.

    Reference uses the *biased* batch variance for the running-stat EMA
    (batch_norm_op.cc:398 saved_variance /= N*sample_size, no Bessel
    correction) — feed `var` straight in."""
    if running_mean is None:
        return
    from ...static.program import Variable as _SVar
    if isinstance(running_mean, _SVar):
        # static graph: stat update is an op writing the persistable
        from ...static.nn import static_assign
        new_rm = running_mean * momentum + mean * (1.0 - momentum)
        new_rv = running_var * momentum + var * (1.0 - momentum)
        static_assign(running_mean, new_rm)
        static_assign(running_var, new_rv)
    else:
        running_mean._value = (momentum * running_mean._value
                               + (1 - momentum) * mean._value)
        running_var._value = (momentum * running_var._value
                              + (1 - momentum) * var._value)


def batch_norm_act(x, running_mean, running_var, weight=None, bias=None,
                   training=False, momentum=0.9, epsilon=1e-5,
                   data_format="NCHW", add=None, use_global_stats=None,
                   name=None):
    """relu(batch_norm(x) [+ add]) with a residual-light fused backward:
    only the BN *input* is kept for autodiff (the relu mask is recomputed
    affine from it), vs the composed path's input + pre-relu output.

    TPU-native analogue of the reference's fuse_bn_act_pass.cc /
    fused_bn_add_activation_op.cc (act='relu'); the byte savings matter
    because ResNet-class conv nets are HBM-bound on v5e.

    use_global_stats follows batch_norm's semantics exactly (None → infer
    from `training`; explicit False → batch stats + EMA update even in
    eval), so the fused and composed paths never diverge."""
    x = _wrap(x)
    c_axis = _channel_axis(x, data_format)
    z = None if add is None else _wrap(add)
    use_stats = (not training) if use_global_stats is None \
        else use_global_stats
    if use_stats:
        out = _bn_infer(x, _wrap(running_mean), _wrap(running_var),
                        None if weight is None else _wrap(weight),
                        None if bias is None else _wrap(bias),
                        epsilon, c_axis)
        if z is not None:
            out = out + z
        from ..functional import relu as _relu
        return _relu(out)
    out, mean, var = _bn_act_train(
        x, z, None if weight is None else _wrap(weight),
        None if bias is None else _wrap(bias), epsilon, c_axis)
    _update_running_stats(running_mean, running_var, mean, var, momentum)
    return out


@op("layer_norm")
def _layer_norm(x, weight, bias, eps, begin_axis):
    axes = tuple(range(begin_axis, x.ndim))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.var(x, axis=axes, keepdims=True)
    out = (x - mean) * jax.lax.rsqrt(var + eps)
    if weight is not None:
        out = out * weight
    if bias is not None:
        out = out + bias
    return out


def layer_norm(x, normalized_shape, weight=None, bias=None, epsilon=1e-5,
               name=None):
    """reference: operators/layer_norm_op.cc (begin_norm_axis semantics)."""
    x = _wrap(x)
    if isinstance(normalized_shape, int):
        normalized_shape = [normalized_shape]
    begin = x.ndim - len(list(normalized_shape))
    return _layer_norm(x, None if weight is None else _wrap(weight),
                       None if bias is None else _wrap(bias), epsilon, begin)


@op("instance_norm")
def _instance_norm(x, weight, bias, eps):
    axes = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.var(x, axis=axes, keepdims=True)
    out = (x - mean) * jax.lax.rsqrt(var + eps)
    if weight is not None:
        shape = [1, -1] + [1] * (x.ndim - 2)
        out = out * weight.reshape(shape)
    if bias is not None:
        shape = [1, -1] + [1] * (x.ndim - 2)
        out = out + bias.reshape(shape)
    return out


def instance_norm(x, running_mean=None, running_var=None, weight=None,
                  bias=None, use_input_stats=True, momentum=0.9, eps=1e-5,
                  data_format="NCHW", name=None):
    return _instance_norm(_wrap(x),
                          None if weight is None else _wrap(weight),
                          None if bias is None else _wrap(bias), eps)


@op("group_norm")
def _group_norm(x, weight, bias, groups, eps, channel_last):
    if channel_last:
        x_cf = jnp.moveaxis(x, -1, 1)
    else:
        x_cf = x
    n, c = x_cf.shape[0], x_cf.shape[1]
    g = x_cf.reshape((n, groups, c // groups) + x_cf.shape[2:])
    axes = tuple(range(2, g.ndim))
    mean = jnp.mean(g, axis=axes, keepdims=True)
    var = jnp.var(g, axis=axes, keepdims=True)
    out = ((g - mean) * jax.lax.rsqrt(var + eps)).reshape(x_cf.shape)
    shape = [1, c] + [1] * (x_cf.ndim - 2)
    if weight is not None:
        out = out * weight.reshape(shape)
    if bias is not None:
        out = out + bias.reshape(shape)
    if channel_last:
        out = jnp.moveaxis(out, 1, -1)
    return out


def group_norm(x, num_groups, epsilon=1e-5, weight=None, bias=None,
               data_format="NCHW", name=None):
    channel_last = data_format in ("NHWC", "NLC", "NDHWC")
    return _group_norm(_wrap(x), None if weight is None else _wrap(weight),
                       None if bias is None else _wrap(bias), num_groups,
                       epsilon, channel_last)


@op("local_response_norm")
def _lrn(x, size, alpha, beta, k):
    sq = jnp.square(x)
    half = size // 2
    c = x.shape[1]
    pads = [(0, 0), (half, size - 1 - half)] + [(0, 0)] * (x.ndim - 2)
    padded = jnp.pad(sq, pads)
    window = (1, size) + (1,) * (x.ndim - 2)
    summed = jax.lax.reduce_window(padded, 0.0, jax.lax.add, window,
                                   (1,) * x.ndim, [(0, 0)] * x.ndim)
    return x / jnp.power(k + alpha * summed, beta)


def local_response_norm(x, size, alpha=1e-4, beta=0.75, k=1.0,
                        data_format="NCHW", name=None):
    return _lrn(_wrap(x), size, alpha, beta, k)


@op("sync_batch_norm")
def _sync_bn_train(x, weight, bias, eps, c_axis, axes_names):
    """reference: operators/sync_batch_norm_op.cu — batch stats allreduced
    across the data-parallel group. Inside a shard_map/SPMD trace the
    lax.pmean over the bound mesh axes computes GLOBAL batch statistics
    over ICI; outside any mesh scope it degenerates to local batch_norm
    (single-rank semantics, same as the reference with nranks==1)."""
    axes = tuple(i for i in range(x.ndim) if i != c_axis)
    mean = jnp.mean(x, axis=axes)
    mean_sq = jnp.mean(x * x, axis=axes)
    for ax in axes_names:
        try:
            mean = jax.lax.pmean(mean, ax)
            mean_sq = jax.lax.pmean(mean_sq, ax)
        except NameError:
            pass  # axis not bound: local stats
    var = mean_sq - mean * mean
    shape = [1] * x.ndim
    shape[c_axis] = x.shape[c_axis]
    inv = jax.lax.rsqrt(var.reshape(shape) + eps)
    out = (x - mean.reshape(shape)) * inv
    if weight is not None:
        out = out * weight.reshape(shape)
    if bias is not None:
        out = out + bias.reshape(shape)
    return out, mean, var


def sync_batch_norm(x, running_mean, running_var, weight=None, bias=None,
                    training=True, momentum=0.9, epsilon=1e-5,
                    data_format="NCHW", sync_axes=("dp",), name=None):
    """Cross-replica batch norm (reference: sync_batch_norm_op.cu +
    nn.SyncBatchNorm). sync_axes: mesh axes to average stats over."""
    xt = _wrap(x)
    c_axis = _channel_axis(xt, data_format)
    if not training:
        return batch_norm(x, running_mean, running_var, weight, bias,
                          training=False, momentum=momentum,
                          epsilon=epsilon, data_format=data_format)
    out, mean, var = _sync_bn_train(
        xt, None if weight is None else _wrap(weight),
        None if bias is None else _wrap(bias), epsilon, c_axis,
        tuple(sync_axes))
    if running_mean is not None:
        from ...static.program import Variable as _SVar
        if isinstance(running_mean, _SVar):
            from ...static.nn import static_assign
            static_assign(running_mean,
                          running_mean * momentum + mean * (1.0 - momentum))
            static_assign(running_var,
                          running_var * momentum + var * (1.0 - momentum))
        else:
            running_mean._value = (momentum * running_mean._value
                                   + (1 - momentum) * mean._value)
            running_var._value = (momentum * running_var._value
                                  + (1 - momentum) * var._value)
    return out
