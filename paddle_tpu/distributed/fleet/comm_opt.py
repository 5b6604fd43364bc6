"""Communication-optimizing strategies: LocalSGD + fp16 allreduce.

Reference:
- fleet/meta_optimizers/localsgd_optimizer.py (LocalSGD + AdaptiveLocalSGD):
  each rank takes k local optimizer steps with NO gradient synchronization,
  then parameters are averaged across ranks; adaptive variant scales k with
  the loss ratio (Lin et al., "Don't Use Large Mini-Batches, Use Local SGD").
- fleet/meta_optimizers/fp16_allreduce_optimizer.py: gradients are cast to
  fp16 before the cross-rank allreduce and back after, halving comm bytes.

TPU-native redesign: instead of program rewriting + NCCL ops, both are
expressed as ONE jitted `shard_map` step over the data-parallel mesh axis:

- Parameters (and optimizer moments) carry a leading per-rank axis sharded
  over 'dp' — rank-local copies, exactly the multi-process state of the
  reference, but laid out on the mesh.
- A local step computes grads from the rank's batch shard and applies the
  optimizer with NO collective (LocalSGD) or with a reduced-precision
  `lax.pmean` (fp16 allreduce).
- Every k-th step `lax.pmean` over 'dp' re-synchronizes parameters (the
  reference's c_allreduce(param)/nranks), riding ICI instead of NCCL rings.
"""
from __future__ import annotations

from typing import Callable, Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ...parallel import shard_map

from ...core.tensor import Tensor
from ...core import random as _random
from ...nn.layer.layers import Layer


def _dp_mesh(mesh: Optional[Mesh]) -> Mesh:
    """A dp-only mesh (full-manual shard_map; partial-manual over a multi-
    axis mesh is rejected by the pinned JAX — see tests/test_distributed)."""
    if mesh is not None and tuple(mesh.axis_names) == ("dp",):
        return mesh
    devs = np.asarray(jax.devices())
    return Mesh(devs, ("dp",))


class _PerRankStep:
    """Shared skeleton: per-rank parameter copies under shard_map."""

    def __init__(self, model: Layer, loss_fn: Callable, optimizer,
                 mesh: Mesh = None, sync_dtype=None, k_steps: int = 1):
        from ...jit import _FunctionalizedLayer
        self.model = model
        self.optimizer = optimizer
        self.mesh = _dp_mesh(mesh)
        self.ndp = self.mesh.shape["dp"]
        self._k = max(int(k_steps), 1)
        self._i = 0
        self._stacked = None      # name → [ndp, ...] per-rank params
        self._opt_state = None
        self._sync_dtype = sync_dtype
        inner = _FunctionalizedLayer(lambda *a: loss_fn(model, *a), model)
        self._inner = inner
        opt = optimizer
        sync_dt = sync_dtype

        def local_step(params, buffers, opt_state, lr, key, do_sync, *args):
            # inside shard_map: leading axis is this rank's slice (size 1)
            p_local = jax.tree_util.tree_map(lambda a: a[0], params)
            b_local = jax.tree_util.tree_map(lambda a: a[0], buffers)
            s_local = jax.tree_util.tree_map(lambda a: a[0], opt_state)

            def loss_of(p):
                out, new_b = inner.pure_call(p, b_local, key, args, {})
                loss = out[0] if isinstance(out, (tuple, list)) else out
                return loss, new_b
            (loss, new_b), grads = jax.value_and_grad(
                loss_of, has_aux=True)(p_local)

            if sync_dt is not None:
                # fp16/bf16 allreduce: halve comm bytes, accumulate in f32
                grads = jax.tree_util.tree_map(
                    lambda g: jax.lax.pmean(
                        g.astype(sync_dt), "dp").astype(g.dtype), grads)

            if opt._grad_clip is not None:
                names = sorted(grads)
                clipped = opt._grad_clip.clip_arrays(
                    [grads[k] for k in names])
                grads = dict(zip(names, clipped))
            new_p, new_s = opt.apply_updates(p_local, grads, s_local, lr)

            def synced(p):
                return jax.tree_util.tree_map(
                    lambda a: jax.lax.pmean(a, "dp"), p)

            new_p = jax.lax.cond(do_sync, synced, lambda p: p, new_p)
            mean_loss = jax.lax.pmean(loss, "dp")
            restack = lambda t: jax.tree_util.tree_map(  # noqa: E731
                lambda a: a[None], t)
            return (mean_loss, restack(new_p), restack(new_b),
                    restack(new_s))

        self._local_step = local_step
        self._jitted = None

    def _build(self, n_args: int):
        # ptlint: disable=PT-S001  manual-collective optimizer: the
        # whole point of this module is hand-controlled dp comm (fuse/
        # quantize/DGC), so the per-rank layout is the mechanism, not a
        # plan bypass — jaxshard models the equivalent implicit psum in
        # train_step.dp
        spec_r = P("dp")  # leading per-rank axis
        sharded = shard_map(
            self._local_step, mesh=self.mesh,
            # ptlint: disable=PT-S001  manual-collective per-rank layout
            in_specs=(spec_r, spec_r, spec_r, P(), P(), P(),
                      # ptlint: disable=PT-S001  same per-rank layout
                      *([P("dp")] * n_args)),
            out_specs=(P(), spec_r, spec_r, spec_r),
            check_vma=False)
        # ptlint: disable=PT-T009  not a registry program: the sharded
        # localsgd step's params/opt/velocity (0/1/2) are consumed by
        # the update in place — jaxplan has no plan entry to consume
        self._jitted = jax.jit(sharded, donate_argnums=(0, 1, 2))

    # ------------------------------------------------------------------
    def _init_state(self):
        params = {k: p._value for k, p in self.model.named_parameters()
                  if getattr(p, "trainable", True) and not p.stop_gradient}
        buffers = {k: b._value for k, b in self.model.named_buffers()
                  if b is not None}
        stack = lambda t: jax.tree_util.tree_map(  # noqa: E731
            lambda a: jnp.broadcast_to(a[None], (self.ndp,) + a.shape), t)
        self._stacked = stack(params)
        self._buffers = stack(buffers)
        self._opt_state = stack(self.optimizer.init_opt_state(params))

    def _should_sync(self) -> bool:
        return (self._i + 1) % self._k == 0

    def __call__(self, *args):
        if self._stacked is None:
            self._init_state()
        arr_args = [a._value if isinstance(a, Tensor) else jnp.asarray(a)
                    for a in args]
        lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
        key = _random.next_key()
        synced_now = bool(self._should_sync())
        do_sync = jnp.asarray(synced_now)
        if self._jitted is None:
            self._build(len(arr_args))
        loss, self._stacked, self._buffers, self._opt_state = self._jitted(
            self._stacked, self._buffers, self._opt_state, lr, key, do_sync,
            *arr_args)
        self._i += 1
        self.optimizer._global_step += 1
        # write back to the Layer exactly when the per-rank copies were
        # synchronized (model.parameters() stays consistent with the
        # distributed state at sync boundaries)
        if synced_now:
            self.sync_to_model()
        return Tensor(loss)

    def sync_to_model(self):
        """Write the rank-averaged params/buffers back into the Layer."""
        named_p = dict(self.model.named_parameters())
        for k, v in self._stacked.items():
            if k in named_p:
                named_p[k]._value = jnp.mean(
                    v.astype(jnp.float32), axis=0).astype(v.dtype)
        named_b = dict(self.model.named_buffers())
        for k, v in self._buffers.items():
            if k in named_b and named_b[k] is not None:
                named_b[k]._value = jnp.mean(
                    v.astype(jnp.float32), axis=0).astype(v.dtype)

    def rank_params(self, rank: int):
        """Debug view: one rank's local parameter copy."""
        return {k: v[rank] for k, v in self._stacked.items()}


class LocalSGDStep(_PerRankStep):
    """k local steps per rank, then param averaging (reference:
    localsgd_optimizer.py; strategy.localsgd_configs['k_steps'])."""

    def __init__(self, model, loss_fn, optimizer, k_steps: int = 4,
                 mesh: Mesh = None, begin_step: int = 1):
        super().__init__(model, loss_fn, optimizer, mesh=mesh,
                         sync_dtype=None, k_steps=k_steps)
        self._begin = max(int(begin_step), 1)

    def _should_sync(self):
        if self._i + 1 < self._begin:
            return False
        return (self._i + 1 - self._begin) % self._k == self._k - 1 \
            if self._k > 1 else True


class AdaptiveLocalSGDStep(LocalSGDStep):
    """Adaptive comm period (reference: adaptive localsgd — AdaComm): the
    sync period grows as the loss plateaus, k_t = ceil(k0 * loss_t/loss_0)
    inverted so early training syncs often."""

    def __init__(self, model, loss_fn, optimizer, init_k_steps: int = 1,
                 max_k_steps: int = 16, mesh: Mesh = None, begin_step: int = 1):
        super().__init__(model, loss_fn, optimizer, k_steps=init_k_steps,
                         mesh=mesh, begin_step=begin_step)
        self._k0 = max(int(init_k_steps), 1)
        self._kmax = max_k_steps
        self._loss0 = None

    def __call__(self, *args):
        loss = super().__call__(*args)
        lv = float(loss.numpy())
        if self._loss0 is None:
            self._loss0 = max(lv, 1e-12)
        # AdaComm schedule: k_t = ceil(sqrt(loss_0 / loss_t) * k0)
        ratio = self._loss0 / max(lv, 1e-12)
        self._k = int(np.clip(np.ceil(np.sqrt(ratio) * self._k0),
                              1, self._kmax))
        return loss


class DGCStep(_PerRankStep):
    """Deep Gradient Compression (reference:
    operators/optimizers/dgc_momentum_op.cc + dgc_op.cc +
    fleet/meta_optimizers/dgc_optimizer.py; Lin et al. 2018).

    Per rank and per parameter, after rampup_begin_step:
      u = m*u + g                (momentum correction: momentum is LOCAL)
      v = v + u                  (error feedback accumulates what was
                                  not communicated)
      mask = |v| >= quantile(|v|, sparsity_t)     (top-k selection)
      synced = pmean(v * mask)   (only selected entries carry signal)
      v, u = v*(1-mask), u*(1-mask)   (communicated entries are cleared)
      p = p - lr * synced        (plain SGD apply — momentum already in u)
    Before rampup_begin_step the step is the dense baseline optimizer
    with pmean'd gradients (the reference swaps ops the same way), and
    sparsity ramps through `sparsity` over `rampup_step` steps.

    TPU honesty note: XLA collectives move dense buffers, so on ICI this
    does NOT reduce bytes (`v*mask` is a dense pmean) — the VALUE here is
    the DGC convergence semantics and, on multi-host DCN deployments, a
    host-side sparse aggregation can plug in at the marked pmean. The
    reference's NCCL path has the same property (dgc allgathers encoded
    chunks of fixed k)."""

    def __init__(self, model, loss_fn, optimizer, mesh: Mesh = None,
                 rampup_begin_step: int = 0, rampup_step: int = 1,
                 sparsity=(0.999,), momentum: Optional[float] = None):
        super().__init__(model, loss_fn, optimizer, mesh=mesh,
                         sync_dtype=None, k_steps=1)
        self._rampup_begin = int(rampup_begin_step)
        self._rampup_step = max(int(rampup_step), 1)
        self._sparsity = [float(s) for s in sparsity]
        self._m = float(momentum if momentum is not None
                        else getattr(optimizer, "_momentum", 0.9))
        self.last_density = None  # observability: fraction communicated
        opt = optimizer
        inner = self._inner
        m_coef = self._m

        def local_step(state, lr, key, q, *args):
            params, buffers, base_state, u, v = state
            p_local = jax.tree_util.tree_map(lambda a: a[0], params)
            b_local = jax.tree_util.tree_map(lambda a: a[0], buffers)
            s_local = jax.tree_util.tree_map(lambda a: a[0], base_state)
            u_local = jax.tree_util.tree_map(lambda a: a[0], u)
            v_local = jax.tree_util.tree_map(lambda a: a[0], v)

            def loss_of(p):
                out, new_b = inner.pure_call(p, b_local, key, args, {})
                loss = out[0] if isinstance(out, (tuple, list)) else out
                return loss, new_b
            (loss, new_b), grads = jax.value_and_grad(
                loss_of, has_aux=True)(p_local)
            if opt._grad_clip is not None:
                names = sorted(grads)
                clipped = opt._grad_clip.clip_arrays(
                    [grads[k] for k in names])
                grads = dict(zip(names, clipped))

            def dense_phase(_):
                g_sync = jax.tree_util.tree_map(
                    lambda g: jax.lax.pmean(g, "dp"), grads)
                new_p, new_s = opt.apply_updates(p_local, g_sync,
                                                 s_local, lr)
                return (new_p, new_s, u_local, v_local,
                        jnp.asarray(1.0, jnp.float32))

            def dgc_phase(_):
                new_u, new_v, new_p = {}, {}, {}
                dens_n = jnp.asarray(0.0, jnp.float32)
                dens_d = jnp.asarray(0.0, jnp.float32)
                for k in sorted(grads):
                    uu = m_coef * u_local[k] + grads[k]
                    vv = v_local[k] + uu
                    thr = jnp.quantile(jnp.abs(vv).ravel().astype(
                        jnp.float32), q)
                    mask = (jnp.abs(vv) >= thr).astype(vv.dtype)
                    # <-- sparse-aggregation plug point (DCN): only
                    # mask-selected entries carry information
                    synced = jax.lax.pmean(vv * mask, "dp")
                    new_v[k] = vv * (1 - mask)
                    new_u[k] = uu * (1 - mask)
                    new_p[k] = p_local[k] - lr * synced
                    dens_n = dens_n + jnp.sum(mask.astype(jnp.float32))
                    dens_d = dens_d + np.prod(mask.shape, dtype=np.float32)
                return (new_p, s_local, new_u, new_v, dens_n / dens_d)

            new_p, new_s, new_u, new_v, density = jax.lax.cond(
                q > 0, dgc_phase, dense_phase, None)
            mean_loss = jax.lax.pmean(loss, "dp")
            restack = lambda t: jax.tree_util.tree_map(  # noqa: E731
                lambda a: a[None], t)
            return (mean_loss, jax.lax.pmean(density, "dp"),
                    (restack(new_p), restack(new_b), restack(new_s),
                     restack(new_u), restack(new_v)))

        self._dgc_local_step = local_step
        self._dgc_jitted = None

    # ------------------------------------------------------------------
    def _sparsity_now(self) -> float:
        """Reference rampup (dgc_optimizer): before rampup_begin dense;
        then sparsity steps through the schedule over rampup_step."""
        if self._i < self._rampup_begin:
            return 0.0
        k = (self._i - self._rampup_begin) * len(self._sparsity) \
            // self._rampup_step
        return self._sparsity[min(k, len(self._sparsity) - 1)]

    def _build_dgc(self, n_args: int):
        # ptlint: disable=PT-S001  manual-collective DGC layout (see
        # _build): hand-controlled dp comm is this module's mechanism
        spec_r = P("dp")
        state_spec = (spec_r,) * 5
        sharded = shard_map(
            self._dgc_local_step, mesh=self.mesh,
            # ptlint: disable=PT-S001  manual-collective per-rank layout
            in_specs=(state_spec, P(), P(), P(), *([P("dp")] * n_args)),
            out_specs=(P(), P(), state_spec),
            check_vma=False)
        # ptlint: disable=PT-T009  not a registry program: the DGC
        # state tuple (0) is replaced wholesale each step — no plan
        # entry exists for this optimizer-internal program
        self._dgc_jitted = jax.jit(sharded, donate_argnums=(0,))

    def _init_state(self):
        super()._init_state()
        zeros = lambda t: jax.tree_util.tree_map(  # noqa: E731
            jnp.zeros_like, t)
        self._u = zeros(self._stacked)
        self._v = zeros(self._stacked)

    def __call__(self, *args):
        if self._stacked is None:
            self._init_state()
        arr_args = [a._value if isinstance(a, Tensor) else jnp.asarray(a)
                    for a in args]
        lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
        key = _random.next_key()
        q = jnp.asarray(self._sparsity_now(), jnp.float32)
        if self._dgc_jitted is None:
            self._build_dgc(len(arr_args))
        state = (self._stacked, self._buffers, self._opt_state,
                 self._u, self._v)
        loss, density, state = self._dgc_jitted(state, lr, key, q,
                                                *arr_args)
        (self._stacked, self._buffers, self._opt_state,
         self._u, self._v) = state
        self._i += 1
        self.optimizer._global_step += 1
        self.last_density = float(np.asarray(density))
        self.sync_to_model()  # all-rank copies identical (synced update)
        return Tensor(loss)


class Fp16AllReduceStep(_PerRankStep):
    """Per-step grad sync in reduced precision (reference:
    fp16_allreduce_optimizer.py; here bf16 by default — the TPU-native
    16-bit format, same 2× comm saving with a wider exponent)."""

    def __init__(self, model, loss_fn, optimizer, mesh: Mesh = None,
                 dtype: str = "bfloat16"):
        dt = {"float16": jnp.float16, "bfloat16": jnp.bfloat16}[dtype]
        super().__init__(model, loss_fn, optimizer, mesh=mesh,
                         sync_dtype=dt, k_steps=1)

    def _should_sync(self):
        # grads are pmean'd (in bf16) every step already, so all rank
        # copies stay bit-identical — an extra f32 param pmean would cost
        # MORE than the comm this strategy exists to save. The step-end
        # writeback still runs (sync_to_model averages identical copies).
        return False

    def __call__(self, *args):
        loss = super().__call__(*args)
        self.sync_to_model()  # copies are identical; mean is exact
        return loss
