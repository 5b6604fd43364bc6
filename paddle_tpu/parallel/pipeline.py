"""Pipeline parallelism.

TPU-native analogue of the reference's pipeline stack:
/root/reference/python/paddle/fluid/optimizer.py:3718 PipelineOptimizer
(splits the program into per-device sections, inserts send/recv),
framework/pipeline_trainer.cc:24 + section_worker.cc:34-105 (per-microbatch
scopes, all-forward-then-all-backward GPipe schedule), and
fleet/meta_optimizers/pipeline_optimizer.py (cross-stage rings).

TPU design: no program splitting and no send/recv ops. Layer parameters are
STACKED on a leading [num_layers] dim and sharded over the mesh's 'pp' axis;
a shard_map gives each pp rank its local layer slab, and the GPipe schedule
is a fori_loop that each step: ppermute-shifts activations one stage down
the ring (the send/recv), injects the next microbatch at stage 0, and runs
the local layers via lax.scan. jax.grad differentiates straight through
(ppermute's transpose is the reverse shift), yielding the backward pipeline
automatically — the part section_worker.cc hand-schedules. Other mesh axes
(dp/tp/sp/sharding) stay in GSPMD 'auto' mode, so pipeline composes with
data parallel sharding of the microbatch dim.
"""
from __future__ import annotations

import functools
from typing import Dict

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core.dispatch import op
from ..core.tensor import Tensor
from . import mesh as _mesh

from jax import shard_map


def pipeline_spmd(stage_fn, mesh, num_stages: int, num_micro: int,
                  remat_stages: bool = True):
    """Build f(stacked_params, xs) -> ys running the GPipe schedule.

    stage_fn(layer_params, x) -> x : ONE layer's forward; layer_params
    leaves have a leading [num_layers] dim in `stacked_params`.
    xs: [num_micro, micro_batch, ...] activations entering the stack.
    Returns ys of the same shape having passed through all layers.

    remat_stages: jax.checkpoint around each per-layer application — the
    backward pipeline recomputes layer internals per microbatch step, so
    stored residuals are bounded by the inter-stage activations (one
    [micro_batch, ...] carry per schedule step) instead of every layer's
    attention/MLP internals × num_micro (the reference bounds this with
    per-microbatch scopes in SectionWorker, section_worker.cc:34-105).
    """
    if remat_stages:
        # ptlint: disable=PT-T009  structural remat: pipeline residency
        # is bounded per microbatch BY CONSTRUCTION (caller opts in via
        # remat_stages), orthogonal to the planner's HBM-envelope policy
        stage_fn = jax.checkpoint(stage_fn)
    other_axes = frozenset(ax for ax in mesh.axis_names if ax != "pp")

    def per_rank(stacked_local, xs):
        rank = jax.lax.axis_index("pp")
        M = xs.shape[0]
        steps = M + num_stages - 1

        def local_stack(x):
            def one(c, layer_params):
                return stage_fn(layer_params, c), None
            y, _ = jax.lax.scan(one, x, stacked_local)
            return y

        perm = [(i, i + 1) for i in range(num_stages - 1)]

        def body(t, carry):
            state, outs = carry
            recv = jax.lax.ppermute(state, "pp", perm) \
                if num_stages > 1 else state
            inject = jax.lax.dynamic_index_in_dim(
                xs, jnp.minimum(t, M - 1), 0, keepdims=False)
            x_in = jnp.where(rank == 0, inject, recv)
            y = local_stack(x_in)
            midx = t - (num_stages - 1)
            updated = jax.lax.dynamic_update_index_in_dim(
                outs, y, jnp.clip(midx, 0, M - 1), 0)
            write = jnp.logical_and(rank == num_stages - 1, midx >= 0)
            outs = jnp.where(write, updated, outs)
            return (y, outs)

        state = jnp.zeros_like(xs[0])
        outs = jnp.zeros_like(xs)
        state, outs = jax.lax.fori_loop(0, steps, body, (state, outs))
        # activations exist on the last stage; replicate across the pp ring
        mask = (rank == num_stages - 1).astype(outs.dtype)
        return jax.lax.psum(outs * mask, "pp")

    if int(mesh.shape.get("pp", 1)) == 1:
        # degenerate single-stage pipeline: no manual axis at all. (A
        # size-1 manual 'pp' subgroup trips an XLA partial-manual
        # RET_CHECK — spmd_partitioner.cc:3497 — when dp/tp stay in auto
        # mode, so run the plain layer scan instead.)
        def no_pp(stacked, xs):
            def local_stack(x):
                def one(c, layer_params):
                    return stage_fn(layer_params, c), None
                y, _ = jax.lax.scan(one, x, stacked)
                return y
            return jax.lax.map(local_stack, xs)
        return no_pp

    # manual over 'pp' only; dp/tp/sp/sharding stay in GSPMD auto mode so
    # pipeline composes with the other parallelisms
    return shard_map(
        per_rank, mesh=mesh,
        # ptlint: disable=PT-S001  the pipeline contract itself: stage
        # params are laid out one-stage-per-'pp'-rank by construction
        in_specs=(P("pp"), P()),
        out_specs=P(),
        axis_names={"pp"},
        check_vma=False)


# ---------------------------------------------------------------------------
# Pipelined GPT: stacked-parameter variant of models.gpt.GPT
# ---------------------------------------------------------------------------
def _gpt_block_forward(p: Dict[str, jax.Array], x: jax.Array,
                       num_heads: int = 1) -> jax.Array:
    """Pure-array GPT block (pre-LN) matching models.gpt.GPTBlock."""
    def ln(x, scale, bias):
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.var(x, axis=-1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + 1e-5) * scale + bias

    B, T, H = x.shape
    h = ln(x, p["ln1_w"], p["ln1_b"])
    qkv = h @ p["qkv_w"] + p["qkv_b"]
    nh = num_heads
    hd = H // nh
    qkv = qkv.reshape(B, T, 3, nh, hd)
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    q = jnp.swapaxes(q, 1, 2)
    k = jnp.swapaxes(k, 1, 2)
    v = jnp.swapaxes(v, 1, 2)
    logits = jnp.einsum("bhtd,bhsd->bhts", q, k) * float(1.0 / np.sqrt(hd))
    causal = jnp.tril(jnp.ones((T, T), bool))
    logits = jnp.where(causal, logits, jnp.asarray(-1e30, logits.dtype))
    probs = jax.nn.softmax(logits, axis=-1)
    att = jnp.einsum("bhts,bhsd->bhtd", probs, v)
    att = jnp.swapaxes(att, 1, 2).reshape(B, T, H)
    x = x + att @ p["out_w"] + p["out_b"]
    h2 = ln(x, p["ln2_w"], p["ln2_b"])
    x = x + jax.nn.gelu(h2 @ p["up_w"] + p["up_b"], approximate=True) \
        @ p["down_w"] + p["down_b"]
    return x


class PipelinedGPT:
    """GPT with layer-stacked parameters for pp sharding.

    Exposes named_parameters()/parameters() like a Layer so it plugs into
    optimizers and parallel.ShardedTrainStep; mark_sharding puts the stacked
    dim on 'pp' (and the TP dims on 'tp' where divisible).
    """

    def __init__(self, cfg, mesh=None):
        from ..models.gpt import GPTConfig  # noqa: F401 (type only)
        from ..nn import initializer as I
        from .api import mark_sharding
        self.cfg = cfg
        self.mesh = mesh or _mesh.ensure_global_mesh()
        self.training = True
        H, L = cfg.hidden_size, cfg.num_layers
        inner = cfg.ffn_mult * H
        init = I.Normal(0.0, 0.02)
        zeros = I.Constant(0.0)
        ones = I.Constant(1.0)

        def param(name, shape, initializer, spec):
            t = Tensor(initializer(shape, jnp.float32), stop_gradient=False,
                       name=name, persistable=True)
            t.is_parameter = True
            t.trainable = True
            mark_sharding(t, *spec)
            return t

        self._params = {
            "wte": param("wte", [cfg.vocab_size, H], init, (None, None)),
            "wpe": param("wpe", [cfg.max_seq_len, H], init, (None, None)),
            "ln_f_w": param("ln_f_w", [H], ones, (None,)),
            "ln_f_b": param("ln_f_b", [H], zeros, (None,)),
            "head_w": param("head_w", [H, cfg.vocab_size], init,
                            (None, "tp")),
            # stacked block params: leading dim L sharded over pp
            "blk.ln1_w": param("blk.ln1_w", [L, H], ones, ("pp",)),
            "blk.ln1_b": param("blk.ln1_b", [L, H], zeros, ("pp",)),
            "blk.qkv_w": param("blk.qkv_w", [L, H, 3 * H], init,
                               ("pp", None, None)),
            "blk.qkv_b": param("blk.qkv_b", [L, 3 * H], zeros,
                               ("pp", None)),
            "blk.out_w": param("blk.out_w", [L, H, H], init,
                               ("pp", None, None)),
            "blk.out_b": param("blk.out_b", [L, H], zeros, ("pp", None)),
            "blk.ln2_w": param("blk.ln2_w", [L, H], ones, ("pp",)),
            "blk.ln2_b": param("blk.ln2_b", [L, H], zeros, ("pp",)),
            "blk.up_w": param("blk.up_w", [L, H, inner], init,
                              ("pp", None, None)),
            "blk.up_b": param("blk.up_b", [L, inner], zeros, ("pp", None)),
            "blk.down_w": param("blk.down_w", [L, inner, H], init,
                                ("pp", None, None)),
            "blk.down_b": param("blk.down_b", [L, H], zeros, ("pp", None)),
        }
        self._num_heads = cfg.num_heads
        self._pp = self.mesh.shape.get("pp", 1)
        self._pipeline = None

    # --- Layer-protocol subset used by train steps ----------------------
    def named_parameters(self, *a, **k):
        return list(self._params.items())

    def parameters(self, include_sublayers=True):
        return list(self._params.values())

    def named_buffers(self, *a, **k):
        return []

    def buffers(self, *a, **k):
        return []

    def sublayers(self, include_self=False):
        return [self] if include_self else []

    def train(self):
        self.training = True
        return self

    def eval(self):
        self.training = False
        return self

    def state_dict(self):
        return dict(self._params)

    # -------------------------------------------------------------- loss
    def loss(self, input_ids, labels, num_micro=None):
        cfg = self.cfg
        num_micro = num_micro or max(self._pp, 1)
        p = {k: (v._value if isinstance(v, Tensor) else v)
             for k, v in self._params.items()}
        ids = input_ids._value if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        lab = labels._value if isinstance(labels, Tensor) \
            else jnp.asarray(labels)
        B, T = ids.shape
        assert B % num_micro == 0, \
            f"batch {B} must divide into {num_micro} microbatches"
        x = jnp.take(p["wte"], ids, axis=0) \
            + p["wpe"][None, :T]
        xs = x.reshape(num_micro, B // num_micro, T, cfg.hidden_size)

        stacked = {
            "ln1_w": p["blk.ln1_w"], "ln1_b": p["blk.ln1_b"],
            "qkv_w": p["blk.qkv_w"], "qkv_b": p["blk.qkv_b"],
            "out_w": p["blk.out_w"], "out_b": p["blk.out_b"],
            "ln2_w": p["blk.ln2_w"], "ln2_b": p["blk.ln2_b"],
            "up_w": p["blk.up_w"], "up_b": p["blk.up_b"],
            "down_w": p["blk.down_w"], "down_b": p["blk.down_b"],
        }
        if self._pipeline is None:
            self._pipeline = pipeline_spmd(
                functools.partial(_gpt_block_forward,
                                  num_heads=self._num_heads),
                self.mesh, self._pp, num_micro)
        ys = self._pipeline(stacked, xs)
        y = ys.reshape(B, T, cfg.hidden_size)
        mu = jnp.mean(y, axis=-1, keepdims=True)
        var = jnp.var(y, axis=-1, keepdims=True)
        y = (y - mu) * jax.lax.rsqrt(var + 1e-5) * p["ln_f_w"] + p["ln_f_b"]
        logits = y @ p["head_w"]
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(
            logp, lab[..., None].astype(jnp.int32), axis=-1)
        return Tensor(jnp.mean(nll))


def pipelined_gpt_loss_fn(model, input_ids, labels):
    return model.loss(input_ids, labels)


# ---------------------------------------------------------------------------
# Generic pipeline container: stack ANY same-shaped Layer blocks
# ---------------------------------------------------------------------------
class PipelineLayer:
    """Pipeline-parallel container over arbitrary same-structured blocks
    (reference: distributed/fleet/meta_parallel PipelineLayer +
    fluid/optimizer.py:3718 PipelineOptimizer's program slicer; the
    capability, redesigned: blocks' parameters are STACKED on a leading
    [num_layers] dim sharded over the mesh 'pp' axis and run under the
    shard_map GPipe schedule of pipeline_spmd).

    Every block must have the same parameter tree (names/shapes) and map
    [micro_batch, ...] -> same shape. Blocks with buffers (e.g. BatchNorm
    running stats) are rejected — stat updates are not functional across
    microbatches in a pipeline; use buffer-free blocks (LayerNorm etc.).
    """

    def __init__(self, layers, mesh=None, num_micro=None,
                 remat_stages=True):
        from ..jit import _FunctionalizedLayer
        from .api import mark_sharding

        self.blocks = list(layers)
        if not self.blocks:
            raise ValueError("PipelineLayer needs at least one block")
        self.mesh = mesh or _mesh.ensure_global_mesh()
        self._pp = int(self.mesh.shape.get("pp", 1))
        L = len(self.blocks)
        if L % max(self._pp, 1) != 0:
            raise ValueError(f"{L} blocks do not divide over pp="
                             f"{self._pp} stages")
        self.num_micro = num_micro
        self.remat_stages = remat_stages
        self.training = True

        names = [k for k, _ in self.blocks[0].named_parameters()]
        for b in self.blocks[1:]:
            other = [k for k, _ in b.named_parameters()]
            if other != names:
                raise ValueError(
                    "PipelineLayer blocks must share one parameter "
                    f"structure; got {names} vs {other}")
        for b in self.blocks:
            if any(True for _ in b.named_buffers()):
                raise ValueError(
                    "PipelineLayer blocks must be buffer-free (running "
                    "stats cannot update functionally across microbatches)")
        from ..nn import layer as _nl
        rng_types = tuple(
            t for t in (getattr(_nl.common, n, None)
                        for n in ("Dropout", "Dropout2D", "Dropout3D",
                                  "AlphaDropout"))
            if t is not None)
        for b in self.blocks:
            for sub in b.sublayers(include_self=True):
                if rng_types and isinstance(sub, rng_types):
                    raise ValueError(
                        f"PipelineLayer blocks may not contain RNG layers "
                        f"({type(sub).__name__}): the staged schedule "
                        "replays one stage function with a fixed key, so "
                        "dropout masks would repeat across layers and "
                        "microbatches")

        self._params = {}
        for name in names:
            vals = [dict(b.named_parameters())[name]._value
                    for b in self.blocks]
            t = Tensor(jnp.stack(vals, axis=0), stop_gradient=False,
                       name=f"pipe.{name}", persistable=True)
            t.is_parameter = True
            t.trainable = True
            mark_sharding(t, *(("pp",) + (None,) * (t._value.ndim - 1)))
            self._params[f"pipe.{name}"] = t
        self._names = names
        self._inner = _FunctionalizedLayer(self.blocks[0].forward,
                                           self.blocks[0])
        self._pipeline = None

    # --- Layer-protocol subset used by train steps ----------------------
    def named_parameters(self, *a, **k):
        return list(self._params.items())

    def parameters(self, include_sublayers=True):
        return list(self._params.values())

    def named_buffers(self, *a, **k):
        return []

    def buffers(self, *a, **k):
        return []

    def sublayers(self, include_self=False):
        return [self] if include_self else []

    def train(self):
        self.training = True
        for b in self.blocks:
            b.train()
        return self

    def eval(self):
        self.training = False
        for b in self.blocks:
            b.eval()
        return self

    def state_dict(self):
        return dict(self._params)

    def _stage_fn(self, layer_params, x):
        per_layer = {n: layer_params[f"pipe.{n}"] for n in self._names}
        out, _ = self._inner.pure_call(per_layer, {},
                                       jax.random.PRNGKey(0),
                                       (Tensor(x),), {})
        out = out[0] if isinstance(out, (tuple, list)) else out
        return out._value if isinstance(out, Tensor) else out

    def forward(self, x, num_micro=None):
        xv = x._value if isinstance(x, Tensor) else jnp.asarray(x)
        M = num_micro or self.num_micro or max(self._pp, 1)
        B = xv.shape[0]
        if B % M:
            raise ValueError(f"batch {B} must divide into {M} microbatches")
        xs = xv.reshape((M, B // M) + xv.shape[1:])
        if self._pipeline is None:
            fn = pipeline_spmd(
                lambda lp, a: self._stage_fn(lp, a), self.mesh,
                self._pp, M, remat_stages=self.remat_stages)
            # partial-manual shard_map (manual 'pp', auto dp/tp/...) only
            # lowers under jit; eager calls go through a cached jit wrapper
            self._pipeline = jax.jit(fn)
        params = {k: v._value for k, v in self._params.items()}
        under_trace = isinstance(xv, jax.core.Tracer) or any(
            isinstance(v, jax.core.Tracer) for v in params.values())
        ys = (self._pipeline.__wrapped__(params, xs) if under_trace
              else self._pipeline(params, xs))
        return Tensor(ys.reshape((B,) + ys.shape[2:]))

    __call__ = forward
