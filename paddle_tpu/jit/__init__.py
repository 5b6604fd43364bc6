"""paddle.jit: dygraph → compiled XLA programs.

TPU-native analogue of /root/reference/python/paddle/fluid/dygraph/
dygraph_to_static/ (ProgramTranslator at program_translator.py:756 — a
25-file AST transpiler rewriting Python into ProgramDesc ops) and jit.py
(save:507 / load:787 / TracedLayer:1047).

The TPU design needs NO AST rewriting: dygraph code is already pure-JAX
under the hood, so `to_static` simply traces the Python callable with
jax.jit — Python control flow is hard-staged at trace time exactly like the
reference's static graph, and the result is one fused XLA executable per
input signature (shape-bucketed cache, mirroring ProgramTranslator's
program cache). `save`/`load` use jax.export StableHLO serialization: the
analogue of save_inference_model's ProgramDesc+params artifact.
"""
from __future__ import annotations

import functools
import os
import pickle
import time
from typing import Callable, Dict, Optional

import numpy as np
import jax
import jax.numpy as jnp

from ..analysis import jaxplan
from ..core.tensor import Tensor
from ..core import random as _random
from ..core.autograd import no_grad
from ..core.dtypes import convert_dtype
from ..nn.layer.layers import Layer


class InputSpec:
    """reference: python/paddle/static/input.py InputSpec."""

    def __init__(self, shape=None, dtype="float32", name=None):
        self.shape = list(shape) if shape is not None else None
        self.dtype = convert_dtype(dtype)
        self.name = name

    def __repr__(self):
        return f"InputSpec(shape={self.shape}, dtype={self.dtype}, " \
               f"name={self.name})"


def _unwrap(x):
    if isinstance(x, Tensor):
        return x._value
    return x


class _FunctionalizedLayer:
    """Makes a Layer's forward pure: (params, buffers, key, *args) →
    (outputs, new_buffers). Parameters/buffers are temporarily rebound to
    traced arrays during the call."""

    def __init__(self, fn, layer: Optional[Layer]):
        self.fn = fn
        self.layer = layer

    def collect_state(self):
        if self.layer is None:
            return {}, {}
        params = {k: p._value for k, p in self.layer.named_parameters()}
        buffers = {k: b._value for k, b in self.layer.named_buffers()
                   if b is not None}
        return params, buffers

    def pure_call(self, params, buffers, key, args, kwargs):
        layer = self.layer
        saved = {}
        named_p = dict(layer.named_parameters()) if layer else {}
        named_b = dict(layer.named_buffers()) if layer else {}
        for k, v in list(params.items()):
            saved[k] = named_p[k]._value
            named_p[k]._value = v
        for k, v in list(buffers.items()):
            saved["__buf__" + k] = named_b[k]._value
            named_b[k]._value = v
        try:
            with _random.trace_key_scope(key):
                wrapped_args = jax.tree_util.tree_map(
                    lambda a: Tensor(a) if isinstance(
                        a, (jax.Array, jax.core.Tracer)) else a, args)
                wrapped_kwargs = jax.tree_util.tree_map(
                    lambda a: Tensor(a) if isinstance(
                        a, (jax.Array, jax.core.Tracer)) else a, kwargs)
                out = self.fn(*wrapped_args, **wrapped_kwargs)
            out_arrays = jax.tree_util.tree_map(
                lambda t: t._value if isinstance(t, Tensor) else t, out,
                is_leaf=lambda t: isinstance(t, Tensor))
            new_buffers = {k: named_b[k]._value for k in buffers}
            return out_arrays, new_buffers
        finally:
            for k, v in params.items():
                named_p[k]._value = saved[k]
            for k in buffers:
                named_b[k]._value = saved["__buf__" + k]


def _is_traceable_leaf(leaf) -> bool:
    """Arrays trace; python scalars (bool/int/float/str...) specialize the
    trace — the reference re-translates the program per python-scalar
    value, so `if flag:` / `x.reshape([n, -1])` on a python scalar keeps
    python semantics here too. Corollary (also reference behavior): a
    python scalar that CHANGES every call recompiles every call — pass
    per-step scalars as paddle.to_tensor(v) to trace them instead."""
    if isinstance(leaf, (bool, np.bool_)):
        return False
    return isinstance(leaf, (jax.Array, jax.core.Tracer, np.ndarray,
                             np.generic))


def _extract_statics(args, kwargs):
    """Pull non-traceable python leaves (bools/strings/callables...) out of
    the arg pytrees; they ride the jit cache key instead of the trace."""
    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
    statics, new_leaves = [], []
    for i, leaf in enumerate(leaves):
        if _is_traceable_leaf(leaf):
            new_leaves.append(leaf)
        else:
            statics.append((i, leaf))
            new_leaves.append(np.int32(0))  # placeholder, replaced in-trace
    args2, kwargs2 = jax.tree_util.tree_unflatten(treedef, new_leaves)
    return tuple(statics), args2, kwargs2


def _restore_statics(statics, args, kwargs):
    if not statics:
        return args, kwargs
    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
    for i, v in statics:
        leaves[i] = v
    return jax.tree_util.tree_unflatten(treedef, leaves)


class StaticFunction:
    """The to_static wrapper (reference: program_translator.StaticFunction)."""

    def __init__(self, fn, layer=None, input_spec=None):
        # AST pass first (reference: ProgramTranslator → DygraphToStaticAst):
        # if/while on tensors become lax-lowered control flow; functions
        # with no rewritable statements come back unchanged
        from .dy2static import convert_to_static
        converted = convert_to_static(fn)
        self._inner = _FunctionalizedLayer(converted, layer)
        self._input_spec = input_spec
        self._raw_fn = fn
        self._layer = layer

        def _jitted_impl(mode_sig, statics, params, buffers, key, args,
                         kwargs):
            # mode_sig: per-(sub)layer training flags — a static cache key
            # so train/eval retrace instead of silently reusing the other
            # mode's trace (Dropout/BatchNorm change the program).
            # statics: ((leaf_index, value), ...) — python-scalar args
            # specialize the trace instead of being traced (see
            # _is_traceable_leaf).
            args, kwargs = _restore_statics(statics, args, kwargs)
            return self._inner.pure_call(params, buffers, key, args, kwargs)
        self._jitted = jax.jit(_jitted_impl, static_argnums=(0, 1))
        functools.update_wrapper(self, fn)

    def _mode_sig(self):
        if self._layer is None:
            return ()
        return tuple(l.training
                     for l in self._layer.sublayers(include_self=True))

    def __call__(self, *args, **kwargs):
        if not ProgramTranslator.get_instance().enabled:
            return self._raw_fn(*args, **kwargs)  # dygraph fallback
        params, buffers = self._inner.collect_state()
        arr_args = jax.tree_util.tree_map(
            _unwrap, args, is_leaf=lambda t: isinstance(t, Tensor))
        arr_kwargs = jax.tree_util.tree_map(
            _unwrap, kwargs, is_leaf=lambda t: isinstance(t, Tensor))
        statics, arr_args, arr_kwargs = _extract_statics(arr_args,
                                                         arr_kwargs)
        key = _random.next_key()
        out, new_buffers = self._jitted(self._mode_sig(), statics, params,
                                        buffers, key, arr_args, arr_kwargs)
        if self._layer is not None and new_buffers:
            named_b = dict(self._layer.named_buffers())
            for k, v in new_buffers.items():
                named_b[k]._value = v
        return jax.tree_util.tree_map(
            lambda a: Tensor(a) if isinstance(a, jax.Array) else a, out)

    @property
    def forward_fn(self):
        return self._raw_fn

    def concrete_program(self, *args):
        """Lowered HLO text for inspection (ProgramDesc analogue)."""
        params, buffers = self._inner.collect_state()
        arr_args = jax.tree_util.tree_map(
            _unwrap, args, is_leaf=lambda t: isinstance(t, Tensor))
        statics, arr_args, arr_kwargs = _extract_statics(arr_args, {})
        key = jax.random.PRNGKey(0)
        return self._jitted.lower(self._mode_sig(), statics, params,
                                  buffers, key, arr_args,
                                  arr_kwargs).as_text()


def to_static(function=None, input_spec=None, build_strategy=None,
              backend=None, **kwargs):
    """paddle.jit.to_static — decorator or call.

    reference: dygraph_to_static ProgramTranslator; here = jax.jit tracing.
    """
    def decorate(fn):
        if isinstance(fn, Layer):
            layer = fn
            static = StaticFunction(layer.forward, layer, input_spec)
            layer.forward = static
            layer._static_function = static
            return layer
        # plain function (may still close over layers)
        return StaticFunction(fn, None, input_spec)
    if function is not None:
        return decorate(function)
    return decorate


class TranslatedLayer(Layer):
    """Deserialized inference artifact (reference: fluid/dygraph/io.py
    TranslatedLayer built from __model__ + params)."""

    def __init__(self, exported, state):
        super().__init__()
        self._exported = exported
        self._state = state

    def forward(self, *args):
        arrs = [a._value if isinstance(a, Tensor) else jnp.asarray(a)
                for a in args]
        out = self._exported.call(self._state, *arrs)
        return jax.tree_util.tree_map(
            lambda a: Tensor(a) if isinstance(a, jax.Array) else a, out)


def save(layer, path, input_spec=None, **configs):
    """paddle.jit.save (reference: fluid/dygraph/jit.py:507 — saves
    __model__ ProgramDesc + params). Artifact: StableHLO (jax.export) +
    pickled params; loadable without the model's Python class.

    Dims given as -1/None in input_spec are exported SYMBOLIC
    (jax.export symbolic_shape), so the saved model serves any batch size
    — the reference's polymorphic batch dim. Falls back to concrete dims
    (with a warning) if the model doesn't trace symbolically."""
    if input_spec is None:
        raise ValueError("paddle.jit.save requires input_spec")

    # ONE symbolic scope for all inputs (independent scopes fail export
    # with 'invalid mixing of symbolic scopes'), and dynamic dims share a
    # symbol BY POSITION across inputs ("b" for dim 0, "d<j>" beyond): the
    # (batch, seq, ...) convention where a tensor and its mask must agree.
    # Inputs whose same-position dynamic dims genuinely differ fail the
    # symbolic export and take the pinned-shape fallback below.
    scope = jax.export.SymbolicScope()

    def _spec(sp):
        dims = list(sp.shape)
        if any(d in (-1, None) for d in dims):
            expr = ",".join(
                ("b" if j == 0 else f"d{j}") if d in (-1, None)
                else str(d) for j, d in enumerate(dims))
            return jax.ShapeDtypeStruct(
                jax.export.symbolic_shape(expr, scope=scope), sp.dtype)
        return jax.ShapeDtypeStruct(tuple(dims), sp.dtype)

    specs = [_spec(s) for s in input_spec]
    fn = layer.forward if isinstance(layer, Layer) else layer
    if isinstance(fn, StaticFunction):
        fn = fn.forward_fn
    params = {k: p._value for k, p in layer.named_parameters()}
    buffers = {k: b._value for k, b in layer.named_buffers()
               if b is not None}
    was_training = layer.training
    layer.eval()
    try:
        def pure(state, *arrs):
            inner = _FunctionalizedLayer(fn, layer)
            out, _ = inner.pure_call(state["params"], state["buffers"],
                                     jax.random.PRNGKey(0), arrs, {})
            return out

        state = {"params": params, "buffers": buffers}
        state_spec = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), state)
        try:
            # ptlint: disable=PT-T004  (export path: jit built once per
            # save() call, traced on specs, never dispatched)
            exported = jax.export.export(jax.jit(pure))(state_spec, *specs)
        except Exception:
            if not any(any(d in (-1, None) for d in s.shape)
                       for s in input_spec):
                raise
            import warnings
            warnings.warn(
                "jit.save: symbolic-batch export failed (a shape-dependent "
                "op in the model); re-exporting with dynamic dims pinned "
                "to 1 — the artifact will only serve that batch size",
                stacklevel=2)
            concrete = [jax.ShapeDtypeStruct(
                tuple(1 if d in (-1, None) else d for d in s.shape),
                s.dtype) for s in input_spec]
            # ptlint: disable=PT-T004  (same export-only jit as above)
            exported = jax.export.export(jax.jit(pure))(state_spec,
                                                        *concrete)
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path + ".pdmodel", "wb") as f:
            f.write(exported.serialize())
        with open(path + ".pdiparams", "wb") as f:
            pickle.dump(jax.tree_util.tree_map(np.asarray, state), f)
    finally:
        if was_training:
            layer.train()


def load(path, **configs):
    """paddle.jit.load (reference: fluid/dygraph/jit.py:787)."""
    with open(path + ".pdmodel", "rb") as f:
        exported = jax.export.deserialize(f.read())
    with open(path + ".pdiparams", "rb") as f:
        state = pickle.load(f)
    state = jax.tree_util.tree_map(jnp.asarray, state)
    return TranslatedLayer(exported, state)


def not_to_static(fn):
    fn._not_to_static = True
    return fn


class ProgramTranslator:
    """Parity shim (reference: program_translator.py:756)."""
    _instance = None
    enabled = True

    @classmethod
    def get_instance(cls):
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    def enable(self, enable_to_static):
        self.enabled = enable_to_static


def enable_to_static(flag=True):
    ProgramTranslator.get_instance().enable(flag)


# ---------------------------------------------------------------------------
# Functional train step: the TPU performance path for dygraph training.
# ---------------------------------------------------------------------------
def _batch_tokens(arr_args) -> int:
    """Token count of one dispatched batch, from host-side shape
    metadata only (never the array values). LM batches are integer
    token-id arrays — first integer arg of rank>=2 counts fully
    (stacked K-step batches included via .size); otherwise fall back
    to leading-two-dims of the first rank>=2 arg (B*T for dense
    features). 0 when nothing looks batched (throughput gauges skip)."""
    for a in arr_args:
        if a.ndim >= 2 and np.issubdtype(np.dtype(a.dtype), np.integer):
            return int(a.size)
    for a in arr_args:
        if a.ndim >= 2:
            return int(a.shape[0] * a.shape[1])
    return 0


class TrainStep:
    """Compile (forward+backward+optimizer) into ONE XLA executable.

    Replaces the reference's per-op dispatch hot loop (§3.2/3.3 of
    SURVEY.md) with a single compiled program: jax.value_and_grad over the
    layer's parameter pytree + the optimizer's pure update. Buffers (BN
    stats) are threaded functionally; randomness via a per-step key.

    Usage:
        step = paddle.jit.TrainStep(model, loss_fn, optimizer)
        loss = step(x, y)   # updates model & optimizer state in place
    loss_fn signature: loss_fn(model, *batch) -> scalar loss Tensor (or a
    tuple whose first element is the loss).
    """

    def __init__(self, model: Layer, loss_fn: Callable, optimizer,
                 donate: bool = True, return_outputs: bool = False,
                 anomaly_guard=None):
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.return_outputs = return_outputs
        # core.anomaly.AnomalyGuard: the NaN/Inf check runs INSIDE the
        # compiled step (pure jnp) and the update is gated through
        # jnp.where, same shape as the static-graph found_inf path; only
        # the counter update needs the host
        self._guard = anomaly_guard
        self._opt_state = None
        inner = _FunctionalizedLayer(
            lambda *args: loss_fn(model, *args), model)
        guard = anomaly_guard

        def step(params, frozen, buffers, opt_state, lr, key_root, rng_ctr,
                 *args):
            # RNG key derived ON DEVICE from a functionally-threaded
            # counter: no per-step host threefry dispatch or key upload
            key = jax.random.fold_in(key_root, rng_ctr)

            def loss_of(p):
                merged = dict(p)
                merged.update(frozen)  # frozen params are constants
                out, new_buffers = inner.pure_call(merged, buffers, key,
                                                   args, {})
                loss = out[0] if isinstance(out, (tuple, list)) else out
                aux = (out, new_buffers)
                return loss, aux
            (loss, (out, new_buffers)), grads = jax.value_and_grad(
                loss_of, has_aux=True)(params)
            bad = None
            if guard is not None:
                from ..core import anomaly as _anomaly
                bad = _anomaly.tree_not_finite((loss, grads))
                if guard.policy == "zero_grads":
                    grads = _anomaly.sanitize_tree(grads)
            if optimizer._grad_clip is not None:
                names = sorted(grads)
                need_clip = [self._need_clip.get(k, True) for k in names]
                clipped = optimizer._grad_clip.clip_arrays(
                    [grads[k] for k in names], need_clip)
                grads = dict(zip(names, clipped))
            new_params, new_opt = optimizer.apply_updates(
                params, grads, opt_state, lr)
            if guard is not None and guard.policy == "skip_step":
                # drop the whole poisoned update: params, accumulators and
                # buffers roll back to the pre-step values
                def keep(old, new):
                    return jax.tree_util.tree_map(
                        lambda o, n: jnp.where(bad, o, n), old, new)
                new_params = keep(params, new_params)
                new_opt = keep(opt_state, new_opt)
                new_buffers = keep(buffers, new_buffers)
            tail = () if bad is None else (bad,)
            if return_outputs:
                return (loss, new_params, new_buffers, new_opt,
                        rng_ctr + 1, out) + tail
            return (loss, new_params, new_buffers, new_opt,
                    rng_ctr + 1) + tail

        # donate params/buffers/opt_state/rng_ctr (argnums 0/2/3/6): all
        # four die inside the step (their updated twins are returned and
        # _dispatch rebinds immediately), so XLA reuses their buffers for
        # the outputs instead of double-residing old+new. frozen (1) is
        # read-only across steps and lr/key_root (4/5) are reused, so
        # they stay undonated. The tuple comes from the committed plan
        # (jaxplan.json, donation planner) with these argnums as the
        # fallback; the jaxcost donation audit gates it either way — an
        # undonated dead argnum here is a tier-1 finding.
        donate_argnums = jaxplan.planned_donation(
            "train_step", default=(0, 2, 3, 6)) if donate else ()
        self._donate_argnums = donate_argnums
        self._raw_step = step  # unjitted; MultiStepTrainStep scans over it
        self._step = jax.jit(step, donate_argnums=donate_argnums)
        self._need_clip = {}
        # per-step dispatch caches (see __call__)
        self._state_cache = None
        self._lr_host = None
        self._lr_dev = None
        self._rng_expected = None
        self._rng_ctr = None
        self._key_root = None
        # previous dispatch timestamp for the obs cadence metric, and the
        # metric children it feeds: looked up once, not every step
        self._prev_dispatch_t = None
        from .. import obs
        self._obs_step_seconds = obs.histogram(
            "train_step_seconds",
            "per-step train time via inter-dispatch cadence",
            unit="seconds").labels()
        self._obs_tokens = obs.counter(
            "train_tokens_total",
            "tokens consumed by dispatched train steps",
            unit="tokens").labels()
        self._obs_tokens_per_sec = obs.gauge(
            "train_tokens_per_sec",
            "training throughput over the last dispatch gap",
            unit="tokens_per_second").labels()

    def invalidate(self):
        """Drop the cached parameter/buffer bindings. Call after changing
        the model's STRUCTURE (adding/removing sublayers or parameters,
        flipping trainable/stop_gradient). Plain value updates
        (set_state_dict, manual ._value assignment) need no invalidation —
        the cache holds Tensor objects, not arrays."""
        self._state_cache = None

    def _split_params(self):
        """Current {name: array} views of the trainable/frozen split (one
        classification lives in _collect_state; this is a thin reader used
        by tests to lower the step by hand)."""
        params_t, frozen_t, _ = self._collect_state()
        return ({k: p._value for k, p in params_t},
                {k: p._value for k, p in frozen_t})

    def _collect_state(self):
        """Traverse the module tree ONCE and cache (name, Tensor) lists —
        the tree walk was ~3000 Python frames per step on ResNet-50 and
        showed up as ~15 ms/step of host dispatch in traces. The structure
        is frozen at first call (same contract as the reference's
        CompiledProgram: the program is fixed at compile); invalidate()
        rescans."""
        if self._state_cache is None:
            params_t, frozen_t = [], []
            for k, p in self.model.named_parameters():
                if getattr(p, "trainable", True) and not p.stop_gradient:
                    params_t.append((k, p))
                    self._need_clip[k] = getattr(p, "need_clip", True)
                else:
                    frozen_t.append((k, p))
            buffers_t = [(k, b) for k, b in self.model.named_buffers()
                         if b is not None]
            self._state_cache = (params_t, frozen_t, buffers_t)
        return self._state_cache

    def _dispatch(self, fn, draws, args, validate=None):
        """Shared per-call host path for the 1-step and K-step variants:
        bind cached state, advance the RNG stream by `draws` (the counter
        itself lives on device and is threaded through the compiled step,
        so a steady-state step uploads nothing — resync only if other code
        drew from the stream between calls: eager dropout, paddle.seed),
        run `fn`, and write the new state back. Returns fn's trailing
        extras (anything after the 5 carried slots)."""
        from ..profiler import RecordEvent
        params_t, frozen_t, buffers_t = self._collect_state()
        params = {k: p._value for k, p in params_t}
        frozen = {k: p._value for k, p in frozen_t}
        buffers = {k: b._value for k, b in buffers_t}
        if self._opt_state is None:
            self._opt_state = self.optimizer.init_opt_state(params)
        arr_args = [a._value if isinstance(a, Tensor) else jnp.asarray(a)
                    for a in args]
        if validate is not None:
            validate(arr_args)
        lr = float(self.optimizer.get_lr())
        if lr != self._lr_host:
            self._lr_dev = jnp.asarray(lr, jnp.float32)
            self._lr_host = lr
        _random._RNGState.counter += draws
        state_now = (_random._RNGState.seed, _random._RNGState.counter)
        if (self._rng_ctr is None
                or self._rng_expected != (state_now[0],
                                          state_now[1] - draws)):
            # first inner step consumes counter c0+1 (the value the old
            # per-call next_key() would have drawn); each step threads +1
            self._key_root = _random._RNGState.get_root_key()
            self._rng_ctr = jnp.asarray(state_now[1] - draws + 1,
                                        jnp.uint32)
        with RecordEvent(type(self).__name__):
            res = fn(params, frozen, buffers, self._opt_state,
                     self._lr_dev, self._key_root, self._rng_ctr,
                     *arr_args)
        # only mark the host/device counters as in-sync once the step has
        # actually consumed the key — an exception above leaves
        # _rng_expected stale so the next call resyncs from the host
        # counter instead of silently running one draw behind
        self._rng_expected = state_now
        loss, new_params, new_buffers, self._opt_state, self._rng_ctr = \
            res[:5]
        for k, p in params_t:
            p._value = new_params[k]
        for k, b in buffers_t:
            b._value = new_buffers[k]
        self.optimizer._global_step += draws
        self._record_dispatch(draws, arr_args)
        return loss, res[5:]

    def _record_dispatch(self, draws, arr_args):
        """Obs telemetry for the training hot loop (docs/observability.md).

        Step time is the INTER-DISPATCH cadence, not the wall time around
        the jitted call: jax dispatch is async, so timing the call alone
        would measure enqueue latency, and forcing completion would add a
        device sync per step (the exact defect class PT-T007 polices).
        In steady state consecutive dispatches are spaced by true device
        step time (the runtime blocks on the previous step's donated
        buffers), so the cadence converges on it with zero added syncs.
        The first dispatch (compile) only arms the clock."""
        now = time.perf_counter()
        prev = self._prev_dispatch_t
        self._prev_dispatch_t = now
        if prev is None:
            return
        interval = now - prev
        self._obs_step_seconds.observe(interval / draws)
        tokens = _batch_tokens(arr_args)
        if tokens and interval > 0:
            self._obs_tokens.inc(tokens)
            self._obs_tokens_per_sec.set(tokens / interval)

    def __call__(self, *args):
        loss, extras = self._dispatch(self._step, 1, args)
        if self._guard is not None:
            # one host bool per step; hapi's fit loop already syncs on the
            # loss scalar each step, so this adds no extra round-trip there
            bad = bool(extras[-1])
            if bad:
                # piggybacks on the guard's existing host sync — the obs
                # counter itself is pure host arithmetic
                from .. import obs
                obs.counter("train_anomaly_skips_total",
                            "train steps flagged non-finite by the "
                            "anomaly guard").inc()
            self._guard.record(bad, where="train step")
            extras = extras[:-1]
        if self.return_outputs:
            return Tensor(loss), jax.tree_util.tree_map(Tensor, extras[0])
        return Tensor(loss)


class MultiStepTrainStep(TrainStep):
    """Run K full optimizer steps per dispatch: `lax.scan` over a stack of
    K batches inside ONE compiled program.

    The reference runs its hot loop outside Python too — `train_from_dataset`
    hands the whole dataset to a C++ trainer (framework/multi_trainer.cc:1,
    device worker loop in framework/device_worker.cc) so Python is out of
    the per-step path. The TPU-native equivalent is a device-side loop: the
    parameter/optimizer/RNG carry is threaded through `lax.scan`, so one
    host dispatch trains K steps and nothing round-trips through the host
    between them. On dispatch-bound workloads (small models, fast steps)
    this removes the per-step host floor entirely.

    Usage:
        step = paddle.jit.MultiStepTrainStep(model, loss_fn, opt, steps=K)
        losses = step(xs, ys)   # xs/ys stacked [K, ...]; returns [K] losses

    Semantics vs. K sequential TrainStep calls: identical parameters,
    buffers, optimizer state and RNG stream (parity-tested), EXCEPT the
    learning rate is sampled once per dispatch — an LRScheduler ticks per
    __call__, not per inner step (same granularity as the reference's
    dataset trainers, which fetch lr from the program once per pass).
    """

    def __init__(self, model: Layer, loss_fn: Callable, optimizer,
                 steps: int, donate: bool = True):
        if steps < 1:
            raise ValueError(f"steps must be >= 1, got {steps}")
        super().__init__(model, loss_fn, optimizer, donate=donate,
                         return_outputs=False)
        self.steps = int(steps)
        raw = self._raw_step

        def multi(params, frozen, buffers, opt_state, lr, key_root, rng_ctr,
                  *stacked):
            def body(carry, batch):
                p, b, o, c = carry
                loss, p, b, o, c = raw(p, frozen, b, o, lr, key_root, c,
                                       *batch)
                return (p, b, o, c), loss
            (params, buffers, opt_state, rng_ctr), losses = jax.lax.scan(
                body, (params, buffers, opt_state, rng_ctr), tuple(stacked))
            return losses, params, buffers, opt_state, rng_ctr

        # same donation set as the 1-step program (see TrainStep): the
        # scan carry consumes params/buffers/opt_state/rng_ctr in place
        donate_argnums = jaxplan.planned_donation(
            "train_step", default=(0, 2, 3, 6)) if donate else ()
        self._donate_argnums = donate_argnums
        self._multi = jax.jit(multi, donate_argnums=donate_argnums)

    def _validate_stacked(self, arr_args):
        for a in arr_args:
            if a.shape[:1] != (self.steps,):
                raise ValueError(
                    f"MultiStepTrainStep(steps={self.steps}) needs every "
                    f"batch arg stacked [steps, ...]; got shape {a.shape}")

    def __call__(self, *args):
        losses, _ = self._dispatch(self._multi, self.steps, args,
                                   validate=self._validate_stacked)
        return Tensor(losses)


class TracedLayer:
    """reference fluid/dygraph/jit.py:1047 TracedLayer — trace a dygraph
    layer with example inputs into a static artifact; `trace` returns
    (outputs, traced) and the traced object replays the captured program
    and saves an inference model. Here the captured program is the jitted
    StableHLO export (same substrate as jit.save)."""

    def __init__(self, layer, input_spec):
        self._layer = layer
        self._input_spec = input_spec

    @classmethod
    def trace(cls, layer, inputs):
        inputs = list(inputs)
        out = layer(*inputs)
        spec = [InputSpec(shape=list(i.shape), dtype=str(i.dtype))
                for i in inputs]
        return out, cls(layer, spec)

    def __call__(self, *args):
        return self._layer(*args)

    def save_inference_model(self, path, feed=None, fetch=None, **cfg):
        if feed is not None or fetch is not None:
            import warnings
            warnings.warn(
                "TracedLayer.save_inference_model: feed/fetch slicing of "
                "the traced program is not supported on the StableHLO "
                "artifact — the FULL traced signature is exported "
                "(reference jit.py:1047 slices the ProgramDesc by these "
                "indices). Wrap the layer to expose the wanted subset "
                "instead.", stacklevel=2)
        save(self._layer, path, input_spec=self._input_spec, **cfg)


def set_code_level(level=100):
    """reference jit/dy2static logging knob: print transformed code at/\
    below `level`. Stored on the dy2static module for its transformer."""
    from . import dy2static
    dy2static.CODE_LEVEL = int(level)


def set_verbosity(level=0, also_to_stdout=False):
    """reference jit logging verbosity (maps onto python logging for the
    paddle_tpu.jit logger)."""
    import logging
    logging.getLogger("paddle_tpu.jit").setLevel(
        logging.DEBUG if level > 0 else logging.WARNING)


from . import dy2static  # noqa: F401,E402
