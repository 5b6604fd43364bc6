"""Mixture-of-Experts with expert parallelism over the 'ep' mesh axis.

Reference capability: the snapshot's sparse scaling story is the
parameter-server distributed lookup table
(/root/reference/python/paddle/fluid/transpiler/distribute_transpiler.py:393
hierarchical sparse tables; fleet pslib). Later Paddle grew
paddle.incubate.distributed.models.moe on the same dispatch/combine design.
This module is the TPU-native expert-parallel layer covering that axis of
scaling for dense transformer training.

TPU-first design (GShard arxiv 2006.16668 / Switch arxiv 2101.03961):

- Experts are STACKED weights ``[E, H, F]`` sharded on dim 0 over the
  ``ep`` mesh axis — every expert matmul is one batched einsum on the MXU,
  no per-expert Python loop.
- Routing is dense one-hot dispatch/combine einsums with a STATIC capacity
  ``C = ceil(k*S/E * capacity_factor)`` — static shapes, no gather/scatter
  with dynamic sizes, which is exactly what XLA/TPU wants.
- Token movement between the data-parallel layout ``[S, H]`` (tokens
  sharded over dp) and the expert layout ``[E, C, H]`` (experts sharded
  over ep) is expressed as sharding constraints; GSPMD derives the
  all-to-all over ICI — nothing hand-written (the reference would
  hand-insert c_alltoall ops; see tests/test_moe.py HLO assertion).
- Router runs in fp32 (softmax stability under bf16 AMP).

Dropped tokens (capacity overflow) contribute zero from the expert path;
inside a transformer block the residual connection carries them through —
the standard Switch behaviour.
"""
from __future__ import annotations

import math

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..nn.layer.layers import Layer
from ..core.dispatch import dispatch
from ..core.tensor import Tensor
from ..parallel.api import mark_sharding
from ..parallel import mesh as _mesh
from ..ops import manipulation as M

__all__ = ["MoEMLP", "moe_dispatch_combine", "held_experts_mlp",
           "batched_form"]


def _ep_constraint(x):
    """Constrain an [E, ...] tensor to be expert-sharded over 'ep'.

    This is the boundary where GSPMD inserts the dp<->ep all-to-all: the
    dispatch einsum's output is token-sharded on S by its operands, and
    this constraint demands expert-sharded on E."""
    mesh = _mesh.get_global_mesh()
    if mesh is None or mesh.shape.get("ep", 1) <= 1:
        return x
    try:
        spec = ("ep",) + (None,) * (x.ndim - 1)
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(*spec)))
    except (ValueError, RuntimeError) as e:
        # e.g. inside a manual shard_map region where the mesh axis is
        # already bound. Dropping the constraint is functionally correct
        # but silently loses expert parallelism (no dp<->ep all-to-all,
        # replicated expert tensors) — say so once, loudly.
        global _WARNED_EP
        if not _WARNED_EP:
            _WARNED_EP = True
            import warnings
            warnings.warn(
                "MoE expert-sharding constraint could not be applied "
                f"({e!r}); continuing WITHOUT expert parallelism — the "
                "expert tensors stay replicated and no ep all-to-all is "
                "emitted", RuntimeWarning, stacklevel=3)
        return x


_WARNED_EP = False


def moe_dispatch_combine(gates, top_k: int, capacity: int):
    """Dense one-hot routing tensors from softmax gates.

    gates: [S, E] fp32. Returns (dispatch [S, E, C], combine [S, E, C],
    aux scalar). combine[s, e, c] is the gate weight with which token s's
    copy in expert e's slot c is folded back; dispatch is its 0/1 support.
    aux is the Switch load-balance loss E * sum_e(frac_tokens_e *
    mean_gate_e) — 1.0 at perfect balance.
    """
    S, E = gates.shape
    g = gates
    combine = jnp.zeros((S, E, capacity), jnp.float32)
    # running per-expert queue length, so slot-1 positions continue after
    # slot-0 assignments (GShard's cumsum chaining)
    offset = jnp.zeros((1, E), jnp.float32)
    denom = jnp.zeros((S,), jnp.float32)
    first_mask = None
    for _ in range(top_k):
        idx = jnp.argmax(g, axis=-1)                       # [S]
        m = jax.nn.one_hot(idx, E, dtype=jnp.float32)      # [S, E]
        if first_mask is None:
            first_mask = m
        gate_val = jnp.sum(gates * m, axis=-1)             # [S]
        denom = denom + gate_val
        pos = jnp.cumsum(m, axis=0) - 1.0 + offset         # [S, E]
        pos_tok = jnp.sum(pos * m, axis=-1)                # [S]
        keep = (pos_tok < capacity).astype(jnp.float32)    # [S]
        slot = jax.nn.one_hot(pos_tok.astype(jnp.int32), capacity,
                              dtype=jnp.float32)           # [S, C]
        ce = m * (gate_val * keep)[:, None]                # [S, E]
        combine = combine + ce[:, :, None] * slot[:, None, :]
        offset = offset + jnp.sum(m, axis=0, keepdims=True)
        g = g * (1.0 - m)                                  # mask chosen
    # normalise by the selected-gate mass (GShard top-2 normalisation;
    # for top_k=1 this is Switch's raw gate divided by itself only when
    # the full softmax mass sits on one expert — keep raw semantics there)
    if top_k > 1:
        combine = combine / jnp.maximum(denom, 1e-9)[:, None, None]
    disp = (combine > 0.0).astype(jnp.float32)
    # load-balance aux (Switch eq. 4): fraction routed (top-1) x mean gate
    frac = jnp.mean(first_mask, axis=0)                    # [E]
    mean_gate = jnp.mean(gates, axis=0)                    # [E]
    aux = E * jnp.sum(frac * mean_gate)
    return disp, combine, aux


def _moe_mlp(x, wr, wu, bu, wd, bd, top_k, capacity_factor, min_capacity):
    """Pure-jax MoE FFN: x [B, T, H] -> (out [B, T, H], aux scalar)."""
    B, T, H = x.shape
    S = B * T
    E = wr.shape[1]
    x2 = x.reshape(S, H)
    logits = x2.astype(jnp.float32) @ wr.astype(jnp.float32)
    gates = jax.nn.softmax(logits, axis=-1)
    capacity = max(int(min_capacity),
                   int(math.ceil(top_k * S / E * capacity_factor)))
    disp, combine, aux = moe_dispatch_combine(gates, top_k, capacity)
    ein = jnp.einsum("sec,sh->ech", disp.astype(x.dtype), x2)
    ein = _ep_constraint(ein)                 # <- dp->ep all-to-all here
    h = jnp.einsum("ech,ehf->ecf", ein, wu) + bu[:, None, :]
    h = jax.nn.gelu(h, approximate=True)
    out_e = jnp.einsum("ecf,efh->ech", h, wd) + bd[:, None, :]
    out_e = _ep_constraint(out_e)             # <- ep->dp all-to-all here
    out = jnp.einsum("sec,ech->sh", combine.astype(x.dtype), out_e)
    return out.reshape(B, T, H), aux.astype(jnp.float32)


#: the tile `jax.lax.ragged_dot`'s grouped kernel multiplies by on a v5e
#: where it divides both sides of an expert's weights (K and N): the kernel
#: then reads the weights of the experts a token reached at about 3/4 of the
#: HBM peak. Where it divides neither the kernel falls to tiles of 256 and
#: 128 and reads them at under a fifth (PERF.md section 6, PR 36)
GROUPED_TILE = 512
#: rows an expert above which a batched product stops being weight-bound on
#: a v5e (197 TFLOP/s / 819 GB/s = 240 rows at bfloat16): padding every
#: expert to more rows than this is no longer free
RIDGE_ROWS = 240


def load_capacity(times, pairs, num_experts):
    """Rows an expert at `times` the load uniform routing gives one
    (`pairs / num_experts`), in whole 16-row sublane tiles of bfloat16."""
    return 16 * math.ceil(times * pairs / (16 * num_experts))


def batched_form(pairs, num_experts, count, hidden, width):
    """Whether `held_experts_mlp` has a batched form at these shapes, and
    when it is taken: (C, least) or None. Static, from shapes alone.

    C, the rows an expert, is four times the load uniform routing gives
    one (`pairs / num_experts`) rounded up to the 16 rows of a bfloat16
    sublane tile; None where C passes the chip's ridge. `least` is how many
    of the `count` held experts a token must reach: a batched product
    reads every held expert's weights (at about 7/8 of the HBM peak), the
    grouped kernel only those reached, so batched wins from 7 in 8 reached
    where the kernel's tile divides `hidden` and `width`, and from 1 in 4
    where it does not (probed on the chip, PERF.md section 6, PR 36)."""
    capacity = load_capacity(4, pairs, num_experts)
    if capacity > RIDGE_ROWS:
        return None
    tiled = hidden % GROUPED_TILE == 0 and width % GROUPED_TILE == 0
    return capacity, math.ceil(count * (7 / 8 if tiled else 1 / 4))


def _choice(scores, bias, groups):
    """The scores the top-k is taken over: `scores + bias`, and with
    `groups` = (n_group, topk_group) -inf outside each token's topk_group
    best groups (a group scores the sum of its two best)."""
    choice = scores if bias is None else scores + bias.astype(jnp.float32)
    if groups is None:
        return choice
    n_group, keep = groups
    tokens, experts = choice.shape
    grouped = choice.reshape(tokens, n_group, experts // n_group)
    best = jax.lax.top_k(jax.lax.top_k(grouped, 2)[0].sum(-1), keep)[1]
    kept = jnp.zeros((tokens, n_group), bool).at[
        jnp.arange(tokens)[:, None], best].set(True)
    return jnp.where(jnp.repeat(kept, experts // n_group, axis=1), choice,
                     -jnp.inf)


def held_experts_mlp(x, router_w, w_gate, w_up, w_down, held, top_k,
                     scale, live=None, scoring="sigmoid", bias=None,
                     groups=None, limit=0.0):
    """The dropless expert layer of ONE chip of an expert-parallel
    deployment: x [T, h] -> (routed part [T, h] float32, counts int32 [8]).

    The router is whole: `router_w` [h, E] scores every token over all E
    experts in float32 (`scoring`, static: "sigmoid" of each logit, or
    "softmax" over all E), the `top_k` largest are chosen and their
    scores normalised to sum to `scale`. This chip holds the experts
    `first .. first + count - 1` (`held = (first, count)`, static;
    `w_gate`, `w_up` [count, h, f] and `w_down` [count, f, h] are theirs)
    and computes exactly the (token, held expert) pairs: the T * top_k
    pairs are sorted by held expert (pairs of experts held elsewhere and of
    rows that `live` [T] switches off sort behind the last group), the
    three products run group by group, and each token sums its weighted
    rows back. Three options of the router, none on by default (with all
    three off the traced program is the one without them):

    - `bias` [E] float32: a correction bias that CHOOSES and does not
      weigh (DeepSeek-V3's `noaux_tc`): the top-k are taken over
      `scores + bias`, their weights are still their scores;
    - `groups` = (n_group, topk_group), static: group-limited top-k. The E
      experts are n_group groups of E / n_group in order; a group scores
      the sum of its two best choice scores, the topk_group best groups are
      kept, and the top_k are taken within them;
    - `limit` (static float): a clamped SwiGLU where > 0, silu(min(gate,
      limit)) * clip(up, -limit, limit).

    No token is dropped at any imbalance; a token none of whose
    experts is held gets zeros. What the absent experts would add is
    another chip's, and nothing here stands in for them or for the
    exchange.

    The products have three forms, all in the program under one
    `jax.lax.switch`, chosen on the device from the loads of the batch:

    - batched: the sorted pairs gathered into [count, C, h] (expert e's
      rows are the sorted pairs start[e] .. start[e] + sizes[e] - 1; a slot
      past sizes[e] holds some other row, whose product is never read) and
      multiplied as batched matmuls over the held experts. Taken when the
      most loaded held expert has at most C rows and enough of them are
      reached for reading every one to pay. C and that number are static
      (`batched_form`): C is four times the uniform load in whole sublane
      tiles, 32 rows at 8 tokens an expert; where it would pass the chip's
      ridge padding stops being free and the form is not in the program (a
      prompt's block of 1,024 tokens over 64 experts).
    - compact: `jax.lax.ragged_dot` over the first R sorted pairs, R twice
      what uniform routing sends here rounded up to the 128 rows of an MXU
      pass (static, from shapes; the kernel's row tile is min(rows, 512)
      whatever the groups hold, so rows behind the last group are paid for
      as padding). Taken when the pairs routed here fit in R rows and the
      batched form was not.
    - full: `ragged_dot` over all T * top_k pairs (a token MAY send all its
      picks here): the dropless fallback at any imbalance. Where R >= T *
      top_k the compact form is this one.

    counts = (pairs routed here, held experts with at least one token, 1
    if the full buffer was multiplied, 1 if the batched form was, 1 (this
    call), 1 if the most loaded held expert got at most twice the uniform
    load in whole sublane tiles (`load_capacity`), 1 if at most four
    times, most tokens one held expert got): sums first, the maximum last
    (`models.spec.merge_counts`); what the tracing reads, at no extra
    fetch. The two fits are counted whether or not the batched form is in
    the program: they say what a capacity of that size WOULD hold."""
    first, count = held
    tokens, pairs = x.shape[0], x.shape[0] * top_k
    if scoring not in ("sigmoid", "softmax"):
        raise ValueError(f"scoring must be 'sigmoid' or 'softmax', got "
                         f"{scoring!r}")
    logits = jnp.dot(x.astype(jnp.float32), router_w, precision="highest")
    scores = jax.nn.sigmoid(logits) if scoring == "sigmoid" \
        else jax.nn.softmax(logits, axis=-1)
    if bias is None and groups is None:
        top_s, top_i = jax.lax.top_k(scores, top_k)
    else:
        top_i = jax.lax.top_k(_choice(scores, bias, groups), top_k)[1]
        top_s = jnp.take_along_axis(scores, top_i, axis=-1)
    weight = (top_s / jnp.sum(top_s, axis=-1, keepdims=True)
              * jnp.float32(scale)).reshape(-1)
    local = top_i.astype(jnp.int32) - first
    mine = (local >= 0) & (local < count)
    if live is not None:
        mine = mine & live[:, None]
    group = jnp.where(mine, local, count).reshape(-1)   # count = not here
    order = jnp.argsort(group, stable=True)
    sizes = jnp.zeros((count + 1,), jnp.int32).at[group].add(1)[:count]
    routed_here, max_load = jnp.sum(sizes), jnp.max(sizes)
    reached = jnp.sum(sizes > 0)
    place = jnp.argsort(order)            # of each (token, pick), sorted

    def combine(out, at):
        """Each (token, pick) takes row `at` of the products' output,
        weighted; a token sums its picks: [T, h]."""
        out = jnp.where(group[:, None] < count, out[at] * weight[:, None],
                        0.0)
        return out.reshape(tokens, top_k, -1).sum(axis=1)

    def gated(rows, product):
        """(silu(rows W_gate) * (rows W_up)) W_down, float32, with the
        experts' weights multiplied as `product` does it."""
        if limit > 0:
            act = jax.nn.silu(jnp.minimum(product(rows, w_gate), limit)) \
                * jnp.clip(product(rows, w_up), -limit, limit)
        else:
            act = jax.nn.silu(product(rows, w_gate)) * product(rows, w_up)
        return product(act.astype(x.dtype), w_down)

    def grouped(n):
        """The first n sorted pairs through `ragged_dot`."""
        out = gated(x[order[:n] // top_k], lambda a, w: jax.lax.ragged_dot(
            a, w, sizes, preferred_element_type=jnp.float32))
        return combine(out, jnp.minimum(place, n - 1))

    def batched(capacity):
        """Every held expert's first `capacity` sorted pairs through
        batched matmuls over the experts."""
        start = jnp.cumsum(sizes) - sizes
        slots = jnp.minimum(start[:, None] + jnp.arange(capacity),
                            pairs - 1)
        out = gated(x[order[slots] // top_k],           # [count, C, h]
                    lambda a, w: jnp.einsum(
                        "eck,ekn->ecn", a, w,
                        preferred_element_type=jnp.float32))
        here = jnp.minimum(group, count - 1)
        at = here * capacity + jnp.clip(place - start[here], 0,
                                        capacity - 1)
        return combine(out.reshape(count * capacity, -1), at)

    compact = 128 * math.ceil(2 * pairs * count
                              / (128 * router_w.shape[1]))
    full = routed_here > compact          # never where compact >= pairs
    if compact < pairs:
        forms, index = [lambda: grouped(compact), lambda: grouped(pairs)], \
            full.astype(jnp.int32)
    else:
        forms, index = [lambda: grouped(pairs)], 0
    form = batched_form(pairs, router_w.shape[1], count, x.shape[1],
                        w_gate.shape[2])
    fits = jnp.bool_(False)
    if form is not None:
        capacity, least = form
        fits = (max_load <= capacity) & (reached >= least)
        forms.insert(0, lambda: batched(capacity))
        index, full = jnp.where(fits, 0, index + 1), full & ~fits
    routed = forms[0]() if len(forms) == 1 else jax.lax.switch(index, forms)
    experts = router_w.shape[1]
    counts = jnp.stack([routed_here, reached, full, fits, 1,
                        max_load <= load_capacity(2, pairs, experts),
                        max_load <= load_capacity(4, pairs, experts),
                        max_load]).astype(jnp.int32)
    return routed, counts


class MoEMLP(Layer):
    """Expert-parallel FFN, drop-in for a dense transformer MLP.

    Stacked expert weights live sharded over 'ep'; with ep == 1 (or no
    mesh) the same einsums run locally, so the layer is debuggable on one
    chip. After forward, ``self.aux_loss`` holds the load-balance loss for
    the caller's objective (weight it, e.g. 0.01, and add to the task
    loss) — consume it in the SAME forward/loss computation (as
    models/gpt.py GPT.loss does). Under a jitted step the stored value is
    a tracer: to log it per step, return it from your loss_fn (e.g.
    ``TrainStep(..., return_outputs=True)``) rather than reading the
    attribute after the step, which raises TracerArrayConversionError.
    """

    def __init__(self, hidden_size: int, num_experts: int,
                 ffn_hidden_size: int = None, top_k: int = 2,
                 capacity_factor: float = 1.25, min_capacity: int = 4,
                 name=None):
        super().__init__()
        if num_experts < 1:
            raise ValueError("num_experts must be >= 1")
        ffn = ffn_hidden_size or 4 * hidden_size
        self.num_experts = num_experts
        self.top_k = min(top_k, num_experts)
        self.capacity_factor = float(capacity_factor)
        self.min_capacity = int(min_capacity)
        from ..nn import initializer as I
        # router replicated + fp32 (tiny; keeping it out of AMP lists)
        self.router = self.create_parameter(
            [hidden_size, num_experts],
            default_initializer=I.Normal(0.0, 0.02))
        mark_sharding(self.router)
        self.w_up = self.create_parameter(
            [num_experts, hidden_size, ffn],
            default_initializer=I.Normal(0.0, 0.02))
        mark_sharding(self.w_up, "ep", None, None)
        self.b_up = self.create_parameter([num_experts, ffn], is_bias=True)
        mark_sharding(self.b_up, "ep", None)
        self.w_down = self.create_parameter(
            [num_experts, ffn, hidden_size],
            default_initializer=I.Normal(0.0, 0.02))
        mark_sharding(self.w_down, "ep", None, None)
        self.b_down = self.create_parameter([num_experts, hidden_size],
                                            is_bias=True)
        mark_sharding(self.b_down, "ep", None)
        self.aux_loss = None

    def forward(self, x):
        squeeze = False
        if len(x.shape) == 2:                 # [T, H] -> [1, T, H]
            x = M.unsqueeze(x, 0)
            squeeze = True
        out, aux = dispatch(
            "moe_mlp", _moe_mlp,
            (x, self.router, self.w_up, self.b_up, self.w_down,
             self.b_down),
            {"top_k": self.top_k, "capacity_factor": self.capacity_factor,
             "min_capacity": self.min_capacity}, True)
        self.aux_loss = aux
        if squeeze:
            out = M.squeeze(out, 0)
        return out
