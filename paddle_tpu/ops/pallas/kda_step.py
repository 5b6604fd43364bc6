"""One decode step of Kimi Delta Attention (KDA) for a batch of rows, each
row's recurrent state updated IN PLACE in its slot.

A KDA layer keeps, per sequence, a state S [H, dk, dv] (float32) and the
history of its short convolution (the last K - 1 inputs of q, k and v). A
decode step of row n at slot s = slots[n], for each head h:

    c = silu(sum_{j<K} w[j] * u_{t-K+1+j})         (u: the row's x W_qkv)
    q, k, v = c's three parts; q, k L2-normalised, q * scale
    S~ = Diag(exp g) S;  d = beta (v - S~^T k);  S' = S~ + k d^T
    o = S'^T q = S~^T q + d (k . q)

with g [dk] one decay a key channel (not one a head, as Gated DeltaNet's),
beta one value a head. The kernel is one grid step a (head group, row):
it reads the row's state tile and history from the slot arrays, which stay
where they are (`input_output_aliases`), computes the step in VMEM and
writes both back, so a live row's state crosses HBM once each way and
nothing gathers or scatters a copy of the state. A row not `live` is not
written: its step maps to the block of the live row before it (rows are
the inner axis of the grid), which the pipeline then neither fetches nor
writes again. Rows before the first live one map to the first live row's
block and copy it through unchanged, and so does every row when none is
live. A `fresh` row (position 0) starts from a zero state and history
whatever its slot held.

`kda_step_reference` is the same step composed of XLA operations, a gather
of the rows' entries and a scatter back: what tests/test_ling_flash.py
holds the kernel to (under `interpret=True` on the CPU).

Layouts (per row and head, so that a head's tile is one block):
u [N, H, 3 dk] in the served dtype, (q, k, v) side by side; g [N, H, dk]
and beta [N, H, dv] (one value broadcast on the lanes) float32; the conv
weight [K, H, 3 dk]; the slot arrays states [slots, H, dk, dv] float32 and
history [slots, H, (K - 1) 3 dk] in the served dtype, oldest input first.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
#: heads a grid step: a block of the state is HEAD_BLOCK x 64 KiB at
#: dk = dv = 128, four of them (in and out, double-buffered) 4 MiB of the
#: v5e's 16 MiB of scoped VMEM
HEAD_BLOCK = 16
EPS = 1e-6

#: what a grid step does with its row: 0 a live row, 1 a live row at
#: position 0 (zero state), 2 copy the block through (no live row before
#: it), 3 nothing (the block is the live row's before it)
LIVE, FRESH, COPY, SKIP = 0, 1, 2, 3


def supported(heads: int, dk: int, dv: int) -> bool:
    """Whether the kernel compiles for the chip at these widths (whole lane
    rows, whole sublane tiles of a head group); elsewhere it runs under
    `interpret=True`, which any width and backend take."""
    return jax.default_backend() == "tpu" and dk % 128 == 0 \
        and dv % 128 == 0 and heads % min(heads, HEAD_BLOCK) == 0 \
        and min(heads, HEAD_BLOCK) % 16 == 0


def row_plan(live, fresh, slots):
    """(mode [N], block slot [N]) of each grid row, int32 (see the module
    docstring): a live row's own slot; a dead row the slot of the last
    live row before it, or of the first live row where none is before."""
    n = live.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    last = jax.lax.cummax(jnp.where(live, idx, -1), axis=0)
    first = jnp.argmax(live).astype(jnp.int32)
    source = jnp.where(last >= 0, last, first)
    mode = jnp.where(live, jnp.where(fresh, FRESH, LIVE),
                     jnp.where(last >= 0, SKIP, COPY))
    return mode.astype(jnp.int32), slots.astype(jnp.int32)[source]


def conv_heads(window, w, dk, scale, dtype):
    """The short convolution and the heads' q, k, v (float32): `window`
    the K inputs [.., 3 dk] oldest first, w [K, .., 3 dk]; silu, rounded
    to `dtype` as the served activations are, q and k L2-normalised, q
    times `scale`. The decode kernel's and the prefill's one form."""
    c = sum(w[j] * window[j] for j in range(len(window)))
    c = (c * jax.nn.sigmoid(c)).astype(dtype).astype(F32)
    q, k, v = c[..., :dk], c[..., dk:2 * dk], c[..., 2 * dk:]
    q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + F32(EPS)) \
        * F32(scale)
    k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + F32(EPS))
    return q, k, v


def _kernel(mode_ref, _block_ref, u_ref, g_ref, b_ref, w_ref, s_ref, h_ref,
            o_ref, s_out, h_out, *, heads, dk, taps, scale):
    mode = mode_ref[pl.program_id(1)]
    width = 3 * dk

    @pl.when(mode == COPY)
    def _():
        s_out[...] = s_ref[...]
        h_out[...] = h_ref[...]

    @pl.when(mode >= COPY)
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    @pl.when(mode < COPY)
    def _():
        fresh = mode == FRESH
        for h in range(heads):
            raw = u_ref[0, h:h + 1, :]                       # (1, 3 dk)
            hist = jnp.where(fresh, F32(0), h_ref[0, h:h + 1, :].astype(F32))
            window = [hist[:, j * width:(j + 1) * width]
                      for j in range(taps - 1)] + [raw.astype(F32)]
            w = [w_ref[j, h:h + 1, :].astype(F32) for j in range(taps)]
            q, k, v = conv_heads(window, w, dk, scale, raw.dtype)
            eg = jnp.exp(g_ref[0, h:h + 1, :])               # (1, dk)
            # the three vectors as columns (key channels on the sublanes):
            # one transpose of an (8, dk) tile
            rows = jnp.concatenate(
                [eg, k, q, jnp.zeros((5, dk), F32)], axis=0)
            cols = rows.T                                    # (dk, 8)
            decayed = cols[:, 0:1] * jnp.where(fresh, F32(0), s_ref[0, h])
            r = jnp.sum(cols[:, 1:2] * decayed, axis=0, keepdims=True)
            sq = jnp.sum(cols[:, 2:3] * decayed, axis=0, keepdims=True)
            d = b_ref[0, h:h + 1, :] * (v - r)               # (1, dv)
            o_ref[0, h:h + 1, :] = sq + d * jnp.sum(k * q, -1, keepdims=True)
            s_out[0, h] = decayed + cols[:, 1:2] * d
            h_out[0, h:h + 1, :(taps - 2) * width] = \
                hist[:, width:].astype(h_out.dtype)
            h_out[0, h:h + 1, (taps - 2) * width:] = raw.astype(h_out.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def kda_step(u, g, beta, conv_w, states, history, slots, live, fresh,
             scale, interpret=False):
    """One KDA decode step for N rows, in place: u [N, H, 3 dk], g
    [N, H, dk], beta [N, H] float32, conv_w [K, H, 3 dk], states
    [slots, H, dk, dv] float32 and history [slots, H, (K - 1) 3 dk]
    (aliased to the results: in place inside a program that owns them, as
    the decode chunk's carry does), slots [N] int32 (a row's slot),
    live, fresh [N] bool -> (o [N, H, dv] float32, zeros where not live;
    states; history). Traced under the name `kda_step`."""
    n, heads, width = u.shape
    dk, dv = g.shape[-1], states.shape[-1]
    taps = conv_w.shape[0]
    hb = min(heads, HEAD_BLOCK)
    mode, block = row_plan(live, fresh, slots)
    beta = jnp.broadcast_to(beta.astype(F32)[..., None], (n, heads, dv))
    hist_w = history.shape[-1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(heads // hb, n),
        in_specs=[
            pl.BlockSpec((1, hb, width), lambda c, r, m, s: (r, c, 0)),
            pl.BlockSpec((1, hb, dk), lambda c, r, m, s: (r, c, 0)),
            pl.BlockSpec((1, hb, dv), lambda c, r, m, s: (r, c, 0)),
            pl.BlockSpec((taps, hb, width), lambda c, r, m, s: (0, c, 0)),
            pl.BlockSpec((1, hb, dk, dv), lambda c, r, m, s: (s[r], c, 0, 0)),
            pl.BlockSpec((1, hb, hist_w), lambda c, r, m, s: (s[r], c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, hb, dv), lambda c, r, m, s: (r, c, 0)),
            pl.BlockSpec((1, hb, dk, dv), lambda c, r, m, s: (s[r], c, 0, 0)),
            pl.BlockSpec((1, hb, hist_w), lambda c, r, m, s: (s[r], c, 0)),
        ])
    kernel = functools.partial(_kernel, heads=hb, dk=dk, taps=taps,
                               scale=float(scale))
    with jax.enable_x64(False):
        return pl.pallas_call(
            kernel, grid_spec=grid_spec, name="kda_step",
            out_shape=[jax.ShapeDtypeStruct((n, heads, dv), F32),
                       jax.ShapeDtypeStruct(states.shape, states.dtype),
                       jax.ShapeDtypeStruct(history.shape, history.dtype)],
            # operands: mode, block, u, g, beta, conv_w, states, history
            input_output_aliases={6: 1, 7: 2},
            # a dead row relies on the step before it: rows in order
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary")),
            interpret=interpret,
        )(mode, block, u, g.astype(F32), beta, conv_w, states, history)


def kda_step_reference(u, g, beta, conv_w, states, history, slots, live,
                       fresh, scale):
    """The same step composed of XLA operations: the rows' entries gathered
    by slot, stepped, scattered back (rows not live dropped)."""
    n, heads, width = u.shape
    dk, taps = g.shape[-1], conv_w.shape[0]
    hist = jnp.where(fresh[:, None, None], 0, history[slots]).astype(F32)
    window = [hist[..., j * width:(j + 1) * width] for j in range(taps - 1)] \
        + [u.astype(F32)]
    q, k, v = conv_heads(window, conv_w.astype(F32), dk, scale, u.dtype)
    state = jnp.where(fresh[:, None, None, None], 0, states[slots])
    decayed = jnp.exp(g.astype(F32))[..., None] * state
    r = jnp.sum(decayed * k[..., None], axis=-2)
    sq = jnp.sum(decayed * q[..., None], axis=-2)
    d = beta.astype(F32)[..., None] * (v - r)
    o = sq + d * jnp.sum(k * q, -1, keepdims=True)
    new = decayed + k[..., None] * d[..., None, :]
    at = jnp.where(live, slots, states.shape[0])
    new_hist = jnp.concatenate([hist[..., width:], u.astype(F32)], -1)
    return (jnp.where(live[:, None, None], o, 0),
            states.at[at].set(new, mode="drop"),
            history.at[at].set(new_hist.astype(history.dtype), mode="drop"))
