"""Qwen3-Next: a hybrid decoder family, three Gated DeltaNet (linear
attention) layers to one gated softmax-attention layer, each followed by a
shared + routed expert block; in pure `jax.numpy` like models/pangu_moe.py,
plus the `nn.Layer` that holds its parameters and the `ModelSpec` that
serves it through `LLMEngine`.

Source of the shapes: huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct
`config.json` (model_type `qwen3_next`). N is a ZERO-CENTRED RMSNorm,
`x / rms(x) * (1 + w)` (eps `rms_norm_eps`, w = 0 at init). Layer i is full
attention when (i + 1) % `full_attention_interval` == 0, else DeltaNet:

    x <- x + Mixer(N1(x));  x <- x + Moe(N2(x));  logits = Nf(x) W_head

Gated DeltaNet for the token at position t (Hk key heads and Hv value heads
of 128; key head j serves the value heads 2j, 2j + 1):

    [q|k|v|z] = x W_qkvz;  [b|a] = x W_ba
    u = [q|k|v];  c_t = silu(sum_{j<4} w[:, j] * u_{t-3+j})   (zero history)
    q, k <- c's parts, L2-normalised per head, q * 1/sqrt(128)
    beta = sigmoid(b);  g = -exp(A_log) * softplus(a + dt_bias)   (float32)
    S~ = exp(g) S_{t-1};  d = beta (v - S~^T k);  S_t = S~ + k d^T
    o = S_t^T q;  out = [RMSNorm_128(o) * w_n * silu(z)] W_out

with S_0 = 0 in R^{128 x 128} per value head. The cache of such a layer is
one fixed-size entry a SEQUENCE, whatever its length: S (float32) and the
last three columns of u (`state_shapes`; the second stored as one row of
3 x channels, oldest first). Prefill evaluates the recurrence chunk-wise
(`gdn_chunked`, the chunked delta rule of arXiv:2412.06464: inside a chunk
of C positions the d's solve one unit-lower-triangular system, between
chunks the state moves by three products) and hands back the final entry;
decode applies one step (`gdn_step`). tests/test_qwen3_next.py holds both
equal to the recurrence as written.

Gated attention (H query heads, G key-value heads of D = 256, G < H):

    [q|gate]_h = x W_q (D + D a head);  k, v = x W_k, x W_v
    q, k <- N_D(q), N_D(k);  RoPE on the first `partial_rotary_factor` * D
    dimensions (i paired with i + half), causal softmax at 1/sqrt(D), KV
    head j serving the query heads 8j .. 8j + 7
    out = [attn * sigmoid(gate)] W_o

The cache of such a layer is a row a position: k and v of G x D ("heads").

Expert block: p = softmax(x W_r) in float32 over all `num_experts`; the
`num_experts_per_tok` largest, renormalised to sum to 1; expert e is
(silu(x W_g) * (x W_u)) W_d; plus the shared expert times
sigmoid(w_sg . x). A chip of an expert-parallel deployment holds the
experts `held = (first, count)` and computes their part alone
(`distributed.moe.held_experts_mlp`, scoring "softmax").

Dtypes: activations take the dtype of `embed.weight`; with bfloat16 weights
every matmul accumulates in float32 and rounds its result to bfloat16. The
router, `A_log` and `dt_bias` are float32; the DeltaNet recurrence (gates,
normalised q and k, the state) is float32 at "highest" precision; logits
are float32.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.scipy.linalg import solve_triangular

from .. import nn
from ..core.dispatch import dispatch
from ..distributed.moe import held_experts_mlp
from ..inference.serving.paged_cache import (SeqState, gather_rows,
                                             write_rows)
from ..nn import initializer as I
from .pangu_moe import COUNTERS, _mm, gated_mlp, rms_norm, rope
from .spec import ModelSpec, merge_counts

__all__ = ["Qwen3NextConfig", "Qwen3Next", "param_shapes", "forward",
           "prefill", "serving_spec", "gdn_recurrence", "gdn_chunked",
           "gdn_step", "init_value"]

F32 = jnp.float32
#: queries a block of the prefill's attention scores at a time
QUERY_BLOCK = 512
#: tokens a block of a prefill's expert layer at a time
MOE_TOKEN_BLOCK = 1024
#: positions a chunk of the prefill's DeltaNet scan
GDN_CHUNK = 64


@dataclass(frozen=True)
class Qwen3NextConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    full_attention_interval: int = 4
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 10000000.0
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    num_experts: int = 512
    num_experts_per_tok: int = 10
    rms_norm_eps: float = 1e-6
    max_seq_len: int = 2048
    #: (first, count) of the routed experts this chip holds; None = all
    held_experts: Tuple[int, int] = None
    dtype: str = "float32"

    @property
    def held(self) -> Tuple[int, int]:
        return self.held_experts or (0, self.num_experts)

    @property
    def key_dim(self) -> int:
        return self.linear_num_key_heads * self.linear_key_head_dim

    @property
    def value_dim(self) -> int:
        return self.linear_num_value_heads * self.linear_value_head_dim

    @property
    def conv_dim(self) -> int:
        return 2 * self.key_dim + self.value_dim

    def is_full_attention(self, i: int) -> bool:
        return (i + 1) % self.full_attention_interval == 0

    @property
    def state_shapes(self):
        """A DeltaNet layer's entry a sequence: S per value head (float32)
        and the conv window's history, oldest column first."""
        return (((self.linear_num_value_heads, self.linear_key_head_dim,
                  self.linear_value_head_dim), "float32"),
                (((self.linear_conv_kernel_dim - 1) * self.conv_dim,),
                 self.dtype))


def param_shapes(cfg: Qwen3NextConfig) -> dict:
    """{name: (shape, dtype name)} of every parameter, flat. `W_qkvz` and
    `W_ba` are laid out flat, [q|k|v|z] and [b|a] (published checkpoints
    interleave them by key-head group)."""
    h, dt, f = cfg.hidden_size, cfg.dtype, cfg.moe_intermediate_size
    H, G, D = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    Hv, held = cfg.linear_num_value_heads, cfg.held[1]
    out = {"embed.weight": ((cfg.vocab_size, h), dt),
           "norm_f.weight": ((h,), dt),
           "lm_head.weight": ((h, cfg.vocab_size), dt)}
    for i in range(cfg.num_hidden_layers):
        pre = f"layers.{i}."
        out[pre + "norm1.weight"] = ((h,), dt)
        out[pre + "norm2.weight"] = ((h,), dt)
        if cfg.is_full_attention(i):
            out[pre + "attn.q.weight"] = ((h, H * 2 * D), dt)
            out[pre + "attn.k.weight"] = ((h, G * D), dt)
            out[pre + "attn.v.weight"] = ((h, G * D), dt)
            out[pre + "attn.q_norm.weight"] = ((D,), dt)
            out[pre + "attn.k_norm.weight"] = ((D,), dt)
            out[pre + "attn.o.weight"] = ((H * D, h), dt)
        else:
            out[pre + "gdn.qkvz.weight"] = (
                (h, cfg.conv_dim + cfg.value_dim), dt)
            out[pre + "gdn.ba.weight"] = ((h, 2 * Hv), dt)
            out[pre + "gdn.conv.weight"] = (
                (cfg.conv_dim, cfg.linear_conv_kernel_dim), dt)
            out[pre + "gdn.A_log"] = ((Hv,), "float32")
            out[pre + "gdn.dt_bias"] = ((Hv,), "float32")
            out[pre + "gdn.norm.weight"] = (
                (cfg.linear_value_head_dim,), dt)
            out[pre + "gdn.out.weight"] = ((cfg.value_dim, h), dt)
        out[pre + "moe.router.weight"] = ((h, cfg.num_experts), "float32")
        out[pre + "moe.experts.gate.weight"] = ((held, h, f), dt)
        out[pre + "moe.experts.up.weight"] = ((held, h, f), dt)
        out[pre + "moe.experts.down.weight"] = ((held, f, h), dt)
        sf = cfg.shared_expert_intermediate_size
        out[pre + "moe.shared.gate.weight"] = ((h, sf), dt)
        out[pre + "moe.shared.up.weight"] = ((h, sf), dt)
        out[pre + "moe.shared.down.weight"] = ((sf, h), dt)
        out[pre + "moe.shared_gate.weight"] = ((h, 1), dt)
    return out


def init_value(name: str):
    """How a parameter that is no matrix starts: ("constant", c) or
    ("uniform", lo, hi); None for a matrix (N(0, initializer_range)).
    Zero-centred norms at 0, the DeltaNet's output norm (a plain weight)
    and `dt_bias` at 1, `A_log` uniform in [log 1/64, 0]: a decay a token
    between exp(-1.3) and exp(-0.02), so that heads of short and of long
    memory are both there (the published initializer, A uniform in
    (0, 16), leaves 15 heads in 16 with no memory past one token)."""
    if name.endswith("gdn.A_log"):
        return ("uniform", -math.log(64.0), 0.0)
    if name.endswith(("gdn.dt_bias", "gdn.norm.weight")):
        return ("constant", 1.0)
    if "norm" in name.rsplit(".", 2)[-2]:
        return ("constant", 0.0)
    return None


# ------------------------------------------------------------ the layers
def rms_norm0(x, w, eps):
    """Zero-centred RMSNorm: x / rms(x) * (1 + w), in float32."""
    return rms_norm(x, F32(1) + w.astype(F32), eps)


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True)
                             + F32(1e-6))


# ----------------------------------------------------- Gated DeltaNet
def gdn_inputs(p, pre, h, cfg):
    """h [..., hidden] -> (u [..., conv_dim] the conv's input, z [...,
    value_dim], beta, g [..., Hv] float32)."""
    mixed = _mm(h, p[pre + "gdn.qkvz.weight"])
    ba = jnp.dot(h, p[pre + "gdn.ba.weight"],
                 preferred_element_type=F32)
    Hv = cfg.linear_num_value_heads
    beta = jax.nn.sigmoid(ba[..., :Hv])
    g = -jnp.exp(p[pre + "gdn.A_log"]) \
        * jax.nn.softplus(ba[..., Hv:] + p[pre + "gdn.dt_bias"])
    return mixed[..., :cfg.conv_dim], mixed[..., cfg.conv_dim:], beta, g


def gdn_conv(p, pre, columns, dtype):
    """silu of the depthwise causal convolution: `columns` the K arrays
    [..., conv_dim] u_{t-K+1} .. u_t -> [..., conv_dim]."""
    w = p[pre + "gdn.conv.weight"].astype(F32)          # [conv_dim, K]
    return jax.nn.silu(sum(col.astype(F32) * w[:, j]
                           for j, col in enumerate(columns))).astype(dtype)


def gdn_heads(c, cfg):
    """The conv's output [..., conv_dim] as float32 heads: q, k
    [..., Hv, dk] (normalised, q scaled, each key head repeated for its
    value heads) and v [..., Hv, dv]."""
    Hk, Hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, kd = cfg.linear_key_head_dim, cfg.key_dim
    lead = c.shape[:-1]
    c = c.astype(F32)
    q = _l2norm(c[..., :kd].reshape(lead + (Hk, dk))) \
        * F32(1.0 / math.sqrt(dk))
    k = _l2norm(c[..., kd:2 * kd].reshape(lead + (Hk, dk)))
    v = c[..., 2 * kd:].reshape(lead + (Hv, cfg.linear_value_head_dim))
    rep = Hv // Hk
    return (jnp.repeat(q, rep, axis=-2), jnp.repeat(k, rep, axis=-2), v)


def gdn_output(p, pre, o, z, cfg, dtype):
    """[RMSNorm_dv(o) * w_n * silu(z)] W_out: o [..., Hv, dv] float32,
    z [..., value_dim]."""
    y = rms_norm(o, p[pre + "gdn.norm.weight"], cfg.rms_norm_eps)
    y = y * jax.nn.silu(z.astype(F32).reshape(y.shape))
    return _mm(y.reshape(y.shape[:-2] + (-1,)).astype(dtype),
               p[pre + "gdn.out.weight"])


def gdn_recurrence(q, k, v, g, beta, state):
    """The delta rule as written, one position after another: q, k
    [T, H, dk], v [T, H, dv], g, beta [T, H], state [H, dk, dv] (float32)
    -> (o [T, H, dv], final state). What `gdn_chunked` and `gdn_step` are
    held to."""
    def step(S, t):
        q_t, k_t, v_t, g_t, b_t = t
        S = jnp.exp(g_t)[:, None, None] * S
        d = b_t[:, None] * (v_t - jnp.sum(S * k_t[:, :, None], axis=1))
        S = S + k_t[:, :, None] * d[:, None, :]
        return S, jnp.sum(S * q_t[:, :, None], axis=1)

    state, o = jax.lax.scan(step, state, (q, k, v, g, beta))
    return o, state


def gdn_chunked(q, k, v, g, beta, state, chunk):
    """The same recurrence over chunks of `chunk` positions (any T: the
    tail is padded with positions that neither decay nor write). With
    gamma the running sum of g inside a chunk and S the state it starts
    from, the d's of a chunk solve the unit-lower-triangular system

        (I + L) D = beta V - (beta exp(gamma) K) S,
        L[i, j] = beta_i exp(gamma_i - gamma_j) (k_i . k_j),  j < i,

    its outputs are exp(gamma) Q S + (tril(Q K^T) * decay) D, and the state
    leaves as exp(gamma_C) S + (exp(gamma_C - gamma) K)^T D: T / chunk
    sequential steps of matrix products in place of T steps. Shapes as
    `gdn_recurrence`. Every exponent is <= 0."""
    T, H, dk = q.shape
    dv, n = v.shape[-1], -(-T // chunk)
    pad = n * chunk - T

    def chunks(x):                  # [T, H, ...] -> [n, H, chunk, ...]
        x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
        x = x.reshape((n, chunk) + x.shape[1:])
        return jnp.moveaxis(x, 1, 2)

    q, k, v, g, beta = (chunks(x) for x in (q, k, v, g, beta))
    with jax.default_matmul_precision("highest"):
        gamma = jnp.cumsum(g, axis=-1)                    # [n, H, C]
        diff = gamma[..., :, None] - gamma[..., None, :]
        lower = jnp.tril(jnp.ones((chunk, chunk), bool))
        decay = jnp.where(lower, jnp.exp(jnp.where(lower, diff, 0.0)), 0.0)
        kb = k * beta[..., None]
        system = jnp.eye(chunk, dtype=F32) + jnp.where(
            jnp.tril(lower, -1), jnp.einsum("nhid,nhjd->nhij", kb, k)
            * decay, 0.0)
        rhs = jnp.concatenate(
            [v * beta[..., None], kb * jnp.exp(gamma)[..., None]], axis=-1)
        solved = solve_triangular(
            system, rhs, lower=True, unit_diagonal=True)
        w, y = solved[..., :dv], solved[..., dv:]
        qk = jnp.einsum("nhid,nhjd->nhij", q, k) * decay
        q_in = q * jnp.exp(gamma)[..., None]
        k_out = k * jnp.exp(gamma[..., -1:] - gamma)[..., None]
        leave = jnp.exp(gamma[..., -1])                   # [n, H]

        def step(S, c):
            w_c, y_c, qk_c, q_c, k_c, leave_c = c
            d = w_c - jnp.einsum("hik,hkv->hiv", y_c, S)
            o = jnp.einsum("hik,hkv->hiv", q_c, S) \
                + jnp.einsum("hij,hjv->hiv", qk_c, d)
            S = leave_c[:, None, None] * S \
                + jnp.einsum("hik,hiv->hkv", k_c, d)
            return S, o

        state, o = jax.lax.scan(step, state,
                                (w, y, qk, q_in, k_out, leave))
    o = jnp.moveaxis(o, 1, 2).reshape(n * chunk, H, dv)
    return o[:T], state


def gdn_step(q, k, v, g, beta, state):
    """One position for N rows: q, k [N, H, dk], v [N, H, dv], g, beta
    [N, H], state [N, H, dk, dv] -> (o [N, H, dv], new state). Two passes
    over the state, both elementwise: S~^T k and S~^T q are read together,
    o = S~^T q + d (k . q) needs no third."""
    decayed = jnp.exp(g)[..., None, None] * state
    r = jnp.sum(decayed * k[..., :, None], axis=-2)
    sq = jnp.sum(decayed * q[..., :, None], axis=-2)
    d = beta[..., None] * (v - r)
    o = sq + d * jnp.sum(k * q, axis=-1, keepdims=True)
    return o, decayed + k[..., :, None] * d[..., None, :]


def _gdn_dense(p, pre, h, cfg):
    """A DeltaNet layer over whole sequences h [B, T, hidden] from zero
    state: (output [B, T, hidden], SeqState of the final S [B, Hv, dk, dv]
    and conv history [B, (K - 1) * conv_dim])."""
    K, B, T = cfg.linear_conv_kernel_dim, h.shape[0], h.shape[1]
    u, z, beta, g = gdn_inputs(p, pre, h, cfg)
    padded = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))   # zero history
    q, k, v = gdn_heads(gdn_conv(
        p, pre, [padded[:, j:j + T] for j in range(K)], h.dtype), cfg)
    zero = jnp.zeros(q.shape[2:] + (v.shape[-1],), F32)
    o, state = jax.vmap(
        lambda *a: gdn_chunked(*a, zero, GDN_CHUNK))(q, k, v, g, beta)
    history = padded[:, T:].reshape(B, -1)
    return gdn_output(p, pre, o, z, cfg, h.dtype), SeqState(state, history)


# --------------------------------------------------- gated attention
def _rotary_width(cfg):
    return int(cfg.head_dim * cfg.partial_rotary_factor)


def _partial_rope(x, positions, cfg):
    r = _rotary_width(cfg)
    return jnp.concatenate(
        [rope(x[..., :r], positions, cfg.rope_theta), x[..., r:]], axis=-1)


def attn_inputs(p, pre, h, positions, cfg):
    """h [..., hidden] at positions [...] -> (q [..., H, D] normed and
    rotated, gate [..., H, D], k [..., G, D] normed and rotated, v
    [..., G, D])."""
    H, G, D = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    lead, eps = h.shape[:-1], cfg.rms_norm_eps
    qg = _mm(h, p[pre + "attn.q.weight"]).reshape(lead + (H, 2 * D))
    k = _mm(h, p[pre + "attn.k.weight"]).reshape(lead + (G, D))
    v = _mm(h, p[pre + "attn.v.weight"]).reshape(lead + (G, D))
    q = rms_norm0(qg[..., :D], p[pre + "attn.q_norm.weight"], eps)
    k = rms_norm0(k, p[pre + "attn.k_norm.weight"], eps)
    at = positions[..., None]
    return (_partial_rope(q, at, cfg), qg[..., D:],
            _partial_rope(k, at, cfg), v)


def _grouped_attention(q, k, v, attend):
    """q [B, Tq, H, D] against k, v [B, S, G, D], attend [B or 1, Tq, S]
    True = attend -> [B, Tq, H, D]; KV head j serves the query heads
    j * H/G .. (j + 1) * H/G - 1. Scores and softmax in float32."""
    B, Tq, H, D = q.shape
    G = k.shape[2]
    scores = jnp.einsum("btgqd,bsgd->bgqts", q.reshape(B, Tq, G, H // G, D),
                        k, preferred_element_type=F32) \
        * F32(1.0 / math.sqrt(D))
    scores = jnp.where(attend[:, None, None], scores, F32(-1e30))
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bgqts,bsgd->btgqd", probs, v,
                     preferred_element_type=F32).astype(v.dtype)
    return out.reshape(B, Tq, H, D)


def _attn_dense(p, pre, h, positions, cfg):
    """A gated-attention layer over whole sequences h [B, T, hidden]:
    (output [B, T, hidden], (k, v) [B, G, T, D] the dense cache rows).
    Causal, `QUERY_BLOCK` queries at a time."""
    q, gate, k, v = attn_inputs(p, pre, h, positions, cfg)
    B, T = h.shape[:2]
    block = min(T, QUERY_BLOCK)
    n = -(-T // block)
    keys = jnp.arange(T)[None, :]

    def attend_block(args):
        """One block of queries against every key, masked: the [H, T, T]
        scores are never whole, and one block's program serves them all
        (a loop unrolled over blocks compiled for 13 s a block)."""
        q_j, start = args
        return _grouped_attention(
            q_j, k, v, ((start + jnp.arange(block))[:, None] >= keys)[None])

    blocks = jnp.pad(q, ((0, 0), (0, n * block - T), (0, 0), (0, 0))) \
        .reshape((B, n, block) + q.shape[2:]).swapaxes(0, 1)
    att = jax.lax.map(attend_block, (blocks, jnp.arange(n) * block)) \
        .swapaxes(0, 1).reshape((B, n * block) + q.shape[2:])[:, :T]
    out = _attn_output(p, pre, att, gate)
    return out, (k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3))


def _attn_output(p, pre, att, gate):
    gated = (att.astype(F32) * jax.nn.sigmoid(gate.astype(F32))) \
        .astype(att.dtype)
    return _mm(gated.reshape(gated.shape[:-2] + (-1,)),
               p[pre + "attn.o.weight"])


# ------------------------------------------------------- expert block
def _moe_tokens(p, pre, flat, cfg, live):
    """flat [T, hidden] -> (the held experts' part plus the gated shared
    expert [T, hidden], counts)."""
    routed, counts = held_experts_mlp(
        flat, p[pre + "router.weight"], p[pre + "experts.gate.weight"],
        p[pre + "experts.up.weight"], p[pre + "experts.down.weight"],
        cfg.held, cfg.num_experts_per_tok, 1.0, live, scoring="softmax")
    shared = gated_mlp(flat, p[pre + "shared.gate.weight"],
                       p[pre + "shared.up.weight"],
                       p[pre + "shared.down.weight"]).astype(F32) \
        * jax.nn.sigmoid(jnp.dot(flat, p[pre + "shared_gate.weight"],
                                 preferred_element_type=F32))
    return (routed + shared).astype(flat.dtype), counts


def map_token_blocks(expert_tokens, h, live=None):
    """`expert_tokens(flat [T, hidden], live [T] or None) -> (out [T,
    hidden], counts)`, an expert block, over h [..., hidden]. A long prompt
    goes through `MOE_TOKEN_BLOCK` tokens at a time: the grouped matmul
    compiles for minutes at tens of thousands of rows, and so every prompt
    length shares one block's program (the tail's padding is switched off
    in the routing and counts nothing)."""
    flat = h.reshape(-1, h.shape[-1])
    T = flat.shape[0]
    if T <= MOE_TOKEN_BLOCK:
        out, counts = expert_tokens(flat, live)
        return out.reshape(h.shape), counts
    n = -(-T // MOE_TOKEN_BLOCK)
    pad = n * MOE_TOKEN_BLOCK - T
    on = jnp.ones((T,), bool) if live is None else live
    out, counts = jax.lax.map(
        lambda block: expert_tokens(block[0], block[1]),
        (jnp.pad(flat, ((0, pad), (0, 0))).reshape(n, MOE_TOKEN_BLOCK, -1),
         jnp.pad(on, (0, pad)).reshape(n, MOE_TOKEN_BLOCK)))
    counts = jnp.concatenate([counts[:, :-1].sum(0), counts[:, -1:].max(0)])
    return out.reshape(n * MOE_TOKEN_BLOCK, -1)[:T].reshape(h.shape), counts


def moe_block(p, i, h, cfg, live=None):
    """The expert block of layer i on h [..., hidden], and the counts."""
    pre = f"layers.{i}.moe."
    return map_token_blocks(
        lambda flat, on: _moe_tokens(p, pre, flat, cfg, on), h, live)


def _dense_layers(p, ids, cfg):
    """The forward over whole sequences ids [B, T]: (hidden states after
    the last layer, per layer the dense (k, v) rows or the final SeqState,
    counts)."""
    positions = jnp.broadcast_to(
        jnp.arange(ids.shape[1], dtype=jnp.int32), ids.shape)
    x = p["embed.weight"][ids]
    cached, total = [], jnp.zeros((len(COUNTERS),), jnp.int32)
    for i in range(cfg.num_hidden_layers):
        pre = f"layers.{i}."
        h = rms_norm0(x, p[pre + "norm1.weight"], cfg.rms_norm_eps)
        mixed, leaf = _attn_dense(p, pre, h, positions, cfg) \
            if cfg.is_full_attention(i) else _gdn_dense(p, pre, h, cfg)
        cached.append(leaf)
        x = x + mixed
        m, counts = moe_block(
            p, i, rms_norm0(x, p[pre + "norm2.weight"], cfg.rms_norm_eps),
            cfg)
        x = x + m
        total = merge_counts(total, counts)
    return x, cached, total


def _head(p, x, cfg):
    return jnp.dot(rms_norm0(x, p["norm_f.weight"], cfg.rms_norm_eps),
                   p["lm_head.weight"], preferred_element_type=F32)


def forward(params, ids, cfg: Qwen3NextConfig):
    """Logits [B, T, V] (float32) of ids [B, T]: the family's forward, no
    cache."""
    x, _, _ = _dense_layers(params, ids, cfg)
    return _head(params, x, cfg)


@functools.partial(jax.jit, static_argnums=(2,))
def prefill(params, ids, cfg: Qwen3NextConfig):
    """The engine's dense prefill: (last-position logits [B, V], per layer
    the (k, v) rows [B, G, max_seq_len, D] zero-padded behind the prompt or
    the DeltaNet layer's final SeqState, counts). One compilation a prompt
    length."""
    x, cached, counts = _dense_layers(params, ids, cfg)
    pad = ((0, 0), (0, 0), (0, cfg.max_seq_len - ids.shape[1]), (0, 0))
    return (_head(params, x[:, -1], cfg),
            tuple(leaf if isinstance(leaf, SeqState)
                  else tuple(jnp.pad(r, pad) for r in leaf)
                  for leaf in cached),
            counts)


# ---------------------------------------------- decode against the pools
def _token_embed(params, tokens, positions):
    return params["embed.weight"][tokens[:, None]]


def _gdn_decode(cfg, p, pre, h, leaf, positions, live, state_slots):
    """One DeltaNet step for N rows against their slots of the layer's
    SeqState: a row at position 0 starts from zeros whatever its slot held,
    a row not live leaves its entry as it is."""
    states, histories = leaf
    K, fresh = cfg.linear_conv_kernel_dim, positions == 0
    u, z, beta, g = gdn_inputs(p, pre, h, cfg)
    history = jnp.where(fresh[:, None], 0, histories[state_slots]) \
        .reshape(h.shape[0], K - 1, -1)
    window = jnp.concatenate([history, u[:, None]], axis=1)
    q, k, v = gdn_heads(gdn_conv(
        p, pre, [window[:, j] for j in range(K)], h.dtype), cfg)
    state = jnp.where(fresh[:, None, None, None], 0, states[state_slots])
    o, state = gdn_step(q, k, v, g, beta, state)
    at = jnp.where(live, state_slots, states.shape[0])   # dropped
    # the entries are back in their slots before the layer's output goes
    # on: left to itself the compiler puts off every layer's write to the
    # end of the trip and keeps all their gathered states until then
    o, states, histories = jax.lax.optimization_barrier((
        o, states.at[at].set(state, mode="drop"),
        histories.at[at].set(window[:, 1:].reshape(h.shape[0], -1),
                             mode="drop")))
    return gdn_output(p, pre, o, z, cfg, h.dtype), \
        SeqState(states, histories)


def _own_group(x, G):
    """x [N, G, Hg, G, D] -> its [n, g, :, g, :] entries, [N, G, Hg, D]."""
    return jnp.sum(x * jnp.eye(G, dtype=x.dtype)[None, :, None, :, None],
                   axis=3)


def _rows_attention(q, ctx_k, ctx_v, att_lens, G, att_starts=None):
    """One query a row against cached positions whose G key-value heads lie
    side by side in ONE row, as the pools keep them: q [N, H, D], ctx_k,
    ctx_v [N, S, G * D], row n attends to its first att_lens[n] positions
    (from att_starts[n] on, where given: a window) -> [N, H, D]. Each query head is laid into its own group's lanes of a
    G * D-wide row (zeros in the others) and multiplied against the whole
    cached row, and takes its group's lanes of the whole value row back:
    G times the multiply-adds, which a decode step does not notice, and no
    [.., G, D] view of the context, which the device would pad and copy."""
    N, H, D = q.shape
    wide = (q.reshape(N, G, H // G, 1, D)
            * jnp.eye(G, dtype=q.dtype)[None, :, None, :, None]) \
        .reshape(N, H, G * D)
    scores = jnp.einsum("nhw,nsw->nhs", wide, ctx_k,
                        preferred_element_type=F32) \
        * F32(1.0 / math.sqrt(D))
    at = jnp.arange(ctx_k.shape[1])[None, :]
    attend = at < att_lens[:, None]
    if att_starts is not None:
        attend = attend & (at >= att_starts[:, None])
    scores = jnp.where(attend[:, None], scores, F32(-1e30))
    probs = jax.nn.softmax(scores, axis=-1).astype(ctx_v.dtype)
    out = jnp.einsum("nhs,nsw->nhw", probs, ctx_v,
                     preferred_element_type=F32).astype(ctx_v.dtype)
    return _own_group(out.reshape(N, G, H // G, G, D), G).reshape(N, H, D)


def _attn_decode(cfg, p, pre, h, pool, slot_blocks, slot_offsets, tables,
                 positions, att_lens):
    """One gated-attention step for N rows against the (k, v) pools: write
    the token's rows at its slot, gather each row's blocks through its
    table, attend (composed of XLA operations)."""
    kp, vp = pool
    G = cfg.num_key_value_heads
    q, gate, k, v = attn_inputs(p, pre, h, positions, cfg)
    kp = write_rows(kp, k, slot_blocks, slot_offsets)
    vp = write_rows(vp, v, slot_blocks, slot_offsets)
    flat = (G * cfg.head_dim,)
    att = _rows_attention(
        q, gather_rows(kp, tables, flat).astype(h.dtype),
        gather_rows(vp, tables, flat).astype(h.dtype), att_lens, G)
    return _attn_output(p, pre, att, gate), (kp, vp)


def _decode_layer(cfg, params, i, x, pool, slot_blocks, slot_offsets,
                  tables, positions, att_lens, live, ragged,
                  state_slots=None, att_starts=None, table_starts=None):
    """Layer i for N rows of one token each (`ModelSpec.decode_layer`;
    `ragged` has no kernel to choose here yet; no layer has a window)."""
    pre, eps = f"layers.{i}.", cfg.rms_norm_eps
    h = rms_norm0(x[:, 0], params[pre + "norm1.weight"], eps)
    if cfg.is_full_attention(i):
        mixed, pool = _attn_decode(cfg, params, pre, h, pool, slot_blocks,
                                   slot_offsets, tables, positions, att_lens)
    else:
        mixed, pool = _gdn_decode(cfg, params, pre, h, pool, positions,
                                  live, state_slots)
    y = x[:, 0] + mixed
    m, counts = moe_block(
        params, i, rms_norm0(y, params[pre + "norm2.weight"], eps), cfg,
        live)
    return (y + m)[:, None], pool, counts


def _decode_head(cfg, params, x):
    return _head(params, x[:, 0], cfg)


@functools.lru_cache(maxsize=None)
def serving_spec(cfg: Qwen3NextConfig) -> ModelSpec:
    """The spec `LLMEngine` serves this family through: rows (k, v of
    G x D) in the full-attention layers, a state a sequence in the
    others."""
    return ModelSpec(
        family="qwen3_next", num_layers=cfg.num_hidden_layers,
        max_seq_len=cfg.max_seq_len, cache_layout="hybrid",
        cache_shape=(cfg.num_key_value_heads, cfg.head_dim),
        cache_dtype=cfg.dtype, embed=_token_embed,
        decode_layer=functools.partial(_decode_layer, cfg),
        head=functools.partial(_decode_head, cfg),
        prefill=lambda params, ids: prefill(params, ids, cfg),
        counters=COUNTERS, config=cfg,
        layer_caches=tuple(
            "rows" if cfg.is_full_attention(i) else "state"
            for i in range(cfg.num_hidden_layers)),
        state_shapes=cfg.state_shapes,
        expert_shape=(cfg.held[1], cfg.hidden_size,
                      cfg.moe_intermediate_size))


# ------------------------------------------------------------ the Layer
class Qwen3Next(nn.Layer):
    """The family as a `paddle.nn.Layer`: parameters under the flat names
    of `param_shapes` (matrices N(0, 0.02), the rest as `init_value`),
    `forward(ids)` -> logits [B, T, V]. `LLMEngine.from_model` serves
    it."""

    def __init__(self, cfg: Qwen3NextConfig = None, **kwargs):
        super().__init__()
        self.cfg = cfg or Qwen3NextConfig(**kwargs)
        for name, (shape, dtype) in param_shapes(self.cfg).items():
            how = init_value(name)
            init = I.Normal(0.0, 0.02) if how is None \
                else I.Constant(how[1]) if how[0] == "constant" \
                else I.Uniform(how[1], how[2])
            self.add_parameter(name, self.create_parameter(
                list(shape), dtype=dtype, default_initializer=init))

    def forward(self, input_ids):
        return dispatch(
            "qwen3_next_forward",
            lambda params, ids: forward(params, ids, self.cfg),
            (dict(self.named_parameters()), input_ids), {}, True)

    def serving_spec(self) -> ModelSpec:
        return serving_spec(self.cfg)
