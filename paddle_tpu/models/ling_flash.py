"""Ling-3.0-flash (`bailing_hybrid`): a hybrid decoder family, Kimi Delta
Attention (KDA, linear attention with one decay a key channel) layers
beside multi-head latent attention (MLA) layers, each followed by a dense
SwiGLU or a shared + routed expert block with a group-limited, bias-
corrected router; in pure `jax.numpy` like models/qwen3_next.py, plus the
`nn.Layer` that holds its parameters and the `ModelSpec` that serves it
through `LLMEngine`.

Source of the shapes: huggingface.co/inclusionAI/Ling-3.0-flash
`config.json`. N is an RMSNorm, x / rms(x) * w (eps `rms_norm_eps`, w = 1
at init). Every layer is pre-norm residual, and an untied head follows a
final norm:

    x <- x + Mixer(N1(x));  x <- x + Mlp(N2(x));  logits = Nf(x) W_head

Layer i is MLA when (p + 1) % `layer_group_size` == 0, p its PUBLISHED
index (`published_layers`: a cut that holds some of the published layers
keeps their mixers), else KDA; it is a dense SwiGLU (`intermediate_size`)
when i < `first_k_dense_replace`, else an expert block.

KDA, H heads of dk = dv = `head_dim` (every head its own q, k, v):

    u = x W_qkv  (per head [q|k|v], 3 dk);  c = silu(causal depthwise
    conv of width K over u, zero history)
    q, k = c's parts L2-normalised per head, q * 1/sqrt(dk);  v = c's third
    beta = sigmoid(x W_b)  (one a head)
    g = lower_bound * sigmoid(exp(A_log_h) * (x W_g + dt_bias))  (one a
    key channel, in (lower_bound, 0): `kda_safe_gate`)
    S_t = (I - beta k k^T) Diag(exp g) S_{t-1} + beta k v^T  (float32)
    o = S_t^T q;  out = [RMSNorm_dv(o) * sigmoid(x W_og)] W_o

with S_0 = 0 in R^{dk x dv} a head. The cache of such a layer is ONE entry
a sequence (`state_shapes`): S and the conv's last K - 1 inputs, per head
[K - 1, 3 dk] oldest first. Prefill evaluates the recurrence in chunks of
`KDA_CHUNK` (`kda_chunked`: within a chunk the d's solve one unit-lower-
triangular system; the per-channel decay of a pair of positions is
factorised about the chunk's middle, each factor within exp(+-40) at the
lower bound, far from float32's subnormals); decode applies one step in place
(`ops/pallas/kda_step.py`, named `kda_step`). tests/test_ling_flash.py
holds the chunks, the kernel and the recurrence as written (`kda_recurrence`)
equal.

MLA, H heads, no query compression (`q_lora_rank` null):

    [q_nope, q_pe]_h = x W_q  (qk_nope + qk_rope a head)
    [ckv, k_pe] = x W_kva;  c = N_kv(ckv);  q_pe, k_pe rotated by
    interleaved RoPE (dimension 2i paired with 2i + 1, theta `rope_theta`)
    [k_nope, v]_h = c W_kvb;  softmax at 1/sqrt(qk_nope + qk_rope)
    out = [concat_h(attn_h * sigmoid(x w_h))] W_o    (head-wise gate)

cached as the row [c, k_pe] a position, attended in the latent space at
decode (`pangu_moe.mla_absorbed`).

Expert block: s = sigmoid(x W_r) in float32 over all `num_experts`; the
choice is over s + b (b the expert bias), group-limited: `n_group` groups,
each scored by the sum of its two best, the `topk_group` best kept, the
`num_experts_per_tok` best within them; weights s_i / sum(chosen s) *
`routed_scaling_factor`; each expert a SwiGLU clamped at the layer's limit
where it is > 0 (`distributed.moe.held_experts_mlp`'s options); plus the
shared expert (its own limit). A chip of an expert-parallel deployment
holds the experts `held = (first, count)` and computes their part alone.

Dtypes: activations take the dtype of `embed.weight`; every matmul
accumulates in float32 and rounds its result to it. The router, its bias,
`A_log` and `dt_bias` are float32; the recurrence (gates, normalised q and
k, the state) is float32 at "highest" precision; logits are float32.
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
from ..ops.pallas import kda_step as kda_kernel
from .pangu_moe import COUNTERS as MOE_COUNTERS
from .pangu_moe import _mm, mla_absorbed, rms_norm
from .qwen3_next import map_token_blocks
from .spec import ModelSpec, merge_counts

__all__ = ["LingFlashConfig", "LingFlash", "param_shapes", "init_value",
           "forward", "prefill", "serving_spec", "kda_recurrence",
           "kda_chunked", "COUNTERS"]

F32 = jnp.float32
#: positions a chunk of the prefill's KDA: at the lower bound -5 a chunk's
#: factorised decay spans exp(+-40) about its middle (`kda_chunked`)
KDA_CHUNK = 16
#: queries a block of the prefill's MLA scores at a time
QUERY_BLOCK = 512
#: the counts of a layer (ModelSpec.counters): the expert layer's, then
#: live rows x KDA layers a decode step updated and the prefill's KDA
#: chunks, the expert layer's maximum last (`merge_counts`)
COUNTERS = MOE_COUNTERS[:-1] + ("kda_state_row_trips",
                                "kda_prefill_chunks") + MOE_COUNTERS[-1:]


@dataclass(frozen=True)
class LingFlashConfig:
    vocab_size: int = 157184
    hidden_size: int = 2560
    num_hidden_layers: int = 42
    #: the published index of each layer held (None: 0, 1, ...): what
    #: decides its mixer
    published_layers: Tuple[int, ...] = None
    layer_group_size: int = 6
    first_k_dense_replace: int = 2
    num_attention_heads: int = 32
    head_dim: int = 128
    short_conv_kernel_size: int = 4
    kda_lower_bound: float = -5.0
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 6000000.0
    intermediate_size: int = 6144
    moe_intermediate_size: int = 768
    moe_shared_expert_intermediate_size: int = 768
    num_experts: int = 512
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scaling_factor: float = 2.5
    #: per layer held, the routed and the shared experts' SwiGLU limit (0:
    #: no clamp; () = 0 everywhere)
    expert_swiglu_limits: Tuple[float, ...] = ()
    shared_swiglu_limits: Tuple[float, ...] = ()
    rms_norm_eps: float = 1e-6
    max_seq_len: int = 2048
    #: (first, count) of the routed experts this chip holds; None = all
    held_experts: Tuple[int, int] = None
    dtype: str = "float32"

    @property
    def held(self) -> Tuple[int, int]:
        return self.held_experts or (0, self.num_experts)

    @property
    def latent_width(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    def is_mla(self, i: int) -> bool:
        p = i if self.published_layers is None else self.published_layers[i]
        return (p + 1) % self.layer_group_size == 0

    def limits(self, i: int) -> Tuple[float, float]:
        """(routed, shared) SwiGLU limit of layer i."""
        return tuple(float(lim[i]) if lim else 0.0 for lim in
                     (self.expert_swiglu_limits, self.shared_swiglu_limits))

    @property
    def state_shapes(self):
        """A KDA layer's entry a sequence: S per head (float32) and each
        head's conv history, [K - 1, 3 dk] flat, oldest input first."""
        H, d, K = (self.num_attention_heads, self.head_dim,
                   self.short_conv_kernel_size)
        return (((H, d, d), "float32"), ((H, (K - 1) * 3 * d), self.dtype))


def param_shapes(cfg: LingFlashConfig) -> dict:
    """{name: (shape, dtype name)} of every parameter, flat. `W_qkv` and
    the conv's weight are laid out head-major, [H, (q|k|v), dk] (a fixed
    permutation of a checkpoint's columns)."""
    h, dt, H, d = (cfg.hidden_size, cfg.dtype, cfg.num_attention_heads,
                   cfg.head_dim)
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    f, fs, held = (cfg.moe_intermediate_size,
                   cfg.moe_shared_expert_intermediate_size, cfg.held[1])
    out = {"embed.weight": ((cfg.vocab_size, h), dt),
           "norm_f.weight": ((h,), dt),
           "lm_head.weight": ((h, cfg.vocab_size), dt)}
    for i in range(cfg.num_hidden_layers):
        pre = f"layers.{i}."
        out[pre + "norm1.weight"] = ((h,), dt)
        out[pre + "norm2.weight"] = ((h,), dt)
        if cfg.is_mla(i):
            out[pre + "attn.q.weight"] = ((h, H * qk), dt)
            out[pre + "attn.kv_a.weight"] = ((h, cfg.latent_width), dt)
            out[pre + "attn.kv_norm.weight"] = ((cfg.kv_lora_rank,), dt)
            out[pre + "attn.kv_b.weight"] = (
                (cfg.kv_lora_rank,
                 H * (cfg.qk_nope_head_dim + cfg.v_head_dim)), dt)
            out[pre + "attn.gate.weight"] = ((h, H), dt)
            out[pre + "attn.o.weight"] = ((H * cfg.v_head_dim, h), dt)
        else:
            out[pre + "kda.qkv.weight"] = ((h, H * 3 * d), dt)
            out[pre + "kda.conv.weight"] = (
                (cfg.short_conv_kernel_size, H * 3 * d), dt)
            out[pre + "kda.g.weight"] = ((h, H * d), dt)
            out[pre + "kda.dt_bias"] = ((H * d,), "float32")
            out[pre + "kda.A_log"] = ((H,), "float32")
            out[pre + "kda.b.weight"] = ((h, H), dt)
            out[pre + "kda.og.weight"] = ((h, H * d), dt)
            out[pre + "kda.norm.weight"] = ((d,), dt)
            out[pre + "kda.o.weight"] = ((H * d, h), dt)
        if i < cfg.first_k_dense_replace:
            width, mlp = cfg.intermediate_size, "mlp."
        else:
            width, mlp = fs, "moe.shared."
            out[pre + "moe.router.weight"] = ((h, cfg.num_experts),
                                              "float32")
            out[pre + "moe.router.bias"] = ((cfg.num_experts,), "float32")
            out[pre + "moe.experts.gate.weight"] = ((held, h, f), dt)
            out[pre + "moe.experts.up.weight"] = ((held, h, f), dt)
            out[pre + "moe.experts.down.weight"] = ((held, f, h), dt)
        out[pre + mlp + "gate.weight"] = ((h, width), dt)
        out[pre + mlp + "up.weight"] = ((h, width), dt)
        out[pre + mlp + "down.weight"] = ((width, h), dt)
    return out


def init_value(name: str):
    """How a parameter that is no plain matrix starts: ("constant", c),
    ("uniform", lo, hi) or ("normal", std); None for a matrix (N(0,
    initializer_range)). Norms 1; `A_log` uniform in [-log 2, log 2] and
    `dt_bias` uniform in [-8, 0], so that a channel's decay a token lies
    between exp(-4) and exp(-0.0002) (short and long memory both there);
    the expert bias N(0, 0.1): at the published widths it changes about
    two thirds of a token's picks (its 8 best sigmoid scores of 512 lie
    within about 0.05 of each other), so the correction is exercised."""
    if name.endswith("kda.A_log"):
        return ("uniform", -math.log(2.0), math.log(2.0))
    if name.endswith("kda.dt_bias"):
        return ("uniform", -8.0, 0.0)
    if name.endswith("router.bias"):
        return ("normal", 0.1)
    if name.endswith(("norm.weight", "norm1.weight", "norm2.weight",
                      "norm_f.weight")):
        return ("constant", 1.0)
    return None


# ------------------------------------------------------------ the layers
def swiglu(x, w_gate, w_up, w_down, limit=0.0):
    """(silu(x W_g) * (x W_u)) W_d, the gate clamped above and the up
    projection on both sides at `limit` where it is > 0."""
    gate = jnp.dot(x, w_gate, preferred_element_type=F32)
    up = jnp.dot(x, w_up, preferred_element_type=F32)
    if limit > 0:
        gate, up = jnp.minimum(gate, limit), jnp.clip(up, -limit, limit)
    return _mm((jax.nn.silu(gate) * up).astype(x.dtype), w_down)


# ------------------------------------------------- Kimi Delta Attention
def kda_gates(p, pre, h, cfg):
    """h [..., hidden] -> (g [..., H, dk] in (lower_bound, 0), beta
    [..., H]), float32."""
    H, d = cfg.num_attention_heads, cfg.head_dim
    a = jnp.dot(h, p[pre + "kda.g.weight"], preferred_element_type=F32) \
        + p[pre + "kda.dt_bias"]
    a = a.reshape(h.shape[:-1] + (H, d))
    g = F32(cfg.kda_lower_bound) * jax.nn.sigmoid(
        jnp.exp(p[pre + "kda.A_log"])[:, None] * a)
    beta = jax.nn.sigmoid(jnp.dot(h, p[pre + "kda.b.weight"],
                                  preferred_element_type=F32))
    return g, beta


def kda_recurrence(q, k, v, g, beta, state):
    """The delta rule as written, one position after another: q, k
    [T, H, dk], v [T, H, dv], g [T, H, dk], beta [T, H], state
    [H, dk, dv] (float32) -> (o [T, H, dv], final state). What
    `kda_chunked` and the decode kernel are held to."""
    def step(S, t):
        q_t, k_t, v_t, g_t, b_t = t
        S = jnp.exp(g_t)[:, :, None] * S
        d = b_t[:, None] * (v_t - jnp.sum(S * k_t[:, :, None], axis=1))
        S = S + k_t[:, :, None] * d[:, None, :]
        return S, jnp.sum(S * q_t[:, :, None], axis=1)

    state, o = jax.lax.scan(step, state, (q, k, v, g, beta))
    return o, state


def kda_chunked(q, k, v, g, beta, state, chunk=KDA_CHUNK):
    """The same recurrence over chunks of `chunk` positions (any T: the
    tail is padded with positions that neither decay nor write). With
    gamma_t the running sum of g inside a chunk (a vector over the key
    channels) and S the state it starts from, the d's of a chunk solve

        (I + beta L) D = beta (V - (K exp(gamma)) S),
        L[t, j] = (k_t exp(gamma_t - gamma_m)) . (k_j exp(gamma_m - gamma_j)),
        j < t,

    its outputs are (Q exp(gamma)) S + tril((Q exp(gamma - gamma_m))
    (K exp(gamma_m - gamma))^T) D, and the state leaves as exp(gamma_C) S
    + (K exp(gamma_C - gamma))^T D. A pair's decay is factorised about the
    chunk's middle position m, so each factor lies within exp(+-chunk / 2
    x the most negative g): at the lower bound -5 and 16 positions
    exp(+-40), far inside float32 (factored about the chunk's start, the
    factors reach exp(+-80) and the small one falls among the subnormals,
    which lose digits on the CPU and are flushed to zero on a TPU)."""
    T, H, dk = q.shape
    dv, n = v.shape[-1], -(-T // chunk)
    pad = n * chunk - T

    def chunks(x):                  # [T, H, ...] -> [n, H, chunk, ...]
        x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
        x = x.reshape((n, chunk) + x.shape[1:])
        return jnp.moveaxis(x, 1, 2)

    q, k, v, g, beta = (chunks(x) for x in (q, k, v, g, beta))
    with jax.default_matmul_precision("highest"):
        gamma = jnp.cumsum(g, axis=2)                     # [n, H, C, dk]
        k_in = k * jnp.exp(gamma)
        q_in = q * jnp.exp(gamma)
        # the pairs' factors taken about the chunk's middle position:
        # exp(gamma_t - gamma_m) exp(gamma_m - gamma_j), each within
        # exp(+-C/2 x 5), so that neither nears float32's subnormals
        mid = gamma[..., chunk // 2:chunk // 2 + 1, :]
        k_pair, q_pair = (x * jnp.exp(gamma - mid) for x in (k, q))
        k_back = k * jnp.exp(mid - gamma)
        below = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
        system = jnp.eye(chunk, dtype=F32) + jnp.where(
            below, beta[..., None] * jnp.einsum("nhid,nhjd->nhij",
                                                k_pair, k_back), 0.0)
        rhs = jnp.concatenate([v, k_in], axis=-1) * beta[..., None]
        solved = solve_triangular(system, rhs, lower=True,
                                  unit_diagonal=True)
        w, y = solved[..., :dv], solved[..., dv:]
        qk = jnp.where(jnp.tril(jnp.ones((chunk, chunk), bool)),
                       jnp.einsum("nhid,nhjd->nhij", q_pair, k_back), 0.0)
        k_out = k * jnp.exp(gamma[..., -1:, :] - gamma)
        leave = jnp.exp(gamma[..., -1, :])                # [n, H, dk]

        def step(S, c):
            w_c, y_c, qk_c, q_c, k_c, leave_c = c
            d = w_c - jnp.einsum("hik,hkv->hiv", y_c, S)
            o = jnp.einsum("hik,hkv->hiv", q_c, S) \
                + jnp.einsum("hij,hjv->hiv", qk_c, d)
            S = leave_c[:, :, None] * S + jnp.einsum("hik,hiv->hkv", k_c, d)
            return S, o

        state, o = jax.lax.scan(step, state,
                                (w, y, qk, q_in, k_out, leave))
    o = jnp.moveaxis(o, 1, 2).reshape(n * chunk, H, dv)
    return o[:T], state


def kda_output(p, pre, o, h, cfg):
    """[RMSNorm_dv(o) * sigmoid(x W_og)] W_o: o [..., H, dv] float32."""
    y = rms_norm(o, p[pre + "kda.norm.weight"], cfg.rms_norm_eps)
    y = y * jax.nn.sigmoid(jnp.dot(
        h, p[pre + "kda.og.weight"], preferred_element_type=F32)
        .reshape(y.shape))
    return _mm(y.reshape(y.shape[:-2] + (-1,)).astype(h.dtype),
               p[pre + "kda.o.weight"])


def _kda_dense(p, pre, h, cfg):
    """A KDA layer over whole sequences h [B, T, hidden] from zero state:
    (output [B, T, hidden], SeqState of the final S [B, H, dk, dv] and
    conv history [B, H, (K - 1) 3 dk])."""
    H, d = cfg.num_attention_heads, cfg.head_dim
    K, B, T = cfg.short_conv_kernel_size, h.shape[0], h.shape[1]
    u = _mm(h, p[pre + "kda.qkv.weight"]).reshape(B, T, H, 3 * d)
    padded = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0), (0, 0)))  # no history
    q, k, v = kda_kernel.conv_heads(
        [padded[:, j:j + T] for j in range(K)],
        p[pre + "kda.conv.weight"].astype(F32).reshape(K, H, 3 * d), d,
        1.0 / math.sqrt(d), h.dtype)
    g, beta = kda_gates(p, pre, h, cfg)
    zero = jnp.zeros((H, d, d), F32)
    o, state = jax.vmap(lambda *a: kda_chunked(*a, zero))(q, k, v, g, beta)
    history = padded[:, T:].transpose(0, 2, 1, 3).reshape(B, H, -1)
    return kda_output(p, pre, o, h, cfg), SeqState(state, history)


def _kda_decode(cfg, p, pre, h, leaf, positions, live, state_slots):
    """One KDA step for N rows, each row's entry updated in place in its
    slot by the `kda_step` kernel (a row at position 0 starts from zeros, a
    row not live is not written)."""
    H, d = cfg.num_attention_heads, cfg.head_dim
    K, N = cfg.short_conv_kernel_size, h.shape[0]
    states, history = leaf
    u = _mm(h, p[pre + "kda.qkv.weight"]).reshape(N, H, 3 * d)
    g, beta = kda_gates(p, pre, h, cfg)
    with jax.named_scope("kda_step"):
        o, states, history = kda_kernel.kda_step(
            u, g, beta, p[pre + "kda.conv.weight"].reshape(K, H, 3 * d),
            states, history, state_slots, live, positions == 0,
            scale=1.0 / math.sqrt(d),
            interpret=not kda_kernel.supported(H, d, d))
    return kda_output(p, pre, o, h, cfg), SeqState(states, history)


# ------------------------------------------------ multi-head latent attn
def rope_interleaved(x, positions, theta):
    """Rotate x [..., d] at `positions` (broadcastable to x.shape[:-1]),
    dimension 2i paired with 2i + 1 (`rope_interleave`)."""
    half = x.shape[-1] // 2
    inv = F32(theta) ** (-jnp.arange(half, dtype=F32) / F32(half))
    ang = positions.astype(F32)[..., None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    pairs = x.astype(F32).reshape(x.shape[:-1] + (half, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1) \
        .reshape(x.shape).astype(x.dtype)


def mla_queries(p, pre, h, positions, cfg):
    """h [..., hidden] at positions [...] -> (q_nope [..., H, nope], q_pe
    [..., H, rope] rotated): no query compression."""
    q = _mm(h, p[pre + "attn.q.weight"]).reshape(
        h.shape[:-1] + (cfg.num_attention_heads, -1))
    q_pe = rope_interleaved(q[..., cfg.qk_nope_head_dim:],
                            positions[..., None], cfg.rope_theta)
    return q[..., :cfg.qk_nope_head_dim], q_pe


def mla_latent(p, pre, h, positions, cfg):
    """The cache row of each token: [N_kv(ckv), RoPE(k_pe)]."""
    kv = _mm(h, p[pre + "attn.kv_a.weight"])
    c = rms_norm(kv[..., :cfg.kv_lora_rank],
                 p[pre + "attn.kv_norm.weight"], cfg.rms_norm_eps)
    return jnp.concatenate(
        [c, rope_interleaved(kv[..., cfg.kv_lora_rank:], positions,
                             cfg.rope_theta)], axis=-1)


def mla_output(p, pre, attn, h, cfg):
    """The head-wise gate and the out-projection: attn [..., H * dv]."""
    gate = jax.nn.sigmoid(jnp.dot(h, p[pre + "attn.gate.weight"],
                                  preferred_element_type=F32))
    heads = attn.reshape(attn.shape[:-1] + (cfg.num_attention_heads, -1))
    gated = (heads.astype(F32) * gate[..., None]).astype(attn.dtype)
    return _mm(gated.reshape(attn.shape), p[pre + "attn.o.weight"])


def _mla_dense(p, pre, h, positions, cfg):
    """An MLA layer over whole sequences h [B, T, hidden]: (output, the
    latent rows [B, T, W]). Keys and values expanded per head once,
    `QUERY_BLOCK` queries at a time against them (causal)."""
    rank, nope = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    H, B, T = cfg.num_attention_heads, h.shape[0], h.shape[1]
    q_nope, q_pe = mla_queries(p, pre, h, positions, cfg)
    rows = mla_latent(p, pre, h, positions, cfg)
    kv = jnp.einsum("bsc,chd->bshd", rows[..., :rank],
                    p[pre + "attn.kv_b.weight"].reshape(rank, H, -1),
                    preferred_element_type=F32).astype(h.dtype)
    k_nope, v, k_pe = kv[..., :nope], kv[..., nope:], rows[..., rank:]
    scale = F32(1.0 / math.sqrt(nope + cfg.qk_rope_head_dim))
    block = min(T, QUERY_BLOCK)
    n = -(-T // block)
    keys = jnp.arange(T)[None, :]

    def attend_block(args):
        qn, qp, start = args                   # [B, block, H, .]
        s = (jnp.einsum("bthd,bshd->bhts", qn, k_nope,
                        preferred_element_type=F32)
             + jnp.einsum("bthr,bsr->bhts", qp, k_pe,
                          preferred_element_type=F32)) * scale
        s = jnp.where(((start + jnp.arange(block))[:, None] >= keys)[None,
                                                                     None],
                      s, F32(-1e30))
        probs = jax.nn.softmax(s, axis=-1).astype(h.dtype)
        return jnp.einsum("bhts,bshd->bthd", probs, v,
                          preferred_element_type=F32).astype(h.dtype)

    def blocks(x):
        x = jnp.pad(x, ((0, 0), (0, n * block - T)) + ((0, 0),) * 2)
        return x.reshape((B, n, block) + x.shape[2:]).swapaxes(0, 1)

    att = jax.lax.map(attend_block, (blocks(q_nope), blocks(q_pe),
                                     jnp.arange(n) * block))
    att = att.swapaxes(0, 1).reshape(B, n * block, -1)[:, :T]
    return mla_output(p, pre, att, h, cfg), rows


def _mla_decode(cfg, p, pre, h, pool, slot_blocks, slot_offsets, tables,
                positions, att_lens):
    """One MLA step for N rows against the latent pool: write the token's
    row at its slot, gather each row's blocks, attend in the latent
    space."""
    q_nope, q_pe = mla_queries(p, pre, h, positions, cfg)
    pool = write_rows(pool, mla_latent(p, pre, h, positions, cfg),
                      slot_blocks, slot_offsets)
    ctx = gather_rows(pool, tables, (cfg.latent_width,))
    attn = mla_absorbed(p, pre, q_nope, q_pe, ctx.astype(h.dtype), att_lens,
                        cfg)
    return mla_output(p, pre, attn, h, cfg), pool


# ------------------------------------------------------- the MLP block
def _moe_tokens(p, pre, flat, cfg, limits, live):
    """flat [T, hidden] -> (the held experts' part plus the shared expert
    [T, hidden], counts)."""
    routed, counts = held_experts_mlp(
        flat, p[pre + "router.weight"], p[pre + "experts.gate.weight"],
        p[pre + "experts.up.weight"], p[pre + "experts.down.weight"],
        cfg.held, cfg.num_experts_per_tok, cfg.routed_scaling_factor, live,
        bias=p[pre + "router.bias"], groups=(cfg.n_group, cfg.topk_group),
        limit=limits[0])
    shared = swiglu(flat, p[pre + "shared.gate.weight"],
                    p[pre + "shared.up.weight"],
                    p[pre + "shared.down.weight"], limits[1])
    return (routed + shared.astype(F32)).astype(flat.dtype), counts


def mlp_block(p, i, h, cfg, live=None):
    """Layer i's MLP on h [..., hidden]: a dense SwiGLU or the expert
    block; and the expert layer's counts (zeros for a dense one)."""
    pre = f"layers.{i}."
    if i < cfg.first_k_dense_replace:
        return swiglu(h, p[pre + "mlp.gate.weight"], p[pre + "mlp.up.weight"],
                      p[pre + "mlp.down.weight"]), \
            jnp.zeros((len(MOE_COUNTERS),), jnp.int32)
    limits = cfg.limits(i)
    return map_token_blocks(
        lambda flat, on: _moe_tokens(p, pre + "moe.", flat, cfg, limits,
                                     on), h, live)


def _counts(moe, state_rows=0, chunks=0):
    """A layer's counts in COUNTERS' order."""
    return jnp.concatenate([moe[:-1], jnp.stack([
        jnp.asarray(state_rows, jnp.int32), jnp.asarray(chunks, jnp.int32)]),
        moe[-1:]])


def _dense_layers(p, ids, cfg):
    """The forward over whole sequences ids [B, T]: (hidden states after
    the last layer, per layer the latent rows [B, T, W] or the final
    SeqState, counts)."""
    B, T = ids.shape
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), ids.shape)
    x = p["embed.weight"][ids]
    cached, total = [], jnp.zeros((len(COUNTERS),), jnp.int32)
    for i in range(cfg.num_hidden_layers):
        pre = f"layers.{i}."
        h = rms_norm(x, p[pre + "norm1.weight"], cfg.rms_norm_eps)
        if cfg.is_mla(i):
            mixed, leaf = _mla_dense(p, pre, h, positions, cfg)
            chunks = 0
        else:
            with jax.named_scope("kda_prefill"):
                mixed, leaf = _kda_dense(p, pre, h, cfg)
            chunks = B * -(-T // KDA_CHUNK)
        cached.append(leaf)
        x = x + mixed
        m, counts = mlp_block(
            p, i, rms_norm(x, p[pre + "norm2.weight"], cfg.rms_norm_eps), cfg)
        x = x + m
        total = merge_counts(total, _counts(counts, chunks=chunks))
    return x, cached, total


def _head(p, x, cfg):
    return jnp.dot(rms_norm(x, p["norm_f.weight"], cfg.rms_norm_eps),
                   p["lm_head.weight"], preferred_element_type=F32)


def forward(params, ids, cfg: LingFlashConfig):
    """Logits [B, T, V] (float32) of ids [B, T]: the family's forward, no
    cache."""
    x, _, _ = _dense_layers(params, ids, cfg)
    return _head(params, x, cfg)


@functools.partial(jax.jit, static_argnums=(2,))
def prefill(params, ids, cfg: LingFlashConfig):
    """The engine's dense prefill: (last-position logits [B, V], per layer
    the latent rows [B, max_seq_len, W] zero-padded behind the prompt or
    the KDA layer's final SeqState, counts). One compilation a prompt
    length."""
    x, cached, counts = _dense_layers(params, ids, cfg)
    pad = ((0, 0), (0, cfg.max_seq_len - ids.shape[1]), (0, 0))
    return (_head(params, x[:, -1], cfg),
            tuple(leaf if isinstance(leaf, SeqState) else jnp.pad(leaf, pad)
                  for leaf in cached),
            counts)


# ---------------------------------------------- decode against the pools
def _token_embed(params, tokens, positions):
    return params["embed.weight"][tokens[:, None]]


def _decode_layer(cfg, params, i, x, pool, slot_blocks, slot_offsets,
                  tables, positions, att_lens, live, ragged,
                  state_slots=None, att_starts=None, table_starts=None):
    """Layer i for N rows of one token each (`ModelSpec.decode_layer`;
    `ragged` has no kernel to choose for the latent rows; no window)."""
    pre, eps = f"layers.{i}.", cfg.rms_norm_eps
    h = rms_norm(x[:, 0], params[pre + "norm1.weight"], eps)
    if cfg.is_mla(i):
        mixed, pool = _mla_decode(cfg, params, pre, h, pool, slot_blocks,
                                  slot_offsets, tables, positions, att_lens)
        rows = 0
    else:
        mixed, pool = _kda_decode(cfg, params, pre, h, pool, positions,
                                  live, state_slots)
        rows = jnp.sum(live)
    y = x[:, 0] + mixed
    m, counts = mlp_block(params, i, rms_norm(y, params[pre + "norm2.weight"],
                                              eps), cfg, live)
    return (y + m)[:, None], pool, _counts(counts, state_rows=rows)


def _decode_head(cfg, params, x):
    return _head(params, x[:, 0], cfg)


@functools.lru_cache(maxsize=None)
def serving_spec(cfg: LingFlashConfig) -> ModelSpec:
    """The spec `LLMEngine` serves this family through: latent rows
    (one pool of W a position) in the MLA layers, a state a sequence in the
    KDA layers."""
    experts = cfg.num_hidden_layers > cfg.first_k_dense_replace
    return ModelSpec(
        family="ling_flash", num_layers=cfg.num_hidden_layers,
        max_seq_len=cfg.max_seq_len, cache_layout="hybrid",
        cache_shape=(cfg.latent_width,), cache_dtype=cfg.dtype,
        embed=_token_embed,
        decode_layer=functools.partial(_decode_layer, cfg),
        head=functools.partial(_decode_head, cfg),
        prefill=lambda params, ids: prefill(params, ids, cfg),
        counters=COUNTERS, config=cfg,
        layer_caches=tuple("rows" if cfg.is_mla(i) else "state"
                           for i in range(cfg.num_hidden_layers)),
        state_shapes=cfg.state_shapes,
        expert_shape=(cfg.held[1], cfg.hidden_size,
                      cfg.moe_intermediate_size) if experts else ())


# ------------------------------------------------------------ the Layer
class LingFlash(nn.Layer):
    """The family as a `paddle.nn.Layer`: parameters under the flat names
    of `param_shapes` (matrices N(0, 0.02), the rest as `init_value`),
    `forward(ids)` -> logits [B, T, V]. `LLMEngine.from_model` serves
    it."""

    def __init__(self, cfg: LingFlashConfig = None, **kwargs):
        super().__init__()
        self.cfg = cfg or LingFlashConfig(**kwargs)
        for name, (shape, dtype) in param_shapes(self.cfg).items():
            how = init_value(name)
            init = I.Normal(0.0, 0.02) if how is None \
                else I.Constant(how[1]) if how[0] == "constant" \
                else I.Normal(0.0, how[1]) if how[0] == "normal" \
                else I.Uniform(how[1], how[2])
            self.add_parameter(name, self.create_parameter(
                list(shape), dtype=dtype, default_initializer=init))

    def forward(self, input_ids):
        return dispatch(
            "ling_flash_forward",
            lambda params, ids: forward(params, ids, self.cfg),
            (dict(self.named_parameters()), input_ids), {}, True)

    def serving_spec(self) -> ModelSpec:
        return serving_spec(self.cfg)
