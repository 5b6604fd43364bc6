"""openPangu-Ultra-MoE: a decoder family with multi-head latent attention
(MLA), sandwich norms and a shared + routed expert layer, in pure
`jax.numpy` like models/generation.py, plus the `nn.Layer` that holds its
parameters and the `ModelSpec` that serves it through `LLMEngine`.

Source of the shapes: huggingface.co/FreedomIntelligence/
openPangu-Ultra-MoE-718B `config.json` (model_type `pangu_ultra_moe`).
The equations, with N an RMSNorm (eps `rms_norm_eps`, weight 1 at init):

    layer (sandwich_norm):  a = x + N2(Attn(N1(x)));  y = a + N4(Mlp(N3(a)))
    after the last layer:   logits = Nf(y) W_head        (head untied)

MLA for the token at position t (H heads; ranks and head sizes from the
config):

    cq = Nq(x W_qa)                          [q_lora_rank]
    [q_nope, q_pe]_h = cq W_qb               [H, qk_nope + qk_rope]
    [ckv, k_pe] = x W_kva                    [kv_lora_rank + qk_rope]
    c = Nkv(ckv);  q_pe, k_pe rotated by RoPE(rope_theta, position t)
    [k_nope, v]_h = c W_kvb                  [H, qk_nope + v_head_dim]
    score_h(t, s) = (q_nope_h . k_nope_h(s) + q_pe_h . k_pe(s))
                    / sqrt(qk_nope + qk_rope), causal softmax
    Attn = concat_h(sum_s p_h(t, s) v_h(s)) W_o

`k_pe` is shared by all heads, and the cache holds the row `[c, k_pe]`
(kv_lora_rank + qk_rope numbers a position) and nothing else. Prefill
expands keys and values per head as written (`mla_expanded`). Decode uses
W_kvb = [W_uk | W_uv] and the identities

    q_nope . (c W_uk) = (q_nope W_uk^T) . c
    sum_s p(s) (c(s) W_uv) = (sum_s p(s) c(s)) W_uv

so the query goes to the latent space, attention runs against the cached
rows themselves, and the value comes back through W_uv (`mla_absorbed`);
tests/test_pangu_moe.py holds the two forms equal.

Dense MLP and every expert: (silu(x W_g) * (x W_u)) W_d. Expert layer:
s = sigmoid(x W_r) in float32 over all `n_routed_experts`; the
`num_experts_per_tok` largest; weights s_i / sum(chosen s) *
`routed_scaling_factor`; result = the chosen experts' weighted sum + the
shared expert. A chip of an expert-parallel deployment holds the experts
`held = (first, count)` and computes their part alone
(`distributed.moe.held_experts_mlp`); what the others would add is left
out, and that partial sum is what goes on to the next layer.

Assumed, where the config is silent (benchmarks/configs/
openpangu-ultra-moe-ep16.json repeats them under `assumed`): sigmoid
scoring with ungrouped top-k and no correction bias (no `scoring_func`,
`n_group`, `topk_group` keys); RoPE without scaling (no `rope_scaling`)
pairing dimension i with i + d/2 ("rotate half"); norms and softmax
computed in float32 whatever the weights' dtype.

Dtypes: activations take the dtype of `embed.weight`. With bfloat16
weights every matmul accumulates in float32 and rounds its result to
bfloat16; the router (`moe.router.weight`) is float32 and multiplies at
"highest" precision; logits are float32.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..core.dispatch import dispatch
from ..distributed.moe import held_experts_mlp
from ..inference.serving.paged_cache import gather_rows, write_rows
from ..nn import initializer as I
from .spec import ModelSpec, merge_counts

__all__ = ["PanguMoEConfig", "PanguMoE", "param_shapes", "forward",
           "prefill", "serving_spec", "mla_expanded", "mla_absorbed"]

#: the counts an expert layer returns (ModelSpec.counters), as
#: `distributed.moe.held_experts_mlp` stacks them
COUNTERS = ("moe_pairs", "moe_experts_hit", "moe_full_buffer_layers",
            "moe_batched_layers", "moe_layer_calls", "moe_fit_2x",
            "moe_fit_4x", "moe_max_load")


@dataclass(frozen=True)
class PanguMoEConfig:
    vocab_size: int = 153600
    hidden_size: int = 7680
    num_hidden_layers: int = 61
    first_k_dense_replace: int = 3
    num_attention_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    n_routed_experts: int = 256
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    rms_norm_eps: float = 1e-5
    rope_theta: float = 25600000.0
    max_seq_len: int = 2048
    #: (first, count) of the routed experts this chip holds; None = all
    held_experts: Tuple[int, int] = None
    dtype: str = "float32"

    @property
    def held(self) -> Tuple[int, int]:
        return self.held_experts or (0, self.n_routed_experts)

    @property
    def latent_width(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim


def param_shapes(cfg: PanguMoEConfig) -> dict:
    """{name: (shape, dtype name)} of every parameter, flat."""
    h, H, dt = cfg.hidden_size, cfg.num_attention_heads, cfg.dtype
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    f, held = cfg.moe_intermediate_size, cfg.held[1]
    out = {"embed.weight": ((cfg.vocab_size, h), dt),
           "norm_f.weight": ((h,), dt),
           "lm_head.weight": ((h, cfg.vocab_size), dt)}
    for i in range(cfg.num_hidden_layers):
        pre = f"layers.{i}."
        for n in ("norm1", "norm2", "norm3", "norm4"):
            out[pre + n + ".weight"] = ((h,), dt)
        out[pre + "attn.q_a.weight"] = ((h, cfg.q_lora_rank), dt)
        out[pre + "attn.q_norm.weight"] = ((cfg.q_lora_rank,), dt)
        out[pre + "attn.q_b.weight"] = ((cfg.q_lora_rank, H * qk), dt)
        out[pre + "attn.kv_a.weight"] = ((h, cfg.latent_width), dt)
        out[pre + "attn.kv_norm.weight"] = ((cfg.kv_lora_rank,), dt)
        out[pre + "attn.kv_b.weight"] = (
            (cfg.kv_lora_rank,
             H * (cfg.qk_nope_head_dim + cfg.v_head_dim)), dt)
        out[pre + "attn.o.weight"] = ((H * cfg.v_head_dim, h), dt)
        if i < cfg.first_k_dense_replace:
            width, mlp = cfg.intermediate_size, "mlp."
        else:
            width, mlp = f * cfg.n_shared_experts, "moe.shared."
            out[pre + "moe.router.weight"] = (
                (h, cfg.n_routed_experts), "float32")
            out[pre + "moe.experts.gate.weight"] = ((held, h, f), dt)
            out[pre + "moe.experts.up.weight"] = ((held, h, f), dt)
            out[pre + "moe.experts.down.weight"] = ((held, f, h), dt)
        out[pre + mlp + "gate.weight"] = ((h, width), dt)
        out[pre + mlp + "up.weight"] = ((h, width), dt)
        out[pre + mlp + "down.weight"] = ((width, h), dt)
    return out


# ------------------------------------------------------------ the layers
def _mm(a, w):
    """a @ w accumulated in float32, rounded to the activations' dtype."""
    return jnp.dot(a, w, preferred_element_type=jnp.float32).astype(a.dtype)


def rms_norm(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True)
                            + jnp.float32(eps))
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def rope(x, positions, theta):
    """Rotate x [..., d] at `positions` (broadcastable to x.shape[:-1]),
    dimension i paired with i + d/2."""
    half = x.shape[-1] // 2
    inv = jnp.float32(theta) ** (
        -jnp.arange(half, dtype=jnp.float32) / jnp.float32(half))
    ang = positions.astype(jnp.float32)[..., None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def gated_mlp(x, w_gate, w_up, w_down):
    return _mm((jax.nn.silu(jnp.dot(
        x, w_gate, preferred_element_type=jnp.float32))
        * jnp.dot(x, w_up, preferred_element_type=jnp.float32)
    ).astype(x.dtype), w_down)


def mla_queries(p, pre, h, positions, cfg):
    """h [..., hidden] at positions [...] -> (q_nope [..., H, nope],
    q_pe [..., H, rope] rotated)."""
    cq = rms_norm(_mm(h, p[pre + "attn.q_a.weight"]),
                  p[pre + "attn.q_norm.weight"], cfg.rms_norm_eps)
    q = _mm(cq, p[pre + "attn.q_b.weight"]).reshape(
        h.shape[:-1] + (cfg.num_attention_heads, -1))
    q_pe = rope(q[..., cfg.qk_nope_head_dim:], positions[..., None],
                cfg.rope_theta)
    return q[..., :cfg.qk_nope_head_dim], q_pe


def mla_latent(p, pre, h, positions, cfg):
    """The cache row of each token: [Nkv(ckv), RoPE(k_pe)], width
    kv_lora_rank + qk_rope_head_dim."""
    kv = _mm(h, p[pre + "attn.kv_a.weight"])
    c = rms_norm(kv[..., :cfg.kv_lora_rank],
                 p[pre + "attn.kv_norm.weight"], cfg.rms_norm_eps)
    return jnp.concatenate(
        [c, rope(kv[..., cfg.kv_lora_rank:], positions, cfg.rope_theta)],
        axis=-1)


def _kv_b(p, pre, cfg):
    return p[pre + "attn.kv_b.weight"].reshape(
        cfg.kv_lora_rank, cfg.num_attention_heads, -1)


def _softmax_scale(cfg):
    return jnp.float32(1.0 / np.sqrt(cfg.qk_nope_head_dim
                                     + cfg.qk_rope_head_dim))


def mla_expanded(p, pre, q_nope, q_pe, rows, mask, cfg):
    """Attention as published: keys and values expanded per head from the
    latent rows. q_* [B, T, H, .], rows [B, S, W], mask [T, S] True =
    attend -> [B, T, H * v_head_dim]."""
    rank, nope = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    kv = jnp.einsum("bsc,chd->bshd", rows[..., :rank], _kv_b(p, pre, cfg),
                    preferred_element_type=jnp.float32).astype(rows.dtype)
    scores = (jnp.einsum("bthd,bshd->bhts", q_nope, kv[..., :nope],
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bthr,bsr->bhts", q_pe, rows[..., rank:],
                           preferred_element_type=jnp.float32))
    scores = jnp.where(mask[None, None], scores * _softmax_scale(cfg),
                       jnp.float32(-1e30))
    probs = jax.nn.softmax(scores, axis=-1).astype(rows.dtype)
    out = jnp.einsum("bhts,bshd->bthd", probs, kv[..., nope:],
                     preferred_element_type=jnp.float32).astype(rows.dtype)
    return out.reshape(out.shape[:2] + (-1,))


def mla_absorbed(p, pre, q_nope, q_pe, ctx, att_lens, cfg):
    """Decode attention in the latent space: q_* [N, H, .], ctx [N, S, W]
    the cached rows, row n attends to its first att_lens[n] positions ->
    [N, H * v_head_dim]."""
    rank, nope = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    kv_b = _kv_b(p, pre, cfg)
    q_lat = jnp.einsum("nhd,chd->nhc", q_nope, kv_b[..., :nope],
                       preferred_element_type=jnp.float32).astype(ctx.dtype)
    scores = jnp.einsum("nhw,nsw->nhs",
                        jnp.concatenate([q_lat, q_pe], axis=-1), ctx,
                        preferred_element_type=jnp.float32)
    attend = jnp.arange(ctx.shape[1])[None, :] < att_lens[:, None]
    scores = jnp.where(attend[:, None, :], scores * _softmax_scale(cfg),
                       jnp.float32(-1e30))
    probs = jax.nn.softmax(scores, axis=-1).astype(ctx.dtype)
    o_lat = jnp.einsum("nhs,nsc->nhc", probs, ctx[..., :rank],
                       preferred_element_type=jnp.float32).astype(ctx.dtype)
    out = jnp.einsum("nhc,chd->nhd", o_lat, kv_b[..., nope:],
                     preferred_element_type=jnp.float32).astype(ctx.dtype)
    return out.reshape(out.shape[0], -1)


def layer_tail(p, i, x, attn, cfg, live=None):
    """Everything of layer i after its attention: the out-projection, the
    two inner norms and the MLP or the expert layer. x, attn [..., .];
    `live` [tokens] switches rows off in the routing (frozen rows of a
    decode batch). Returns (y, counts int32 [len(COUNTERS)]; zeros for a
    dense layer)."""
    pre, eps = f"layers.{i}.", cfg.rms_norm_eps
    a = x + rms_norm(_mm(attn, p[pre + "attn.o.weight"]),
                     p[pre + "norm2.weight"], eps)
    h = rms_norm(a, p[pre + "norm3.weight"], eps)
    if i < cfg.first_k_dense_replace:
        m = gated_mlp(h, p[pre + "mlp.gate.weight"],
                      p[pre + "mlp.up.weight"], p[pre + "mlp.down.weight"])
        counts = jnp.zeros((len(COUNTERS),), jnp.int32)
    else:
        flat = h.reshape(-1, h.shape[-1])
        routed, counts = held_experts_mlp(
            flat, p[pre + "moe.router.weight"],
            p[pre + "moe.experts.gate.weight"],
            p[pre + "moe.experts.up.weight"],
            p[pre + "moe.experts.down.weight"], cfg.held,
            cfg.num_experts_per_tok, cfg.routed_scaling_factor, live)
        shared = gated_mlp(flat, p[pre + "moe.shared.gate.weight"],
                           p[pre + "moe.shared.up.weight"],
                           p[pre + "moe.shared.down.weight"])
        m = (routed + shared.astype(jnp.float32)).astype(h.dtype) \
            .reshape(h.shape)
    return a + rms_norm(m, p[pre + "norm4.weight"], eps), counts


def _dense_layers(p, ids, cfg):
    """The forward over whole sequences ids [B, T]: (hidden states after
    the last layer, per-layer latent rows [B, T, W], counts)."""
    T = ids.shape[1]
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), ids.shape)
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    x = p["embed.weight"][ids]
    rows_of, total = [], jnp.zeros((len(COUNTERS),), jnp.int32)
    for i in range(cfg.num_hidden_layers):
        pre = f"layers.{i}."
        h = rms_norm(x, p[pre + "norm1.weight"], cfg.rms_norm_eps)
        q_nope, q_pe = mla_queries(p, pre, h, positions, cfg)
        rows = mla_latent(p, pre, h, positions, cfg)
        rows_of.append(rows)
        x, counts = layer_tail(
            p, i, x, mla_expanded(p, pre, q_nope, q_pe, rows, causal, cfg),
            cfg)
        total = merge_counts(total, counts)
    return x, rows_of, total


def _head(p, x, cfg):
    return jnp.dot(rms_norm(x, p["norm_f.weight"], cfg.rms_norm_eps),
                   p["lm_head.weight"], preferred_element_type=jnp.float32)


def forward(params, ids, cfg: PanguMoEConfig):
    """Logits [B, T, V] (float32) of ids [B, T]: the family's forward, no
    cache."""
    x, _, _ = _dense_layers(params, ids, cfg)
    return _head(params, x, cfg)


@functools.partial(jax.jit, static_argnums=(2,))
def prefill(params, ids, cfg: PanguMoEConfig):
    """The engine's dense prefill: (last-position logits [B, V], L-tuple
    of latent rows [B, max_seq_len, W] zero-padded behind the prompt,
    counts). One compilation a prompt length, as models.generation.prefill;
    the padded rows keep `write_prefill_scatter` at one compilation."""
    x, rows_of, counts = _dense_layers(params, ids, cfg)
    pad = cfg.max_seq_len - ids.shape[1]
    return (_head(params, x[:, -1], cfg),
            tuple(jnp.pad(r, ((0, 0), (0, pad), (0, 0))) for r in rows_of),
            counts)


# ---------------------------------------------- decode against the pool
def _token_embed(params, tokens, positions):
    return params["embed.weight"][tokens[:, None]]


def _decode_layer(cfg, params, i, x, pool, slot_blocks, slot_offsets,
                  tables, positions, att_lens, live, ragged,
                  state_slots=None, att_starts=None, table_starts=None):
    """One layer for N rows of one token each against the latent pool:
    write the token's row at its slot, gather each row's blocks through
    its table (`paged_cache.write_rows` / `gather_rows`), attend in the
    latent space. Composed of XLA operations (`ragged` has no kernel to
    choose here yet)."""
    pre = f"layers.{i}."
    h = rms_norm(x[:, 0], params[pre + "norm1.weight"], cfg.rms_norm_eps)
    q_nope, q_pe = mla_queries(params, pre, h, positions, cfg)
    row = mla_latent(params, pre, h, positions, cfg)
    pool = write_rows(pool, row, slot_blocks, slot_offsets)
    ctx = gather_rows(pool, tables, (cfg.latent_width,))
    attn = mla_absorbed(params, pre, q_nope, q_pe, ctx.astype(h.dtype),
                        att_lens, cfg)
    y, counts = layer_tail(params, i, x[:, 0], attn, cfg, live)
    return y[:, None], pool, counts


def _decode_head(cfg, params, x):
    return _head(params, x[:, 0], cfg)


@functools.lru_cache(maxsize=None)
def serving_spec(cfg: PanguMoEConfig) -> ModelSpec:
    """The spec `LLMEngine` serves this family through."""
    return ModelSpec(
        family="pangu_ultra_moe", num_layers=cfg.num_hidden_layers,
        max_seq_len=cfg.max_seq_len, cache_layout="latent",
        cache_shape=(cfg.latent_width,), cache_dtype=cfg.dtype,
        embed=_token_embed,
        decode_layer=functools.partial(_decode_layer, cfg),
        head=functools.partial(_decode_head, cfg),
        prefill=lambda params, ids: prefill(params, ids, cfg),
        counters=COUNTERS, config=cfg,
        expert_shape=(cfg.held[1], cfg.hidden_size,
                      cfg.moe_intermediate_size)
        if cfg.num_hidden_layers > cfg.first_k_dense_replace else ())


# ------------------------------------------------------------ the Layer
class PanguMoE(nn.Layer):
    """The family as a `paddle.nn.Layer`: parameters under the flat names
    of `param_shapes` (N(0, 0.02), norms 1), `forward(ids)` -> logits
    [B, T, V]. `LLMEngine.from_model` serves it."""

    def __init__(self, cfg: PanguMoEConfig = None, **kwargs):
        super().__init__()
        self.cfg = cfg or PanguMoEConfig(**kwargs)
        for name, (shape, dtype) in param_shapes(self.cfg).items():
            init = I.Constant(1.0) if len(shape) == 1 \
                else I.Normal(0.0, 0.02)
            self.add_parameter(name, self.create_parameter(
                list(shape), dtype=dtype, default_initializer=init))

    def forward(self, input_ids):
        return dispatch(
            "pangu_moe_forward",
            lambda params, ids: forward(params, ids, self.cfg),
            (dict(self.named_parameters()), input_ids), {}, True)

    def serving_spec(self) -> ModelSpec:
        return serving_spec(self.cfg)
