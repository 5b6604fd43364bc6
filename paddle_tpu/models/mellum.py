"""Mellum 2: a decoder family of sliding-window attention layers beside
full-attention ones, three to one, grouped-query attention with a rotary
embedding a layer KIND, and a routed expert block in every layer; in pure
`jax.numpy` like models/qwen3_next.py, plus the `nn.Layer` that holds its
parameters and the `ModelSpec` that serves it through `LLMEngine`.

Source of the shapes: huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct
`config.json` (model_type `mellum`). N is RMSNorm, `x / rms(x) * w` (eps
`rms_norm_eps`, w = 1 at init), no bias anywhere. Layer i of
`num_hidden_layers`:

    h = x + Attn_i(N1(x));  y = h + Moe(N2(h));  logits = Nf(y) W_head

Attention (H query heads, G key-value heads of D, G < H; KV head j serves
the query heads j * H/G .. (j + 1) * H/G - 1):

    q, k, v = x W_q, x W_k, x W_v;  q, k <- N_D(q), N_D(k) per head
    (ASSUMED: no key of the config states the per-head norm; it is the
    convention of the lineage whose key names the config uses)
    q, k <- RoPE_kind(q, k) on all D dimensions, i paired with i + D/2
    scores q . k / sqrt(D), softmax in float32, out = attn W_o

- `layer_types[i] == "sliding_attention"` (i % 4 != 3): position p attends
  to positions max(0, p - window + 1) .. p (`sliding_window` keys with its
  own). RoPE with inv_freq_j = theta^(-2j / D), no scaling.
- `layer_types[i] == "full_attention"`: causal over everything. RoPE with
  YaRN (`rope_parameters.full_attention`): inv_freq_j blended between
  theta^(-2j / D) and that over `factor` by a linear ramp between the
  correction dimensions of `beta_fast` and `beta_slow`,
  d(beta) = D ln(original_max / (2 pi beta)) / (2 ln theta), floor of the
  first and ceil of the second (ASSUMED, as transformers'
  `_compute_yarn_parameters`), clamped to [0, D/2 - 1]; cos and sin times
  `attention_factor`.

The cache of a full layer is a row a position, k and v of G x D ("rows");
that of a sliding layer is the same row, but only of the last `window`
positions ("window": the cache manager keeps those layers' blocks in a
group of their own and takes back each block the window has moved past).

Expert block: p = softmax(x W_r) in float32 over all `num_experts`; the
`num_experts_per_tok` largest, renormalised to sum to 1 (`norm_topk_prob`);
expert e is (silu(x W_g) * (x W_u)) W_d; no shared expert. Through
`distributed.moe.held_experts_mlp` (scoring "softmax"), which is told the
experts held here: all of them (`held = (0, num_experts)`) unless the
config says otherwise.

Dtypes: activations take the dtype of `embed.weight`; with bfloat16 weights
every matmul accumulates in float32 and rounds its result to bfloat16. The
router is float32 at "highest" precision; logits are float32.
"""
from __future__ import annotations

import functools
import math
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
from .pangu_moe import COUNTERS, _mm, rms_norm
from .qwen3_next import (QUERY_BLOCK, _grouped_attention, _rows_attention,
                         full_rows_attention, map_token_blocks)
from .spec import ModelSpec, merge_counts

__all__ = ["MellumConfig", "Mellum", "param_shapes", "forward", "prefill",
           "serving_spec", "yarn_inv_freq", "init_value"]

F32 = jnp.float32
SLIDING, FULL = "sliding_attention", "full_attention"


@dataclass(frozen=True)
class MellumConfig:
    vocab_size: int = 98304
    hidden_size: int = 2304
    num_hidden_layers: int = 28
    #: per layer SLIDING or FULL; None = three sliding to one full
    layer_types: Tuple[str, ...] = None
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    sliding_window: int = 1024
    rope_theta: float = 500000.0
    # rope_parameters.full_attention (rope_type "yarn")
    yarn_factor: float = 16.0
    yarn_original_max_position_embeddings: int = 8192
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_attention_factor: float = 1.2772588722239782
    moe_intermediate_size: int = 896
    num_experts: int = 64
    num_experts_per_tok: int = 8
    rms_norm_eps: float = 1e-6
    max_seq_len: int = 2048
    #: (first, count) of the routed experts this chip holds; None = all
    held_experts: Tuple[int, int] = None
    dtype: str = "float32"

    @property
    def held(self) -> Tuple[int, int]:
        return self.held_experts or (0, self.num_experts)

    @property
    def kinds(self) -> Tuple[str, ...]:
        return tuple(self.layer_types) if self.layer_types else tuple(
            FULL if i % 4 == 3 else SLIDING
            for i in range(self.num_hidden_layers))


def param_shapes(cfg: MellumConfig) -> dict:
    """{name: (shape, dtype name)} of every parameter, flat."""
    h, dt, f = cfg.hidden_size, cfg.dtype, cfg.moe_intermediate_size
    H, G, D = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    held = cfg.held[1]
    out = {"embed.weight": ((cfg.vocab_size, h), dt),
           "norm_f.weight": ((h,), dt),
           "lm_head.weight": ((h, cfg.vocab_size), dt)}
    for i in range(cfg.num_hidden_layers):
        pre = f"layers.{i}."
        out[pre + "norm1.weight"] = ((h,), dt)
        out[pre + "norm2.weight"] = ((h,), dt)
        out[pre + "attn.q.weight"] = ((h, H * D), dt)
        out[pre + "attn.k.weight"] = ((h, G * D), dt)
        out[pre + "attn.v.weight"] = ((h, G * D), dt)
        out[pre + "attn.q_norm.weight"] = ((D,), dt)
        out[pre + "attn.k_norm.weight"] = ((D,), dt)
        out[pre + "attn.o.weight"] = ((H * D, h), dt)
        out[pre + "moe.router.weight"] = ((h, cfg.num_experts), "float32")
        out[pre + "moe.experts.gate.weight"] = ((held, h, f), dt)
        out[pre + "moe.experts.up.weight"] = ((held, h, f), dt)
        out[pre + "moe.experts.down.weight"] = ((held, f, h), dt)
    return out


def init_value(name: str):
    """How a parameter that is no matrix starts: ("constant", c); None for
    a matrix (N(0, initializer_range)). Every norm is a plain weight, 1."""
    if "norm" in name.rsplit(".", 2)[-2]:
        return ("constant", 1.0)
    return None


# ------------------------------------------------------------ the rotary
def yarn_inv_freq(cfg: MellumConfig) -> np.ndarray:
    """The D/2 inverse frequencies of the full-attention layers (float64):
    extrapolated (the plain ones) below the first correction dimension,
    interpolated (over `yarn_factor`) above the second, a linear ramp
    between."""
    D, half = cfg.head_dim, cfg.head_dim // 2
    plain = cfg.rope_theta ** (-np.arange(half, dtype=np.float64) / half)

    def correction_dim(beta):
        return D * math.log(cfg.yarn_original_max_position_embeddings
                            / (beta * 2 * math.pi)) \
            / (2 * math.log(cfg.rope_theta))

    low = max(math.floor(correction_dim(cfg.yarn_beta_fast)), 0)
    high = min(math.ceil(correction_dim(cfg.yarn_beta_slow)), half - 1)
    if low == high:
        high += 0.001                       # no division by zero
    ramp = np.clip((np.arange(half, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    return plain / cfg.yarn_factor * ramp + plain * (1.0 - ramp)


def _rotary(cfg: MellumConfig, kind: str):
    """(inverse frequencies [D/2] float32, what cos and sin are times) of
    a layer kind."""
    if kind == FULL:
        return yarn_inv_freq(cfg).astype(np.float32), \
            cfg.yarn_attention_factor
    half = cfg.head_dim // 2
    return (cfg.rope_theta ** (-np.arange(half, dtype=np.float64) / half)) \
        .astype(np.float32), 1.0


def _rope(x, positions, inv_freq, scale):
    """Rotate x [..., d] at `positions` (broadcastable to x.shape[:-1]),
    dimension i paired with i + d/2, cos and sin times `scale`."""
    half = x.shape[-1] // 2
    ang = positions.astype(F32)[..., None] * jnp.asarray(inv_freq)
    cos, sin = jnp.cos(ang) * F32(scale), jnp.sin(ang) * F32(scale)
    x1, x2 = x[..., :half].astype(F32), x[..., half:].astype(F32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


# ------------------------------------------------------------- attention
def attn_inputs(p, pre, h, positions, cfg, kind):
    """h [..., hidden] at positions [...] -> (q [..., H, D], k [..., G, D],
    both normed per head and rotated as the layer kind says, v
    [..., G, D])."""
    H, G, D = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    lead, eps = h.shape[:-1], cfg.rms_norm_eps
    q = _mm(h, p[pre + "attn.q.weight"]).reshape(lead + (H, D))
    k = _mm(h, p[pre + "attn.k.weight"]).reshape(lead + (G, D))
    v = _mm(h, p[pre + "attn.v.weight"]).reshape(lead + (G, D))
    q = rms_norm(q, p[pre + "attn.q_norm.weight"], eps)
    k = rms_norm(k, p[pre + "attn.k_norm.weight"], eps)
    inv_freq, scale = _rotary(cfg, kind)
    at = positions[..., None]
    return _rope(q, at, inv_freq, scale), _rope(k, at, inv_freq, scale), v


def _attn_dense(p, pre, h, positions, cfg, kind):
    """An attention layer over whole sequences h [B, T, hidden]: (output
    [B, T, hidden], (k, v) the dense cache rows). `QUERY_BLOCK` queries at
    a time, one block's program for all of them. A full layer's block
    multiplies every key, masked causal, and hands back rows [B, G, T, D].
    A sliding layer's block multiplies only the keys of its band, the
    window - 1 before its first query to its last query, and hands back the
    rows of the last `window` positions alone, [B, G, window, D] (row r the
    position max(0, T - window) + r, zeros behind a shorter prompt)."""
    q, k, v = attn_inputs(p, pre, h, positions, cfg, kind)
    B, T = h.shape[:2]
    W = cfg.sliding_window
    block = min(T, QUERY_BLOCK)
    n = -(-T // block)
    banded = kind == SLIDING and T > W      # else the window holds them all
    band = W - 1 + block
    if banded:
        # key s of the padded arrays is position s - (W - 1)
        pad = ((0, 0), (W - 1, n * block - T), (0, 0), (0, 0))
        keys_of, values_of = jnp.pad(k, pad), jnp.pad(v, pad)

    def attend_block(args):
        q_j, start = args
        at = (start + jnp.arange(block))[:, None]
        if not banded:
            return _grouped_attention(
                q_j, k, v, (at >= jnp.arange(T)[None, :])[None])
        keys = (start - (W - 1) + jnp.arange(band))[None, :]
        return _grouped_attention(
            q_j, jax.lax.dynamic_slice_in_dim(keys_of, start, band, 1),
            jax.lax.dynamic_slice_in_dim(values_of, start, band, 1),
            ((keys >= 0) & (keys <= at) & (keys > at - W))[None])

    blocks = jnp.pad(q, ((0, 0), (0, n * block - T), (0, 0), (0, 0))) \
        .reshape((B, n, block) + q.shape[2:]).swapaxes(0, 1)
    att = jax.lax.map(attend_block, (blocks, jnp.arange(n) * block)) \
        .swapaxes(0, 1).reshape((B, n * block) + q.shape[2:])[:, :T]
    out = _mm(att.reshape(att.shape[:-2] + (-1,)), p[pre + "attn.o.weight"])
    if kind == SLIDING:
        behind = ((0, 0), (0, max(0, W - T)), (0, 0), (0, 0))
        k, v = (jnp.pad(r[:, max(0, T - W):], behind) for r in (k, v))
    return out, (k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3))


# ---------------------------------------------------------- expert block
def moe_block(p, i, h, cfg, live=None):
    """The expert block of layer i on h [..., hidden] (float of h's dtype),
    and the counts; a long prompt in blocks of tokens
    (`qwen3_next.map_token_blocks`)."""
    pre = f"layers.{i}.moe."

    def expert_tokens(flat, on):
        routed, counts = held_experts_mlp(
            flat, p[pre + "router.weight"], p[pre + "experts.gate.weight"],
            p[pre + "experts.up.weight"], p[pre + "experts.down.weight"],
            cfg.held, cfg.num_experts_per_tok, 1.0, on, scoring="softmax")
        return routed.astype(flat.dtype), counts

    return map_token_blocks(expert_tokens, h, live)


def _dense_layers(p, ids, cfg):
    """The forward over whole sequences ids [B, T]: (hidden states after
    the last layer, per layer the dense (k, v) rows, counts)."""
    positions = jnp.broadcast_to(
        jnp.arange(ids.shape[1], dtype=jnp.int32), ids.shape)
    x = p["embed.weight"][ids]
    cached, total = [], jnp.zeros((len(COUNTERS),), jnp.int32)
    for i, kind in enumerate(cfg.kinds):
        pre = f"layers.{i}."
        mixed, rows = _attn_dense(
            p, pre, rms_norm(x, p[pre + "norm1.weight"], cfg.rms_norm_eps),
            positions, cfg, kind)
        cached.append(rows)
        x = x + mixed
        m, counts = moe_block(
            p, i, rms_norm(x, p[pre + "norm2.weight"], cfg.rms_norm_eps),
            cfg)
        x = x + m
        total = merge_counts(total, counts)
    return x, cached, total


def _head(p, x, cfg):
    return jnp.dot(rms_norm(x, p["norm_f.weight"], cfg.rms_norm_eps),
                   p["lm_head.weight"], preferred_element_type=F32)


def forward(params, ids, cfg: MellumConfig):
    """Logits [B, T, V] (float32) of ids [B, T]: the family's forward, no
    cache."""
    x, _, _ = _dense_layers(params, ids, cfg)
    return _head(params, x, cfg)


@functools.partial(jax.jit, static_argnums=(2,))
def prefill(params, ids, cfg: MellumConfig):
    """The engine's dense prefill: (last-position logits [B, V], per layer
    the (k, v) rows, a full layer's [B, G, max_seq_len, D] zero-padded
    behind the prompt, a sliding layer's [B, G, window, D] of the prompt's
    last window, counts). One compilation a prompt length."""
    x, cached, counts = _dense_layers(params, ids, cfg)
    pad = ((0, 0), (0, 0), (0, cfg.max_seq_len - ids.shape[1]), (0, 0))
    return (_head(params, x[:, -1], cfg),
            tuple(rows if kind == SLIDING
                  else tuple(jnp.pad(r, pad) for r in rows)
                  for kind, rows in zip(cfg.kinds, cached)),
            counts)


# ---------------------------------------------- decode against the pools
def _token_embed(params, tokens, positions):
    return params["embed.weight"][tokens[:, None]]


def _decode_layer(cfg, params, i, x, pool, slot_blocks, slot_offsets,
                  tables, positions, att_lens, live, ragged,
                  state_slots=None, att_starts=None, table_starts=None):
    """Layer i for N rows of one token each (`ModelSpec.decode_layer`):
    write the token's k and v at its slot, attend, run the expert block. A
    full layer attends to the first att_lens positions of the sequence's
    whole table through `qwen3_next.full_rows_attention` (under `ragged`,
    where the kernel applies, it walks the row's live blocks; elsewhere the
    table is gathered as flat rows of G * D); a sliding layer gathers its
    WINDOW table (row w of the gathered context is position table_starts +
    w) and attends to att_starts .. att_lens - 1, composed of XLA
    operations."""
    pre, eps, kind = f"layers.{i}.", cfg.rms_norm_eps, cfg.kinds[i]
    G = cfg.num_key_value_heads
    h = rms_norm(x[:, 0], params[pre + "norm1.weight"], eps)
    q, k, v = attn_inputs(params, pre, h, positions, cfg, kind)
    kp, vp = pool
    kp = write_rows(kp, k, slot_blocks, slot_offsets)
    vp = write_rows(vp, v, slot_blocks, slot_offsets)
    if kind == SLIDING:         # as rows of the gathered window
        att_lens, att_starts = att_lens - table_starts, \
            att_starts - table_starts
        flat = (G * cfg.head_dim,)
        att = _rows_attention(
            q, gather_rows(kp, tables, flat).astype(h.dtype),
            gather_rows(vp, tables, flat).astype(h.dtype), att_lens, G,
            att_starts)
    else:
        att = full_rows_attention(q, kp, vp, tables, att_lens, G, ragged)
    y = x[:, 0] + _mm(att.reshape(att.shape[0], -1),
                      params[pre + "attn.o.weight"])
    m, counts = moe_block(
        params, i, rms_norm(y, params[pre + "norm2.weight"], eps), cfg, live)
    return (y + m)[:, None], (kp, vp), counts


def _decode_head(cfg, params, x):
    return _head(params, x[:, 0], cfg)


@functools.lru_cache(maxsize=None)
def serving_spec(cfg: MellumConfig) -> ModelSpec:
    """The spec `LLMEngine` serves this family through: rows (k, v of
    G x D) of every position in the full layers, of the last
    `sliding_window` positions in the sliding ones."""
    caches = tuple("window" if kind == SLIDING else "rows"
                   for kind in cfg.kinds)
    windowed = "window" in caches
    return ModelSpec(
        family="mellum", num_layers=cfg.num_hidden_layers,
        max_seq_len=cfg.max_seq_len,
        cache_layout="hybrid" if windowed else "heads",
        cache_shape=(cfg.num_key_value_heads, cfg.head_dim),
        cache_dtype=cfg.dtype, embed=_token_embed,
        decode_layer=functools.partial(_decode_layer, cfg),
        head=functools.partial(_decode_head, cfg),
        prefill=lambda params, ids: prefill(params, ids, cfg),
        counters=COUNTERS, config=cfg,
        layer_caches=caches if windowed else (),
        window=cfg.sliding_window if windowed else 0,
        expert_shape=(cfg.held[1], cfg.hidden_size,
                      cfg.moe_intermediate_size))


# ------------------------------------------------------------ the Layer
class Mellum(nn.Layer):
    """The family as a `paddle.nn.Layer`: parameters under the flat names
    of `param_shapes` (matrices N(0, 0.02), norms 1), `forward(ids)` ->
    logits [B, T, V]. `LLMEngine.from_model` serves it."""

    def __init__(self, cfg: MellumConfig = None, **kwargs):
        super().__init__()
        self.cfg = cfg or MellumConfig(**kwargs)
        for name, (shape, dtype) in param_shapes(self.cfg).items():
            init = I.Normal(0.0, 0.02) if init_value(name) is None \
                else I.Constant(init_value(name)[1])
            self.add_parameter(name, self.create_parameter(
                list(shape), dtype=dtype, default_initializer=init))

    def forward(self, input_ids):
        return dispatch(
            "mellum_forward",
            lambda params, ids: forward(params, ids, self.cfg),
            (dict(self.named_parameters()), input_ids), {}, True)

    def serving_spec(self) -> ModelSpec:
        return serving_spec(self.cfg)
