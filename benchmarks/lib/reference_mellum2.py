"""Mellum 2 forward in plain float32 jax.numpy: the reference.

Follows the layer equations of the published config (huggingface.co/
JetBrains/Mellum2-12B-A2.5B-Instruct config.json, model_type mellum;
written out in paddle_tpu/models/mellum.py, the inferences under `assumed`
in the configuration file): RMSNorm x / rms(x) * w, layer i a
sliding-window attention layer or a full one as `layer_types[i]` says,
per-head RMSNorm on q and k, a rotary embedding a layer kind (plain for the
sliding layers, YaRN for the full ones, both from their formulas here), a
softmax router over all experts with the top-k renormalised, no shared
expert. No cache, no kernel, no batching, nothing imported from paddle_tpu:
attention expands the key-value heads to the query heads and masks
[T, T] scores by POSITIONS (key <= query, and for a sliding layer key >
query - window); the experts are a scan over the held ones, every token
through every expert, weighted by its router weight, 0 where not chosen.
Matmuls at "highest" precision.

It reads the program's own arrays (bfloat16 on the chip) and upcasts ONE
matrix at a time; attention runs in blocks of heads and the head in blocks
of positions, so that 9,216 positions fit beside the engine's 11 GB and
9,216 x 98,304 logits never stand whole.

`low`: None, or the name of a dtype below the served one
("float8_e4m3fn"): every matmul operand is rounded to it first. That is
the reading "the reference computed in the nearest precision below", which
the cell's limits must reject (PERF.md).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HEAD_BLOCK = 2
POSITION_BLOCK = 1024


def _round(x, low):
    return x.astype(low).astype(F32) if low else x


def _mm(a, w, low):
    return _round(a, low) @ _round(w.astype(F32), low)


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + F32(eps)) \
        * w.astype(F32)


def inverse_frequencies(size, full: bool):
    """(the head_dim / 2 inverse frequencies, what cos and sin are times).
    Sliding layers: theta^(-2j / D), times 1. Full layers, YaRN: below the
    correction dimension of beta_fast the same, above that of beta_slow
    divided by `factor`, a linear ramp between; times attention_factor."""
    D = size["head_dim"]
    plain = [size["rope_theta"] ** (-2.0 * j / D) for j in range(D // 2)]
    if not full:
        return np.asarray(plain, np.float32), 1.0
    y = size["yarn"]

    def dim_of(beta):
        return D * math.log(y["original_max_position_embeddings"]
                            / (beta * 2 * math.pi)) \
            / (2 * math.log(size["rope_theta"]))

    low = max(math.floor(dim_of(y["beta_fast"])), 0)
    high = min(math.ceil(dim_of(y["beta_slow"])), D // 2 - 1)
    if low == high:
        high += 0.001
    out = []
    for j, f in enumerate(plain):
        ramp = min(max((j - low) / (high - low), 0.0), 1.0)
        out.append(f / y["factor"] * ramp + f * (1.0 - ramp))
    return np.asarray(out, np.float32), y["attention_factor"]


def _rope(x, pos, inv, scale):
    """x [T, H, d], pos [T]: dimension i pairs with i + d/2."""
    half = x.shape[-1] // 2
    ang = pos.astype(F32)[:, None, None] * jnp.asarray(inv)
    cos, sin = jnp.cos(ang) * F32(scale), jnp.sin(ang) * F32(scale)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(p, b, x, pos, size, full, low):
    H, G = size["num_attention_heads"], size["num_key_value_heads"]
    D, eps, T = size["head_dim"], size["rms_norm_eps"], x.shape[0]
    q = _mm(x, p[b + "attn.q.weight"], low).reshape(T, H, D)
    k = _mm(x, p[b + "attn.k.weight"], low).reshape(T, G, D)
    v = _mm(x, p[b + "attn.v.weight"], low).reshape(T, G, D)
    q = _rms(q, p[b + "attn.q_norm.weight"], eps)
    k = _rms(k, p[b + "attn.k_norm.weight"], eps)
    inv, scale = inverse_frequencies(size, full)
    q, k = _rope(q, pos, inv, scale), _rope(k, pos, inv, scale)
    # every query head gets its own copy of its key-value head
    k, v = (jnp.repeat(a, H // G, axis=1) for a in (k, v))
    allowed = pos[None, :] <= pos[:, None]
    if not full:
        allowed &= pos[None, :] > pos[:, None] - size["sliding_window"]
    hb = min(HEAD_BLOCK, H)

    def blocks(a):                      # [T, H, D] -> [H / hb, T, hb, D]
        return a.reshape(T, H // hb, hb, D).transpose(1, 0, 2, 3)

    def block(_, qkv):
        """`hb` heads at a time, so that [hb, T, T] scores fit."""
        q_b, k_b, v_b = qkv
        s = jnp.einsum("thd,shd->hts", _round(q_b, low), _round(k_b, low)) \
            * F32(1 / math.sqrt(D))
        s = jnp.where(allowed, s, F32(-jnp.inf))
        return None, jnp.einsum("hts,shd->thd",
                                _round(jax.nn.softmax(s, -1), low),
                                _round(v_b, low))

    _, outs = jax.lax.scan(block, None, (blocks(q), blocks(k), blocks(v)))
    att = outs.transpose(1, 0, 2, 3).reshape(T, H * D)
    return _mm(att, p[b + "attn.o.weight"], low)


def _experts(p, b, x, size, low):
    """The held experts' part (all of them, unless `held` says otherwise),
    and the number of (token, held expert) pairs."""
    first, count = size["held"]
    with jax.default_matmul_precision("highest"):
        scores = jax.nn.softmax(x @ p[b + "router.weight"].astype(F32), -1)
    top_s, top_i = jax.lax.top_k(scores, size["num_experts_per_tok"])
    weight = top_s / jnp.sum(top_s, -1, keepdims=True)

    def expert(out, w):
        """One expert, its matrices upcast here: every token through it,
        weighted by its router weight, 0 where the token did not choose
        it."""
        e, w_gate, w_up, w_down = w
        w_e = jnp.sum(jnp.where(top_i == first + e, weight, F32(0)), -1)
        y = _mm(jax.nn.silu(_mm(x, w_gate, low)) * _mm(x, w_up, low),
                w_down, low)
        return out + w_e[:, None] * y, jnp.sum(w_e > 0)

    out, pairs = jax.lax.scan(
        expert, jnp.zeros_like(x),
        (jnp.arange(count), p[b + "experts.gate.weight"],
         p[b + "experts.up.weight"], p[b + "experts.down.weight"]))
    return out, jnp.sum(pairs)


def hidden_and_pairs(params, ids, size, low=None):
    """ids [T] int32 -> (the normed hidden states before the head [T, h]
    float32, (token, held expert) pairs over all layers)."""
    with jax.default_matmul_precision("highest"):
        p, eps = params, size["rms_norm_eps"]
        pos = jnp.arange(ids.shape[0], dtype=jnp.int32)
        x = p["embed.weight"][ids].astype(F32)
        pairs = 0
        for i, kind in enumerate(size["layer_types"]):
            b = f"layers.{i}."
            x = x + _attention(p, b, _rms(x, p[b + "norm1.weight"], eps),
                               pos, size, kind == "full_attention", low)
            m, n = _experts(p, b + "moe.",
                            _rms(x, p[b + "norm2.weight"], eps), size, low)
            x, pairs = x + m, pairs + n
        return _rms(x, p["norm_f.weight"], eps), pairs


def logits_and_pairs(params, ids, size, low=None):
    """ids [T] int32 -> (logits [T, V] float32, pairs). `size`: the
    configuration's numbers as a dict (see `sizes`)."""
    x, pairs = hidden_and_pairs(params, ids, size, low)
    with jax.default_matmul_precision("highest"):
        return _mm(x, params["lm_head.weight"], low), pairs


def logits(params, ids, size, low=None):
    return logits_and_pairs(params, ids, size, low)[0]


def gaps_and_rows(params, ids, followers, rows_at, size, low=None):
    """One program for both of the runner's comparisons, over one
    teacher-forced row ids [T]: how far `followers[t]` sits below the best
    logit at t ([T] float32; followers = ids shifted by one gives the
    distance of every teacher-forced token below the best), the logits at
    the positions `rows_at`, and the best token at every position. The
    head runs `POSITION_BLOCK` positions at a time: [T, V] logits are never
    whole."""
    x, _ = hidden_and_pairs(params, ids, size, low)
    T = x.shape[0]
    n = -(-T // POSITION_BLOCK)
    pad = n * POSITION_BLOCK - T
    with jax.default_matmul_precision("highest"):
        head = _round(params["lm_head.weight"].astype(F32), low)

        def block(xs):
            x_b, f_b = xs
            lg = _round(x_b, low) @ head
            picked = jnp.take_along_axis(lg, f_b[:, None], -1)[:, 0]
            return jnp.max(lg, -1) - picked, \
                jnp.argmax(lg, -1).astype(jnp.int32)

        gaps, best = jax.lax.map(block, (
            jnp.pad(x, ((0, pad), (0, 0))).reshape(n, POSITION_BLOCK, -1),
            jnp.pad(followers, (0, pad)).reshape(n, POSITION_BLOCK)))
        rows = _round(x[rows_at], low) @ head
    return gaps.reshape(-1)[:T], rows, best.reshape(-1)[:T]


def sizes(cfg) -> dict:
    """The numbers the reference needs, from the program's config object
    (`kinds`, `yarn_*` attributes) or from a dict with the published keys
    (`layer_types`, `rope_parameters`); `held`: (first, count) of the
    routed experts held."""
    if isinstance(cfg, dict):
        get = cfg.get
        full = cfg["rope_parameters"]["full_attention"]
        kinds, theta = tuple(cfg["layer_types"]), full["rope_theta"]
        yarn = {k: full[k] for k in (
            "factor", "original_max_position_embeddings", "beta_fast",
            "beta_slow", "attention_factor")}
        held = get("held") or (0, cfg["num_experts"])
    else:
        get = lambda k: getattr(cfg, k)                         # noqa: E731
        kinds, theta, held = cfg.kinds, cfg.rope_theta, cfg.held
        yarn = {k: getattr(cfg, "yarn_" + k) for k in (
            "factor", "original_max_position_embeddings", "beta_fast",
            "beta_slow", "attention_factor")}
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "sliding_window", "num_experts_per_tok", "rms_norm_eps")
    return {**{k: get(k) for k in keys}, "layer_types": kinds,
            "rope_theta": float(theta), "yarn": yarn, "held": tuple(held)}
