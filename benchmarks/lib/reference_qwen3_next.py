"""Qwen3-Next forward in plain float32 jax.numpy: the reference.

Follows the layer equations of the published config (huggingface.co/Qwen/
Qwen3-Next-80B-A3B-Instruct config.json, model_type qwen3_next; written out
in paddle_tpu/models/qwen3_next.py, the inferences under `assumed` in the
configuration file): zero-centred RMSNorm, layer i full attention when
(i + 1) % full_attention_interval == 0 and Gated DeltaNet otherwise, a
softmax router over all experts with the top-k renormalised, a gated shared
expert. The DeltaNet is the recurrence AS WRITTEN, one `lax.scan` step a
position (no chunks, no cache); attention expands the key-value heads to
the query heads (no grouping, no cache); the experts are a scan over the
held ones. No kernel, no batching, nothing imported from paddle_tpu.
Matmuls at "highest" precision.

The share: `size["held"] = (first, count)` says which routed experts the
chip holds; the reference loops over exactly those (every token through
every held expert, weighted by its router weight, 0 where not chosen) and
leaves out what the others would add, as the program does.

It reads the program's own arrays (bfloat16 on the chip) and upcasts ONE
matrix at a time; attention runs in blocks of heads and the head in blocks
of positions, so that 5,120 positions fit beside the engine's 11.7 GB.

`low`: None, or the name of a dtype below the served one
("float8_e4m3fn"): every matmul operand is rounded to it first. That is
the reading "the reference computed in the nearest precision below", which
the cell's limits must reject (PERF.md).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
HEAD_BLOCK = 4
POSITION_BLOCK = 1024


def _round(x, low):
    return x.astype(low).astype(F32) if low else x


def _mm(a, w, low):
    return _round(a, low) @ _round(w.astype(F32), low)


def _rms0(x, w, eps):
    """Zero-centred: x / rms(x) * (1 + w)."""
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + F32(eps)) \
        * (F32(1) + w.astype(F32))


def _l2(x):
    return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + F32(1e-6))


def _rope(x, pos, theta):
    """x [T, H, d], pos [T]: dimension i pairs with i + d/2."""
    half = x.shape[-1] // 2
    inv = F32(theta) ** (-jnp.arange(half, dtype=F32) / F32(half))
    ang = pos.astype(F32)[:, None, None] * inv
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def _mlp(x, wg, wu, wd, low):
    return _mm(jax.nn.silu(_mm(x, wg, low)) * _mm(x, wu, low), wd, low)


def _delta_net(p, b, x, size, low):
    """Gated DeltaNet over x [T, hidden] from zero state, one position
    after another."""
    Hk, Hv = size["linear_num_key_heads"], size["linear_num_value_heads"]
    dk, dv = size["linear_key_head_dim"], size["linear_value_head_dim"]
    K, T = size["linear_conv_kernel_dim"], x.shape[0]
    kd, vd = Hk * dk, Hv * dv
    mixed = _mm(x, p[b + "gdn.qkvz.weight"], low)
    ba = _mm(x, p[b + "gdn.ba.weight"], low)
    u, z = mixed[:, :2 * kd + vd], mixed[:, 2 * kd + vd:]
    w = p[b + "gdn.conv.weight"].astype(F32)                # [C, K]
    padded = jnp.concatenate([jnp.zeros((K - 1, u.shape[1]), F32), u])
    c = jax.nn.silu(sum(padded[j:j + T] * w[:, j] for j in range(K)))
    q = _l2(c[:, :kd].reshape(T, Hk, dk)) / F32(math.sqrt(dk))
    k = _l2(c[:, kd:2 * kd].reshape(T, Hk, dk))
    v = c[:, 2 * kd:].reshape(T, Hv, dv)
    q, k = (jnp.repeat(a, Hv // Hk, axis=1) for a in (q, k))
    beta = jax.nn.sigmoid(ba[:, :Hv])
    g = -jnp.exp(p[b + "gdn.A_log"].astype(F32)) \
        * jax.nn.softplus(ba[:, Hv:] + p[b + "gdn.dt_bias"].astype(F32))

    def step(S, t):
        q_t, k_t, v_t, g_t, b_t = t                  # [Hv, .]
        S = jnp.exp(g_t)[:, None, None] * S
        r = jnp.einsum("hkv,hk->hv", S, k_t)
        S = S + jnp.einsum("hk,hv->hkv", k_t, b_t[:, None] * (v_t - r))
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    _, o = jax.lax.scan(step, jnp.zeros((Hv, dk, dv), F32),
                        (q, k, v, g, beta))
    o = o / jnp.sqrt(jnp.mean(o * o, -1, keepdims=True)
                     + F32(size["rms_norm_eps"])) \
        * p[b + "gdn.norm.weight"].astype(F32)
    o = o * jax.nn.silu(z.reshape(T, Hv, dv))
    return _mm(o.reshape(T, vd), p[b + "gdn.out.weight"], low)


def _attention(p, b, x, pos, size, low):
    H, G = size["num_attention_heads"], size["num_key_value_heads"]
    D, eps, T = size["head_dim"], size["rms_norm_eps"], x.shape[0]
    rot = int(D * size["partial_rotary_factor"])
    qg = _mm(x, p[b + "attn.q.weight"], low).reshape(T, H, 2 * D)
    q, gate = qg[..., :D], qg[..., D:]
    k = _mm(x, p[b + "attn.k.weight"], low).reshape(T, G, D)
    v = _mm(x, p[b + "attn.v.weight"], low).reshape(T, G, D)
    q = _rms0(q, p[b + "attn.q_norm.weight"], eps)
    k = _rms0(k, p[b + "attn.k_norm.weight"], eps)

    def rotate(a):
        return jnp.concatenate(
            [_rope(a[..., :rot], pos, size["rope_theta"]), a[..., rot:]], -1)

    q, k = rotate(q), rotate(k)
    # every query head gets its own copy of its key-value head
    k, v = (jnp.repeat(a, H // G, axis=1) for a in (k, v))
    causal = jnp.tril(jnp.ones((T, T), bool))
    hb = min(HEAD_BLOCK, H)

    def blocks(a):                      # [T, H, D] -> [H / hb, T, hb, D]
        return a.reshape(T, H // hb, hb, D).transpose(1, 0, 2, 3)

    def block(_, qkv):
        """`hb` heads at a time, so that [hb, T, T] scores fit."""
        q_b, k_b, v_b = qkv
        s = jnp.einsum("thd,shd->hts", _round(q_b, low), _round(k_b, low)) \
            * F32(1 / math.sqrt(D))
        s = jnp.where(causal, s, F32(-jnp.inf))
        return None, jnp.einsum("hts,shd->thd",
                                _round(jax.nn.softmax(s, -1), low),
                                _round(v_b, low))

    _, outs = jax.lax.scan(block, None, (blocks(q), blocks(k), blocks(v)))
    att = outs.transpose(1, 0, 2, 3).reshape(T, H, D) * jax.nn.sigmoid(gate)
    return _mm(att.reshape(T, H * D), p[b + "attn.o.weight"], low)


def _experts(p, b, x, size, low):
    """The held experts' part plus the gated shared expert, and the number
    of (token, held expert) pairs."""
    first, count = size["held"]
    with jax.default_matmul_precision("highest"):
        scores = jax.nn.softmax(x @ p[b + "router.weight"].astype(F32), -1)
    top_s, top_i = jax.lax.top_k(scores, size["num_experts_per_tok"])
    weight = top_s / jnp.sum(top_s, -1, keepdims=True)
    out = _mlp(x, p[b + "shared.gate.weight"], p[b + "shared.up.weight"],
               p[b + "shared.down.weight"], low) \
        * jax.nn.sigmoid(_mm(x, p[b + "shared_gate.weight"], low))

    def expert(out, w):
        """One held expert: every token through it, weighted by its router
        weight, 0 where the token did not choose it."""
        e, w_gate, w_up, w_down = w
        w_e = jnp.sum(jnp.where(top_i == first + e, weight, F32(0)), -1)
        return out + w_e[:, None] * _mlp(x, w_gate, w_up, w_down, low), \
            jnp.sum(w_e > 0)

    out, pairs = jax.lax.scan(
        expert, out, (jnp.arange(count), p[b + "experts.gate.weight"],
                      p[b + "experts.up.weight"],
                      p[b + "experts.down.weight"]))
    return out, jnp.sum(pairs)


def hidden_and_pairs(params, ids, size, low=None):
    """ids [T] int32 -> (the normed hidden states before the head [T, h]
    float32, (token, held expert) pairs over all layers)."""
    with jax.default_matmul_precision("highest"):
        p, eps = params, size["rms_norm_eps"]
        pos = jnp.arange(ids.shape[0], dtype=jnp.int32)
        x = p["embed.weight"][ids].astype(F32)
        pairs = 0
        for i in range(size["num_hidden_layers"]):
            b = f"layers.{i}."
            h = _rms0(x, p[b + "norm1.weight"], eps)
            if (i + 1) % size["full_attention_interval"] == 0:
                x = x + _attention(p, b, h, pos, size, low)
            else:
                x = x + _delta_net(p, b, h, size, low)
            m, n = _experts(p, b + "moe.",
                            _rms0(x, p[b + "norm2.weight"], eps), size, low)
            x, pairs = x + m, pairs + n
        return _rms0(x, p["norm_f.weight"], eps), pairs


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
    """The numbers the reference needs, from any object or dict with the
    published keys (`held`: (first, count) of the routed experts held)."""
    get = cfg.get if isinstance(cfg, dict) else lambda k: getattr(cfg, k)
    keys = ("num_hidden_layers", "full_attention_interval",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "partial_rotary_factor", "rope_theta", "linear_num_key_heads",
            "linear_num_value_heads", "linear_key_head_dim",
            "linear_value_head_dim", "linear_conv_kernel_dim",
            "num_experts_per_tok", "rms_norm_eps", "held")
    return {k: get(k) for k in keys}
