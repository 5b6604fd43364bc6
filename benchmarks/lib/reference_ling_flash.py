"""Ling-3.0-flash forward in plain float32 jax.numpy: the reference.

Follows the layer equations of the published config (huggingface.co/
inclusionAI/Ling-3.0-flash config.json, model_type bailing_hybrid; written
out in paddle_tpu/models/ling_flash.py, the readings under `assumed` in
the configuration file): RMSNorm, layer p (its published index) multi-head
latent attention when (p + 1) % layer_group_size == 0 and Kimi Delta
Attention otherwise, a dense SwiGLU in the first `first_k_dense_replace`
layers and a shared + routed expert block after, the router a sigmoid over
all experts, chosen over the scores plus the expert bias, group-limited.
KDA is the recurrence AS WRITTEN, one `lax.scan` step a position (no
chunks, no cache, no kernel); attention expands the latent rows to every
head's keys and values (no absorption, no cache); the experts are a scan
over the held ones. Nothing imported from paddle_tpu. Matmuls at "highest"
precision.

The share: `size["held"] = (first, count)` says which routed experts the
chip holds; the reference loops over exactly those (every token through
every held expert, weighted by its router weight, 0 where not chosen) and
leaves out what the others would add, as the program does.

It reads the program's own arrays (bfloat16 on the chip) and upcasts ONE
matrix at a time; attention runs in blocks of heads and the head in blocks
of positions, so that 5,120 positions fit beside the engine.

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


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + F32(eps)) \
        * w.astype(F32)


def _l2(x):
    return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + F32(1e-6))


def _rope_pairs(x, pos, theta):
    """x [T, ..., d], pos [T]: dimensions 2i and 2i + 1 rotated together
    (interleaved)."""
    half = x.shape[-1] // 2
    inv = F32(theta) ** (-jnp.arange(half, dtype=F32) / F32(half))
    ang = pos.astype(F32).reshape((-1,) + (1,) * (x.ndim - 1)) * inv
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                      b * jnp.cos(ang) + a * jnp.sin(ang)],
                     -1).reshape(x.shape)


def _swiglu(x, wg, wu, wd, limit, low):
    gate, up = _mm(x, wg, low), _mm(x, wu, low)
    if limit > 0:
        gate, up = jnp.minimum(gate, limit), jnp.clip(up, -limit, limit)
    return _mm(jax.nn.silu(gate) * up, wd, low)


def _kda(p, b, x, size, low):
    """Kimi Delta Attention over x [T, hidden] from zero state, one position
    after another."""
    H, d, K = size["num_attention_heads"], size["head_dim"], \
        size["short_conv_kernel_size"]
    T = x.shape[0]
    u = _mm(x, p[b + "kda.qkv.weight"], low)              # [T, H * 3 * d]
    w = p[b + "kda.conv.weight"].astype(F32)              # [K, H * 3 * d]
    padded = jnp.concatenate([jnp.zeros((K - 1, u.shape[1]), F32), u])
    c = jax.nn.silu(sum(padded[j:j + T] * w[j] for j in range(K)))
    c = c.reshape(T, H, 3, d)
    q = _l2(c[:, :, 0]) / F32(math.sqrt(d))
    k, v = _l2(c[:, :, 1]), c[:, :, 2]
    beta = jax.nn.sigmoid(_mm(x, p[b + "kda.b.weight"], low))    # [T, H]
    a = (_mm(x, p[b + "kda.g.weight"], low)
         + p[b + "kda.dt_bias"].astype(F32)).reshape(T, H, d)
    g = F32(size["kda_lower_bound"]) * jax.nn.sigmoid(
        jnp.exp(p[b + "kda.A_log"].astype(F32))[:, None] * a)

    def step(S, t):
        q_t, k_t, v_t, g_t, b_t = t                       # [H, .]
        S = jnp.exp(g_t)[:, :, None] * S                  # a decay a row
        r = jnp.einsum("hkv,hk->hv", S, k_t)
        S = S + jnp.einsum("hk,hv->hkv", k_t, b_t[:, None] * (v_t - r))
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    _, o = jax.lax.scan(step, jnp.zeros((H, d, d), F32), (q, k, v, g, beta))
    o = _rms(o, p[b + "kda.norm.weight"], size["rms_norm_eps"])
    o = o * jax.nn.sigmoid(_mm(x, p[b + "kda.og.weight"], low)
                           .reshape(T, H, d))
    return _mm(o.reshape(T, H * d), p[b + "kda.o.weight"], low)


def _mla(p, b, x, pos, size, low):
    """Multi-head latent attention with keys and values expanded per head
    from the latent rows, a head-wise sigmoid gate on the output."""
    H, T = size["num_attention_heads"], x.shape[0]
    rank, nope = size["kv_lora_rank"], size["qk_nope_head_dim"]
    rope, dv = size["qk_rope_head_dim"], size["v_head_dim"]
    q = _mm(x, p[b + "attn.q.weight"], low).reshape(T, H, nope + rope)
    q_pe = _rope_pairs(q[..., nope:], pos, size["rope_theta"])
    kv = _mm(x, p[b + "attn.kv_a.weight"], low)
    c = _rms(kv[:, :rank], p[b + "attn.kv_norm.weight"], size["rms_norm_eps"])
    k_pe = _rope_pairs(kv[:, rank:], pos, size["rope_theta"])   # [T, rope]
    kv = _mm(c, p[b + "attn.kv_b.weight"], low).reshape(T, H, nope + dv)
    keys = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe[:, None], (T, H, rope))], -1)
    queries = jnp.concatenate([q[..., :nope], q_pe], -1)
    values = kv[..., nope:]
    causal = jnp.tril(jnp.ones((T, T), bool))
    hb = min(HEAD_BLOCK, H)

    def blocks(a):                      # [T, H, D] -> [H / hb, T, hb, D]
        return a.reshape(T, H // hb, hb, -1).transpose(1, 0, 2, 3)

    def block(_, qkv):
        q_b, k_b, v_b = qkv
        s = jnp.einsum("thd,shd->hts", _round(q_b, low), _round(k_b, low)) \
            * F32(1 / math.sqrt(nope + rope))
        s = jnp.where(causal, s, F32(-jnp.inf))
        return None, jnp.einsum("hts,shd->thd",
                                _round(jax.nn.softmax(s, -1), low),
                                _round(v_b, low))

    _, outs = jax.lax.scan(block, None,
                           (blocks(queries), blocks(keys), blocks(values)))
    att = outs.transpose(1, 0, 2, 3).reshape(T, H, dv) \
        * jax.nn.sigmoid(_mm(x, p[b + "attn.gate.weight"], low))[..., None]
    return _mm(att.reshape(T, H * dv), p[b + "attn.o.weight"], low)


def route(scores, bias, size):
    """The chosen experts [T, top_k] of sigmoid `scores` [T, E]: over
    scores + bias, within each token's `topk_group` best of `n_group`
    groups (a group ranked by the sum of its two best)."""
    T, E = scores.shape
    n_group = size["n_group"]
    choice = scores + bias.astype(F32)
    grouped = choice.reshape(T, n_group, E // n_group)
    group_score = jnp.sort(grouped, -1)[..., -2:].sum(-1)
    rank = jnp.argsort(jnp.argsort(-group_score, -1), -1)
    kept = jnp.repeat(rank < size["topk_group"], E // n_group, axis=1)
    masked = jnp.where(kept, choice, -jnp.inf)
    return jnp.argsort(-masked, -1)[:, :size["num_experts_per_tok"]]


def _experts(p, b, x, size, layer, low):
    """The held experts' part plus the shared expert, and the number of
    (token, held expert) pairs."""
    first, count = size["held"]
    limit, shared_limit = (float(lim[layer]) if lim else 0.0 for lim in (
        size["expert_swiglu_limits"], size["shared_swiglu_limits"]))
    with jax.default_matmul_precision("highest"):
        scores = jax.nn.sigmoid(x @ p[b + "router.weight"].astype(F32))
    top_i = route(scores, p[b + "router.bias"], size)
    top_s = jnp.take_along_axis(scores, top_i, -1)
    weight = top_s / jnp.sum(top_s, -1, keepdims=True) \
        * F32(size["routed_scaling_factor"])
    out = _swiglu(x, p[b + "shared.gate.weight"], p[b + "shared.up.weight"],
                  p[b + "shared.down.weight"], shared_limit, low)

    def expert(out, w):
        """One held expert: every token through it, weighted by its router
        weight, 0 where the token did not choose it."""
        e, w_gate, w_up, w_down = w
        w_e = jnp.sum(jnp.where(top_i == first + e, weight, F32(0)), -1)
        return out + w_e[:, None] * _swiglu(x, w_gate, w_up, w_down, limit,
                                            low), jnp.sum(w_e > 0)

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
        for i, published in enumerate(size["published_layers"]):
            b = f"layers.{i}."
            h = _rms(x, p[b + "norm1.weight"], eps)
            if (published + 1) % size["layer_group_size"] == 0:
                x = x + _mla(p, b, h, pos, size, low)
            else:
                x = x + _kda(p, b, h, size, low)
            h = _rms(x, p[b + "norm2.weight"], eps)
            if i < size["first_k_dense_replace"]:
                x = x + _swiglu(h, p[b + "mlp.gate.weight"],
                                p[b + "mlp.up.weight"],
                                p[b + "mlp.down.weight"], 0.0, low)
            else:
                m, n = _experts(p, b + "moe.", h, size, i, low)
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
    logit at t ([T] float32), the logits at the positions `rows_at`, and
    the best token at every position. The head runs `POSITION_BLOCK`
    positions at a time: [T, V] logits are never whole."""
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
    published keys (`held`: (first, count) of the routed experts held;
    `published_layers`: the published index of each layer, None = 0, 1,
    ...; the two SwiGLU limit lists one entry a layer, () = none)."""
    get = cfg.get if isinstance(cfg, dict) else lambda k: getattr(cfg, k)
    keys = ("num_hidden_layers", "layer_group_size", "first_k_dense_replace",
            "num_attention_heads", "head_dim", "short_conv_kernel_size",
            "kda_lower_bound", "kv_lora_rank", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim", "rope_theta",
            "num_experts_per_tok", "n_group", "topk_group",
            "routed_scaling_factor", "expert_swiglu_limits",
            "shared_swiglu_limits", "rms_norm_eps", "held",
            "published_layers")
    size = {k: get(k) for k in keys}
    if size["published_layers"] is None:
        size["published_layers"] = tuple(range(size["num_hidden_layers"]))
    return size
