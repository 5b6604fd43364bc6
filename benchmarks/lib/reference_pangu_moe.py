"""openPangu-Ultra-MoE forward in plain float32 jax.numpy: the reference.

Follows the layer equations of the published config (huggingface.co/
FreedomIntelligence/openPangu-Ultra-MoE-718B config.json, model_type
pangu_ultra_moe; the equations are written out in paddle_tpu/models/
pangu_moe.py and the inferences under `assumed` in the configuration
file): RMSNorm, sandwich norms (four a layer), multi-head latent attention
with keys and values EXPANDED per head (no absorbed form, no cache), RoPE
on the rotary part (rotate-half pairing), gated-SiLU MLPs, a sigmoid
router over all routed experts with the top-k normalised and scaled, a
shared expert. No kernel, no cache, no batching, nothing imported from
paddle_tpu. Matmuls at "highest" precision.

The share: `size["held"] = (first, count)` says which routed experts the
chip holds; the reference loops over exactly those (every token through
every held expert, weighted by its router weight, 0 where not chosen) and
leaves out what the others would add, as the program does.

It reads the program's own arrays (bfloat16 on the chip) and upcasts ONE
matrix at a time, never a float32 copy of a layer; attention runs in
blocks of heads so that 2,048 positions fit beside the engine's 11.4 GB.

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
HEAD_BLOCK = 16


def _round(x, low):
    return x.astype(low).astype(F32) if low else x


def _mm(a, w, low):
    return _round(a, low) @ _round(w.astype(F32), low)


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + F32(eps)) \
        * w.astype(F32)


def _rope(x, pos, theta):
    """x [T, ..., d], pos [T]: dimension i pairs with i + d/2."""
    half = x.shape[-1] // 2
    inv = F32(theta) ** (-jnp.arange(half, dtype=F32) / F32(half))
    ang = pos.astype(F32).reshape((-1,) + (1,) * (x.ndim - 2) + (1,)) * inv
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def _mlp(x, wg, wu, wd, low):
    return _mm(jax.nn.silu(_mm(x, wg, low)) * _mm(x, wu, low), wd, low)


def _attention(p, b, x, pos, size, low):
    H, rank = size["num_attention_heads"], size["kv_lora_rank"]
    nope, rot = size["qk_nope_head_dim"], size["qk_rope_head_dim"]
    vd, eps, T = size["v_head_dim"], size["rms_norm_eps"], x.shape[0]
    cq = _rms(_mm(x, p[b + "attn.q_a.weight"], low),
              p[b + "attn.q_norm.weight"], eps)
    kv_a = _mm(x, p[b + "attn.kv_a.weight"], low)
    c = _rms(kv_a[:, :rank], p[b + "attn.kv_norm.weight"], eps)
    k_pe = _rope(kv_a[:, rank:], pos, size["rope_theta"])       # [T, rot]
    causal = jnp.tril(jnp.ones((T, T), bool))
    hb = min(HEAD_BLOCK, H)

    def blocks(w):                      # [in, H * d] -> [H / hb, in, hb * d]
        return w.reshape(w.shape[0], H // hb, -1).transpose(1, 0, 2)

    def block(_, w):
        """`hb` heads at a time, so that [hb, T, T] scores fit."""
        q = _mm(cq, w[0], low).reshape(T, hb, nope + rot)
        kv = _mm(c, w[1], low).reshape(T, hb, nope + vd)
        q_pe = _rope(q[..., nope:], pos, size["rope_theta"])
        s = (jnp.einsum("thd,shd->hts", _round(q[..., :nope], low),
                        _round(kv[..., :nope], low))
             + jnp.einsum("thr,sr->hts", _round(q_pe, low),
                          _round(k_pe, low))) \
            * F32(1 / math.sqrt(nope + rot))
        s = jnp.where(causal, s, F32(-jnp.inf))
        return None, jnp.einsum("hts,shd->thd",
                                _round(jax.nn.softmax(s, -1), low),
                                _round(kv[..., nope:], low)).reshape(T, -1)

    _, outs = jax.lax.scan(block, None,
                           (blocks(p[b + "attn.q_b.weight"]),
                            blocks(p[b + "attn.kv_b.weight"])))
    return _mm(outs.transpose(1, 0, 2).reshape(T, -1),
               p[b + "attn.o.weight"], low)


def _experts(p, b, x, size, low):
    """The held experts' part plus the shared expert, and the number of
    (token, held expert) pairs."""
    first, count = size["held"]
    with jax.default_matmul_precision("highest"):
        scores = jax.nn.sigmoid(x @ p[b + "moe.router.weight"].astype(F32))
    top_s, top_i = jax.lax.top_k(scores, size["num_experts_per_tok"])
    weight = top_s / jnp.sum(top_s, -1, keepdims=True) \
        * F32(size["routed_scaling_factor"])
    out = _mlp(x, p[b + "moe.shared.gate.weight"],
               p[b + "moe.shared.up.weight"],
               p[b + "moe.shared.down.weight"], low)

    def expert(out, w):
        """One held expert: every token through it, weighted by its router
        weight, 0 where the token did not choose it."""
        e, w_gate, w_up, w_down = w
        w_e = jnp.sum(jnp.where(top_i == first + e, weight, F32(0)), -1)
        return out + w_e[:, None] * _mlp(x, w_gate, w_up, w_down, low), \
            jnp.sum(w_e > 0)

    out, pairs = jax.lax.scan(
        expert, out, (jnp.arange(count), p[b + "moe.experts.gate.weight"],
                      p[b + "moe.experts.up.weight"],
                      p[b + "moe.experts.down.weight"]))
    return out, jnp.sum(pairs)


def logits_and_pairs(params, ids, size, low=None):
    """ids [T] int32 -> (logits [T, V] float32, (token, held expert)
    pairs over all expert layers). `size`: the configuration's numbers as
    a dict (see `sizes`)."""
    with jax.default_matmul_precision("highest"):
        p, eps = params, size["rms_norm_eps"]
        pos = jnp.arange(ids.shape[0], dtype=jnp.int32)
        x = p["embed.weight"][ids].astype(F32)
        pairs = 0
        for i in range(size["num_hidden_layers"]):
            b = f"layers.{i}."
            a = x + _rms(_attention(
                p, b, _rms(x, p[b + "norm1.weight"], eps), pos, size, low),
                p[b + "norm2.weight"], eps)
            m = _rms(a, p[b + "norm3.weight"], eps)
            if i < size["first_k_dense_replace"]:
                m = _mlp(m, p[b + "mlp.gate.weight"], p[b + "mlp.up.weight"],
                         p[b + "mlp.down.weight"], low)
            else:
                m, n = _experts(p, b, m, size, low)
                pairs = pairs + n
            x = a + _rms(m, p[b + "norm4.weight"], eps)
        return _mm(_rms(x, p["norm_f.weight"], eps), p["lm_head.weight"],
                   low), pairs


def logits(params, ids, size, low=None):
    return logits_and_pairs(params, ids, size, low)[0]


def gaps_and_rows(params, ids, followers, rows_at, size, low=None):
    """One program for both of the runner's comparisons, over one
    teacher-forced row ids [T]: how far `followers[t]` sits below the best
    logit at t ([T] float32; followers = ids shifted by one gives the
    distance of every teacher-forced token below the best),
    the logits at the positions `rows_at`, and the best token at every
    position."""
    lg = logits(params, ids, size, low)
    picked = jnp.take_along_axis(lg, followers[:, None], -1)[:, 0]
    return jnp.max(lg, -1) - picked, lg[rows_at], \
        jnp.argmax(lg, -1).astype(jnp.int32)


def sizes(cfg) -> dict:
    """The numbers the reference needs, from any object or dict with the
    published keys (`held`: (first, count) of the routed experts held)."""
    get = cfg.get if isinstance(cfg, dict) else lambda k: getattr(cfg, k)
    keys = ("num_hidden_layers", "first_k_dense_replace",
            "num_attention_heads", "kv_lora_rank", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim", "num_experts_per_tok",
            "routed_scaling_factor", "rms_norm_eps", "rope_theta", "held")
    return {k: get(k) for k in keys}
