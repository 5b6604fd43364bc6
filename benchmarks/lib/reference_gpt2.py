"""GPT-2 forward and loss in plain float32 jax.numpy: the reference.

Follows Radford et al. 2019 / the openai-community/gpt2 modelling code:
learned token + position embeddings, pre-LN blocks (LayerNorm eps 1e-5,
causal softmax attention scaled by 1/sqrt(head_dim), GELU-tanh MLP x4),
final LayerNorm, unembedding. One departure, the program's: the
unembedding is its own matrix `lm_head.weight` [h, V], not wte transposed.
No kernel, no cache, no batching tricks, and nothing imported from
paddle_tpu. `import paddle_tpu` turns x64 on, so every constant carries
its dtype. Matmuls run at "highest" precision: on a TPU a float32 product
is otherwise done in one bf16 pass. (A reference at that default precision
sits no closer to the engine: largest gap 1.06e-2 against 1.43e-2 on one
seed, PERF.md PR 26, so the exact one stays.)

Parameters: a flat {name: array}, names as models/gpt.py gives them
(`wte.weight`, `blocks.<i>.attn.qkv.weight` [h, 3h] as q|k|v, ...).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _layer_norm(x, w, b):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + F32(1e-5)) * w + b


def _gelu_tanh(x):
    return F32(0.5) * x * (F32(1) + jnp.tanh(
        F32(math.sqrt(2 / math.pi)) * (x + F32(0.044715) * x * x * x)))


def logits(params, ids, n_layer: int, n_head: int):
    """ids [B, T] int32 -> logits [B, T, V] float32."""
    with jax.default_matmul_precision("highest"):
        p = {k: jnp.asarray(v, F32) for k, v in params.items()}
        B, T = ids.shape
        x = p["wte.weight"][ids] + p["wpe.weight"][:T][None]
        h = x.shape[-1]
        d = h // n_head
        causal = jnp.tril(jnp.ones((T, T), bool))
        for i in range(n_layer):
            b = f"blocks.{i}."
            a = _layer_norm(x, p[b + "ln1.weight"], p[b + "ln1.bias"])
            qkv = a @ p[b + "attn.qkv.weight"] + p[b + "attn.qkv.bias"]
            q, k, v = (t.reshape(B, T, n_head, d).transpose(0, 2, 1, 3)
                       for t in jnp.split(qkv, 3, -1))
            s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * F32(1 / math.sqrt(d))
            s = jnp.where(causal, s, F32(-jnp.inf))
            a = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
            a = a.transpose(0, 2, 1, 3).reshape(B, T, h)
            x = x + a @ p[b + "attn.out.weight"] + p[b + "attn.out.bias"]
            m = _layer_norm(x, p[b + "ln2.weight"], p[b + "ln2.bias"])
            m = _gelu_tanh(m @ p[b + "mlp.up.weight"] + p[b + "mlp.up.bias"])
            x = x + m @ p[b + "mlp.down.weight"] + p[b + "mlp.down.bias"]
        x = _layer_norm(x, p["ln_f.weight"], p["ln_f.bias"])
        return x @ p["lm_head.weight"]


def loss_sum(params, ids, labels, n_layer: int, n_head: int):
    """Sum over the B*T positions of the cross entropy of `labels`."""
    lg = logits(params, ids, n_layer, n_head)
    logz = jax.nn.logsumexp(lg, -1)
    picked = jnp.take_along_axis(lg, labels[..., None], -1)[..., 0]
    return jnp.sum(logz - picked)


def token_gaps(params, ids, n_layer: int, n_head: int):
    """For a teacher-forced row ids [1, T]: at every position t, how far
    the logit of the token that follows (ids[t+1]) sits below the best
    logit at t. [T-1] float32, 0 where the follower is the argmax."""
    lg = logits(params, ids, n_layer, n_head)[0, :-1]
    follower = jnp.take_along_axis(lg, ids[0, 1:, None], -1)[:, 0]
    return jnp.max(lg, -1) - follower
