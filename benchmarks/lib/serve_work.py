"""The operations and bytes that serving the latent-attention expert family
REQUIRES, computed from the configuration's shapes and the program's
counters: what `serve_mfu` and `decode_trip_hbm_pct` divide by time. What an
implementation does beyond that (padding rows, gathered copies of the
cache, whole blocks) is not counted, so the shares stay valid when a
kernel is rewritten.
"""
from __future__ import annotations


def matrices(c: dict) -> dict:
    """Elements of each kind of matrix, from the config's published keys
    (`vocab_size`, `n_routed_experts` as held here)."""
    h, H = c["hidden_size"], c["num_attention_heads"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    rank, f = c["kv_lora_rank"], c["moe_intermediate_size"]
    return {
        "attention": h * c["q_lora_rank"] + c["q_lora_rank"] * H * qk
        + h * (rank + c["qk_rope_head_dim"])
        + rank * H * (c["qk_nope_head_dim"] + c["v_head_dim"])
        + H * c["v_head_dim"] * h,
        "dense_mlp": 3 * h * c["intermediate_size"],
        "shared_expert": 3 * h * f * c["n_shared_experts"],
        "router": h * c["routed_experts_scored"],
        "routed_expert": 3 * h * f,
        "head": h * c["vocab_size"],
    }


def per_token_fixed(c: dict) -> int:
    """Matrix elements EVERY token multiplies: the attention projections of
    every layer, the dense MLPs, and per expert layer the shared expert and
    the router. Routed experts and the head are counted by their counters."""
    m, dense = matrices(c), c["first_k_dense_replace"]
    experts = c["num_hidden_layers"] - dense
    return (c["num_hidden_layers"] * m["attention"] + dense * m["dense_mlp"]
            + experts * (m["shared_expert"] + m["router"]))


def attention_width(c: dict) -> tuple:
    """Multiply-adds one head spends on one (query, cached position) pair:
    (decode in the latent space: scores over the whole cached row, values
    over its compressed part; prefill with keys and values expanded)."""
    rank, rope = c["kv_lora_rank"], c["qk_rope_head_dim"]
    return (rank + rope + rank,
            c["qk_nope_head_dim"] + rope + c["v_head_dim"])


def serve_flops(c: dict, tokens: int, sampled: int, moe_pairs: int,
                decode_context: int, prefill_pairs: int) -> float:
    """Operations for `tokens` positions pushed through the layers (prompt
    positions prefilled and tokens decoded), `sampled` positions whose
    logits were needed (one a prefill, one a decoded token), `moe_pairs`
    (token, held expert) pairs (the program's counter), `decode_context`
    cached positions attended to by decode queries and `prefill_pairs`
    causal (query, key) pairs of the prefills, the last two a layer."""
    m = matrices(c)
    decode_w, prefill_w = attention_width(c)
    H, L = c["num_attention_heads"], c["num_hidden_layers"]
    return 2.0 * (tokens * per_token_fixed(c)
                  + moe_pairs * m["routed_expert"] + sampled * m["head"]
                  + L * H * (decode_context * decode_w
                             + prefill_pairs * prefill_w))


def decode_trip_bytes(c: dict, trips: int, experts_hit: int,
                      context_tokens: int, itemsize: int = 2) -> float:
    """Bytes `trips` scan trips of the decode chunk have to read: every
    matrix that every token multiplies and the head, once a trip (the
    router is float32); a routed expert's three matrices once for each
    (trip, layer) in which a token reached it (`experts_hit`, the
    program's counter); the cached row of every position a live row
    attends to, in every layer."""
    m = matrices(c)
    experts = c["num_hidden_layers"] - c["first_k_dense_replace"]
    fixed = (per_token_fixed(c) + m["head"]) * itemsize \
        + experts * m["router"] * (4 - itemsize)
    row = (c["kv_lora_rank"] + c["qk_rope_head_dim"]) * itemsize
    return (trips * fixed + experts_hit * m["routed_expert"] * itemsize
            + context_tokens * row * c["num_hidden_layers"])
