"""The operations and bytes that serving the KDA + MLA family (Kimi Delta
Attention layers beside multi-head latent attention layers, a dense SwiGLU
or a shared + routed expert block after each) REQUIRES, computed from the
configuration's shapes and the program's counters: what `serve_mfu_kda`,
`decode_trip_hbm_pct_kda` and `kda_step_hbm_pct` divide by time. What an
implementation does beyond that (padded rows, gathered copies of the cache,
whole blocks, the lanes a latent row is padded to) is not counted, so the
shares stay valid when a kernel is rewritten. Also the readers of what a
device trace holds of the decode chunk and of the `kda_step` kernel.
"""
from __future__ import annotations

from lib import spans

PROGRAM = "jit_fused_decode_chunk"
KERNEL = "kda_step"


def layers(c: dict) -> tuple:
    """(KDA layers, MLA layers) of those held."""
    mla = len(c["mla_layers"])
    return c["num_hidden_layers"] - mla, mla


def matrices(c: dict) -> dict:
    """Elements of each kind of matrix, from the config's published keys
    (`vocab_size` as held here)."""
    h, H, d = c["hidden_size"], c["num_attention_heads"], c["head_dim"]
    rank, nope, rope = (c["kv_lora_rank"], c["qk_nope_head_dim"],
                        c["qk_rope_head_dim"])
    return {
        # q, k, v; the depthwise conv over them; the decay gate; beta; the
        # output gate; the output
        "kda": 3 * h * H * d + c["short_conv_kernel_size"] * 3 * H * d
        + h * H * d + h * H + h * H * d + H * d * h,
        # the queries, the latent row, its expansion, the head-wise gate,
        # the output
        "mla": h * H * (nope + rope) + h * (rank + rope)
        + rank * H * (nope + c["v_head_dim"]) + h * H
        + H * c["v_head_dim"] * h,
        "dense_mlp": 3 * h * c["intermediate_size"],
        "shared_expert": 3 * h * c["moe_shared_expert_intermediate_size"],
        "router": h * c["experts_scored"],
        "routed_expert": 3 * h * c["moe_intermediate_size"],
        "head": h * c["vocab_size"],
    }


def per_token_fixed(c: dict) -> int:
    """Matrix elements EVERY token multiplies: each layer's mixer and its
    dense MLP, or shared expert and router. Routed experts and the head are
    counted by their counters."""
    m, (kda, mla) = matrices(c), layers(c)
    dense = c["first_k_dense_replace"]
    experts = c["num_hidden_layers"] - dense
    return (kda * m["kda"] + mla * m["mla"] + dense * m["dense_mlp"]
            + experts * (m["shared_expert"] + m["router"]))


def attention_width(c: dict) -> tuple:
    """Multiply-adds one MLA head spends on one (query, cached position)
    pair: (decode in the latent space: scores over the whole cached row,
    values over its compressed part; prefill with keys and values
    expanded)."""
    rank, rope = c["kv_lora_rank"], c["qk_rope_head_dim"]
    return (rank + rope + rank,
            c["qk_nope_head_dim"] + rope + c["v_head_dim"])


def state_elements(c: dict) -> tuple:
    """(elements of the recurrent state, of the conv history) a sequence a
    KDA layer."""
    H, d = c["num_attention_heads"], c["head_dim"]
    return H * d * d, (c["short_conv_kernel_size"] - 1) * 3 * H * d


def serve_flops(c: dict, tokens: int, sampled: int, moe_pairs: int,
                decode_context: int, prefill_pairs: int) -> float:
    """Operations for `tokens` positions pushed through the layers (prompt
    positions prefilled and tokens decoded), `sampled` positions whose
    logits were needed, `moe_pairs` (token, held expert) pairs (the
    program's counter), `decode_context` cached positions attended to by
    decode queries and `prefill_pairs` causal (query, key) pairs of the
    prefills, both an MLA layer, and the recurrence: per position, KDA
    layer and head three products of key x value size (S^T k, S^T q,
    k d^T; the decay is an elementwise scaling)."""
    m, (kda, mla) = matrices(c), layers(c)
    decode_w, prefill_w = attention_width(c)
    state, _ = state_elements(c)
    return 2.0 * (tokens * per_token_fixed(c)
                  + moe_pairs * m["routed_expert"] + sampled * m["head"]
                  + mla * c["num_attention_heads"]
                  * (decode_context * decode_w + prefill_pairs * prefill_w)
                  + kda * tokens * 3 * state)


def state_bytes(c: dict, state_row_trips: int) -> float:
    """Bytes the live rows' KDA entries are read and written, once each a
    (row, trip, KDA layer) (`state_row_trips`, the program's counter
    `kda_state_row_trips`): the recurrent state (float32) and the conv
    history."""
    state, history = state_elements(c)
    return 2.0 * state_row_trips * (state * c["state_itemsize"]
                                    + history * c["itemsize"])


def decode_trip_bytes(c: dict, trips: int, experts_hit: int,
                      context_tokens: int, state_row_trips: int) -> float:
    """Bytes `trips` scan trips of the decode chunk have to move: every
    matrix that every token multiplies and the head, once a trip (the
    router is float32); a routed expert's three matrices once for each
    (trip, layer) in which a token reached it (`experts_hit`); the latent
    row of every position a live row attends to, in every MLA layer; the
    KDA entries of every live row read and written once a layer."""
    m, (_, mla) = matrices(c), layers(c)
    size = c["itemsize"]
    experts = c["num_hidden_layers"] - c["first_k_dense_replace"]
    fixed = (per_token_fixed(c) + m["head"]) * size \
        + experts * m["router"] * (4 - size)
    row = (c["kv_lora_rank"] + c["qk_rope_head_dim"]) * size
    return (trips * fixed + experts_hit * m["routed_expert"] * size
            + context_tokens * row * mla
            + state_bytes(c, state_row_trips))


# ------------------------------------------------------- the device trace
def _decode_spans(tr):
    return [sp for sp, _, _ in spans.under(tr, "serving.decode")
            if "kda_state_row_trips" in sp.stats
            and "moe_experts_hit" in sp.stats]


def traced(record):
    """{chunks, programs, trips, experts_hit, context_tokens,
    state_row_trips, program_seconds} over the traced window; None where
    the trace has no device plane, no chunk ran, or no `serving.decode`
    span carries `kda_state_row_trips` (a program without the counter)."""
    tr = spans.load(record)
    if tr is None:
        return None
    found = _decode_spans(tr)
    runs = [(s, e) for s, e, name in tr.modules
            if name.startswith(PROGRAM) and tr.inside(s, e)]
    if not found or not runs:
        return None
    total = lambda stat: sum(int(sp.stats[stat]) for sp in found)  # noqa
    return {
        "chunks": len(found), "programs": len(runs),
        "trips": total("chunk"), "experts_hit": total("moe_experts_hit"),
        "context_tokens": total("context_tokens"),
        "state_row_trips": total("kda_state_row_trips"),
        # a span and its program need not both lie wholly in the window:
        # scale the seconds to the spans counted
        "program_seconds": sum(e - s for s, e in runs) / 1e9
        * len(found) / len(runs)}


def kernel(record):
    """The `kda_step` kernel's device operations inside the traced
    `serving.decode` spans that carry `kda_state_row_trips`: {seconds,
    calls, trips, state_row_trips}; None without a trace, such a span or
    the kernel (a program that updates the state out of place)."""
    tr = spans.load(record)
    if tr is None:
        return None
    found = [(sp, spans.kernel_ops(tr, KERNEL, sp.start, sp.end))
             for sp in _decode_spans(tr)]
    found = [(sp, ops) for sp, ops in found if ops]
    if not found:
        return None
    return {"seconds": sum(spans.seconds(ops) for _, ops in found),
            "calls": sum(len(ops) for _, ops in found),
            "trips": sum(int(sp.stats["chunk"]) for sp, _ in found),
            "state_row_trips": sum(int(sp.stats["kda_state_row_trips"])
                                   for sp, _ in found)}
