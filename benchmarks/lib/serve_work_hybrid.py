"""The operations and bytes that serving the hybrid family (Gated DeltaNet
layers beside gated grouped-query attention, a shared + routed expert block
in every layer) REQUIRES, computed from the configuration's shapes and the
program's counters: what `serve_mfu_hybrid`, `decode_trip_hbm_pct_hybrid`
and `gdn_step_hbm_pct` divide by time. What an implementation does beyond
that (padding rows, gathered copies of the cache or of the state, whole
blocks) is not counted, so the shares stay valid when a kernel is
rewritten. Also the reader of what a device trace holds of the decode chunk
and of the operations that touch the recurrent state.
"""
from __future__ import annotations

import re

from lib import spans

PROGRAM = "jit_fused_decode_chunk"
#: operations that only hold others (their time is their bodies')
CONTAINERS = re.compile(r"^%?(while|conditional|call|async)[\w.\-]* = ")


def layers(c: dict) -> tuple:
    """(Gated DeltaNet layers, full-attention layers) of those held."""
    full = sum((i + 1) % c["full_attention_interval"] == 0
               for i in range(c["num_hidden_layers"]))
    return c["num_hidden_layers"] - full, full


def matrices(c: dict) -> dict:
    """Elements of each kind of matrix, from the config's published keys
    (`vocab_size` as held here)."""
    h, f = c["hidden_size"], c["moe_intermediate_size"]
    H, G, D = (c["num_attention_heads"], c["num_key_value_heads"],
               c["head_dim"])
    kd = c["linear_num_key_heads"] * c["linear_key_head_dim"]
    vd = c["linear_num_value_heads"] * c["linear_value_head_dim"]
    return {
        # q, k, v, z; b, a; the depthwise conv over q, k, v; the output
        "deltanet": h * (2 * kd + 2 * vd)
        + h * 2 * c["linear_num_value_heads"]
        + (2 * kd + vd) * c["linear_conv_kernel_dim"] + vd * h,
        # a query and a gate a head; k, v; the output
        "attention": h * H * 2 * D + 2 * h * G * D + H * D * h,
        "shared_expert": 3 * h * c["shared_expert_intermediate_size"] + h,
        "router": h * c["experts_scored"],
        "routed_expert": 3 * h * f,
        "head": h * c["vocab_size"],
    }


def per_token_fixed(c: dict) -> int:
    """Matrix elements EVERY token multiplies: each layer's mixer, shared
    expert and router. Routed experts and the head are counted by their
    counters."""
    m, (gdn, full) = matrices(c), layers(c)
    return (gdn * m["deltanet"] + full * m["attention"]
            + (gdn + full) * (m["shared_expert"] + m["router"]))


def state_elements(c: dict) -> tuple:
    """(elements of the recurrent state, of the conv history) a sequence a
    DeltaNet layer."""
    kd = c["linear_num_key_heads"] * c["linear_key_head_dim"]
    vd = c["linear_num_value_heads"] * c["linear_value_head_dim"]
    return (c["linear_num_value_heads"] * c["linear_key_head_dim"]
            * c["linear_value_head_dim"],
            (c["linear_conv_kernel_dim"] - 1) * (2 * kd + vd))


def serve_flops(c: dict, tokens: int, sampled: int, moe_pairs: int,
                decode_context: int, prefill_pairs: int) -> float:
    """Operations for `tokens` positions pushed through the layers (prompt
    positions prefilled and tokens decoded), `sampled` positions whose
    logits were needed, `moe_pairs` (token, held expert) pairs (the
    program's counter), `decode_context` cached positions attended to by
    decode queries and `prefill_pairs` causal (query, key) pairs of the
    prefills, both a full-attention layer (a head spends head_dim
    multiply-adds on the score and head_dim on the value of a pair), and
    the recurrence: per position, DeltaNet layer and value head three
    products of key x value size (S^T k, k d^T, S^T q)."""
    m, (gdn, full) = matrices(c), layers(c)
    state, _ = state_elements(c)
    return 2.0 * (tokens * per_token_fixed(c)
                  + moe_pairs * m["routed_expert"] + sampled * m["head"]
                  + full * c["num_attention_heads"] * 2 * c["head_dim"]
                  * (decode_context + prefill_pairs)
                  + gdn * tokens * 3 * state)


def state_bytes(c: dict, live_row_trips: int, conv: bool = True) -> float:
    """Bytes the live rows' state entries are read and written, once each a
    (row, trip, DeltaNet layer): the recurrent state (float32) and, with
    `conv`, the conv history."""
    state, history = state_elements(c)
    entry = state * c["state_itemsize"] \
        + (history * c["itemsize"] if conv else 0)
    return 2.0 * live_row_trips * layers(c)[0] * entry


def decode_trip_bytes(c: dict, trips: int, experts_hit: int,
                      context_tokens: int, live_row_trips: int) -> float:
    """Bytes `trips` scan trips of the decode chunk have to move: every
    matrix that every token multiplies and the head, once a trip (the
    router is float32); a routed expert's three matrices once for each
    (trip, layer) in which a token reached it (`experts_hit`, the
    program's counter); k and v of every position a live row attends to,
    in every full-attention layer; the recurrent state and the conv
    history of every live row read and written once a DeltaNet layer."""
    m, (gdn, full) = matrices(c), layers(c)
    size = c["itemsize"]
    fixed = (per_token_fixed(c) + m["head"]) * size \
        + (gdn + full) * m["router"] * (4 - size)
    row = 2 * c["num_key_value_heads"] * c["head_dim"] * size
    return (trips * fixed + experts_hit * m["routed_expert"] * size
            + context_tokens * row * full
            + state_bytes(c, live_row_trips))


# ------------------------------------------------------- the device trace
def traced(record):
    """{chunks, programs, trips, experts_hit, context_tokens,
    live_row_trips, program_seconds} over the traced window; None where
    the trace has no device plane, no chunk ran, or no `serving.decode`
    span carries `live_row_trips` (a program from before the stat)."""
    tr = spans.load(record)
    if tr is None:
        return None
    found = [sp for sp, _, _ in spans.under(tr, "serving.decode")
             if "live_row_trips" in sp.stats
             and "moe_experts_hit" in sp.stats]
    runs = [(s, e) for s, e, name in tr.modules
            if name.startswith(PROGRAM) and tr.inside(s, e)]
    if not found or not runs:
        return None
    total = lambda stat: sum(int(sp.stats[stat]) for sp in found)  # noqa
    return {
        "chunks": len(found), "programs": len(runs),
        "trips": total("chunk"), "experts_hit": total("moe_experts_hit"),
        "context_tokens": total("context_tokens"),
        "live_row_trips": total("live_row_trips"),
        # a span and its program need not both lie wholly in the window:
        # scale the seconds to the spans counted
        "program_seconds": sum(e - s for s, e in runs) / 1e9
        * len(found) / len(runs)}


def state_ops(record):
    """The device operations of the decode chunk that read or write the
    recurrent state, found by SHAPE: the update is composed of XLA
    operations (no Pallas kernel, so no kernel name), and every one of them
    (the gather of the rows' entries, the fusions of the step, the scatter
    back) names among its operands or results a float32 array of [rows or
    slots, value heads, key size, value size]. Returns {seconds, trips,
    live_row_trips, ops}, or None without a trace, the stat, the `work`
    facts, or such an operation."""
    work = record.get("facts", {}).get("work")
    tr = spans.load(record)
    if tr is None or not work:
        return None
    c = work["config"]
    entry = [c["linear_num_value_heads"], c["linear_key_head_dim"],
             c["linear_value_head_dim"]]
    found = [sp for sp, _, _ in spans.under(tr, "serving.decode")
             if "live_row_trips" in sp.stats]
    # a `while` (the scan itself) or a `conditional` names the state among
    # its operands too, and lasts as long as everything inside it: only the
    # operations that do the work are timed
    ops = [op for sp in found for op in tr.ops
           if op[0] >= sp.start and op[1] <= sp.end
           and not CONTAINERS.match(op[2])
           and any(dtype == "f32" and dims[1:] == entry
                   for dtype, dims, _ in spans.shapes(op[2]))]
    if not ops:
        return None
    return {"seconds": spans.seconds(ops), "ops": len(ops),
            "trips": sum(int(sp.stats["chunk"]) for sp in found),
            "live_row_trips": sum(int(sp.stats["live_row_trips"])
                                  for sp in found)}
