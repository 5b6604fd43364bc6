"""The operations and bytes that serving a family of sliding-window
attention layers beside full ones (grouped-query attention, a routed expert
block in every layer) REQUIRES, computed from the configuration's shapes
and the program's counters: what `serve_mfu_swa`, `decode_trip_hbm_pct_swa`
and `swa_attn_hbm_pct` divide by time. A window layer is counted at the
keys its mask leaves, min(context, window) a query; what an implementation
does beyond that (padding rows, gathered copies of the cache, whole blocks,
the dead blocks of a full layer's table) is not counted, so the shares stay
valid when a kernel is rewritten. Also the reader of what a device trace
holds of the decode chunk and of the operations that read the window pools.
"""
from __future__ import annotations

import re

from lib import spans

PROGRAM = "jit_fused_decode_chunk"
#: operations that only hold others (their time is their bodies')
CONTAINERS = re.compile(r"^%?(while|conditional|call|async)[\w.\-]* = ")


def matrices(c: dict) -> dict:
    """Elements of each kind of matrix, from the config's published keys."""
    h, f = c["hidden_size"], c["moe_intermediate_size"]
    H, G, D = (c["num_attention_heads"], c["num_key_value_heads"],
               c["head_dim"])
    return {"attention": h * H * D + 2 * h * G * D + H * D * h,
            "router": h * c["num_experts"],
            "routed_expert": 3 * h * f,
            "head": h * c["vocab_size"]}


def per_token_fixed(c: dict) -> int:
    """Matrix elements EVERY token multiplies: each layer's attention
    projections and router. Routed experts and the head are counted by
    their counters."""
    m = matrices(c)
    return c["num_hidden_layers"] * (m["attention"] + m["router"])


def row_bytes(c: dict) -> int:
    """Bytes of one cached position of one layer: k and v."""
    return 2 * c["num_key_value_heads"] * c["head_dim"] * c["itemsize"]


def attended(c: dict, full_keys: int, window_keys: int) -> int:
    """(query, key) pairs over all layers, from the pairs of ONE full layer
    and of ONE window layer."""
    return c["full_layers"] * full_keys + c["window_layers"] * window_keys


def serve_flops(c: dict, tokens: int, sampled: int, moe_pairs: int,
                decode_context: int, decode_window_context: int,
                prefill_pairs: int, prefill_window_pairs: int) -> float:
    """Operations for `tokens` positions pushed through the layers (prompt
    positions prefilled and tokens decoded), `sampled` positions whose
    logits were needed, `moe_pairs` (token, expert) pairs (the program's
    counter), and attention: cached positions attended to by decode queries
    in a full layer (`decode_context`, the program's `context_tokens`) and
    in a window layer (`decode_window_context`, its
    `window_context_tokens`: min(context, window) a query), causal (query,
    key) pairs of the prefills in a full layer and, at most `window` keys a
    query, in a window layer. A head spends head_dim multiply-adds on the
    score and head_dim on the value of a pair."""
    m = matrices(c)
    pairs = attended(c, decode_context + prefill_pairs,
                     decode_window_context + prefill_window_pairs)
    return 2.0 * (tokens * per_token_fixed(c)
                  + moe_pairs * m["routed_expert"] + sampled * m["head"]
                  + c["num_attention_heads"] * 2 * c["head_dim"] * pairs)


def window_bytes(c: dict, window_context_tokens: int) -> float:
    """Bytes of the window layers' rows the decode queries had to read: k
    and v of min(context, window) positions a (row, trip), every window
    layer."""
    return float(c["window_layers"] * window_context_tokens * row_bytes(c))


def decode_trip_bytes(c: dict, trips: int, experts_hit: int,
                      context_tokens: int,
                      window_context_tokens: int) -> float:
    """Bytes `trips` scan trips of the decode chunk have to move: every
    matrix that every token multiplies and the head, once a trip (the
    router is float32); an expert's three matrices once for each (trip,
    layer) in which a token reached it (`experts_hit`, the program's
    counter); k and v of every position a live row attends to, all of its
    context in the full layers, its window in the others."""
    m, size = matrices(c), c["itemsize"]
    fixed = (per_token_fixed(c) + m["head"]) * size \
        + c["num_hidden_layers"] * m["router"] * (4 - size)
    return (trips * fixed + experts_hit * m["routed_expert"] * size
            + c["full_layers"] * context_tokens * row_bytes(c)
            + window_bytes(c, window_context_tokens))


# ------------------------------------------------------- the device trace
def _chunk_spans(tr):
    return [sp for sp, _, _ in spans.under(tr, "serving.decode")
            if "window_context_tokens" in sp.stats]


def traced(record):
    """{chunks, programs, trips, experts_hit, context_tokens,
    window_context_tokens, program_seconds} over the traced window; None
    where the trace has no device plane, no chunk ran, or no
    `serving.decode` span carries `window_context_tokens` (a program
    without window layers, or from before the stat)."""
    tr = spans.load(record)
    if tr is None:
        return None
    found = [sp for sp in _chunk_spans(tr) if "moe_experts_hit" in sp.stats]
    runs = [(s, e) for s, e, name in tr.modules
            if name.startswith(PROGRAM) and tr.inside(s, e)]
    if not found or not runs:
        return None
    total = lambda stat: sum(int(sp.stats[stat]) for sp in found)  # noqa
    return {
        "chunks": len(found), "programs": len(runs),
        "trips": total("chunk"), "experts_hit": total("moe_experts_hit"),
        "context_tokens": total("context_tokens"),
        "window_context_tokens": total("window_context_tokens"),
        # a span and its program need not both lie wholly in the window:
        # scale the seconds to the spans counted
        "program_seconds": sum(e - s for s, e in runs) / 1e9
        * len(found) / len(runs)}


def window_ops(record):
    """The device operations of the decode chunk that read the window
    layers' pools, found by SHAPE: the window layers' attention is composed
    of XLA operations (no Pallas kernel, so no kernel name), and every one
    that touches a window pool (the gather of the rows' window blocks and
    whatever the compiler fused with it, the write of the new row) names
    among its operands or results an array of the pools' own shape
    [window blocks, block size, k or v row], which no other array of the
    program has. Returns {seconds, trips, window_context_tokens, ops}, or
    None without a trace, the stat, the `work` facts, or such an
    operation."""
    work = record.get("facts", {}).get("work")
    tr = spans.load(record)
    if tr is None or not work or "window_pool_shape" not in work:
        return None
    pool = list(work["window_pool_shape"])
    found = _chunk_spans(tr)
    # a `while` (the scan itself) or a `conditional` names the pools among
    # its operands too, and lasts as long as everything inside it: only the
    # operations that do the work are timed
    ops = [op for sp in found for op in tr.ops
           if op[0] >= sp.start and op[1] <= sp.end
           and not CONTAINERS.match(op[2])
           and any(dims == pool for _, dims, _ in spans.shapes(op[2]))]
    if not ops:
        return None
    return {"seconds": spans.seconds(ops), "ops": len(ops),
            "trips": sum(int(sp.stats["chunk"]) for sp in found),
            "window_context_tokens": sum(
                int(sp.stats["window_context_tokens"]) for sp in found)}
