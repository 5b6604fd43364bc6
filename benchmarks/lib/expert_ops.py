"""The expert products of a dense prefill in a device trace, found by
SHAPE, and the work they REQUIRE: what `prefill_expert_ms_per_ktok` and
`prefill_expert_roofline_pct` divide.

The program says what to look for and what was routed; nothing here names
a family or reads a configuration. A spec with expert layers puts the stat
`moe_shape` on `serving.prefill` (a chunk's products are not read here,
and `serving.decode` does not carry it): "ExKxN", the shape
of one of an expert layer's stacked weights on this chip (E held experts,
K the hidden size, N an expert's width). Every operation that multiplies
by the experts names among its operands an array of that shape (gate, up)
or of its transpose [E, N, K] (down), which no other array of the program
has: the grouped kernel (`ragged-dot`), the batched products' fusions and
whatever copy of the weights the compiler makes for either. The counts
ride the prefill's one fetch and stand on the same span: `moe_pairs`
((token, held expert) pairs routed), `moe_experts_hit` (held experts with a
token, summed over calls), `moe_layer_calls` (calls of the expert layer: a
long prompt goes through a layer in blocks of tokens), `moe_fit_2x`,
`moe_fit_4x`, `moe_batched_layers` (calls of them whose largest load was
at most twice / four times the uniform load, and that multiplied batched
over the experts), `moe_max_load` (most tokens one held expert got in one
call).

Required work of the traced prefills, the larger of two bounds:
- MXU: `moe_pairs` x 6 K N operations (three products of K x N
  multiply-adds a pair);
- HBM: the weights of as many experts as ONE call reached on average
  (`moe_experts_hit` / `moe_layer_calls`), read once a LAYER, 3 K N
  elements each. A lower bound on what the prompt reached (its blocks need
  not reach the same experts); reading them again for every block of
  tokens is the program's choice and is not required work.
The number of expert layers is read from the `serving.decode` spans: a
decode trip calls every expert layer once, so `moe_layer_calls` / `chunk`
there is their number.
"""
from __future__ import annotations

import re
import statistics

from lib import chip, spans

PREFILL, DECODE = "serving.prefill", "serving.decode"
#: operations that only hold others (their time is their bodies'): the
#: map over a prompt's blocks of tokens is a `while`, the choice of the
#: products' form a `conditional`, and both name the weights
CONTAINERS = re.compile(r"^%?(while|conditional|call|async)[\w.\-]* = ")
COUNTS = ("moe_pairs", "moe_experts_hit", "moe_layer_calls", "moe_fit_2x",
          "moe_fit_4x", "moe_batched_layers", "moe_max_load")


def shape_of(span):
    """(E, K, N) from the span's `moe_shape`, or None."""
    try:
        e, k, n = (int(d) for d in str(span.stats["moe_shape"]).split("x"))
    except (KeyError, ValueError):
        return None
    return e, k, n


def ops_by_shape(trace, shape, start=None, end=None):
    """([(start, end, hlo text)], bytes per element of the weights): the
    operations inside [start, end] (default: the window) that name an
    array [E, K, N] or [E, N, K] and are no container. The trace is
    searched once a shape."""
    e, k, n = shape
    key = ("expert_ops", shape)
    if key not in trace.kernels:
        want = ([e, k, n], [e, n, k])
        marks = tuple("[%d,%d,%d]" % tuple(w) for w in want)
        found, sizes = [], set()
        for op in trace.ops:
            text = op[2]
            if not any(m in text for m in marks) or CONTAINERS.match(text):
                continue
            named = {b for _, dims, b in spans.shapes(text) if dims in want}
            if named:
                found.append(op)
                sizes |= named
        trace.kernels[key] = found, min(sizes, default=None)
    found, itemsize = trace.kernels[key]
    start = trace.lo if start is None else start
    end = trace.hi if end is None else end
    return [op for op in found if op[0] >= start and op[1] <= end], itemsize


def expert_layers(trace):
    """Expert layers of the program, from the decode chunks of the window:
    `moe_layer_calls` / `chunk` (a trip calls each once); None without
    such a span."""
    seen = [int(sp.stats["moe_layer_calls"]) // int(sp.stats["chunk"])
            for sp, _, _ in spans.under(trace, DECODE)
            if "moe_layer_calls" in sp.stats and int(sp.stats["chunk"])]
    return statistics.mode(seen) if seen else None


def traced(record):
    """What the window's whole prefills hold of the expert layer:
    {shape, itemsize, layers, prefills: [{tokens, seconds, ops, <the
    counts>}], seconds, tokens, window_seconds}. `seconds` are device
    seconds of the operations found by shape inside the spans;
    `window_seconds` those of every such operation of the window that lies
    in no `serving.decode` span (the prefills cut by the window's edge
    too: what the run's `breakdown` sums by name). None where the trace
    has no device plane, no `serving.prefill` carries `moe_shape` and
    `moe_layer_calls` (a program without expert layers, or from before
    the stats), or no operation names the shape."""
    if "_expert_prefills" in record:
        return record["_expert_prefills"]
    record["_expert_prefills"] = None
    trace = spans.load(record)
    found = [sp for sp, _, _ in (spans.under(trace, PREFILL) if trace else [])
             if shape_of(sp) and "moe_layer_calls" in sp.stats]
    if not found:
        return None
    shape = shape_of(found[0])
    in_window, itemsize = ops_by_shape(trace, shape)
    if itemsize is None:
        return None
    prefills = []
    for sp in found:
        ops, _ = ops_by_shape(trace, shape, sp.start, sp.end)
        prefills.append({"tokens": int(sp.stats["tokens"]),
                         "seconds": spans.seconds(ops), "ops": len(ops),
                         **{c: int(sp.stats.get(c, 0)) for c in COUNTS}})
    in_decode = [(sp.start, sp.end) for sp in trace.spans if sp.name == DECODE]
    record["_expert_prefills"] = {
        "shape": list(shape), "itemsize": itemsize,
        "layers": expert_layers(trace), "prefills": prefills,
        "seconds": sum(p["seconds"] for p in prefills),
        "tokens": sum(p["tokens"] for p in prefills),
        "window_seconds": spans.seconds(
            [op for op in in_window
             if not any(s <= op[0] and op[1] <= e for s, e in in_decode)])}
    return record["_expert_prefills"]


def required(seen, device_kind: str):
    """{flops, bytes, seconds, bound} the traced prefills require (module
    docstring); None where the number of expert layers is not known."""
    if seen["layers"] is None:
        return None
    _, k, n = seen["shape"]
    peaks = chip.peaks(device_kind)
    flops = 6.0 * k * n * sum(p["moe_pairs"] for p in seen["prefills"])
    reached = sum(p["moe_experts_hit"] / p["moe_layer_calls"]
                  for p in seen["prefills"] if p["moe_layer_calls"])
    size = 3.0 * k * n * seen["itemsize"] * seen["layers"] * reached
    by = {"bf16_flops_per_s": flops / peaks["bf16_flops_per_s"],
          "hbm_bytes_per_s": size / peaks["hbm_bytes_per_s"]}
    bound = max(by, key=by.get)
    return {"flops": flops, "bytes": size, "seconds": by[bound],
            "bound": bound}
