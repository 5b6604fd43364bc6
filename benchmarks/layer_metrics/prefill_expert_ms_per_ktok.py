"""Device milliseconds a thousand prompt tokens that dense prefill spends
in the expert products: the operations inside the window's whole
`serving.prefill` spans that name an array of the experts' weights' shape
(the stat `moe_shape`) or its transpose, over the spans' `tokens`
(`lib/expert_ops.traced`; no family is named). As an earlier line, per
traced prefill: its tokens, its largest load over the MEAN load that
reached a held expert in a call (`max_load_over_routed_mean`: the pairs
routed here over calls x held experts), and the shares of its calls whose
largest load was at most twice / four times the uniform load and that
multiplied batched over the experts. The two fits are the device's own
test against `load_capacity`: the uniform load of a call's static rows,
dead and padded ones included, over ALL the experts. The ratio is against
what arrived here, and equals a ratio to that uniform load only where
every expert is held and no row is dead: it describes the concentration,
the fits decide a capacity. None without a device trace or the stats."""
import json

from lib import expert_ops


def compute(record, trace):
    seen = expert_ops.traced(record)
    if seen is None or not seen["tokens"]:
        return None
    held = seen["shape"][0]
    told = []
    for p in seen["prefills"]:
        calls = p["moe_layer_calls"]
        if not calls or not p["moe_pairs"]:
            continue
        told.append({
            "tokens": p["tokens"], "calls": calls,
            "max_load_over_routed_mean": p["moe_max_load"] * calls * held
            / p["moe_pairs"],
            "fit_2x": p["moe_fit_2x"] / calls,
            "fit_4x": p["moe_fit_4x"] / calls,
            "batched": p["moe_batched_layers"] / calls,
            "experts_hit_a_call": p["moe_experts_hit"] / calls,
            "device_ms": 1e3 * p["seconds"]})
    print(json.dumps({"prefill_expert": {
        "shape": seen["shape"], "layers": seen["layers"],
        "seconds": seen["seconds"], "window_seconds": seen["window_seconds"],
        "tokens": seen["tokens"], "prefills": told}}), flush=True)
    return 1e6 * seen["seconds"] / seen["tokens"]
