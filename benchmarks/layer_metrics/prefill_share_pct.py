"""Share of the engine's time that dense prefill takes: host time of the
`serving.prefill` spans inside the `serving.engine_step` spans that lie
wholly in the traced window, over those steps' host time. Both sums are
over the SAME steps: a prefill whose step the window's edge cuts is left
out with its step. A prefill blocks on its one fetch, so its span holds
its device time; with the chip busy throughout it is the share of the
chip the prompts cost. The count of both is printed as an earlier line.

A SAMPLE: a trace holds 7 to 11 steps of an expert cell, and the share
also rises when decode alone gets faster. The yardsticks of the prefill
layer from PR to PR are `prefill_ms_per_ktok` and
`prefill_expert_ms_per_ktok` (a rate per token moves with neither). None
without a device trace, a whole step or a dense prefill in one (every
prompt riding the decode scan)."""
import json

from lib import spans


def compute(record, trace):
    t = spans.load(record)
    steps = [sp for sp, _, _ in
             (spans.under(t, "serving.engine_step") if t else [])]
    prefills = [sp for sp in (t.spans if steps else [])
                if sp.name == "serving.prefill"
                and any(s.start <= sp.start and sp.end <= s.end
                        for s in steps)]
    if not prefills:
        return None
    step_ns = sum(s.end - s.start for s in steps)
    prefill_ns = sum(p.end - p.start for p in prefills)
    print(json.dumps({"prefill_share": {
        "steps": len(steps), "prefills": len(prefills),
        "step_seconds": step_ns / 1e9,
        "prefill_seconds": prefill_ns / 1e9}}), flush=True)
    return 100.0 * prefill_ns / step_ns
