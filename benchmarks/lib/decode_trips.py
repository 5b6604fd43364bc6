"""What a device trace holds of the fused decode chunk: the program's
executions (`XLA Modules`) and, from the `serving.decode` spans that cover
them, the counts the program returned with its result."""
from __future__ import annotations

from lib import spans

PROGRAM = "jit_fused_decode_chunk"


def traced(record):
    """{chunks, programs, trips, experts_hit, context_tokens,
    program_seconds} over the traced window; None where the trace has no
    device plane, no chunk ran, or no span carries `moe_experts_hit` (a
    family without experts, a parent without the stat)."""
    tr = spans.load(record)
    if tr is None:
        return None
    found = [sp for sp, _, _ in spans.under(tr, "serving.decode")
             if "moe_experts_hit" in sp.stats]
    runs = [(s, e) for s, e, name in tr.modules
            if name.startswith(PROGRAM) and tr.inside(s, e)]
    if not found or not runs:
        return None
    return {
        "chunks": len(found), "programs": len(runs),
        "trips": sum(int(sp.stats["chunk"]) for sp in found),
        "experts_hit": sum(int(sp.stats["moe_experts_hit"]) for sp in found),
        "context_tokens": sum(int(sp.stats["context_tokens"])
                              for sp in found),
        # a span and its program need not both lie wholly in the window:
        # scale the seconds to the spans counted
        "program_seconds": sum(e - s for s, e in runs) / 1e9
        * len(found) / len(runs)}
