"""Milliseconds per dense prefill, host clock round work that ends in the
fetch of its logits (EngineStats time_prefill over host_syncs("prefill"))."""


def compute(record, trace):
    d = record["facts"].get("engine")
    if not d or not d["syncs_prefill"]:
        return None
    return 1e3 * d["time_prefill"] / d["syncs_prefill"]
