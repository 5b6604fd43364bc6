"""Milliseconds per fused decode chunk, host clock round work that ends in
the chunk's one fetch (EngineStats time_decode over host_syncs("decode"))."""


def compute(record, trace):
    d = record["facts"].get("engine")
    if not d or not d["syncs_decode"]:
        return None
    return 1e3 * d["time_decode"] / d["syncs_decode"]
