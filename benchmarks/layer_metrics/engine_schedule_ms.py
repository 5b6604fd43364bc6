"""Host milliseconds the scheduler took per engine step (EngineStats
time_schedule over steps, as deltas over the window)."""


def compute(record, trace):
    d = record["facts"].get("engine")
    return 1e3 * d["time_schedule"] / d["steps"] if d and d["steps"] else None
