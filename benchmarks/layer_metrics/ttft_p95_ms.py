"""95th percentile of the time to the first token, over the requests
submitted in the window: the tail of `ttft_p50_ms`. A layer metric, with no
bound: a 40 s window holds about 115 requests, so six lie beyond it, and
in this closed loop it sits at a step with three prefills or with four by
the order of the requests (PERF.md section 6, PR 26)."""


def compute(record, trace):
    return record["end_to_end"].get("ttft_p95_ms")
