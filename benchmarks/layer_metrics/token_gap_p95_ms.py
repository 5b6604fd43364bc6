"""95th percentile of the wait between consecutive deliveries of new tokens
to one request: the tail of `token_gap_mean_ms`. A layer metric, with no
bound: it sits on the edge between steps with two prefills and with three,
so it reads 606 or 855 ms by the order of the requests (PERF.md section 6,
PR 26)."""


def compute(record, trace):
    return record["end_to_end"].get("token_gap_p95_ms")
