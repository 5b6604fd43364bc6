"""Median milliseconds from a request's `serving.add_request` to the start
of the `serving.prefill` with the same `request_id`: how long it waited
before its own prefill began. Over the requests added in the traced
window; the sample count is printed as an earlier line."""
import json
import statistics

from lib import spans


def compute(record, trace):
    t = spans.load(record)
    waits = [1e-6 * (b.start - a.start) for a, b in spans.joined(
        t, "serving.add_request", "serving.prefill", "request_id")] \
        if t else []
    if not waits:
        return None
    print(json.dumps({"ttft_wait_samples": len(waits),
                      "ttft_wait_ms_min_max": [min(waits), max(waits)]}),
          flush=True)
    return statistics.median(waits)
