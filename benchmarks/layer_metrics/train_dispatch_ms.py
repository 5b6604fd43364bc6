"""Median host milliseconds for `step(x, y)` to return, unblocked."""
import statistics


def compute(record, trace):
    samples = record["facts"].get("_dispatch_s")
    return 1e3 * statistics.median(samples) if samples else None
