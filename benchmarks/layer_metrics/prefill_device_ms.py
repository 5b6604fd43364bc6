"""Device-busy milliseconds inside `serving.prefill` per prefill: what a
prefill costs the chip, beside `prefill_ms`, what it costs the host."""
from lib import spans


def compute(record, trace):
    return spans.ms_per_span(record, "serving.prefill", device=True)
