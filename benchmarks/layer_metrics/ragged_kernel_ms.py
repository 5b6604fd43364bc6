"""Device milliseconds of the `ragged_decode_attention` kernel per
`serving.decode` span (one fused chunk: layers x chunk calls)."""
from lib import spans


def compute(record, trace):
    found = spans.kernel_by_span(record, "ragged_decode_attention",
                                 "serving.decode")
    if not found:
        return None
    return 1e3 * sum(spans.seconds(ops) for _, ops in found) / len(found)
