"""Host milliseconds in `serving.prefill.write_cache`
(PagedKVCache.write_prefill) per prefill: the part of `prefill_ms` that is
the host scattering the dense cache into the pools."""
from lib import spans


def compute(record, trace):
    return spans.ms_per_span(record, "serving.prefill.write_cache",
                             device=False)
