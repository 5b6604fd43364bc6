"""Share of the traced window in which the device idled while the driving
thread was in `serving.prefill` or one of its children (forward,
write_cache, fetch, sample). With the other three `serve_idle_*_pct` it
adds up to `device_idle_share.serve`. Prints the whole table, idle seconds
by innermost span, as an earlier line."""
from lib import spans


def compute(record, trace):
    return spans.idle_pct(record, "prefill", tell=True)
