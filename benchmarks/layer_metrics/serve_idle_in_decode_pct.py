"""Share of the traced window in which the device idled while the driving
thread was in `serving.decode` or one of its children (pack, dispatch,
fetch, drain)."""
from lib import spans


def compute(record, trace):
    return spans.idle_pct(record, "decode")
