"""Share of the traced window in which the device idled inside a span of
the engine that is neither phase: `serving.engine_step` outside its
children, `serving.schedule`, `serving.add_request`."""
from lib import spans


def compute(record, trace):
    return spans.idle_pct(record, "engine_other")
