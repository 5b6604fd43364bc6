"""Share of the traced window in which the device idled under no span of
the program: the client's code between two calls into the engine."""
from lib import spans


def compute(record, trace):
    return spans.idle_pct(record, spans.OUTSIDE)
