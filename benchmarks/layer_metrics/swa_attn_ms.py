"""Device milliseconds a scan trip of the decode chunk spends in the
operations that read the sliding-window layers' pools (all window layers of
the trip together): the gather of each row's window blocks with whatever
the compiler fused to it, and the write of the new row. The window
attention is composed of XLA operations, so they are found by the window
pools' SHAPE among an operation's operands and results
(`lib/serve_work_swa.window_ops`), not by a kernel's name. None without a
device trace or such an operation."""
from lib import serve_work_swa


def compute(record, trace):
    seen = serve_work_swa.window_ops(record)
    if seen is None or not seen["trips"]:
        return None
    return 1e3 * seen["seconds"] / seen["trips"]
