"""Device milliseconds a scan trip of the decode chunk spends in the
operations that read or write the Gated DeltaNet layers' recurrent state
(all DeltaNet layers of the trip together). The update is composed of XLA
operations, so they are found by the state's SHAPE among an operation's
operands and results (`lib/serve_work_hybrid.state_ops`), not by a kernel's
name. None without a device trace or such an operation."""
from lib import serve_work_hybrid


def compute(record, trace):
    seen = serve_work_hybrid.state_ops(record)
    if seen is None or not seen["trips"]:
        return None
    return 1e3 * seen["seconds"] / seen["trips"]
