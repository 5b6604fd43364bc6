"""Device milliseconds a scan trip of the decode chunk spends in the
`kda_step` kernel (all KDA layers of the trip together): the Pallas kernel
that updates each live row's recurrent state and conv history in place in
its slot, found by its name (`lib/serve_work_kda.kernel`). None without a
device trace or the kernel."""
from lib import serve_work_kda


def compute(record, trace):
    seen = serve_work_kda.kernel(record)
    if seen is None or not seen["trips"]:
        return None
    return 1e3 * seen["seconds"] / seen["trips"]
