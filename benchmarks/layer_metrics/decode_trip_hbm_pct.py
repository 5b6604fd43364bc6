"""Share of the chip's HBM bandwidth that the decode chunk achieves on the
bytes its scan trips HAVE to read (`lib/serve_work.decode_trip_bytes`:
every matrix that every token multiplies and the head once a trip, a held
expert's matrices once for each trip and layer in which a token reached it
(`moe_experts_hit`, a stat of `serving.decode`), the cached row of every
position attended to (`context_tokens`) in every layer), over the device
seconds of the chunk program's executions (`XLA Modules`) in the traced
window (`lib/decode_trips.traced`). Bound: HBM. None where the trace has
nothing to read or the runner gives no `work`."""
import json

from lib import chip, decode_trips, serve_work


def compute(record, trace):
    work = record.get("facts", {}).get("work")
    seen = decode_trips.traced(record) if work else None
    if seen is None:
        return None
    need = serve_work.decode_trip_bytes(
        work["config"], seen["trips"], seen["experts_hit"],
        seen["context_tokens"], work["config"]["itemsize"])
    peak = chip.peaks(record["device"]["kind"])["hbm_bytes_per_s"]
    print(json.dumps({"decode_trip": {
        **seen, "bytes_required": need,
        "ms_per_trip": 1e3 * seen["program_seconds"] / seen["trips"],
        "bound": "hbm_bytes_per_s"}}), flush=True)
    return 100.0 * need / seen["program_seconds"] / peak
