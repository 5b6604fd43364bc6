"""Share of the chip's HBM bandwidth that the decode chunk of the KDA + MLA
family achieves on the bytes its scan trips HAVE to move
(`lib/serve_work_kda.decode_trip_bytes`: every matrix that every token
multiplies and the head once a trip, a held expert's matrices once for each
trip and layer in which a token reached it (`moe_experts_hit`), the latent
row of every position attended to (`context_tokens`) in the MLA layers, the
KDA state and conv history of every live row read and written once a layer
(`kda_state_row_trips`); all stats of `serving.decode`), over the device
seconds of the chunk program's executions in the traced window. Bound: HBM.
None where the trace has nothing to read or the runner gives no `work`."""
import json

from lib import chip, serve_work_kda


def compute(record, trace):
    work = record.get("facts", {}).get("work")
    if not work or "mla_layers" not in work.get("config", {}):
        return None
    seen = serve_work_kda.traced(record)
    if seen is None:
        return None
    need = serve_work_kda.decode_trip_bytes(
        work["config"], seen["trips"], seen["experts_hit"],
        seen["context_tokens"], seen["state_row_trips"])
    peak = chip.peaks(record["device"]["kind"])["hbm_bytes_per_s"]
    print(json.dumps({"decode_trip_kda": {
        **seen, "bytes_required": need,
        "state_bytes_required": serve_work_kda.state_bytes(
            work["config"], seen["state_row_trips"]),
        "ms_per_trip": 1e3 * seen["program_seconds"] / seen["trips"],
        "bound": "hbm_bytes_per_s"}}), flush=True)
    return 100.0 * need / seen["program_seconds"] / peak
