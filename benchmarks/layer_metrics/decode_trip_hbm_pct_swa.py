"""Share of the chip's HBM bandwidth that the decode chunk of the
sliding-window family achieves on the bytes its scan trips HAVE to move
(`lib/serve_work_swa.decode_trip_bytes`: every matrix that every token
multiplies and the head once a trip, an expert's matrices once for each
trip and layer in which a token reached it (`moe_experts_hit`), k and v of
every position attended to, the whole context in the full layers
(`context_tokens`) and the window in the others
(`window_context_tokens`); all stats of `serving.decode`), over the device
seconds of the chunk program's executions (`XLA Modules`) in the traced
window. Bound: HBM. None where the trace has nothing to read or the runner
gives no `work`."""
import json

from lib import chip, serve_work_swa


def compute(record, trace):
    work = record.get("facts", {}).get("work")
    if not work or "window_layers" not in work.get("config", {}):
        return None
    seen = serve_work_swa.traced(record)
    if seen is None:
        return None
    need = serve_work_swa.decode_trip_bytes(
        work["config"], seen["trips"], seen["experts_hit"],
        seen["context_tokens"], seen["window_context_tokens"])
    peak = chip.peaks(record["device"]["kind"])["hbm_bytes_per_s"]
    print(json.dumps({"decode_trip_swa": {
        **seen, "bytes_required": need,
        "window_bytes_required": serve_work_swa.window_bytes(
            work["config"], seen["window_context_tokens"]),
        "ms_per_trip": 1e3 * seen["program_seconds"] / seen["trips"],
        "bound": "hbm_bytes_per_s"}}), flush=True)
    return 100.0 * need / seen["program_seconds"] / peak
