"""Share of the chip's HBM bandwidth that the operations of `gdn_step_ms`
achieve on the bytes the recurrence HAS to move: the float32 recurrent
state of every live row read once and written once a DeltaNet layer and
trip (`live_row_trips`, a stat of `serving.decode`; the conv history is
left out: its operations are not among those timed), over those operations'
device seconds. Gathered copies, second reads and the scatter back are the
implementation's and are not counted. Bound: HBM."""
import json

from lib import chip, serve_work_hybrid


def compute(record, trace):
    seen = serve_work_hybrid.state_ops(record)
    if seen is None or not seen["seconds"]:
        return None
    need = serve_work_hybrid.state_bytes(
        record["facts"]["work"]["config"], seen["live_row_trips"],
        conv=False)
    peak = chip.peaks(record["device"]["kind"])["hbm_bytes_per_s"]
    print(json.dumps({"gdn_step": {**seen, "bytes_required": need,
                                   "bound": "hbm_bytes_per_s"}}),
          flush=True)
    return 100.0 * need / seen["seconds"] / peak
