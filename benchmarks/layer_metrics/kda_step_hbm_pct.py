"""Share of the chip's HBM bandwidth that the `kda_step` kernel achieves on
the bytes the recurrence HAS to move: the float32 state and the conv
history of every live row read once and written once a KDA layer and trip
(`kda_state_row_trips`, a stat of `serving.decode`;
`lib/serve_work_kda.state_bytes`), over the kernel's device seconds. The
row's inputs and its output are the kernel's too and are not counted.
Bound: HBM."""
import json

from lib import chip, serve_work_kda


def compute(record, trace):
    work = record.get("facts", {}).get("work")
    seen = serve_work_kda.kernel(record)
    if seen is None or not seen["seconds"] or not work:
        return None
    need = serve_work_kda.state_bytes(work["config"],
                                      seen["state_row_trips"])
    peak = chip.peaks(record["device"]["kind"])["hbm_bytes_per_s"]
    print(json.dumps({"kda_step": {**seen, "bytes_required": need,
                                   "bound": "hbm_bytes_per_s"}}),
          flush=True)
    return 100.0 * need / seen["seconds"] / peak
