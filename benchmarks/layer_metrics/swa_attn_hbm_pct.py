"""Share of the chip's HBM bandwidth that the operations of `swa_attn_ms`
achieve on the bytes the window layers' attention HAS to read: k and v of
min(context, window) positions a live row and trip in every window layer
(`window_context_tokens`, a stat of `serving.decode`), over those
operations' device seconds. The whole blocks of a window table, the rows of
dead slots and gathered copies are the implementation's and are not
counted: the roofline share of what stands in for a window-attention
kernel. Bound: HBM."""
import json

from lib import chip, serve_work_swa


def compute(record, trace):
    seen = serve_work_swa.window_ops(record)
    if seen is None or not seen["seconds"]:
        return None
    need = serve_work_swa.window_bytes(
        record["facts"]["work"]["config"], seen["window_context_tokens"])
    peak = chip.peaks(record["device"]["kind"])["hbm_bytes_per_s"]
    print(json.dumps({"swa_attn": {**seen, "bytes_required": need,
                                   "bound": "hbm_bytes_per_s"}}),
          flush=True)
    return 100.0 * need / seen["seconds"] / peak
