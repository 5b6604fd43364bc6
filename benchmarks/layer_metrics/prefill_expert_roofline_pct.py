"""Share of the chip's roofline that the operations of
`prefill_expert_ms_per_ktok` achieve on the work a prompt's expert layers
REQUIRE: the larger of the pairs' operations at the MXU's peak and, at the
HBM's, the weights of as many experts as one call reached on average, read
once a layer (`lib/expert_ops.required`), over those operations' device
seconds. Which of the two bounds is printed as an earlier line. Padding to
a tile or a capacity, and the weights read again for every block of a
prompt's tokens, are the implementation's and are not counted. Bound: the
larger of MXU and HBM."""
import json

from lib import expert_ops


def compute(record, trace):
    seen = expert_ops.traced(record)
    if seen is None or not seen["seconds"]:
        return None
    need = expert_ops.required(seen, record["device"]["kind"])
    if need is None:
        return None
    print(json.dumps({"prefill_expert_required": {
        **need, "device_seconds": seen["seconds"]}}), flush=True)
    return 100.0 * need["seconds"] / seen["seconds"]
