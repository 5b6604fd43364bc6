"""Share of the chip's peak bf16 FLOP/s that the packed-pair flash kernels
achieve on the operations causal attention REQUIRES: forward 2 B H T^2 d
(two products over half the square), backward 4 B H T^2 d (four), nothing
for what a backward recomputes. B, T and H d are read from each call's
result type `[B, heads or pairs, T, lanes]`: H d is the product of its head
and lane dimensions whether or not heads are packed."""
import json

from lib import chip, spans

#: products of B H T^2 d a call requires, by the kernel's name; the two
#: halves of the long-sequence backward share its four
PRODUCTS = (("packed_flash_bwd_dq", 2), ("packed_flash_bwd_dkv", 2),
            ("packed_flash_bwd", 4), ("packed_flash_fwd", 2))


def compute(record, trace):
    found = spans.kernel_by_program(record, "packed_flash_")
    if not found:
        return None
    flops = 0
    for _, _, text in (op for ops in found for op in ops):
        _, (b, heads, t, lanes), _ = spans.shapes(text)[0]
        flops += next(n for k, n in PRODUCTS if k in text.split(" = ")[0]) \
            * b * heads * lanes * t * t
    busy = sum(spans.seconds(ops) for ops in found)
    peak = chip.peaks(record["device"]["kind"])["bf16_flops_per_s"]
    print(json.dumps({"packed_flash_kernels": {
        "executions": len(found), "calls": sum(len(o) for o in found),
        "flops_required": flops, "kernel_seconds": busy,
        "bound": "bf16_flops_per_s"}}), flush=True)
    return 100.0 * flops / busy / peak
