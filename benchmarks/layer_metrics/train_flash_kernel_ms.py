"""Device milliseconds of the packed-pair flash kernels (`packed_flash_fwd`,
`packed_flash_bwd`, and `_bwd_dq` / `_bwd_dkv` on the long-sequence branch)
per executed program that runs them (`jit_step`)."""
from lib import spans


def compute(record, trace):
    found = spans.kernel_by_program(record, "packed_flash_")
    if not found:
        return None
    return 1e3 * sum(spans.seconds(ops) for ops in found) / len(found)
