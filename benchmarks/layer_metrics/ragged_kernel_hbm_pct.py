"""Share of the chip's HBM bandwidth that the `ragged_decode_attention`
kernel's calls achieve on the bytes they HAD to read: per layer and scan
trip the K and V of every position a live row attends to, `context_tokens`
(a stat of `serving.decode`, host arithmetic of the engine) x heads x
head_dim x element size x 2. Heads, head_dim and the element size are those
of the pool the call reads, its first four-dimensional operand `[blocks,
block_size, heads, head_dim]`; layers are the span's kernel calls over its
`chunk`. What an implementation moves beyond
that (whole blocks, dead rows' DMAs) is not counted, so the share stays
valid when the kernel is rewritten."""
import json

from lib import chip, spans


def compute(record, trace):
    found = spans.kernel_by_span(record, "ragged_decode_attention",
                                 "serving.decode")
    if not found:
        return None
    need = 0
    for sp, ops in found:
        _, (_, _, heads, head_dim), itemsize = next(
            a for a in spans.shapes(ops[0][2]) if len(a[1]) == 4)
        layers = len(ops) // sp.stats["chunk"]
        need += sp.stats["context_tokens"] * heads * head_dim * itemsize \
            * 2 * layers
    busy = sum(spans.seconds(ops) for _, ops in found)
    peak = chip.peaks(record["device"]["kind"])["hbm_bytes_per_s"]
    print(json.dumps({"ragged_kernel": {
        "chunks": len(found), "calls": sum(len(o) for _, o in found),
        "bytes_required": need, "kernel_seconds": busy,
        "bound": "hbm_bytes_per_s"}}), flush=True)
    return 100.0 * need / busy / peak
