"""Share of the chip's peak bf16 FLOP/s that is model work in a serving
window of the KDA + MLA family: the operations the positions prefilled and
decoded in the window REQUIRE (`lib/serve_work_kda.serve_flops`: every
matrix a token multiplies, the routed experts by the program's `moe_pairs`
counter, the head for sampled positions, latent attention by cached
positions attended to in the MLA layers, the recurrence's three products a
position, KDA layer and head) over the window's seconds and the peak. The
share of the whole step. Host clock; the counts are the program's."""
from lib import chip, serve_work_kda


def compute(record, trace):
    work = record.get("facts", {}).get("work")
    if not work or "mla_layers" not in work.get("config", {}):
        return None
    flops = serve_work_kda.serve_flops(
        work["config"], work["positions_through_layers"],
        work["sampled_positions"], record["facts"]["moe_pairs"],
        work["decode_context_tokens"], work["prefill_pairs"])
    peak = chip.peaks(record["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * flops / record["facts"]["window_seconds"] \
        / (peak * record["device"]["count"])
