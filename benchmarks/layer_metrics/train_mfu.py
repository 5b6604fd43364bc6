"""Share of the chip's peak bf16 FLOP/s that is model work: tokens/s times
the operations a forward and backward pass require per token."""
from lib import chip


def compute(record, trace):
    rate = record["end_to_end"].get("train_tokens_per_s")
    if rate is None:
        return None
    peak = chip.peaks(record["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * rate * record["facts"]["flops_per_token"] \
        / (peak * record["device"]["count"])
