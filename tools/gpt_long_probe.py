"""Long-context single-chip probe: flagship GPT at T=2048/4096/8192.

Long-context series (flash attention keeps HBM O(T), so MFU should RISE
with sequence while the attention-flops share grows). An earlier builder
reported T=2048 MFU 0.650, T=4096 0.688, T=8192 0.749 on one v5e chip;
not re-measured on today's code.
Run: python tools/gpt_long_probe.py [T] [bs]
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main(T=8192, bs=4):
    from bench import run_gpt_probe
    from paddle_tpu.models.gpt import GPTConfig

    cfg = GPTConfig(vocab_size=32768, hidden_size=768, num_layers=12,
                    num_heads=6, max_seq_len=T)
    # ~1M tokens per timed window, matching the standard bench geometry
    # (30 iters x 32 x 1024)
    iters = max(4, 1_000_000 // (bs * T))
    return run_gpt_probe(cfg, bs, iters, "gpt_long")


if __name__ == "__main__":
    main(int(sys.argv[1]) if len(sys.argv) > 1 else 8192,
         int(sys.argv[2]) if len(sys.argv) > 2 else 4)
