"""GPT text generation end to end: train briefly, then decode three ways
— greedy KV-cache, temperature sampling, beam search — and serve the
exported StableHLO decoder without the model class.

Run: python examples/gpt_generate.py
"""
import os
import sys

# runnable as `python examples/<name>.py` from anywhere: the repo
# root (one level up) must be importable
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
import tempfile

import numpy as np

import paddle_tpu as paddle
from paddle_tpu.utils.compile_cache import enable_compile_cache
import paddle_tpu.optimizer as opt
from paddle_tpu.models.gpt import GPT, GPTConfig, gpt_loss_fn
from paddle_tpu.models.generation import (DecoderPredictor,
                                          beam_search_generate,
                                          export_decoder, generate)


def main():
    enable_compile_cache()
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=2,
                    num_heads=4, max_seq_len=32)
    model = GPT(cfg)
    optim = opt.AdamW(3e-3, parameters=model.parameters())
    step = paddle.jit.TrainStep(model, gpt_loss_fn, optim)

    # teach it a trivial skill: predict token (t + 1) % 128
    rng = np.random.RandomState(0)
    for i in range(400):
        x = rng.randint(0, 127, (8, 24))  # len 24: positions past the
        # served prefill window (16) are trained too
        y = (x + 1) % 128
        loss = step(paddle.to_tensor(x), paddle.to_tensor(y))
    print(f"final train loss: {float(loss.numpy()):.3f}")

    model.eval()
    prompt = np.arange(5, 10)[None, :]
    print("prompt:     ", prompt[0].tolist())
    print("greedy:     ", generate(model, prompt, 6)[0, 5:].tolist())
    print("sampled:    ", generate(model, prompt, 6, temperature=0.7,
                                   top_k=8, seed=1)[0, 5:].tolist())
    beams, scores = beam_search_generate(model, prompt, beam_size=4,
                                         max_new_tokens=6)
    print("beam-4:     ", beams[0, 5:].tolist(),
          f"(logprob {float(scores[0]):.2f})")

    with tempfile.TemporaryDirectory() as d:
        export_decoder(model, d + "/gpt")
        served = DecoderPredictor(d + "/gpt")
        full = np.arange(0, served.prefill_len)[None, :] % 128
        out = served.generate(full, 4)
        print("served:     ", out[0, -4:].tolist(),
              "(StableHLO artifacts, no model class)")


if __name__ == "__main__":
    main()
