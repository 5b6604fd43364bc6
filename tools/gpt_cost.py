"""Flagship GPT train-step cost/traffic audit (bench geometry).

Usage: python tools/gpt_cost.py [top_n]
"""
from __future__ import annotations

import os
import sys

import numpy as np

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_ROOT, "tools"))
sys.path.insert(0, _ROOT)
from hlo_bytes import audit_text  # noqa: E402
from bench import _peak_flops, _gpt_flops_per_token  # noqa: E402


def main():
    top_n = int(sys.argv[1]) if len(sys.argv) > 1 else 25
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    import paddle_tpu.optimizer as opt
    from paddle_tpu.models.gpt import GPT, GPTConfig, gpt_loss_fn
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=32768, hidden_size=768, num_layers=12,
                    num_heads=6, max_seq_len=1024)
    bs, seq = 32, 1024
    model = GPT(cfg)
    optim = opt.AdamW(1e-4, parameters=model.parameters(),
                      grad_clip=paddle.nn.ClipGradByGlobalNorm(1.0))
    model, optim = paddle.amp.decorate(model, optim, level="O2",
                                       dtype="bfloat16")
    step = paddle.jit.TrainStep(
        model, lambda m, x, y: gpt_loss_fn(m, x, y), optim)
    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.randint(0, cfg.vocab_size, (bs, seq),
                                     dtype=np.int32))
    y = paddle.to_tensor(rng.randint(0, cfg.vocab_size, (bs, seq),
                                     dtype=np.int32))
    step(x, y)
    params, frozen = step._split_params()
    buffers = {k: b._value for k, b in step._collect_state()[2]}
    lowered = step._step.lower(
        params, frozen, buffers, step._opt_state,
        jnp.asarray(1e-4, jnp.float32), step._key_root,
        jnp.asarray(2, jnp.uint32), x._value, y._value)
    compiled = lowered.compile()
    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    flops, ba = ca.get("flops", 0.0), ca.get("bytes accessed", 0.0)
    peak = _peak_flops(jax.devices()[0])
    model_flops = _gpt_flops_per_token(cfg) * bs * seq
    print(f"cost_analysis: {flops/1e12:.2f} TFLOP/step (model accounting "
          f"{model_flops/1e12:.2f}), {ba/1e9:.2f} GB accessed/step")
    print(f"  flop floor {flops/peak*1e3:.1f} ms | byte floor "
          f"{ba/819e9*1e3:.1f} ms")
    hlo = compiled.as_text()
    with open("/tmp/gpt_hlo.txt", "w") as f:
        f.write(hlo)
    audit_text(hlo, top_n)


if __name__ == "__main__":
    main()
