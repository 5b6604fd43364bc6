"""GPT pretraining on one chip or an SPMD mesh.

Single chip:   python examples/gpt_pretrain.py
SPMD (dp/tp):  python examples/gpt_pretrain.py --dp 2 --tp 2 --sharding 2
(Test multi-chip layouts anywhere with
 XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu.)
"""
import os
import sys

# runnable as `python examples/<name>.py` from anywhere: the repo
# root (one level up) must be importable
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
import argparse

import numpy as np

import paddle_tpu as paddle
from paddle_tpu.utils.compile_cache import enable_compile_cache
from paddle_tpu.models.gpt import GPT, GPTConfig, gpt_loss_fn


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--sharding", type=int, default=1)
    args = ap.parse_args()

    paddle.seed(0)
    cfg = GPTConfig(vocab_size=8192, hidden_size=256, num_layers=4,
                    num_heads=4, max_seq_len=args.seq)
    model = GPT(cfg)
    optim = paddle.optimizer.AdamW(
        3e-4, parameters=model.parameters(),
        grad_clip=paddle.nn.ClipGradByGlobalNorm(1.0))
    model, optim = paddle.amp.decorate(model, optim, level="O2",
                                       dtype="bfloat16")

    if args.dp * args.tp * args.sharding > 1:
        from paddle_tpu.parallel import ShardedTrainStep, ShardingStage
        from paddle_tpu.parallel.mesh import build_mesh, set_global_mesh
        mesh = build_mesh(dp=args.dp, tp=args.tp, sharding=args.sharding)
        set_global_mesh(mesh)
        step = ShardedTrainStep(
            model, gpt_loss_fn, optim, mesh=mesh,
            sharding_stage=ShardingStage.GRADIENT
            if args.sharding > 1 else ShardingStage.OFF)
    else:
        step = paddle.jit.TrainStep(model, gpt_loss_fn, optim)

    # a fixed synthetic corpus with next-token structure (y = shifted x),
    # so the loss demonstrably falls
    rs = np.random.RandomState(0)
    tokens = rs.randint(0, cfg.vocab_size,
                        (args.batch_size, args.seq + 1), dtype=np.int32)
    x = paddle.to_tensor(tokens[:, :-1])
    y = paddle.to_tensor(tokens[:, 1:])
    for i in range(args.steps):
        loss = step(x, y)
        if i % 5 == 0 or i == args.steps - 1:
            print(f"step {i}: loss {float(loss.numpy()):.4f}")


if __name__ == "__main__":
    main()
