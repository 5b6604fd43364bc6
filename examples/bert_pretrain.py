"""BERT-base MLM+NSP pretraining (BASELINE.md config 3), synthetic batches.

One chip:  python examples/bert_pretrain.py
ERNIE-large with ZeRO-2 + AMP over a mesh (config 4):
           python examples/bert_pretrain.py --ernie-large --sharding 8
Small/CPU: JAX_PLATFORMS=cpu python examples/bert_pretrain.py --tiny
"""
import os
import sys

# runnable as `python examples/<name>.py` from anywhere: the repo
# root (one level up) must be importable
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
import argparse
import time

import jax

import numpy as np

import paddle_tpu as paddle
from paddle_tpu.utils.compile_cache import enable_compile_cache
import paddle_tpu.optimizer as opt
from paddle_tpu.models.bert import (BertConfig, BertForPretraining,
                                    bert_pretrain_loss_fn, ernie_large)


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch-size", type=int, default=64)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ernie-large", action="store_true")
    ap.add_argument("--tiny", action="store_true",
                    help="CPU-sized config for smoke runs")
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--sharding", type=int, default=1,
                    help="ZeRO sharding degree")
    args = ap.parse_args()

    paddle.seed(0)
    if args.tiny:
        cfg = BertConfig(vocab_size=512, hidden_size=64, num_layers=2,
                         num_heads=4, max_position=64)
        args.batch_size, args.seq = min(args.batch_size, 4), 32
    elif args.ernie_large:
        cfg = ernie_large()
    else:
        cfg = BertConfig()  # bert-base
    model = BertForPretraining(cfg)
    optim = opt.AdamW(1e-4, parameters=model.parameters())
    on_tpu = jax.default_backend() != "cpu"
    if on_tpu:
        model, optim = paddle.amp.decorate(model, optim, level="O2",
                                           dtype="bfloat16")

    if args.dp > 1 or args.sharding > 1:
        from paddle_tpu.parallel import (build_mesh, set_global_mesh,
                                         ShardedTrainStep, ShardingStage)
        mesh = build_mesh(dp=args.dp, sharding=args.sharding)
        set_global_mesh(mesh)
        step = ShardedTrainStep(model, bert_pretrain_loss_fn, optim,
                                mesh=mesh,
                                sharding_stage=ShardingStage.GRADIENT)
    else:
        step = paddle.jit.TrainStep(model, bert_pretrain_loss_fn, optim)

    bs, seq = args.batch_size, args.seq
    rng = np.random.RandomState(0)
    # masked-position MLM (15% of tokens, the reference design:
    # bert_dygraph_model.py:335 gathers mask positions before the head)
    from paddle_tpu.models.bert import make_bert_pretrain_batch
    x, tt, mlm, nsp, pos_t = (paddle.to_tensor(a) for a in
                              make_bert_pretrain_batch(
                                  rng, cfg.vocab_size, bs, seq))

    step(x, tt, mlm, nsp, pos_t)  # trace 1: optimizer state
    step(x, tt, mlm, nsp, pos_t)  # trace 2: settled signature
    t0 = time.perf_counter()
    losses = [float(step(x, tt, mlm, nsp, pos_t).numpy())
              for _ in range(args.steps)]
    dt = time.perf_counter() - t0
    name = "ernie-large" if args.ernie_large else "bert-base"
    print(f"{name} bs={bs} seq={seq}: loss {losses[0]:.3f} -> "
          f"{losses[-1]:.3f}, {args.steps * bs / dt:.0f} samples/s "
          f"(incl. host dispatch)")


if __name__ == "__main__":
    main()
