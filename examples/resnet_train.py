"""ResNet-50 training (BASELINE.md config 2), synthetic ImageNet batches.

One chip (the bench recipe — NCHW, O2 bf16, fused bn+relu, one compiled
step):       python examples/resnet_train.py
Small/CPU:   JAX_PLATFORMS=cpu python examples/resnet_train.py --depth 18 \
                 --image-size 64 --batch-size 8 --steps 5
Data-parallel SPMD over a mesh:  python examples/resnet_train.py --dp 8
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


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--depth", type=int, default=50,
                    choices=[18, 34, 50, 101, 152])
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch-size", type=int, default=128)
    ap.add_argument("--image-size", type=int, default=224)
    ap.add_argument("--classes", type=int, default=1000)
    ap.add_argument("--dp", type=int, default=1,
                    help="data-parallel degree (SPMD mesh)")
    ap.add_argument("--nhwc", action="store_true",
                    help="channel-last end-to-end + space-to-depth stem")
    args = ap.parse_args()

    paddle.seed(0)
    from paddle_tpu.vision.models import resnet
    ctor = {18: resnet.resnet18, 34: resnet.resnet34, 50: resnet.resnet50,
            101: resnet.resnet101, 152: resnet.resnet152}[args.depth]
    kwargs = dict(num_classes=args.classes)
    if args.nhwc:
        kwargs.update(data_format="NHWC", stem_space_to_depth=True)
    model = ctor(**kwargs)
    optim = paddle.optimizer.Momentum(0.1, momentum=0.9,
                                      parameters=model.parameters())
    on_tpu = jax.default_backend() != "cpu"
    if on_tpu:
        model, optim = paddle.amp.decorate(model, optim, level="O2",
                                           dtype="bfloat16")

    def loss_fn(m, x, y):
        return paddle.nn.functional.cross_entropy(m(x), y)

    if args.dp > 1:
        from paddle_tpu.parallel import (build_mesh, set_global_mesh,
                                         ShardedTrainStep)
        mesh = build_mesh(dp=args.dp)
        set_global_mesh(mesh)
        step = ShardedTrainStep(model, loss_fn, optim, mesh=mesh)
    else:
        step = paddle.jit.TrainStep(model, loss_fn, optim)

    bs, size = args.batch_size, args.image_size
    shape = (bs, size, size, 3) if args.nhwc else (bs, 3, size, size)
    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.randn(*shape).astype(np.float32))
    if on_tpu:
        x = x.astype("bfloat16")
    y = paddle.to_tensor(
        rng.randint(0, args.classes, (bs, 1)).astype(np.int64))

    step(x, y)  # trace 1: creates optimizer state
    step(x, y)  # trace 2: compiles against the settled signature
    t0 = time.perf_counter()
    losses = [float(step(x, y).numpy()) for _ in range(args.steps)]
    dt = time.perf_counter() - t0
    print(f"resnet{args.depth} bs={bs}@{size}: "
          f"loss {losses[0]:.3f} -> {losses[-1]:.3f}, "
          f"{args.steps * bs / dt:.0f} imgs/s (incl. host dispatch)")


if __name__ == "__main__":
    main()
