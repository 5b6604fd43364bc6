"""Train LeNet on (synthetic-fallback) MNIST — the minimum end-to-end slice
(BASELINE config 1). Run: python examples/mnist_lenet.py [--epochs N]
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


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--batch-size", type=int, default=64)
    args = ap.parse_args()

    train_ds = paddle.vision.datasets.MNIST(mode="train")
    loader = paddle.io.DataLoader(train_ds, batch_size=args.batch_size,
                                  shuffle=True)

    model = paddle.vision.models.LeNet()
    optim = paddle.optimizer.Adam(1e-3, parameters=model.parameters())
    # one compiled XLA module for fwd+bwd+update
    step = paddle.jit.TrainStep(
        model, lambda m, x, y: paddle.nn.functional.cross_entropy(m(x), y),
        optim)

    for epoch in range(args.epochs):
        losses = []
        # DeviceLoader double-buffers the host->HBM transfer: batch N+1 is
        # already in flight while the compiled step runs batch N
        for x, y in paddle.io.DeviceLoader(loader, size=2):
            losses.append(float(step(x, y).numpy()))
        print(f"epoch {epoch}: loss {np.mean(losses):.4f}")

    # evaluate
    model.eval()
    test_ds = paddle.vision.datasets.MNIST(mode="test")
    correct = total = 0
    for x, y in paddle.io.DataLoader(test_ds, batch_size=256):
        pred = model(x).numpy().argmax(-1)
        correct += int((pred == y.numpy().reshape(-1)).sum())
        total += len(pred)
    print(f"test accuracy: {correct / total:.3f}")


if __name__ == "__main__":
    main()
