"""ResNet-50 train-step HBM-traffic audit (round-4: 2,606 -> >=2,800 imgs/s).

Compiles the bench-identical step, then reports:
  1. compiled.cost_analysis() aggregate flops / bytes accessed
  2. memory_analysis (args/output/temp sizes)
  3. the optimized-HLO byte ranking via tools/hlo_bytes.py (shared parser)
The optimized HLO text is also dumped to /tmp/rn_hlo.txt for ad-hoc greps.

Usage:  python tools/resnet_cost.py [top_n]
"""
from __future__ import annotations

import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from hlo_bytes import audit_text  # noqa: E402


def main():
    top_n = int(sys.argv[1]) if len(sys.argv) > 1 else 25
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.vision.models import resnet50

    paddle.seed(0)
    model = resnet50(num_classes=1000)
    optim = paddle.optimizer.Momentum(0.1, parameters=model.parameters())
    model, optim = paddle.amp.decorate(model, optim, level="O2",
                                       dtype="bfloat16")
    bs = 128
    step = paddle.jit.TrainStep(
        model, lambda m, x, y: paddle.nn.functional.cross_entropy(
            m(x), y), optim)
    x = paddle.to_tensor(
        np.random.randn(bs, 3, 224, 224).astype(np.float32)).astype(
            "bfloat16")
    y = paddle.to_tensor(
        np.random.randint(0, 1000, (bs, 1)).astype(np.int64))
    step(x, y)  # settle opt state
    import jax.numpy as jnp
    params, frozen = step._split_params()
    buffers = {k: b._value for k, b in step._collect_state()[2]}
    lowered = step._step.lower(
        params, frozen, buffers, step._opt_state,
        jnp.asarray(0.1, jnp.float32), step._key_root,
        jnp.asarray(2, jnp.uint32), x._value, y._value)
    compiled = lowered.compile()
    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    flops = ca.get("flops", 0.0)
    ba = ca.get("bytes accessed", 0.0)
    print(f"cost_analysis: {flops/1e12:.2f} TFLOP/step, "
          f"{ba/1e9:.2f} GB accessed/step")
    if ba:
        # v5e: 197 Tf/s bf16 peak, 819 GB/s HBM
        print(f"  flop-bound floor: {flops/197e12*1e3:.1f} ms;  "
              f"byte-bound floor: {ba/819e9*1e3:.1f} ms")
    mem = compiled.memory_analysis()
    if mem is not None:
        print(f"memory_analysis: args {mem.argument_size_in_bytes/1e9:.2f} GB, "
              f"output {mem.output_size_in_bytes/1e9:.2f} GB, "
              f"temp {mem.temp_size_in_bytes/1e9:.2f} GB, "
              f"peak-ish total {(mem.argument_size_in_bytes + mem.temp_size_in_bytes)/1e9:.2f} GB")
    hlo = compiled.as_text()
    with open("/tmp/rn_hlo.txt", "w") as f:
        f.write(hlo)
    audit_text(hlo, top_n)


if __name__ == "__main__":
    main()
