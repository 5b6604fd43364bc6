"""BERT-base train-step cost/traffic audit (bench config 3 geometry).

Usage: python tools/bert_cost.py [top_n]
"""
from __future__ import annotations

import os
import sys

import numpy as np

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_ROOT, "tools"))
sys.path.insert(0, _ROOT)
from hlo_bytes import audit_text  # noqa: E402
from bench import _hbm_bw, _peak_flops  # noqa: E402 - chip-keyed peaks


def main():
    top_n = int(sys.argv[1]) if len(sys.argv) > 1 else 25
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    import paddle_tpu.optimizer as opt
    from paddle_tpu.models.bert import (BertConfig, BertForPretraining,
                                        bert_pretrain_loss_fn,
                                        make_bert_pretrain_batch)
    paddle.seed(0)
    cfg = BertConfig()
    bs, seq = 128, 128  # match the bench geometry (bench_bert bs=128)
    model = BertForPretraining(cfg)
    optim = opt.AdamW(1e-4, parameters=model.parameters())
    model, optim = paddle.amp.decorate(model, optim, level="O2",
                                       dtype="bfloat16")
    step = paddle.jit.TrainStep(model, bert_pretrain_loss_fn, optim)
    rng = np.random.RandomState(0)
    x, tt, mlm, nsp, pos_t = (paddle.to_tensor(a) for a in
                              make_bert_pretrain_batch(
                                  rng, cfg.vocab_size, bs, seq))
    step(x, tt, mlm, nsp, pos_t)
    params, frozen = step._split_params()
    buffers = {k: b._value for k, b in step._collect_state()[2]}
    lowered = step._step.lower(
        params, frozen, buffers, step._opt_state,
        jnp.asarray(1e-4, jnp.float32), step._key_root,
        jnp.asarray(2, jnp.uint32), x._value, tt._value, mlm._value,
        nsp._value, pos_t._value)
    compiled = lowered.compile()
    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    flops, ba = ca.get("flops", 0.0), ca.get("bytes accessed", 0.0)
    peak = _peak_flops(jax.devices()[0])
    bw = _hbm_bw(jax.devices()[0])
    print(f"cost_analysis: {flops/1e12:.3f} TFLOP/step, "
          f"{ba/1e9:.2f} GB accessed/step")
    msg = f"  flop floor {flops/peak*1e3:.1f} ms | byte floor " \
          f"{ba/bw*1e3:.1f} ms"
    print(msg)
    hlo = compiled.as_text()
    with open("/tmp/bert_hlo.txt", "w") as f:
        f.write(hlo)
    audit_text(hlo, top_n)


if __name__ == "__main__":
    main()
