"""Runner `train_lm`: one jit.TrainStep of a GPT language model under the
cell's `trainer` settings, fed by the `lm_batches` traffic mix.

build_trainer / the warm-up / program facts are copies of chip_smoke.py
(they ran on the v5e in PR 23) with a timed window round them. Steps are
dispatched back to back, each with its batch uploaded by paddle.to_tensor
inside the loop; the loss is fetched every `fetch_every`-th step and at
the window's end, as a training job logs it. `train_tokens_per_s` is the
tokens of all steps of the window over the seconds to the last fetch.
"""
from __future__ import annotations

import math
import time

import numpy as np

from lib import chip, gpt2, program, reference_gpt2, traffic
from lib.tracing import device_trace, span

#: sequences per reference call, so that float32 logits [4, T, V] fit
REFERENCE_CHUNK = 4


def build_trainer(size, settings, seed):
    import paddle_tpu as paddle
    import paddle_tpu.optimizer as opt
    from paddle_tpu.models.gpt import gpt_loss_fn

    model = gpt2.build_model(size, seed)
    optim = getattr(opt, settings["optimizer"])(
        settings["learning_rate"], parameters=model.parameters(),
        grad_clip=paddle.nn.ClipGradByGlobalNorm(settings["clip_global_norm"]))
    model, optim = paddle.amp.decorate(model, optim,
                                       level=settings["amp_level"],
                                       dtype=settings["amp_dtype"])
    return model, paddle.jit.TrainStep(model, gpt_loss_fn, optim)


def reference_loss(params32, x, y, size):
    """Mean loss of the plain reference on one batch, in chunks."""
    import jax
    import jax.numpy as jnp
    fn = jax.jit(reference_gpt2.loss_sum, static_argnums=(3, 4))
    total = 0.0
    for i in range(0, x.shape[0], REFERENCE_CHUNK):
        total += float(fn(params32, jnp.asarray(x[i:i + REFERENCE_CHUNK]),
                          jnp.asarray(y[i:i + REFERENCE_CHUNK]),
                          size["n_layer"], size["n_head"]))
    return total / x.size


def measure(step, batches, first, duration, fetch_every):
    """Dispatch steps for `duration` seconds, then wait for the last.
    Returns the facts of that window."""
    import paddle_tpu as paddle
    upload, dispatch, fetched = [], [], []
    n = 0
    t0 = time.perf_counter()
    while True:
        x, y = batches[(first + n) % len(batches)]
        ta = time.perf_counter()
        with span("bench.upload"):
            xt, yt = paddle.to_tensor(x), paddle.to_tensor(y)
        tb = time.perf_counter()
        with span("bench.dispatch"):
            loss = step(xt, yt)
        tc = time.perf_counter()
        upload.append(tb - ta)
        dispatch.append(tc - tb)
        n += 1
        over = tc - t0 >= duration
        if over or n % fetch_every == 0:
            with span("bench.fetch_loss"):
                fetched.append(float(loss.numpy()))
            if over:
                break
    return {"steps": n, "seconds": time.perf_counter() - t0,
            "upload_s": upload, "dispatch_s": dispatch, "fetched": fetched}


def run(ctx):
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.analysis.jaxpr_audit import train_step_args
    from paddle_tpu.nn.functional import attention as attn

    size = gpt2.sizes(ctx.config, ctx.rehearse)
    mix = ctx.mix()
    seq = min(mix["seq"], size["n_positions"])
    batches = traffic.lm_batches(mix, ctx.seed, size["vocab_size"], seq)
    tokens_per_step = mix["batch"] * seq
    model, step = build_trainer(size, ctx.cell["trainer"], ctx.seed)

    # the parameters as the first step will read them, in float32, before
    # the step donates their buffers
    params32 = jax.jit(lambda p: {k: v.astype(jnp.float32)
                                  for k, v in p.items()})(
        {k: p._value for k, p in model.named_parameters()})
    x0, y0 = batches[0]
    warm = [float(step(paddle.to_tensor(x0), paddle.to_tensor(y0)).numpy())
            for _ in range(3)]
    ref = reference_loss(params32, x0, y0, size)
    del params32

    xt, yt = paddle.to_tensor(x0), paddle.to_tensor(y0)
    text, prog = program.facts(
        step._step.lower(*train_step_args(step, xt, yt)).compile())
    gate = bool(model.blocks[0].attn._pack_gate(seq))
    expect = ctx.cell["expect"]
    checks = {
        "warmup_losses_finite_and_falling":
            bool(np.all(np.isfinite(warm)) and warm[2] < warm[0]),
        "first_loss_near_ln_vocab":
            abs(warm[0] - math.log(size["vocab_size"])) < 1.0,
        "first_loss_equals_reference":
            abs(warm[0] - ref) <= ctx.cell["loss_tolerance"],
    }
    if ctx.on_chip:     # off the chip the gates route to composed attention
        checks.update({
            "attention_path": attn.LAST_PATH == expect["attention_path"],
            "pack_gate": gate == expect["pack_gate"],
            "mosaic_kernels_in_step":
                (prog["tpu_custom_calls"] > 0) == expect["mosaic_kernels"],
            "upstream_flash_in_step":
                ("flash_mha" in text) == expect["upstream_flash"]})
    # the loop's own pattern once more, so that nothing is new in the window
    measure(step, batches, 0, 0.0, mix["fetch_every"])

    ctx.window_opens()
    main_s = ctx.seconds - (ctx.trace_seconds if ctx.trace else 0.0)
    win = measure(step, batches, 1, main_s, mix["fetch_every"])
    compiles = ctx.compiled_in_window()
    trace_dir = None
    if ctx.trace:
        with device_trace(ctx.trace_dir), span("bench.window"):
            measure(step, batches, 1 + win["steps"], ctx.trace_seconds,
                    mix["fetch_every"])
        trace_dir = ctx.trace_dir
    checks["no_compile_in_window"] = compiles == 0
    bad = int(np.sum(~np.isfinite(win["fetched"])))
    checks["window_losses_finite"] = bad == 0

    rate = win["steps"] * tokens_per_step / win["seconds"]
    return {
        "end_to_end": {"train_tokens_per_s": rate},
        "attempted": win["steps"], "failed": bad, "checks": checks,
        "memory_peak_bytes": prog["program_total_bytes"],
        "trace_dir": trace_dir,
        "facts": {
            "compiles_in_window": compiles,
            "warmup_losses": warm, "reference_loss": ref,
            "loss_minus_reference": warm[0] - ref,
            "attention_path": attn.LAST_PATH, "pack_gate": gate, **prog,
            "steps": win["steps"], "window_seconds": win["seconds"],
            "tokens_per_step": tokens_per_step,
            "step_seconds": win["seconds"] / win["steps"],
            "losses_fetched": len(win["fetched"]),
            "last_loss": win["fetched"][-1],
            "dispatch_ms_median": 1e3 * float(np.median(win["dispatch_s"])),
            "upload_ms_median": 1e3 * float(np.median(win["upload_s"])),
            "flops_per_token": chip.gpt_train_flops_per_token(
                size["n_embd"], size["n_layer"], size["vocab_size"], seq),
            "_dispatch_s": win["dispatch_s"],
        },
    }

