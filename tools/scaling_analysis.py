"""Data-parallel weak-scaling receipt: 8 -> 256 devices (BASELINE metric 3).

The BASELINE north star asks for "Fleet data-parallel scaling efficiency
measured 8 -> 256 chips". No such machine is at hand, so this tool
produces the honest compile-level counterpart, in two layers:

1. MEASURED (virtual mesh, per device count, own subprocess because XLA
   fixes the device count at backend init): build the dp=N mesh, compile
   the real ShardedTrainStep over it, and extract from the PARTITIONED
   artifact
     - per-device flops from XLA's own cost model (cost_analysis) —
       weak scaling demands this stays CONSTANT as N grows;
     - the gradient all-reduce payload bytes parsed from the partitioned
       HLO — ring all-reduce moves 2*(N-1)/N * payload per device, so
       the per-device wire bytes must stay ~CONSTANT as N grows.
   These are the same invariants the reference's fleet meta-optimizer
   tests assert on ProgramDesc (test_fleet_sharding_meta_optimizer.py),
   checked on the artifact XLA will actually run.

2. PROJECTED (clearly labeled as a model, not a measurement; only with
   --anchor FILE): scaling efficiency = t_compute / (t_compute +
   t_allreduce) anchored to (a) the flagship step time measured on a
   chip (FILE holds the JSON line `python bench.py` printed there) and
   (b) the payload verified in layer 1, over v5e ICI ring bandwidth.
   No overlap is assumed (worst case); XLA's latency-hiding scheduler
   overlaps the grad all-reduce with the backward pass in practice, so
   real efficiency sits between this floor and 1.0.

Run: python tools/scaling_analysis.py [--anchor FILE] [N ...]
     (default 8 64 256)
Child: python tools/scaling_analysis.py --child N
       python tools/scaling_analysis.py --static-roofline
"""
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# HLO byte accounting lives in ONE place (analysis/hlo_bytes.py, shared
# with tools/hlo_bytes.py and jaxcost). Import it as a top-level package
# so the parent process stays jax-free; drop the path entry again —
# paddle_tpu/ holds Paddle-parity modules (sysconfig.py, ...) that would
# shadow the stdlib for later imports.
_PKG_DIR = os.path.join(ROOT, "paddle_tpu")
sys.path.insert(0, _PKG_DIR)
try:
    from analysis.hlo_bytes import allreduce_payload  # noqa: E402
finally:
    sys.path.remove(_PKG_DIR)

FLAGSHIP_METRIC = "gpt_small_train_tokens_per_sec"


def read_flagship_anchor(path):
    """(step_seconds, source_label) for the projection anchor, from a file
    holding the JSON line `python bench.py` printed on a chip:
    {"metric": ..., "value": ...} — the value key, NOT a metric-named
    top-level key (ADVICE round 5). There is no fallback constant: without
    a measured step there is no projection. A file that carries the wrong
    metric or a malformed value is a re-pointed headline and raises."""
    with open(path) as f:
        d = json.load(f)
    if d.get("metric") != FLAGSHIP_METRIC:
        raise ValueError(
            f"{path} headline metric is {d.get('metric')!r},"
            f" expected {FLAGSHIP_METRIC!r}")
    tok_s = float(d["value"])  # missing/NaN-shaped value also fails loudly
    step_s = round(32 * 1024 / tok_s, 4)  # flagship bs32 seq1024
    return step_s, f"{os.path.basename(path)} ({tok_s:.0f} tok/s)"


def child(n_devices: int):
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n_devices}"
        ).strip()
    import jax

    # the virtual mesh lives on the CPU whatever the machine holds
    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.optimizer as opt
    from paddle_tpu.models.gpt import GPT, GPTConfig, gpt_loss_fn
    from paddle_tpu.parallel import (ShardedTrainStep, build_mesh,
                                     set_global_mesh)

    mesh = build_mesh(dp=n_devices)
    set_global_mesh(mesh)
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=512, hidden_size=128, num_layers=2,
                    num_heads=4, max_seq_len=64)
    model = GPT(cfg)
    optim = opt.AdamW(1e-3, parameters=model.parameters())
    step = ShardedTrainStep(model, gpt_loss_fn, optim, mesh=mesh)
    per_dev_batch = 2
    B = per_dev_batch * n_devices
    x = paddle.to_tensor(np.zeros((B, 64), np.int64))
    y = paddle.to_tensor(np.zeros((B, 64), np.int64))
    t0 = time.perf_counter()
    compiled = step.compiled_step(x, y)
    compile_s = time.perf_counter() - t0
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):  # older jax: one dict per program
        ca = ca[0]
    payload, n_ar = allreduce_payload(compiled.as_text())
    print(json.dumps({
        "devices": n_devices,
        "per_device_batch": per_dev_batch,
        "per_device_gflops": round(float(ca.get("flops", 0.0)) / 1e9, 4),
        "allreduce_payload_bytes": payload,
        "allreduce_count": n_ar,
        "compile_s": round(compile_s, 1),
    }))


def static_roofline_child():
    """Print one JSON line with the jaxcost STATIC model of the flagship
    train step (f32 trace on the CPU backend — a conservative byte count
    vs the bf16-AMP chip recipe) and its v5e MXU roofline tokens/s:
    batch_tokens * MXU_peak / flops. Flops-only on purpose: the static
    byte totals are pre-fusion jaxpr traffic (a budget gate), not an HBM
    bandwidth bound. Own subprocess for the same reason as child():
    backend state is fixed at init."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.optimizer as opt
    from paddle_tpu.analysis.jaxcost import estimate_train_step
    from paddle_tpu.models.gpt import GPT, GPTConfig, gpt_loss_fn

    paddle.seed(0)
    # the flagship bench geometry (bench.py bench_gpt on_tpu)
    cfg = GPTConfig(vocab_size=32768, hidden_size=768, num_layers=12,
                    num_heads=6, max_seq_len=1024)
    batch, seq = 32, 1024
    model = GPT(cfg)
    optim = opt.AdamW(1e-4, parameters=model.parameters(),
                      grad_clip=paddle.nn.ClipGradByGlobalNorm(1.0))
    step = paddle.jit.TrainStep(model, gpt_loss_fn, optim)
    x = paddle.to_tensor(np.zeros((batch, seq), np.int32))
    y = paddle.to_tensor(np.zeros((batch, seq), np.int32))
    cost = estimate_train_step(step, x, y)
    peak_flops = 197e12  # v5e bf16 MXU peak
    nbytes = cost.bytes_read + cost.bytes_written
    print(json.dumps({
        "static_flops_per_step": cost.flops,
        "static_bytes_per_step": nbytes,
        "static_peak_bytes": cost.peak_bytes,
        "static_roofline_tokens_per_sec": round(
            batch * seq * peak_flops / cost.flops, 1),
        "static_note": "f32 CPU trace of the flagship step (jaxcost); "
                       "MXU roofline at v5e 197 TFLOP/s — measured/"
                       "roofline is the achieved MFU as the static model "
                       "counts flops; byte totals are pre-fusion jaxpr "
                       "traffic (budget gate, not a bandwidth bound)",
    }))


# v5e interconnect: 2D torus, 4 ICI links/chip at ~45 GB/s each direction.
# A bidirectional ring all-reduce rides 2 links; payload crossing the wire
# per device is 2*(N-1)/N * bytes (reduce-scatter + all-gather phases).
_ICI_RING_BW = 2 * 45e9


def project(results, step_s: float, grad_bytes: int):
    """Efficiency floor per device count: compute / (compute + unoverlapped
    ring all-reduce of grad_bytes over ICI)."""
    rows = []
    for r in results:
        n = r["devices"]
        t_comm = 2 * (n - 1) / n * grad_bytes / _ICI_RING_BW
        rows.append({"devices": n,
                     "efficiency_floor": round(step_s / (step_s + t_comm), 4)})
    return rows


def main(counts, anchor=None):
    results = []
    for n in counts:
        env = dict(os.environ)
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
        env["JAX_PLATFORMS"] = "cpu"
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--child", str(n)],
            env=env, capture_output=True, text=True, cwd=ROOT, timeout=1800)
        if out.returncode != 0:
            print(f"devices={n} FAILED:\n{out.stderr[-2000:]}",
                  file=sys.stderr)
            continue
        line = out.stdout.strip().splitlines()[-1]
        results.append(json.loads(line))
        print(line, flush=True)

    if len(results) >= 2:
        g = [r["per_device_gflops"] for r in results]
        p = [r["allreduce_payload_bytes"] for r in results]
        drift = (max(g) - min(g)) / max(g)
        print(json.dumps({
            "weak_scaling_flops_drift": round(drift, 4),
            "payload_constant": max(p) == min(p),
            "verdict": "per-device flops constant and all-reduce payload "
                       "constant across device counts — compile-level weak "
                       "scaling holds" if drift < 0.02 and max(p) == min(p)
                       else "DRIFT DETECTED — inspect per-device partitioning",
        }))
        if anchor is None:
            print(json.dumps({"projection": "skipped: no --anchor FILE with "
                              "a flagship step measured on a chip"}))
            return
        # projection anchored to the flagship step measured on a chip
        # (124M-param GPT, bs32 x seq1024, bf16 grad all-reduce = 248 MB)
        step_s, anchor_src = read_flagship_anchor(anchor)
        print(json.dumps({"anchor_source": anchor_src,
                          "anchor_step_s": step_s}), flush=True)
        # static-model roofline for the SAME flagship step, right next to
        # the measured anchor: how much headroom the static cost model
        # says the chip still has (measured/roofline ~= achievable MFU)
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--static-roofline"],
            env=dict(os.environ, JAX_PLATFORMS="cpu"),
            capture_output=True, text=True, cwd=ROOT, timeout=1800)
        if out.returncode == 0:
            sr = json.loads(out.stdout.strip().splitlines()[-1])
            measured_tok_s = 32 * 1024 / step_s
            sr["measured_vs_roofline"] = round(
                measured_tok_s / sr["static_roofline_tokens_per_sec"], 4)
            print(json.dumps(sr), flush=True)
        else:
            print(f"static roofline child FAILED:\n{out.stderr[-2000:]}",
                  file=sys.stderr)
        # sharded static model (shardplan.json, committed by
        # tools/jaxshard.py): per-mesh-axis collective wire bytes and
        # per-device peak for the fsdp x tp train step, beside the
        # measured anchor. Plain-JSON read — this parent stays jax-free.
        try:
            sp = json.load(open(os.path.join(ROOT, "shardplan.json")))
            tr = sp["programs"]["train_step.fsdp_tp"]
            print(json.dumps({
                "shard_static_model": "train_step.fsdp_tp",
                "mesh": tr["mesh"],
                "implicit_axis_bytes": tr["implicit_axis_bytes"],
                "explicit_axis_bytes": tr["explicit_axis_bytes"],
                "per_device_peak_bytes": tr["per_device_peak_bytes"],
                "envelope_ok": tr["envelope_ok"],
            }), flush=True)
        except (OSError, ValueError, KeyError) as e:
            print(f"shard static model unavailable: {e!r}",
                  file=sys.stderr)
        print(json.dumps({
            "projection_note": "efficiency floor = compute/(compute+"
            "unoverlapped ICI ring all-reduce); anchored to measured "
            f"flagship step {step_s*1e3:.1f} ms ({anchor_src}), "
            "bf16 grads 248 MB",
            "rows": project(results, step_s, 248_000_000)}))


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--child":
        child(int(sys.argv[2]))
    elif len(sys.argv) > 1 and sys.argv[1] == "--static-roofline":
        static_roofline_child()
    else:
        argv = sys.argv[1:]
        anchor = None
        if "--anchor" in argv:
            i = argv.index("--anchor")
            anchor = argv[i + 1]
            del argv[i:i + 2]
        main([int(a) for a in argv] or [8, 64, 256], anchor)
