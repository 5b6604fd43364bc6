"""chip_smoke.py: does the sharded trainer still start on four chips?

    python chip_smoke.py        four TPU chips: the sharded trainer
                                (sharding=2 x tp=2, ZeRO-1) against one device
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python chip_smoke.py --rehearse
                                the same code at a tiny size on the CPU

What one chip does is measured on every PR by the benchmark's cells
(`python3 benchmarks/run.py --workload <cell> --seed <n>`); no cell spans
chips yet (ROADMAP B14), so this script keeps the one check that does.

One process, no child. Every phase prints one JSON line (wall seconds
split into compile and run, and its facts); a phase that fails raises and
the script exits non-zero. The last line of a chip run is
{"ok": true, "device": {...}} with the device as JAX reports it. Without
four TPU chips the script fails in its device phase; a rehearsal never
prints "ok": true and says so in its last line. The seconds it prints are
facts of one run, not benchmark results.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import re
import time

import numpy as np

from benchmarks.lib import program
from benchmarks.lib.clock import Clock

#: GPT-medium at full width on the chips; the rehearsal keeps every code
#: path, shrunk
REAL = dict(medium=dict(vocab_size=32768, hidden_size=1024, num_layers=24,
                        num_heads=16, max_seq_len=1024),
            medium_batch=8)
TINY = dict(medium=dict(vocab_size=512, hidden_size=128, num_layers=2,
                        num_heads=4, max_seq_len=64),
            medium_batch=4)
CHIPS = 4


@contextlib.contextmanager
def phase(clock, name):
    """Time the block; the block fills `facts`. Prints the phase's JSON line
    only when the block did not raise."""
    facts = {}
    before, t0 = clock.snapshot(), time.perf_counter()
    yield facts
    wall = time.perf_counter() - t0
    spent = clock.snapshot().since(before)
    print(json.dumps({
        "phase": name, "seconds": round(wall, 3),
        "compile_seconds": round(spent.compile_s, 3),
        "run_seconds": round(wall - spent.compile_s, 3),
        "cache_hits": spent.hits, "cache_misses": spent.misses, **facts}),
        flush=True)


def peak_bytes(dev):
    """The allocator's high-water mark. On the v5e it counts the arrays a
    process holds, not the temporaries inside a running program: those are
    in `program_bytes`."""
    stats = dev.memory_stats()
    return None if not stats else stats.get("peak_bytes_in_use")


# ------------------------------------------------------------- four chips
def axis_groups(mesh, axis):
    """Replica groups (sets of logical device indices) of one mesh axis."""
    ids = np.arange(mesh.devices.size).reshape(mesh.devices.shape)
    a = mesh.axis_names.index(axis)
    return {frozenset(g) for g in
            np.moveaxis(ids, a, -1).reshape(-1, mesh.devices.shape[a])
            .tolist()}


def collective_groups(text, op):
    """Replica groups of every `op` in compiled HLO text, both spellings:
    {{0,1},{2,3}} and the iota form [2,2]<=[2,2]T(1,0)."""
    found = []
    for line in text.splitlines():
        # "<result type> all-reduce(": a type ends in ], } or ) (a tuple)
        if not re.search(rf"[\]}})] {op}(?:-start)?\(", line):
            continue
        m = re.search(r"replica_groups=\{(\{[\d,{}]*\})\}", line)
        if m:
            found.append({frozenset(int(i) for i in g.split(","))
                          for g in re.findall(r"\{([\d,]+)\}", m.group(1))})
            continue
        m = re.search(r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\]"
                      r"(?:T\(([\d,]+)\))?", line)
        if m:
            dims = [int(d) for d in m.group(3).split(",")]
            ids = np.arange(int(np.prod(dims))).reshape(dims)
            if m.group(4):
                ids = ids.transpose([int(p) for p in m.group(4).split(",")])
            found.append({frozenset(g) for g in ids.reshape(
                int(m.group(1)), int(m.group(2))).tolist()})
    return found


def run_sharded(size, devices, seed, sharding, tp):
    """Three ShardedTrainStep steps of the GPT-medium recipe on a
    sharding x tp mesh (ZeRO-1: the train_step.fsdp_tp layout of
    shardplan.json). Returns (losses, step, mesh, first batch)."""
    import paddle_tpu as paddle
    import paddle_tpu.optimizer as opt
    from paddle_tpu.models.gpt import GPT, GPTConfig, gpt_loss_fn
    from paddle_tpu.parallel import (ShardedTrainStep, ShardingStage,
                                     build_mesh, set_global_mesh)

    mesh = build_mesh(sharding=sharding, tp=tp, devices=devices)
    set_global_mesh(mesh)
    paddle.seed(seed)
    cfg = GPTConfig(**size["medium"])
    model = GPT(cfg)
    optim = opt.AdamW(1e-4, parameters=model.parameters(),
                      grad_clip=paddle.nn.ClipGradByGlobalNorm(1.0))
    model, optim = paddle.amp.decorate(model, optim, level="O2",
                                       dtype="bfloat16")
    step = ShardedTrainStep(model, gpt_loss_fn, optim, mesh=mesh,
                            sharding_stage=ShardingStage.OPTIMIZER)
    rng = np.random.RandomState(seed)
    shape = (size["medium_batch"], cfg.max_seq_len)
    batches = [[paddle.to_tensor(rng.randint(0, cfg.vocab_size, shape,
                                             dtype=np.int32))
                for _ in range(2)] for _ in range(3)]
    losses = [float(step(x, y).numpy()) for x, y in batches]
    if not all(np.isfinite(losses)):
        raise AssertionError(f"non-finite loss: {losses}")
    return losses, step, mesh, batches[0]


def check_spread(step, mesh):
    """Every parameter and optimizer moment holds one shard on each of the
    mesh's devices, of the shape its planned sharding says; some of both
    are really split. Returns per-device bytes of that state."""
    want = set(mesh.devices.flat)
    held = {d.id: 0 for d in want}
    split = {"params": 0, "moments": 0}

    def check(kind, name, arr, sharding):
        shards = arr.addressable_shards
        if {s.device for s in shards} != want:
            raise AssertionError(
                f"{kind} {name} lives on {sorted(s.device.id for s in shards)}"
                f", not on all of {sorted(held)}")
        shape = sharding.shard_shape(arr.shape)
        for s in shards:
            if s.data.shape != shape:
                raise AssertionError(
                    f"{kind} {name}: shard {s.data.shape}, planned {shape}")
            held[s.device.id] += s.data.nbytes
        split[kind] += shape != arr.shape

    for name, p in step.model.named_parameters():
        check("params", name, p._value, step._param_shardings[name])
    for name, state in step._opt_state.items():
        for leaf in (v for v in state.values() if getattr(v, "ndim", 0)):
            check("moments", name, leaf,
                  step._opt_state_sharding_fn(name, leaf))
    if not split["params"] or not split["moments"]:
        raise AssertionError(f"nothing was split: {split}")
    return held, split


def phase_four_chips(clock, size, seed, on_chip):
    import jax
    devices = jax.devices()[:CHIPS]
    with phase(clock, "sharded_train") as facts:
        losses4, step, mesh, (x, y) = run_sharded(size, devices, seed,
                                                  sharding=2, tp=2)
        held, split = check_spread(step, mesh)
        text, compiled = program.facts(step.compiled_step(x, y))
        tp, sh = axis_groups(mesh, "tp"), axis_groups(mesh, "sharding")
        over = {
            "all-reduce over tp": tp in collective_groups(text, "all-reduce"),
            "reduce-scatter or all-gather over sharding": sh in (
                collective_groups(text, "reduce-scatter")
                + collective_groups(text, "all-gather")),
        }
        if not all(over.values()):
            raise AssertionError(f"collectives missing: {over}")
        facts.update(compiled)                      # bytes on each device
        facts.update(
            losses=losses4, mesh={"sharding": 2, "tp": 2},
            state_bytes_per_device=held, tensors_split=split,
            collectives=over,
            peak_bytes_in_use={d.id: peak_bytes(d) for d in devices})
        if on_chip and None in facts["peak_bytes_in_use"].values():
            raise AssertionError("a device reported no peak memory")
    del step
    gc.collect()

    with phase(clock, "one_device_train") as facts:
        losses1, step, _, _ = run_sharded(size, devices[:1], seed,
                                          sharding=1, tp=1)
        # the tolerance __graft_entry__.dryrun_multichip holds its pairs to
        np.testing.assert_allclose(
            losses4, losses1, rtol=2e-3, atol=2e-3,
            err_msg="sharding=2 x tp=2 losses leave the one-device run")
        facts.update(losses=losses1, max_abs_diff=float(np.max(np.abs(
            np.asarray(losses4) - np.asarray(losses1)))))
    del step
    gc.collect()


# ------------------------------------------------------------------- main
def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on whatever backend JAX finds; never "
                         "prints ok: true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from paddle_tpu.utils.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax

    clock = Clock()
    with phase(clock, "device") as facts:
        devs = jax.devices()
        device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
                  "count": len(devs)}
        on_chip = device["platform"] == "tpu"
        if not on_chip and not args.rehearse:
            raise SystemExit(
                f"chip_smoke: no TPU found (JAX reports {device}); "
                "--rehearse runs the phases at a tiny size without one")
        if len(devs) < CHIPS:
            raise SystemExit(
                f"chip_smoke: needs {CHIPS} devices, JAX reports "
                f"{len(devs)}")
        facts.update(device=device, compile_cache=cache_dir, jax=jax.__version__)

    phase_four_chips(clock, TINY if args.rehearse else REAL, args.seed,
                     on_chip)

    if args.rehearse:
        print(json.dumps({"rehearsal": True, "passed": True,
                          "device": device}))
    else:
        print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
