"""chip_smoke.py: does the program still start on the chip?

    python chip_smoke.py              one TPU chip: device, train, serve
    python chip_smoke.py --chips 4    four chips: the sharded trainer against
                                      one device, and no other phase
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse [--chips 4]
                                      the same code at a tiny size on the CPU

One process, no child. Every phase prints one JSON line (wall seconds
split into compile and run, and its facts); a phase that fails raises and
the script exits non-zero. The last line of a chip run is
{"ok": true, "device": {...}} with the device as JAX reports it. Without a
TPU the script fails in its device phase; a rehearsal never prints
"ok": true, skips the kernel-engagement assertions (off the chip the gates
route to composed attention) and says so in its last line. The seconds it
prints are facts of one run, not benchmark results.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import re
import time

import numpy as np

#: sizes: the chip run is the flagship at full width (bench.py bench_gpt /
#: bench_serve_decode geometry; tools/gpt_medium_probe.py for four chips);
#: the rehearsal keeps head_dim 128 / 64 and every code path, shrunk
REAL = dict(
    gpt=dict(vocab_size=32768, hidden_size=768, num_layers=12,
             max_seq_len=1024),
    heads_flash=6, heads_packed=12, batch=32, seq=1024,
    engine=dict(num_blocks=512, block_size=32, max_num_seqs=8,
                max_prefill_tokens=2048, prefill_chunk_threshold=128),
    prompt_lens=(64, 128, 192, 256), new_tokens=(32, 48, 64),
    medium=dict(vocab_size=32768, hidden_size=1024, num_layers=24,
                num_heads=16, max_seq_len=1024),
    medium_batch=8)
TINY = dict(
    gpt=dict(vocab_size=512, hidden_size=256, num_layers=2,
             max_seq_len=128),
    heads_flash=2, heads_packed=4, batch=2, seq=128,
    engine=dict(num_blocks=48, block_size=8, max_num_seqs=4,
                max_prefill_tokens=128, prefill_chunk_threshold=16),
    prompt_lens=(8, 16, 24, 32), new_tokens=(4, 6, 8),
    medium=dict(vocab_size=512, hidden_size=128, num_layers=2,
                num_heads=4, max_seq_len=64),
    medium_batch=4)
N_REQUESTS = 16
#: the one tolerance in this file: when greedy tokens leave generate()'s,
#: the engine's token must be within this of the reference's best logit
LOGIT_TOL = 1e-2


class Clock:
    """Compile seconds and cache hits from jax.monitoring, per phase."""

    def __init__(self):
        from jax import monitoring
        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **_):
        # the XLA compile or its load from the persistent cache; tracing
        # and lowering nest (a jit inside a jit is timed twice), so they
        # are left on the "run" side of the split
        if name == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    @contextlib.contextmanager
    def phase(self, name):
        """Time the block; the block fills `facts`. Prints the phase's JSON
        line only when the block did not raise."""
        facts = {}
        c0, h0, m0 = self.compile_s, self.hits, self.misses
        t0 = time.perf_counter()
        yield facts
        wall = time.perf_counter() - t0
        compile_s = self.compile_s - c0
        print(json.dumps({
            "phase": name, "seconds": round(wall, 3),
            "compile_seconds": round(compile_s, 3),
            "run_seconds": round(wall - compile_s, 3),
            "cache_hits": self.hits - h0,
            "cache_misses": self.misses - m0, **facts}), flush=True)


def peak_bytes(dev):
    """The allocator's high-water mark. On the v5e it counts the arrays a
    process holds, not the temporaries inside a running program: those are
    in `program_bytes`."""
    stats = dev.memory_stats()
    return None if not stats else stats.get("peak_bytes_in_use")


def program_bytes(compiled):
    """What the compiler reserved for one program on one device."""
    m = compiled.memory_analysis()
    return {"arguments": m.argument_size_in_bytes,
            "outputs": m.output_size_in_bytes,
            "aliased": m.alias_size_in_bytes,
            "temporaries": m.temp_size_in_bytes}


# ------------------------------------------------------------------ train
def build_trainer(gpt_kw, num_heads, batch, seq, seed):
    """Flagship recipe of bench.py bench_gpt: AdamW + global-norm clip,
    AMP O2 bf16, one jit.TrainStep; one fixed seeded batch."""
    import paddle_tpu as paddle
    import paddle_tpu.optimizer as opt
    from paddle_tpu.models.gpt import GPT, GPTConfig, gpt_loss_fn

    paddle.seed(seed)
    cfg = GPTConfig(num_heads=num_heads, **gpt_kw)
    model = GPT(cfg)
    optim = opt.AdamW(1e-4, parameters=model.parameters(),
                      grad_clip=paddle.nn.ClipGradByGlobalNorm(1.0))
    model, optim = paddle.amp.decorate(model, optim, level="O2",
                                       dtype="bfloat16")
    step = paddle.jit.TrainStep(model, gpt_loss_fn, optim)
    rng = np.random.RandomState(seed)
    x, y = (paddle.to_tensor(rng.randint(
        0, cfg.vocab_size, (batch, seq), dtype=np.int32)) for _ in range(2))
    return model, step, x, y


def run_trainer(step, x, y, n_steps):
    """n_steps on the one batch. Returns (losses, seconds per step, jit
    cache size after each step); every step ends in a host fetch of the
    loss, so the seconds are whole steps."""
    losses, secs, cache = [], [], []
    for _ in range(n_steps):
        t0 = time.perf_counter()
        losses.append(float(step(x, y).numpy()))
        secs.append(time.perf_counter() - t0)
        cache.append(step._step._cache_size())
    if not all(np.isfinite(losses)):
        raise AssertionError(f"non-finite loss: {losses}")
    return losses, secs, cache


def program_facts(compiled, dev):
    """(compiled HLO text, facts) of one executable: how many Mosaic
    kernels it holds and what it reserves on the device."""
    text = compiled.as_text()
    return text, dict(tpu_custom_calls=text.count("tpu_custom_call"),
                      program_bytes=program_bytes(compiled),
                      peak_bytes_in_use=peak_bytes(dev))


def train_program_facts(step, x, y, dev):
    """`program_facts` of the step as dispatched: jit's own in-memory
    caches answer the compile, so nothing compiles again."""
    from paddle_tpu.analysis.jaxpr_audit import train_step_args
    return program_facts(
        step._step.lower(*train_step_args(step, x, y)).compile(), dev)


def phase_train(clock, size, seed, on_chip):
    import jax
    from paddle_tpu.nn.functional import attention as attn
    from paddle_tpu.ops.pallas import flash_attention as fa

    dev = jax.devices()[0]
    with clock.phase("train") as facts:
        model, step, x, y = build_trainer(
            size["gpt"], size["heads_flash"], size["batch"], size["seq"],
            seed)
        losses, secs, cache = run_trainer(step, x, y, 5)
        if not losses[4] < losses[0]:
            raise AssertionError(f"loss did not fall: {losses}")
        # a second compile at step 2 is allowed (bench.py warms up twice
        # for it); none may happen after that
        if cache[1:] != [cache[1]] * 4:
            raise AssertionError(f"recompiled after step 2: {cache}")
        facts.update(losses=losses, step_seconds_after_warmup=secs[2:],
                     attention_path=attn.LAST_PATH,
                     flash_patch=fa.applied_patch())
        text, program = train_program_facts(step, x, y, dev)
        facts.update(program)
        # upstream's kernels carry a flash_mha_* scope into the HLO's op
        # names; the repo's packed ones do not
        if on_chip and (attn.LAST_PATH != "flash" or "flash_mha" not in text
                        or not program["tpu_custom_calls"]):
            raise AssertionError(
                f"6-head train step: path {attn.LAST_PATH}, {program}, "
                f"upstream flash among the kernels: {'flash_mha' in text}")
    del model, step
    gc.collect()

    with clock.phase("train_packed") as facts:
        model, step, x, y = build_trainer(
            size["gpt"], size["heads_packed"], size["batch"], size["seq"],
            seed)
        losses, secs, _ = run_trainer(step, x, y, 2)
        gate = model.blocks[0].attn._pack_gate(size["seq"])
        facts.update(losses=losses, step_seconds=secs, pack_gate=gate,
                     attention_path=attn.LAST_PATH)
        text, program = train_program_facts(step, x, y, dev)
        facts.update(program)
        # kernels, and none of them upstream's, is the packed pair
        if on_chip and (not gate or attn.LAST_PATH != "flash"
                        or "flash_mha" in text
                        or not program["tpu_custom_calls"]):
            raise AssertionError(
                f"12-head train step: gate {gate}, path {attn.LAST_PATH}, "
                f"{program}, upstream flash among the kernels: "
                f"{'flash_mha' in text}")
    del model, step
    gc.collect()


# ------------------------------------------------------------------ serve
def make_requests(size, vocab, seed):
    """Sixteen seeded (prompt, max_tokens): lengths drawn from a few values
    on both sides of prefill_chunk_threshold, so dense and chunked
    admission both happen and the reference compiles once per length."""
    rng = np.random.RandomState(seed)
    lens = rng.choice(size["prompt_lens"], N_REQUESTS)
    news = rng.choice(size["new_tokens"], N_REQUESTS)
    return [(rng.randint(0, vocab, (int(n),), dtype=np.int32), int(m))
            for n, m in zip(lens, news)]


def divergence(eng, prompt, got, ref):
    """First position where the engine left generate(), and how far the
    engine's token sits below the reference's best logit there."""
    import jax.numpy as jnp
    from paddle_tpu.models import generation as gen
    n = min(len(got), len(ref))
    pos = next((i for i in range(n) if got[i] != ref[i]), n)
    if pos == n:
        return {"position": pos, "logit_gap": None,
                "lengths": [len(got), len(ref)]}
    ctx = np.concatenate([prompt, ref[:pos]]).astype(np.int32)
    logits, _ = gen.prefill(eng.params, jnp.asarray(ctx[None]), eng.geom)
    logits = np.asarray(logits[0], np.float32)
    return {"position": pos, "engine_token": int(got[pos]),
            "reference_token": int(ref[pos]),
            "logit_gap": float(logits.max() - logits[int(got[pos])])}


def phase_serve(clock, size, seed, on_chip):
    import jax
    import paddle_tpu as paddle
    from paddle_tpu import inference
    from paddle_tpu.inference.serving import (EngineConfig, LLMEngine,
                                              SamplingParams)
    from paddle_tpu.inference.serving.attention import (PACK_COLS,
                                                        fused_decode_chunk)
    from paddle_tpu.models.generation import generate
    from paddle_tpu.models.gpt import GPT, GPTConfig

    dev = jax.devices()[0]
    with clock.phase("serve") as facts:
        paddle.seed(seed)
        cfg = GPTConfig(num_heads=size["heads_flash"], **size["gpt"])
        model = GPT(cfg)
        model.eval()
        ecfg = EngineConfig(**size["engine"])   # every other field default
        eng = LLMEngine.from_model(model, ecfg)
        requests = make_requests(size, cfg.vocab_size, seed)
        rids = [eng.add_request(p, SamplingParams(max_tokens=m))
                for p, m in requests]
        # step() streams a RequestOutput per token; run() would drop them
        final = {}
        while eng.has_unfinished():
            final.update({o.request_id: o for o in eng.step() if o.finished})
        reasons = {r: final[r].finish_reason for r in rids}
        if any(v not in ("stop", "length") for v in reasons.values()):
            raise AssertionError(f"abnormal finish: {reasons}")
        results = {r: np.asarray(final[r].token_ids) for r in rids}
        integrity = eng.cache.check_integrity()     # raises on a violation
        chunked = sum(len(p) > ecfg.prefill_chunk_threshold
                      for p, _ in requests)
        if not 0 < chunked < N_REQUESTS or eng.stats.prefill_chunks() == 0:
            raise AssertionError("dense and chunked admission not both hit")
        facts.update(requests=N_REQUESTS, admitted_chunked=chunked,
                     generated_tokens=sum(len(results[r]) for r in rids),
                     engine_steps=eng.stats.as_dict()["steps"],
                     cache_integrity=integrity, kernel=ecfg.kernel)
        packed = np.zeros((ecfg.max_num_seqs, PACK_COLS
                           + ecfg.decode_chunk_size
                           + eng.max_blocks_per_seq), np.int32)
        _, program = program_facts(fused_decode_chunk.lower(
            eng.params, eng.cache.pools, packed, eng.geom,
            ecfg.decode_chunk_size, ecfg.kernel).compile(), dev)
        facts.update(program)
        if on_chip and not program["tpu_custom_calls"]:
            raise AssertionError(
                "fused decode chunk took the gather path: no "
                "tpu_custom_call in its compiled text")

    with clock.phase("serve_reference") as facts:
        diverged = []
        for i, ((prompt, _), rid) in enumerate(zip(requests, rids)):
            got = results[rid]
            # one reference length for all: greedy is prefix-stable, so a
            # shorter request is the head of the longest one
            ref = generate(model, prompt[None], max(size["new_tokens"]))[
                0, len(prompt):][:len(got)]
            if not np.array_equal(got, ref):
                diverged.append({"request": i,
                                 **divergence(eng, prompt, got, ref)})
        facts.update(bitwise_equal=N_REQUESTS - len(diverged),
                     diverged=diverged)
        # docs/serving.md states token equality. Where the chip breaks it,
        # that is reported above and held to a logit tolerance here, for
        # this assertion only.
        bad = [d for d in diverged
               if d["logit_gap"] is None or d["logit_gap"] > LOGIT_TOL]
        if bad:
            raise AssertionError(
                f"engine tokens left generate() beyond {LOGIT_TOL}: {bad}")
        facts["logit_tolerance_used"] = LOGIT_TOL if diverged else None

    with clock.phase("serve_facade") as facts:
        prompt, max_new = requests[0]
        conf = inference.Config()
        conf.enable_llm_engine(model=model, max_tokens=max_new,
                               **size["engine"])
        pred = inference.create_predictor(conf)
        [seqs] = pred.run([prompt[None].astype(np.int64),
                           np.asarray([len(prompt)])])
        got = seqs[0, len(prompt):len(prompt) + max_new]
        if not np.array_equal(got, results[rids[0]]):
            raise AssertionError(
                "facade and engine disagree on the same request")
        facts.update(new_tokens=len(got))
    del model, eng, pred
    gc.collect()


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
    devices = jax.devices()[:4]
    with clock.phase("sharded_train") as facts:
        losses4, step, mesh, (x, y) = run_sharded(size, devices, seed,
                                                  sharding=2, tp=2)
        held, split = check_spread(step, mesh)
        text, program = program_facts(step.compiled_step(x, y), devices[0])
        tp, sh = axis_groups(mesh, "tp"), axis_groups(mesh, "sharding")
        over = {
            "all-reduce over tp": tp in collective_groups(text, "all-reduce"),
            "reduce-scatter or all-gather over sharding": sh in (
                collective_groups(text, "reduce-scatter")
                + collective_groups(text, "all-gather")),
        }
        if not all(over.values()):
            raise AssertionError(f"collectives missing: {over}")
        facts.update(program)       # per device; its peak is device 0's
        facts.update(
            losses=losses4, mesh={"sharding": 2, "tp": 2},
            state_bytes_per_device=held, tensors_split=split,
            collectives=over,
            peak_bytes_in_use={d.id: peak_bytes(d) for d in devices})
        if on_chip and None in facts["peak_bytes_in_use"].values():
            raise AssertionError("a device reported no peak memory")
    del step
    gc.collect()

    with clock.phase("one_device_train") as facts:
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
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on whatever backend JAX finds; never "
                         "prints ok: true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from paddle_tpu.utils.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax

    clock = Clock()
    with clock.phase("device") as facts:
        devs = jax.devices()
        device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
                  "count": len(devs)}
        on_chip = device["platform"] == "tpu"
        if not on_chip and not args.rehearse:
            raise SystemExit(
                f"chip_smoke: no TPU found (JAX reports {device}); "
                "--rehearse runs the phases at a tiny size without one")
        if len(devs) < args.chips:
            raise SystemExit(
                f"chip_smoke: --chips {args.chips} needs {args.chips} "
                f"devices, JAX reports {len(devs)}")
        facts.update(device=device, compile_cache=cache_dir, jax=jax.__version__)

    size = TINY if args.rehearse else REAL
    if args.chips == 4:
        phase_four_chips(clock, size, args.seed, on_chip)
    else:
        phase_train(clock, size, args.seed, on_chip)
        phase_serve(clock, size, args.seed, on_chip)

    if args.rehearse:
        print(json.dumps({
            "rehearsal": True, "passed": True, "device": device,
            "skipped": [] if on_chip else
            ["kernel-engagement assertions: off the chip the gates route "
             "to composed attention"]}))
    else:
        print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
