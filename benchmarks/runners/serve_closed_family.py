"""Runner `serve_closed_family`: `serve_closed`'s closed loop for any
family that `LLMEngine` serves through a `ModelSpec`.

The loop, the clock and the latency arithmetic are `serve_closed`'s own
(`ClosedLoop`, `warm_up`, `latency_stats`, imported). What differs is how
the engine is built and how `correct` is decided:

- the configuration names its builder (`"builder": "pangu_moe"` ->
  `lib/pangu_moe.py`: `build_engine(config, seed, engine_config,
  rehearse) -> (engine, program config)`, `work_config`) and its plain
  reference (`"reference"`: `sizes`, `gaps_and_rows`);
- (a) during set-up, for one seeded request of each prompt length: the
  last-position logits of the engine's own prefill program and of 16
  `paged_decode_step`s through the engine's pools (greedy tokens) against
  the reference's full forward over the same ids, every logit of the 17
  rows: `logit_error_limit`. This also compiles each length's prefill;
- (b) after the window, a seeded sample of `reference_sample` requests
  finished in it, every prompt length present, teacher-forced through the
  reference (padded to the engine's max context, so that the reference
  compiles once): every engine token
  within `logit_tolerance` of the reference's best logit.

`lower_precision` in the cell file (as committed: in its `rehearsal` block
only, so the tests run the path and the chip does not pay for it) adds the
reading
PERF.md's limits rest on: the same comparisons with the reference computed
in that dtype in place of the engine.
"""
from __future__ import annotations

import functools
import importlib
import importlib.util
from pathlib import Path

import numpy as np

from lib import program, serve_work, traffic
from lib.tracing import device_trace, span
from runners.serve_closed import (ClosedLoop, latency_stats, stats_snapshot,
                                  warm_up)

ROOT = Path(__file__).resolve().parents[2]
DECODE_STEPS = 16


def load_reference(config: dict):
    path = ROOT / config["reference"]
    spec = importlib.util.spec_from_file_location(path.stem, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def snapshot(eng) -> dict:
    return {**stats_snapshot(eng), "moe_pairs": eng.stats.moe_pairs,
            "moe_experts_hit": eng.stats.moe_experts_hit,
            "context_tokens": eng.stats.context_tokens}


def padded(ids, length):
    """Causal attention: what stands behind the row changes nothing in
    it, and one length is one compilation of the reference."""
    out = np.zeros((length,), np.int32)
    out[:len(ids)] = ids
    return out


def prefill_and_decode_rows(eng, ids, step_fn):
    """[1 + DECODE_STEPS, V] logits the serving path gives for `ids`: its
    prefill program, then greedy `paged_decode_step`s through the engine's
    own pools and allocator; and the tokens it chose."""
    import jax.numpy as jnp
    cache, n = eng.cache, len(ids)
    cache.allocate("check", n)
    logits, dense, _ = eng.spec.prefill(eng.params,
                                        jnp.asarray(ids[None], jnp.int32))
    cache.write_prefill("check", dense, n)
    rows = [np.asarray(logits, np.float32)[0]]
    table = np.zeros((1, eng.max_blocks_per_seq), np.int32)
    for _ in range(DECODE_STEPS):
        tok = int(rows[-1].argmax())
        block, offset, pos = cache.append_slot("check")
        ids = np.append(ids, np.int32(tok))
        t = cache.block_table("check")
        table[0, :len(t)] = t
        logits, cache.pools = step_fn(
            eng.params, cache.pools, np.asarray([tok], np.int32),
            np.asarray([pos], np.int32), table,
            np.asarray([block], np.int32), np.asarray([offset], np.int32))
        rows.append(np.asarray(logits, np.float32)[0])
    cache.free("check")
    return np.stack(rows), ids


def check_against_forward(ctx, eng, lens, vocab, reference, low_reference):
    """Check (a). Returns per-length facts and the error of every row."""
    import jax
    from paddle_tpu.inference.serving.attention import paged_decode_step
    step_fn = jax.jit(functools.partial(paged_decode_step, geom=eng.geom),
                      donate_argnums=(1,))
    found, errors, low_errors = [], [], []
    for i, n in enumerate(lens):
        prompt = traffic.prompt(ctx.seed, 1000 + i, n, vocab, warm_up=True)
        rows, ids = prefill_and_decode_rows(eng, prompt, step_fn)
        at = np.arange(n - 1, n + DECODE_STEPS, dtype=np.int32)
        row = padded(ids, eng.spec.max_seq_len)
        exact = np.asarray(reference(eng.params, row, row, at)[1])
        err = np.abs(rows - exact).max(axis=1)
        errors += err.tolist()
        found.append({
            "prompt_len": n, "logit_error_prefill": float(err[0]),
            "logit_error_decode_max": float(err[1:].max()),
            "logit_abs_max": float(np.abs(exact).max()),
            "greedy_agree": int((rows.argmax(1) == exact.argmax(1)).sum())})
        if low_reference is not None:
            low = np.asarray(low_reference(eng.params, row, row, at)[1])
            low_errors += np.abs(low - exact).max(axis=1).tolist()
    return found, errors, low_errors


def spread(values, q):
    """{typical: the q-th percentile, largest, count, median} of a list of
    errors."""
    v = np.asarray(values, np.float64)
    return {"typical": float(np.percentile(v, q)), "largest": float(v.max()),
            "count": int(v.size), "median": float(np.median(v))}


def within(found: dict, limits: dict) -> bool:
    return found["typical"] <= limits["typical"] \
        and found["largest"] <= limits["largest"]


def sample_finished(finished, count, seed):
    """`count` of the finished requests (all, if fewer), every prompt
    length present, the rest drawn by the seed."""
    rng = np.random.default_rng([int(seed), 4])
    order = [int(i) for i in rng.permutation(len(finished))]
    first = {}
    for i in order:
        first.setdefault(len(finished[i]["prompt"]), i)
    picked = list(first.values())
    picked += [i for i in order if i not in set(picked)][
        :max(0, count - len(picked))]
    return [finished[i] for i in picked]


def run(ctx):
    import jax
    from paddle_tpu.inference.serving import EngineConfig
    from paddle_tpu.inference.serving.attention import (PACK_COLS,
                                                        fused_decode_chunk)

    builder = importlib.import_module("lib." + ctx.config["builder"])
    reference = load_reference(ctx.config)
    mix = ctx.mix()
    scale = mix.get("scale", 1.0)
    ecfg = EngineConfig(**ctx.setting("engine"))    # other fields: default
    eng, cfg = builder.build_engine(ctx.config, ctx.seed, ecfg, ctx.rehearse)
    vocab, k = cfg.vocab_size, ecfg.decode_chunk_size
    size = reference.sizes(cfg)
    work = builder.work_config(ctx.config, cfg)
    # one program and one padded length serve checks (a) and (b)
    exact = jax.jit(functools.partial(reference.gaps_and_rows, size=size))
    low = (ctx.cell["rehearsal"] if ctx.rehearse else ctx.cell).get(
        "lower_precision")
    lower = jax.jit(functools.partial(
        reference.gaps_and_rows, size=size, low=low)) if low else None
    no_rows = np.zeros((DECODE_STEPS + 1,), np.int32)
    length = eng.spec.max_seq_len

    lens = [max(1, int(n * scale)) for n in mix["prompt_lens"]]
    against_forward, row_errors, low_errors = check_against_forward(
        ctx, eng, lens, vocab, exact, lower)
    per_length = warm_up(ctx, eng, lens, vocab, k)
    packed = np.zeros((ecfg.max_num_seqs,
                       PACK_COLS + k + eng.max_blocks_per_seq), np.int32)
    _, prog = program.facts(fused_decode_chunk.lower(
        eng.params, eng.cache.pools, packed, eng.geom, k,
        ecfg.kernel).compile())

    loop = ClosedLoop(eng, mix, ctx.seed, vocab, scale)
    loop.run_until_finished(mix["steady_state"]["finished_requests"])

    ctx.window_opens()
    before = snapshot(eng)
    main_s = ctx.seconds - (ctx.trace_seconds if ctx.trace else 0.0)
    t0, t1 = loop.run_for(main_s)
    after = snapshot(eng)
    compiles = ctx.compiled_in_window()
    trace_dir = None
    if ctx.trace:
        with device_trace(ctx.trace_dir), span("bench.window"):
            loop.run_for(ctx.trace_seconds)
        trace_dir = ctx.trace_dir
    loop.drain()
    integrity = eng.cache.check_integrity()         # raises on a violation

    records = loop.records()
    in_window = [r for r in records if t0 <= r["submitted"] < t1]
    ttft = [r["delivered_at"][0] - r["submitted"] for r in in_window
            if r["delivered_at"]]
    gaps = [b - a for r in records
            for a, b in zip(r["delivered_at"], r["delivered_at"][1:])
            if t0 < b <= t1]
    tokens = sum(n for t, n in loop.deliveries if t0 < t <= t1)
    failed = [r for r in in_window
              if r["finish_reason"] not in ("stop", "length")]
    finished_in = [r for r in records if t0 < r.get("finished", t0) <= t1]
    sample = sample_finished(finished_in, ctx.cell["reference_sample"],
                             ctx.seed)
    token_gaps, low_gaps = [], []
    for r in sample:
        n = len(r["prompt"])
        ids = np.concatenate([r["prompt"], r["tokens"]]).astype(np.int32)
        row = padded(ids, length)
        followers = np.roll(row, -1)            # position t is followed by
        below = np.asarray(exact(eng.params, row, followers, no_rows)[0])[
            n - 1:len(ids) - 1]                 # the token at t + 1
        r["reference_gap"] = float(below.max())
        token_gaps += below.tolist()
        if lower is not None:
            best = np.asarray(lower(eng.params, row, followers, no_rows)[2])
            low_gaps += np.asarray(exact(eng.params, row, best, no_rows)[0])[
                n - 1:len(ids) - 1].tolist()
    used = [n for t, n in loop.steps if t0 < t <= t1]

    delta = {key: after[key] - before[key] for key in after}
    # positions pushed through the layers: prompts prefilled, and one per
    # decoded token (a request's first token comes from its prefill)
    decoded = delta["generated_tokens"] - delta["syncs_prefill"]
    through = delta["prefill_tokens"] + decoded
    expert_layers = cfg.num_hidden_layers - cfg.first_k_dense_replace
    uniform = through * expert_layers * cfg.num_experts_per_tok \
        * cfg.held[1] / cfg.n_routed_experts
    waits = {**latency_stats("ttft", ttft), **latency_stats("token_gap", gaps)}
    logit_error = spread(row_errors, 90)
    token_gap = spread(token_gaps, 99) if token_gaps else None
    checks = {
        "all_requests_end_stop_or_length": not failed and bool(in_window),
        "every_request_got_a_first_token": len(ttft) == len(in_window),
        "cache_integrity": True,
        "prefill_and_paged_decode_logits_match_reference":
            within(logit_error, ctx.cell["logit_error"]),
        "engine_tokens_within_tolerance_of_reference":
            token_gap is not None
            and within(token_gap, ctx.cell["token_gap"]),
        "reference_sample_holds_every_prompt_length":
            {len(r["prompt"]) for r in sample}
            == {len(r["prompt"]) for r in finished_in},
        "no_compile_in_window": compiles == 0,
        "cache_bytes_per_token_as_computed":
            eng.stats.cache_bytes_per_token
            == work["cache_bytes_per_token"],
    }
    if ctx.on_chip:
        checks["mosaic_kernels_in_chunk"] = \
            (prog["tpu_custom_calls"] > 0) == ctx.cell["expect"]["mosaic_kernels"]
    return {
        "end_to_end": {"serve_tokens_per_s": tokens / (t1 - t0), **waits},
        "attempted": len(in_window), "failed": len(failed), "checks": checks,
        "memory_peak_bytes": prog["program_total_bytes"],
        "trace_dir": trace_dir,
        "samples": {
            "window_s": t1 - t0,
            "steps": [[t - t0, n] for t, n in loop.steps],
            "deliveries": [[t - t0, n] for t, n in loop.deliveries],
            "requests": [
                {"prompt_len": len(r["prompt"]), "max_tokens": r["max_tokens"],
                 "submitted": r["submitted"] - t0,
                 "delivered_at": [t - t0 for t in r["delivered_at"]],
                 "reference_gap": r.get("reference_gap")} for r in records]},
        "facts": {
            "compiles_in_window": compiles, "window_seconds": t1 - t0,
            "tokens_delivered": tokens, "requests_submitted": len(in_window),
            "requests_finished_in_window": len(finished_in),
            "ttft_samples": len(ttft), "token_gap_samples": len(gaps),
            **waits, "ttft_max_ms": 1e3 * max(ttft, default=0.0),
            "token_gap_max_ms": 1e3 * max(gaps, default=0.0),
            "engine": delta, "cache_integrity": repr(integrity)[:200],
            "cache_bytes_per_token": eng.stats.cache_bytes_per_token,
            "pool_blocks": ecfg.num_blocks,
            "pool_blocks_used_mean": float(np.mean(used)) if used else None,
            "pool_blocks_used_max": max(used, default=None),
            "against_forward": against_forward,
            "logit_error": logit_error,
            "logit_errors_largest": sorted(row_errors)[-6:],
            "reference_requests": len(sample),
            "reference_prompt_lens": sorted({len(r["prompt"])
                                             for r in sample}),
            "token_gap": token_gap,
            "token_gaps_over": {str(x): int((np.asarray(token_gaps) > x)
                                            .sum())
                                for x in (0.05, 0.1, 0.2, 0.4, 0.8)},
            "lower_precision": low and {
                "dtype": low, "logit_error": spread(low_errors, 90),
                "token_gap": spread(low_gaps, 99)},
            "moe_pairs": delta["moe_pairs"],
            "moe_pairs_if_routing_were_uniform": uniform,
            "moe_pairs_over_uniform":
                delta["moe_pairs"] / uniform if uniform else None,
            "work": {
                "config": work, "positions_through_layers": through,
                "sampled_positions": delta["generated_tokens"],
                "decode_context_tokens": delta["context_tokens"],
                "prefill_pairs": sum(
                    len(r["prompt"]) * (len(r["prompt"]) + 1) // 2
                    for r in in_window)},
            "kernel": ecfg.kernel,
            "decode_chunk_size": k, "warm_up_per_prompt_length": per_length,
            **prog},
    }

