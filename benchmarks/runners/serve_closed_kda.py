"""Runner `serve_closed_kda`: `serve_closed_hybrid`'s closed loop and
checks for a family whose cache keeps LATENT rows (one pool of W a
position, multi-head latent attention) in some layers beside one state a
sequence in the others (Kimi Delta Attention), and whose decode updates the
state in place (`ops/pallas/kda_step.py`).

Check (a), the closed loop, the clock, the latency arithmetic and the
limits are `serve_closed_hybrid`'s (imported). What differs:

- the cache gauges: a latent row is stored padded to whole lanes (576 ->
  640, `paged_cache.physical_shape`), so the bytes a position as the pools
  store it are the spec's bytes a position plus that padding, not equal to
  them, as a (k, v) pair's are;
- the engine's counts include the family's two (`kda_state_row_trips`,
  `kda_prefill_chunks`), and the facts hold what `lib/serve_work_kda.py`
  counts from (`state_row_trips` beside `context_tokens`).
"""
from __future__ import annotations

import functools
import importlib

import numpy as np

from lib import program
from lib.tracing import device_trace, span
from runners.serve_closed import ClosedLoop, latency_stats, warm_up
from runners.serve_closed_family import (DECODE_STEPS, load_reference, padded,
                                         sample_finished, spread, within)
from runners.serve_closed_hybrid import check_against_forward
from runners.serve_closed_hybrid import snapshot as hybrid_snapshot

COUNTS = ("kda_state_row_trips", "kda_prefill_chunks")


def snapshot(eng) -> dict:
    return {**hybrid_snapshot(eng),
            **{k: getattr(eng.stats, k) for k in COUNTS}}


def stored_row_bytes(eng) -> int:
    """Bytes a position holds in the latent pools as STORED."""
    return sum(int(np.prod(p.shape[2:])) * p.dtype.itemsize
               for p, kind in zip(eng.cache.pools, eng.spec.layer_caches)
               if kind == "rows")


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
    # the chunk's upload: the control columns, the prompt feed, the block
    # table and the row's state slot
    packed = np.zeros((ecfg.max_num_seqs,
                       PACK_COLS + k + eng.max_blocks_per_seq + 1), np.int32)
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
    slots_in_use = eng.stats.state_slots_in_use
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
    uniform = through * cfg.num_hidden_layers * cfg.num_experts_per_tok \
        * cfg.held[1] / cfg.num_experts
    waits = {**latency_stats("ttft", ttft), **latency_stats("token_gap", gaps)}
    logit_error = spread(row_errors, 90)
    token_gap = spread(token_gaps, 99) if token_gaps else None
    # a rehearsal (float32 against float32 at toy widths) has limits of its
    # own, so that its lower-precision reading can fail them
    limits = {**ctx.cell, **(ctx.cell["rehearsal"] if ctx.rehearse else {})}
    spec = eng.spec
    checks = {
        "all_requests_end_stop_or_length": not failed and bool(in_window),
        "every_request_got_a_first_token": len(ttft) == len(in_window),
        "cache_integrity": True,
        "prefill_and_paged_decode_logits_match_reference":
            within(logit_error, limits["logit_error"]),
        "engine_tokens_within_tolerance_of_reference":
            token_gap is not None
            and within(token_gap, limits["token_gap"]),
        "reference_sample_holds_every_prompt_length":
            {len(r["prompt"]) for r in sample}
            == {len(r["prompt"]) for r in finished_in},
        "no_compile_in_window": compiles == 0,
        "cache_bytes_as_the_spec_states":
            eng.stats.cache_bytes_per_token == spec.cache_bytes_per_token > 0
            and eng.stats.cache_physical_bytes_per_token
            == stored_row_bytes(eng) >= spec.cache_bytes_per_token
            and eng.stats.state_bytes_per_seq == spec.state_bytes_per_seq > 0,
        # a client whose request ended in the window's last step holds
        # none until the next step admits its next request
        "state_slots_are_the_sequences_that_hold_cache":
            0 < slots_in_use <= mix["clients"]
            and integrity["state_slots_without_table"] == 0,
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
            "engine": delta, "cache_integrity": repr(integrity)[:300],
            "cache_bytes_per_token": eng.stats.cache_bytes_per_token,
            "cache_physical_bytes_per_token":
                eng.stats.cache_physical_bytes_per_token,
            "state_bytes_per_seq": eng.stats.state_bytes_per_seq,
            "state_slots_in_use": slots_in_use,
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
                "live_row_trips": delta["live_row_trips"],
                "state_row_trips": delta["kda_state_row_trips"],
                "prefill_chunks": delta["kda_prefill_chunks"],
                "prefill_pairs": sum(
                    len(r["prompt"]) * (len(r["prompt"]) + 1) // 2
                    for r in in_window)},
            "kernel": ecfg.kernel,
            "decode_chunk_size": k, "warm_up_per_prompt_length": per_length,
            **prog},
    }
