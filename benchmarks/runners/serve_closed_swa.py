"""Runner `serve_closed_swa`: `serve_closed_family`'s closed loop and two
checks for a family that has sliding-window attention layers beside full
ones (`ModelSpec.layer_caches` names "window" layers): their rows live in a
second group of pools with a table of its own a sequence, and the cache
manager takes back each block the window has moved past.

The loop, the clock, the latency arithmetic and the limits' two-part form
are the other runners' own (`ClosedLoop`, `warm_up`, `latency_stats`,
`sample_finished`, `spread`, `within`, imported). What differs:

- check (a)'s paged decode steps carry the sequence's WINDOW TABLE and the
  logical index of its first block beside its block table, through the
  engine's own allocator (`cache.window_table`), and give back the blocks
  behind the window after every step (`cache.release_behind`) as the engine
  does after every chunk: at a prompt longer than the window the prefill
  has written only the last window's rows, the table starts past block 0,
  and the mask's start moves and a block is freed inside the 32 steps;
- the packed upload of the decode chunk carries the window table and its
  first index behind the block table;
- the facts hold what `lib/serve_work_swa.py` counts from
  (`window_context_tokens` beside `context_tokens`), the gauges checked are
  the two the spec states (bytes a position in the full layers, bytes a
  sequence at most in the window layers), and the window group's own
  accounting is checked: never more blocks out than a window and a chunk's
  look-ahead a row, allocated == freed once drained;
- a rehearsal is held to limits of its own (`logit_error`, `token_gap` in
  the cell's `rehearsal` block), as in `serve_closed_hybrid`.
"""
from __future__ import annotations

import functools
import importlib

import numpy as np

from lib import program, traffic
from lib.tracing import device_trace, span
from runners.serve_closed import (ClosedLoop, latency_stats, stats_snapshot,
                                  warm_up)
from runners.serve_closed_family import (load_reference, padded,
                                         sample_finished, spread, within)

#: paged decode steps behind each prefill of check (a): twice the other
#: runners' 16, so that at a prompt length that is a multiple of the block
#: (all of the mix's are) the window still moves past a block's end inside
#: them (at 32 a block: in step 31) and the block comes back
DECODE_STEPS = 32

COUNTS = ("moe_pairs", "moe_experts_hit", "moe_full_buffer_layers",
          "context_tokens", "live_row_trips", "window_context_tokens",
          "window_blocks_freed")


def snapshot(eng) -> dict:
    return {**stats_snapshot(eng),
            **{k: getattr(eng.stats, k) for k in COUNTS}}


def prefill_and_decode_rows(eng, ids, step_fn, width):
    """[1 + DECODE_STEPS, V] logits the serving path gives for `ids`: its
    prefill program, then greedy `paged_decode_step`s through the engine's
    own pools and allocator, both tables; the tokens it chose; and after
    each step (window blocks held, the table's first block, blocks the
    step's `release_behind` gave back)."""
    import jax.numpy as jnp
    cache, n = eng.cache, len(ids)
    cache.allocate("check", n)
    logits, dense, _ = eng.spec.prefill(eng.params,
                                        jnp.asarray(ids[None], jnp.int32))
    cache.write_prefill("check", dense, n)
    rows, held = [np.asarray(logits, np.float32)[0]], []
    table = np.zeros((1, eng.max_blocks_per_seq), np.int32)
    for _ in range(DECODE_STEPS):
        tok = int(rows[-1].argmax())
        block, offset, pos = cache.append_slot("check")
        ids = np.append(ids, np.int32(tok))
        t = cache.block_table("check")
        table[0, :len(t)] = t
        in_window, first = cache.window_table("check")
        window_table = np.zeros((1, width), np.int32)
        window_table[0, :len(in_window)] = in_window
        logits, cache.pools = step_fn(
            eng.params, cache.pools, np.asarray([tok], np.int32),
            np.asarray([pos], np.int32), table,
            np.asarray([block], np.int32), np.asarray([offset], np.int32),
            window_tables=window_table,
            window_firsts=np.asarray([first], np.int32))
        rows.append(np.asarray(logits, np.float32)[0])
        back = cache.release_behind("check")
        in_window, first = cache.window_table("check")
        held.append((len(in_window), first, back))
    cache.free("check")
    return np.stack(rows), ids, held


def check_against_forward(ctx, eng, lens, vocab, reference, low_reference,
                          width):
    """Check (a): per prompt length the engine's own prefill program, then
    DECODE_STEPS greedy `paged_decode_step`s through its pools and both
    tables, against the reference's full forward over the same ids.
    Returns per-length facts and the error of every row."""
    import jax
    from paddle_tpu.inference.serving.attention import paged_decode_step
    step = jax.jit(functools.partial(paged_decode_step, geom=eng.geom),
                   donate_argnums=(1,))
    found, errors, low_errors = [], [], []
    for i, n in enumerate(lens):
        prompt = traffic.prompt(ctx.seed, 1000 + i, n, vocab, warm_up=True)
        rows, ids, held = prefill_and_decode_rows(eng, prompt, step, width)
        at = np.arange(n - 1, n + DECODE_STEPS, dtype=np.int32)
        row = padded(ids, eng.spec.max_seq_len)
        exact = np.asarray(reference(eng.params, row, row, at)[1])
        err = np.abs(rows - exact).max(axis=1)
        errors += err.tolist()
        found.append({
            "prompt_len": n, "past_the_window": n > eng.spec.window,
            "window_blocks_held_max": max(h for h, _, _ in held),
            "window_table_first_block": held[-1][1],
            "window_blocks_released_behind": sum(b for _, _, b in held),
            "logit_error_prefill": float(err[0]),
            "logit_error_decode_max": float(err[1:].max()),
            "logit_abs_max": float(np.abs(exact).max()),
            "greedy_agree": int((rows.argmax(1) == exact.argmax(1)).sum())})
        if low_reference is not None:
            low = np.asarray(low_reference(eng.params, row, row, at)[1])
            low_errors += np.abs(low - exact).max(axis=1).tolist()
    return found, errors, low_errors


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
    spec, cache = eng.spec, eng.cache
    size = reference.sizes(cfg)
    work = builder.work_config(ctx.config, cfg)
    # one program and one padded length serve checks (a) and (b)
    exact = jax.jit(functools.partial(reference.gaps_and_rows, size=size))
    low = (ctx.cell["rehearsal"] if ctx.rehearse else ctx.cell).get(
        "lower_precision")
    lower = jax.jit(functools.partial(
        reference.gaps_and_rows, size=size, low=low)) if low else None
    no_rows = np.zeros((DECODE_STEPS + 1,), np.int32)
    length = spec.max_seq_len
    # the widest a row's window table gets: the group's share of a row
    width = cache.num_window_blocks // ecfg.max_num_seqs
    window_pool = next(p[0] for p, kind in zip(cache.pools,
                                               spec.layer_caches)
                       if kind == "window")

    lens = [max(1, int(n * scale)) for n in mix["prompt_lens"]]
    against_forward, row_errors, low_errors = check_against_forward(
        ctx, eng, lens, vocab, exact, lower, width)
    per_length = warm_up(ctx, eng, lens, vocab, k)
    # the chunk's upload: the control columns, the prompt feed, the block
    # table, the window table and the index of its first block
    packed = np.zeros((ecfg.max_num_seqs, PACK_COLS + k
                       + eng.max_blocks_per_seq + width + 1), np.int32)
    _, prog = program.facts(fused_decode_chunk.lower(
        eng.params, cache.pools, packed, eng.geom, k,
        ecfg.kernel).compile())

    loop = ClosedLoop(eng, mix, ctx.seed, vocab, scale)
    loop.run_until_finished(mix["steady_state"]["finished_requests"])

    ctx.window_opens()
    before = snapshot(eng)
    main_s = ctx.seconds - (ctx.trace_seconds if ctx.trace else 0.0)
    t0, t1 = loop.run_for(main_s)
    after = snapshot(eng)
    window_blocks_in_use = eng.stats.window_blocks_in_use
    compiles = ctx.compiled_in_window()
    trace_dir = None
    if ctx.trace:
        with device_trace(ctx.trace_dir), span("bench.window"):
            loop.run_for(ctx.trace_seconds)
        trace_dir = ctx.trace_dir
    loop.drain()
    integrity = cache.check_integrity()             # raises on a violation
    pool = cache.stats()

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

    def causal_pairs(n, most):
        """(query, key) pairs of a prompt of n in a layer whose queries
        attend to at most `most` keys."""
        short = min(n, most)
        return short * (short + 1) // 2 + (n - short) * most

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
            eng.stats.cache_bytes_per_token == spec.cache_bytes_per_token
            == eng.stats.cache_physical_bytes_per_token
            and eng.stats.window_bytes_per_seq
            == spec.window_bytes_per_seq > 0,
        # check (a) ran past the window's edge: the table starts behind
        # block 0 and a block came back inside the steps
        "check_a_crossed_the_window":
            any(f["past_the_window"] and f["window_table_first_block"] > 0
                and f["window_blocks_released_behind"] > 0
                for f in against_forward),
        "window_blocks_never_above_a_window_and_a_chunk_a_row":
            0 < pool["window_high_water"] <= cache.num_window_blocks
            and 0 < window_blocks_in_use <= mix["clients"] * width,
        "both_groups_allocated_equal_freed":
            pool["blocks_allocated"] == pool["blocks_freed"] > 0
            and pool["window_blocks_allocated"]
            == pool["window_blocks_freed"] > 0
            and delta["window_blocks_freed"] > 0,
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
            "engine": delta, "cache_integrity": repr(integrity)[:400],
            "cache_bytes_per_token": eng.stats.cache_bytes_per_token,
            "window_bytes_per_seq": eng.stats.window_bytes_per_seq,
            "window_blocks_in_use": window_blocks_in_use,
            "pool": pool, "pool_blocks": ecfg.num_blocks,
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
                "decode_window_context_tokens":
                    delta["window_context_tokens"],
                "prefill_pairs": sum(
                    causal_pairs(len(r["prompt"]), len(r["prompt"]))
                    for r in in_window),
                "prefill_window_pairs": sum(
                    causal_pairs(len(r["prompt"]), spec.window)
                    for r in in_window),
                "window_pool_shape": list(window_pool.shape)},
            "kernel": ecfg.kernel,
            "decode_chunk_size": k, "warm_up_per_prompt_length": per_length,
            **prog},
    }
