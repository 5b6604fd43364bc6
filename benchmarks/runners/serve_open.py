"""Runner `serve_open`: LLMEngine under an OPEN loop on the wall clock.

Requests fall due at the instants `lib/arrivals.poisson_offsets` makes from
the traffic file (its own `arrival_seed`; `--seed` orders the sizes and
makes tokens and weights, as in `serve_closed`). One thread: before every
engine step, every request that has fallen due is submitted; a request
that falls due while a step runs is submitted when the step returns, and
that wait is inside its latency, because every latency counts from the DUE
instant. How late the generator submitted (submission minus due) is
printed: mean, 95th percentile, largest. With nothing in flight the thread
sleeps until the next due instant.

The lead-in (stream 1 of the file) runs until `steady_state.
finished_requests` have finished; the window's stream (stream 0) starts at
the window's first instant, so every run's window is offered the same
requests at the same offsets. Waits, throughput and `correct` are
`serve_closed`'s (its `ClosedLoop` bookkeeping, `warm_up`, `latency_stats`
and `reference_gap`, imported).
"""
from __future__ import annotations

import time

import numpy as np

from lib import arrivals, gpt2, program, reference_gpt2, traffic
from lib.tracing import device_trace, span
from runners.serve_closed import (ClosedLoop, latency_stats, reference_gap,
                                  stats_snapshot, warm_up)


class OpenLoop(ClosedLoop):
    """Requests submitted when due; nobody resubmits."""

    def __init__(self, eng, mix, seed, vocab, scale):
        super().__init__(eng, dict(mix, clients=0), seed, vocab, scale)
        self.mix = mix
        self.late = []              # (due, submission - due) per request

    def submit_due(self, due):
        plen, new = next(self.sizes)
        ids = traffic.prompt(self.seed, self.submitted, plen, self.vocab)
        self.submitted += 1
        rid = self.eng.add_request(ids, self.params(max_tokens=new))
        self.late.append((due, time.perf_counter() - due))
        self.live[rid] = {"prompt": ids, "max_tokens": new, "submitted": due,
                          "delivered_at": [], "finish_reason": None}

    def run_stream(self, stream, seconds=None, finished=None):
        """Offer the file's `stream` from now on, for `seconds` or until
        `finished` requests have finished. Returns (start, end)."""
        t0 = now = time.perf_counter()
        offsets = arrivals.poisson_offsets(self.mix, stream)
        due = t0 + next(offsets)
        while (now - t0 < seconds) if finished is None \
                else (len(self.done) < finished):
            while due <= now:
                self.submit_due(due)
                due = t0 + next(offsets)
            if self.live:
                now = self.step(resubmit=False)
            else:
                time.sleep(max(0.0, min(due - time.perf_counter(), 0.002)))
                now = time.perf_counter()
        return t0, now


def run(ctx):
    import jax
    from paddle_tpu.inference.serving import EngineConfig, LLMEngine
    from paddle_tpu.inference.serving.attention import (PACK_COLS,
                                                        fused_decode_chunk)

    size = gpt2.sizes(ctx.config, ctx.rehearse)
    mix = ctx.mix()
    scale = mix.get("scale", 1.0)
    vocab = size["vocab_size"]
    model = gpt2.build_model(size, ctx.seed)
    model.eval()
    ecfg = EngineConfig(**ctx.setting("engine"))    # other fields: default
    eng = LLMEngine.from_model(model, ecfg)
    k = ecfg.decode_chunk_size

    lens = [max(1, int(n * scale)) for n in mix["prompt_lens"]]
    per_length = warm_up(ctx, eng, lens, vocab, k)
    packed = np.zeros((ecfg.max_num_seqs,
                       PACK_COLS + k + eng.max_blocks_per_seq), np.int32)
    _, prog = program.facts(fused_decode_chunk.lower(
        eng.params, eng.cache.pools, packed, eng.geom, k,
        ecfg.kernel).compile())

    loop = OpenLoop(eng, mix, ctx.seed, vocab, scale)
    loop.run_stream(1, finished=mix["steady_state"]["finished_requests"])

    ctx.window_opens()
    before = stats_snapshot(eng)
    main_s = ctx.seconds - (ctx.trace_seconds if ctx.trace else 0.0)
    t0, t1 = loop.run_stream(0, seconds=main_s)
    after = stats_snapshot(eng)
    compiles = ctx.compiled_in_window()
    trace_dir = None
    if ctx.trace:
        with device_trace(ctx.trace_dir), span("bench.window"):
            loop.run_stream(2, seconds=ctx.trace_seconds)
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
    gaps_fn = jax.jit(reference_gpt2.token_gaps, static_argnums=(2, 3))
    for r in finished_in:
        r["reference_gap"] = reference_gap(gaps_fn, eng, r, size)
    ref_gaps = [r["reference_gap"] for r in finished_in]
    used = [n for t, n in loop.steps if t0 < t <= t1]
    late = [1e3 * d for due, d in loop.late if t0 <= due < t1]
    rows = rows_in_flight(loop.steps, records, t0, t1)

    delta = {key: after[key] - before[key] for key in after}
    waits = {**latency_stats("ttft", ttft), **latency_stats("token_gap", gaps)}
    checks = {
        "all_requests_end_stop_or_length": not failed and bool(in_window),
        "every_request_got_a_first_token": len(ttft) == len(in_window),
        "cache_integrity": True,
        "engine_tokens_within_tolerance_of_reference":
            bool(ref_gaps) and max(ref_gaps) <= ctx.cell["logit_tolerance"],
        "no_compile_in_window": compiles == 0,
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
                 "due": r["submitted"] - t0,
                 "delivered_at": [t - t0 for t in r["delivered_at"]],
                 "reference_gap": r.get("reference_gap")} for r in records]},
        "facts": {
            "compiles_in_window": compiles, "window_seconds": t1 - t0,
            "tokens_delivered": tokens,
            "rate_per_s": mix["rate_per_s"],
            "requests_offered": len(in_window),
            "tokens_offered_per_s":
                sum(r["max_tokens"] for r in in_window) / (t1 - t0),
            "generator_late_ms": {
                "mean": float(np.mean(late)) if late else None,
                "p95": float(np.percentile(late, 95)) if late else None,
                "max": max(late, default=None)},
            "rows_in_flight_mean": float(np.mean(rows)) if rows else None,
            "rows_in_flight_max": max(rows, default=None),
            "requests_finished_in_window": len(finished_in),
            "ttft_samples": len(ttft), "token_gap_samples": len(gaps),
            **waits, "ttft_max_ms": 1e3 * max(ttft, default=0.0),
            "token_gap_max_ms": 1e3 * max(gaps, default=0.0),
            "engine": delta, "cache_integrity": repr(integrity)[:200],
            "pool_blocks": ecfg.num_blocks,
            "pool_blocks_used_mean": float(np.mean(used)) if used else None,
            "pool_blocks_used_max": max(used, default=None),
            "reference_requests": len(ref_gaps),
            "reference_logit_gap_max": max(ref_gaps, default=None),
            "kernel": ecfg.kernel,
            "decode_chunk_size": k, "warm_up_per_prompt_length": per_length,
            **prog},
    }


def rows_in_flight(steps, records, t0, t1):
    """Per engine step of the window, the requests that were due before it
    returned and had not finished before it: what the step held."""
    held = [(r["submitted"], r.get("finished", float("inf")))
            for r in records]
    return [sum(s <= t <= e for s, e in held)
            for t, _ in steps if t0 < t <= t1]
