"""Runner `serve_closed`: LLMEngine under a closed loop of clients.

The loop is chip_smoke.py's `phase_serve` (it ran on the v5e in PR 23) with
arrivals and a clock of the benchmark's own: one thread, no client threads,
no think time. After each `eng.step()` returns, its deliveries are stamped
with time.perf_counter() and every client whose request finished in it
submits its next request at once. The engine's own TTFT / token-gap
histograms are not read.

- ttft: add_request -> return of the step() that delivered the request's
  first token, over requests submitted inside the window;
- token gap: wait between consecutive DELIVERIES of new tokens to one
  request (a step may deliver up to decode_chunk_size tokens at once; the
  zero gaps inside a delivery are not samples), over deliveries inside the
  window;
- serve_tokens_per_s: tokens delivered inside the window over its seconds.

Of both waits the record holds mean, median, 90th and 95th percentile
(`latency_stats`); BENCHMARK.json says which are end-to-end metrics and
which layer metrics, and PERF.md why. The raw samples go to
`<out>/<cell>/samples.json`.
"""
from __future__ import annotations

import time

import numpy as np

from lib import chip, gpt2, program, reference_gpt2, traffic
from lib.tracing import device_trace, span

#: EngineStats fields the layer metrics read as deltas over the window
COUNTERS = ("steps", "time_schedule", "time_prefill", "time_decode",
            "generated_tokens", "preemptions", "prefill_tokens")


def stats_snapshot(eng) -> dict:
    snap = {k: getattr(eng.stats, k) for k in COUNTERS}
    snap["syncs_prefill"] = eng.stats.host_syncs("prefill")
    snap["syncs_decode"] = eng.stats.host_syncs("decode")
    return snap


class ClosedLoop:
    """`clients` callers, each with one request in flight."""

    def __init__(self, eng, mix, seed, vocab, scale):
        from paddle_tpu.inference.serving import SamplingParams
        self.eng, self.seed, self.vocab = eng, seed, vocab
        self.params = SamplingParams
        self.sizes = traffic.closed_loop_sizes(mix, seed, scale)
        self.submitted = 0
        self.live = {}              # request id -> record
        self.done = []              # records, in order of finishing
        self.deliveries = []        # (instant, tokens) of every delivery
        self.steps = []             # (instant, pool blocks in use) per step
        for _ in range(mix["clients"]):
            self.submit()

    def submit(self):
        plen, new = next(self.sizes)
        ids = traffic.prompt(self.seed, self.submitted, plen, self.vocab)
        self.submitted += 1
        t = time.perf_counter()
        rid = self.eng.add_request(ids, self.params(max_tokens=new))
        self.live[rid] = {"prompt": ids, "max_tokens": new, "submitted": t,
                          "delivered_at": [], "finish_reason": None}

    def step(self, resubmit: bool):
        with span("bench.engine_step"):
            outs = self.eng.step()
        now = time.perf_counter()
        got = {}
        for o in outs:
            rec = self.live[o.request_id]
            if o.new_token is not None:
                got[o.request_id] = got.get(o.request_id, 0) + 1
            if o.finished:
                rec["finish_reason"] = o.finish_reason
                rec["tokens"] = np.asarray(o.token_ids, np.int32)
                rec["finished"] = now
        for rid, n in got.items():
            self.live[rid]["delivered_at"].append(now)
            self.deliveries.append((now, n))
        self.steps.append((now, self.eng.cache.num_used()))
        with span("bench.resubmit"):
            for rid in [r for r, rec in self.live.items()
                        if rec["finish_reason"] is not None]:
                self.done.append(self.live.pop(rid))
                if resubmit:
                    self.submit()
        return now

    def run_for(self, seconds):
        """Step with resubmission until `seconds` have passed. Returns
        (start, end) instants; the end is the return of the last step."""
        t0 = now = time.perf_counter()
        while now - t0 < seconds:
            now = self.step(resubmit=True)
        return t0, now

    def run_until_finished(self, n):
        """Step with resubmission until `n` requests have finished: a count
        and not a time, so that every run's window opens at the same place
        in the stream of requests."""
        while len(self.done) < n:
            self.step(resubmit=True)

    def drain(self):
        while self.live:
            self.step(resubmit=False)

    def records(self):
        return self.done + list(self.live.values())


def warm_up(ctx, eng, lens, vocab, chunk):
    """One request of each prompt length through the engine itself, so that
    dense prefill, write_prefill and the decode chunk compile at the shapes
    the traffic uses and at no other. Returns per-length facts."""
    from paddle_tpu.inference.serving import SamplingParams
    found = []
    for i, n in enumerate(lens):
        before, t0 = ctx.clock.snapshot(), time.perf_counter()
        eng.add_request(traffic.prompt(ctx.seed, i, n, vocab, warm_up=True),
                        SamplingParams(max_tokens=chunk + 2))
        while eng.has_unfinished():
            eng.step()
        d = ctx.clock.snapshot().since(before)
        found.append({"prompt_len": n, "seconds": time.perf_counter() - t0,
                      "compile_seconds": d.compile_s, "compiles": d.compiles,
                      "cache_hits": d.hits})
    return found


def reference_gap(fn, eng, rec, size):
    """Largest distance of an engine token below the plain reference's best
    logit at its position: one teacher-forced forward over prompt + engine
    tokens, padded to n_positions so that the reference (`fn`: the jitted
    reference_gpt2.token_gaps) compiles once."""
    import jax.numpy as jnp
    n_prompt, n_new = len(rec["prompt"]), len(rec["tokens"])
    ids = np.zeros((1, size["n_positions"]), np.int32)
    ids[0, :n_prompt] = rec["prompt"]
    ids[0, n_prompt:n_prompt + n_new] = rec["tokens"]
    gaps = np.asarray(fn(eng.params, jnp.asarray(ids), size["n_layer"],
                         size["n_head"]))
    return float(gaps[n_prompt - 1:n_prompt + n_new - 1].max())


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

    loop = ClosedLoop(eng, mix, ctx.seed, vocab, scale)
    loop.run_until_finished(mix["steady_state"]["finished_requests"])

    ctx.window_opens()
    before = stats_snapshot(eng)
    main_s = ctx.seconds - (ctx.trace_seconds if ctx.trace else 0.0)
    t0, t1 = loop.run_for(main_s)
    after = stats_snapshot(eng)
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
    gaps_fn = jax.jit(reference_gpt2.token_gaps, static_argnums=(2, 3))
    for r in finished_in:
        r["reference_gap"] = reference_gap(gaps_fn, eng, r, size)
    ref_gaps = [r["reference_gap"] for r in finished_in]
    used = [n for t, n in loop.steps if t0 < t <= t1]

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
            "pool_blocks": ecfg.num_blocks,
            "pool_blocks_used_mean": float(np.mean(used)) if used else None,
            "pool_blocks_used_max": max(used, default=None),
            "reference_requests": len(ref_gaps),
            "reference_logit_gap_max": max(ref_gaps, default=None),
            "reference_logit_gaps_over_1e-3":
                sorted(g for g in ref_gaps if g > 1e-3),
            "kernel": ecfg.kernel,
            "decode_chunk_size": k, "warm_up_per_prompt_length": per_length,
            **prog},
    }


def latency_stats(name, seconds):
    """{<name>_mean_ms, _p50_ms, _p90_ms, _p95_ms} of a list of waits in
    seconds; nothing for no samples."""
    if not seconds:
        return {}
    ms = 1e3 * np.asarray(seconds, np.float64)
    out = {f"{name}_mean_ms": float(ms.mean())}
    for q in (50, 90, 95):
        out[f"{name}_p{q}_ms"] = chip.percentile(ms, q)[0]
    return out
