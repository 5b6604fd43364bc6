"""LLMEngine: continuous-batching serving loop over the paged KV cache.

The serving analogue of the reference inference layer's
AnalysisPredictor::Run — but instead of one synchronous batch per call,
requests stream in (add_request), the engine interleaves prefill and
decode per step() under the scheduler's FCFS/preemption policy, and
outputs stream back token by token.

Device work per step:
- prefill: models.generation.prefill (the SAME jitted program the dense
  generate() path uses — one compilation per prompt-length bucket),
  scattered into the sequence's blocks by ONE more program over all
  layers with the pools donated (PagedKVCache.write_prefill);
- decode: serving.attention.fused_decode_chunk — a jitted lax.scan that
  decodes, SAMPLES and tracks termination for up to decode_chunk_size
  tokens per running sequence entirely on device, padded to a
  power-of-two bucket capped at max_num_seqs, so XLA compiles once per
  (bucket, k) and never recompiles per request mix.

Host/device contract (docs/serving.md "Device-resident decode"): the
host uploads ONE packed control array per chunk and fetches ONE
(tokens[k], finished, not-finite flags) result — host syncs in
steady-state decode are 1 per k tokens, not 1 per token (the obs
host-sync counter pins this). The first token of a request is sampled
on host from the prefill logits (host numpy, per-request RNG); every
subsequent token is sampled in-scan with a fold_in(seed,
tokens-generated) PRNG key, a function of request progress only — so
token streams are invariant under chunk size, preemption and crash
replay, and greedy engine output token-matches
models.generation.generate (tests/test_serving.py pins this end to
end, preemptions included; tests/test_serving_chunked.py pins k-chunk
vs k x 1-chunk bitwise, temperature paths included).

Hardened step (docs/serving.md "Failure semantics"): every step first
expires overdue requests (deadline_s / queue_ttl_s → 'timeout'), then
runs prefill/decode under an anomaly guard (core/anomaly NaN/Inf
detection on the logits) and a step-progress watchdog
(step_timeout_s). A poisoned or wedged step quarantines the offending
request ('error'), scrubs+frees its blocks, and REBUILDS the remaining
running requests by requeueing them for re-prefill from their token
logs — bitwise-equivalent to an undisturbed run for the survivors, so
one bad request costs one request, not the fleet. Admission control
(max_waiting + admission_policy, cache_high_watermark) bounds the queue
('shed' / EngineOverloaded) before overload can strand decodes.

Telemetry (PR 6, docs/observability.md): every phase runs under an
obs.trace span — the step itself is cat="serving", the phases carry
their own categories (cat="schedule"/"prefill"/"decode") — so a chrome
trace exported with profiler.export_chrome_tracing (or obs.trace
.export_chrome) shows schedule/prefill/decode per engine step with
request counts in args. The same spans, with the pieces of each phase
as children (serving.prefill.forward/.write_cache/.fetch/.sample,
serving.decode.pack/.dispatch/.fetch/.drain) and serving.add_request,
appear in any open jax.profiler session on the device trace's clock,
with request_id / tokens / context_tokens as stats: the catalog in
docs/observability.md is the contract with the benchmark's readers.
EngineStats is a thin view over the obs
metrics registry, and the step loop additionally records TTFT /
inter-token / request-latency / step-time histograms plus queue and
cache-occupancy gauges — all host-side on values the step already
fetched, so instrumentation adds ZERO device syncs (PT-T007 clean).
"""
from __future__ import annotations

import contextlib
import itertools
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np
import jax.numpy as jnp

from ... import obs
from ...analysis import holds_lock
from ...core import anomaly
from ...models import generation as gen
from ...profiler import RecordEvent
from .attention import PACK_COLS, as_spec, fused_decode_chunk, pack_f32
from .paged_cache import (CacheExhausted, PagedKVCache,
                          window_blocks_per_seq)
from .scheduler import (HOLD_REASONS, EngineOverloaded, Request,
                        RequestState, SamplingParams, ScheduledBatch,
                        Scheduler, SchedulerConfig, record_promotion_events)

__all__ = ["EngineConfig", "EngineStats", "LLMEngine", "RequestOutput",
           "ServingPredictor"]


@dataclass
class EngineConfig:
    block_size: int = 16
    num_blocks: int = 256
    max_num_seqs: int = 8
    max_prefill_tokens: int = 2048
    # static-cost admission (docs/serving.md): a PrefillCostModel
    # (analysis/jaxplan) pricing each admission by its modelled prefill
    # FLOPs instead of a flat token count. "auto" loads the committed
    # plan's model (jaxplan.json; falls back to flat if no plan is
    # committed); None keeps the flat budget.
    prefill_cost_model: Optional[object] = None
    # tokens decoded per fused device chunk (the k of
    # attention.fused_decode_chunk): the host syncs with the device
    # once per k tokens instead of once per token. 1 reproduces the
    # classic single-token step (useful for A/B and debugging); larger
    # k amortizes dispatch further but coarsens the granularity at
    # which deadlines/watchdog/fault quarantine act (they all run at
    # chunk boundaries).
    decode_chunk_size: int = 8
    # serving attention kernel (docs/serving.md "Ragged paged attention
    # and chunked prefill"): "ragged" (default) pads every decode batch
    # to the ONE fixed max_num_seqs width — dead rows cost zero kernel
    # work under the pallas ragged paged-attention kernel, and a single
    # compilation covers every batch mix. "bucketed" keeps the legacy
    # power-of-two bucket padding (one compile per bucket) as the
    # fallback and parity oracle. Off-TPU both lower to the same
    # gather + composed attention, so they are bitwise-identical there.
    kernel: str = "ragged"
    # prompts STRICTLY longer than this are admitted CHUNKED: their
    # prefill rides the fused decode scan decode_chunk_size tokens per
    # step instead of a dedicated dense prefill dispatch, so long
    # prompts never stall a step. None disables chunking.
    prefill_chunk_threshold: Optional[int] = None
    # prefix caching (docs/serving.md "Prefix caching"): share KV
    # blocks across requests through a radix-trie index with
    # refcounts, copy-on-write forking and LRU eviction. Prompts with
    # a cached prefix are admitted chunked and prefill only their
    # uncached suffix; greedy output is bitwise-identical either way.
    enable_prefix_cache: bool = False
    # hierarchical tiering (docs/serving.md "Hierarchical KV-cache
    # tiering"): > 0 gives the prefix cache a host-RAM spill tier of
    # that many blocks — LRU eviction demotes payloads into it instead
    # of destroying them, and a later match promotes them back (sha256-
    # verified) instead of re-prefilling. Needs enable_prefix_cache.
    host_tier_blocks: int = 0
    # wall-clock budget for one promotion run; an overrun stops the run
    # (entries stay host-resident, retryable) and the request re-prefills
    # the unpromoted suffix. None = unbounded.
    promote_timeout_s: Optional[float] = None
    # KV pool storage dtype (docs/serving.md "int8 KV blocks"): "int8"
    # stores the block pools (and host-tier spills) as int8 codes +
    # per-(block, head) scales via serving/kv_quant.py, ~4x less
    # resident KV. Decoded output then tracks the f32 engine within the
    # dequantization bound jaxnum derives and numplan.json commits
    # (serving.kv_block_codec). "float32" (default) is the historical
    # bitwise-exact pool.
    kv_cache_dtype: str = "float32"
    # ----------------------------- robustness layer (docs/serving.md)
    max_waiting: Optional[int] = None    # bounded waiting queue (None=∞)
    admission_policy: str = "reject"     # 'reject' | 'shed_oldest'
    cache_high_watermark: float = 1.0    # pause prefill admission above
    step_timeout_s: Optional[float] = None  # watchdog budget per step
    # prefix for this engine's `engine` label in the obs registry; the
    # final label is ALWAYS uniquified per instance (prefix-N) so two
    # engines can never merge their metric series
    obs_label: Optional[str] = None
    # multi-tenant serving (serving/tenancy.TenantRegistry): shared
    # fleet-wide by REFERENCE (dataclasses.replace keeps it), so quota
    # windows and fair shares are fleet-level facts. Enables WFQ
    # admission, sliding-window quota enforcement, deadline-aware early
    # reject and share-weighted trie eviction. None (default) keeps the
    # historical single-tenant FCFS stack bit-for-bit.
    tenants: Optional[object] = None
    # multi-model fleets (serving/deploy.py): which model's weights this
    # engine serves and which published revision of them. The pair keys
    # every KV payload that leaves the engine (export_request,
    # export_prefix) and every admit path refuses a payload keyed for a
    # different (model, revision) — stale KV can never cross a weight
    # rollout. The defaults keep single-model stacks untagged and their
    # reqtrace dumps byte-identical to the pre-deploy schema.
    model: str = "default"
    revision: str = "r0"


@dataclass
class RequestOutput:
    """One streamed step result for one request. finish_reason taxonomy
    (docs/serving.md): 'stop' | 'length' | 'cancelled' | 'timeout'
    (deadline_s / queue_ttl_s) | 'shed' (admission eviction) | 'error'
    (quarantined by the anomaly guard / watchdog). Abnormal terminals
    carry new_token=None."""
    request_id: str
    new_token: Optional[int]
    token_ids: List[int]                 # all generated tokens so far
    finished: bool
    finish_reason: Optional[str] = None


# int event counters (serving_events_total{engine,event}); field name
# IS the event label. 'rejected' (EngineOverloaded raises) is new in
# the obs layer — the pre-obs stats never counted refused admissions.
_STAT_EVENTS = ("steps", "prefill_tokens", "generated_tokens",
                "preemptions", "completed", "cancelled", "expired",
                "timeouts", "shed", "errors", "recoveries", "rebuilt",
                "watchdog_trips", "rejected",
                # expert families (ModelSpec.counters): (token, held
                # expert) pairs routed, held experts hit per trip/layer,
                # layers and trips that multiplied the full pair buffer,
                # and those that multiplied batched over the experts
                "moe_pairs", "moe_experts_hit", "moe_full_buffer_layers",
                "moe_batched_layers",
                # calls of the expert layer, and those whose largest load
                # was at most two / four times the uniform one
                "moe_layer_calls", "moe_fit_2x", "moe_fit_4x",
                # a family with KDA layers: live rows x KDA layers that a
                # decode step updated in place, and the prefills' chunks
                "kda_state_row_trips", "kda_prefill_chunks",
                # a family with Gated DeltaNet layers: live rows x DeltaNet
                # layers that a decode step updated in place
                "gdn_state_row_trips",
                # the `context_tokens`, `live_row_trips` and `live_blocks`
                # stats of serving.decode, summed
                "context_tokens", "live_row_trips", "live_blocks",
                # a spec with window layers: the `window_context_tokens`
                # stat of serving.decode summed, and the window blocks the
                # drains gave back (`PagedKVCache.release_behind`)
                "window_context_tokens", "window_blocks_freed",
                # decode chunks dispatched with at least one row that
                # samples (temperature > 0): the chunks whose scan took
                # the sampler's sampled branch
                "sampled_chunks")
# float phase-time accumulators (serving_phase_seconds_total{engine,phase})
_STAT_PHASES = {"time_schedule": "schedule", "time_prefill": "prefill",
                "time_decode": "decode"}
# per-request wall-time sums over COMPLETED requests (the historical
# avg_ttft_s / avg_request_latency_s denominators)
_STAT_REQ_SUMS = {"ttft_sum": "ttft", "latency_sum": "latency"}

_ENGINE_IDS = itertools.count()


class EngineStats:
    """Engine statistics as a THIN VIEW over the obs registry (PR 6).

    Field surface and `as_dict()` are unchanged from the old dataclass
    (tests and tools/chaos_serve.py read `stats.errors`,
    `stats.as_dict()` exactly as before), but every field is now a
    generated property over a registry child — `stats.completed += 1`
    increments `serving_events_total{engine=...,event="completed"}` —
    so Prometheus/JSON exporters, the load suite and the engine itself
    all read ONE sink. Each instance gets a unique `engine` label
    (never shared: chaos_serve's reference and faulted engines must not
    merge), and the view also carries the engine's latency histograms
    (TTFT / inter-token gap / request latency / step time) and per-step
    gauges, recorded via the observe_*/set_* helpers below.

    Registry children are individually thread-safe and the engine
    mutates stats only under its own lock, so the view itself needs no
    `_GUARDED_BY` contract.
    """

    def __init__(self, label: str = None):
        if label is None:
            label = "engine"
        # ALWAYS uniquified — a caller-supplied label is a prefix
        self.label = f"{label}-{next(_ENGINE_IDS)}"
        lbl = dict(engine=self.label)
        ev = obs.counter("serving_events_total",
                         "engine lifecycle/robustness event counts",
                         labels=("engine", "event"))
        self._events = {f: ev.labels(event=f, **lbl) for f in _STAT_EVENTS}
        ph = obs.counter("serving_phase_seconds_total",
                         "host wall time accumulated per engine phase",
                         labels=("engine", "phase"), unit="seconds")
        self._phases = {f: ph.labels(phase=p, **lbl)
                        for f, p in _STAT_PHASES.items()}
        rs = obs.counter("serving_request_seconds_total",
                         "per-request wall-time sums over completed "
                         "requests (kind=ttft|latency)",
                         labels=("engine", "kind"), unit="seconds")
        self._req_sums = {f: rs.labels(kind=k, **lbl)
                          for f, k in _STAT_REQ_SUMS.items()}
        self._ttft = obs.histogram(
            "serving_ttft_seconds",
            "time to first token, observed once per request",
            labels=("engine",), unit="seconds").labels(**lbl)
        self._token_gap = obs.histogram(
            "serving_token_gap_seconds",
            "inter-token latency (gap between consecutive emitted "
            "tokens of one request)",
            labels=("engine",), unit="seconds").labels(**lbl)
        self._latency = obs.histogram(
            "serving_request_latency_seconds",
            "request wall time arrival→finish, observed at completion",
            labels=("engine",), unit="seconds").labels(**lbl)
        self._step = obs.histogram(
            "serving_step_seconds", "engine step() wall time",
            labels=("engine",), unit="seconds").labels(**lbl)
        self._decode_chunk = obs.histogram(
            "serving_decode_chunk_seconds",
            "fused k-token decode chunk wall time (the device scan plus "
            "its single host fetch)",
            labels=("engine",), unit="seconds").labels(**lbl)
        sy = obs.counter(
            "serving_host_syncs_total",
            "device->host synchronizations: one per prefill logits "
            "fetch, one per fused decode chunk fetch",
            labels=("engine", "phase"))
        self._syncs = {p: sy.labels(phase=p, **lbl)
                       for p in ("prefill", "decode")}
        ah = obs.counter(
            "serving_admission_holds_total",
            "engine steps by what ended their admission "
            "(scheduler.HOLD_REASONS): none (the queue ran empty) | rows "
            "(max_num_seqs) | budget (max_prefill_tokens) | watermark | "
            "window (the window group's free blocks) | blocks "
            "(CacheExhausted)",
            labels=("engine", "reason"))
        self._holds = {r: ah.labels(reason=r, **lbl) for r in HOLD_REASONS}
        g_run = obs.gauge("serving_running", "running sequences",
                          labels=("engine",))
        g_wait = obs.gauge("serving_waiting", "waiting-queue depth",
                           labels=("engine",))
        g_blk = obs.gauge("serving_cache_blocks",
                          "paged-cache block pool occupancy",
                          labels=("engine", "state"), unit="blocks")
        g_spend = obs.gauge("serving_prefill_spend_tokens",
                            "prompt tokens admitted to prefill this step "
                            "(per-step spend against max_prefill_tokens)",
                            labels=("engine",), unit="tokens")
        self._c_prefill_chunks = obs.counter(
            "serving_prefill_chunks_total",
            "prompt chunks consumed inside the fused decode scan — one "
            "per mid-prefill row per chunk dispatch (chunked prefill)",
            labels=("engine",)).labels(**lbl)
        self._g_cache_bytes_per_token = obs.gauge(
            "serving_cache_bytes_per_token",
            "bytes of cache one position costs over all layers (the "
            "model spec's layout and dtype)",
            labels=("engine",), unit="bytes").labels(**lbl)
        self._g_cache_physical_bytes_per_token = obs.gauge(
            "serving_cache_physical_bytes_per_token",
            "bytes one position holds in the pools as stored, over all "
            "layers: serving_cache_bytes_per_token plus the padding of "
            "the shape the cache keeps block-major on the device "
            "(paged_cache.physical_shape)",
            labels=("engine",), unit="bytes").labels(**lbl)
        self._g_state_bytes_per_seq = obs.gauge(
            "serving_state_bytes_per_seq",
            "bytes of cache one SEQUENCE costs whatever its length: the "
            "fixed-size entries of the spec's state layers (0 without any)",
            labels=("engine",), unit="bytes").labels(**lbl)
        self._g_state_slots_in_use = obs.gauge(
            "serving_state_slots_in_use",
            "state slots owned by a sequence after the last step (a spec "
            "with state layers: one a sequence that holds cache)",
            labels=("engine",)).labels(**lbl)
        self._g_window_bytes_per_seq = obs.gauge(
            "serving_window_bytes_per_seq",
            "bytes of cache the spec's window layers cost a sequence at "
            "most: `window` positions each, however long the sequence "
            "grows (0 without any)",
            labels=("engine",), unit="bytes").labels(**lbl)
        self._g_window_blocks_in_use = obs.gauge(
            "serving_window_blocks_in_use",
            "blocks of the window layers' group owned by a sequence after "
            "the last step (a spec with window layers)",
            labels=("engine",), unit="blocks").labels(**lbl)
        self._g_running = g_run.labels(**lbl)
        self._g_waiting = g_wait.labels(**lbl)
        self._g_blocks_used = g_blk.labels(state="used", **lbl)
        self._g_blocks_free = g_blk.labels(state="free", **lbl)
        self._g_prefill_spend = g_spend.labels(**lbl)
        # prefix cache (docs/observability.md): hit/miss/eviction
        # counters mirrored from the cache's lifetime counters via the
        # delta-inc pattern, plus cached/shared block gauges and the
        # cached-prompt-token ratio
        self._prefix_counters = {
            "hits": obs.counter(
                "serving_prefix_cache_hits_total",
                "admissions that attached at least one cached prefix "
                "token", labels=("engine",)).labels(**lbl),
            "misses": obs.counter(
                "serving_prefix_cache_misses_total",
                "admissions that matched nothing in the prefix trie",
                labels=("engine",)).labels(**lbl),
            "evictions": obs.counter(
                "serving_prefix_cache_evictions_total",
                "unreferenced cached blocks reclaimed under pool "
                "pressure (LRU leaf first)",
                labels=("engine",)).labels(**lbl),
        }
        self._g_prefix_ratio = obs.gauge(
            "serving_prefix_cached_tokens_ratio",
            "prompt tokens served from cache / prompt tokens admitted "
            "(lifetime, per engine)",
            labels=("engine",)).labels(**lbl)
        g_pfx = obs.gauge(
            "serving_prefix_cache_blocks",
            "prefix-cache block census: kind=cached (trie-indexed) | "
            "shared (refcount >= 2); tenant='*' is the all-tenants "
            "total, per-tenant children carry kind=cached only "
            "(cardinality bounded by the TenantRegistry)",
            labels=("engine", "kind", "tenant"), unit="blocks")
        self._f_prefix_blocks = g_pfx
        self._g_prefix_cached = g_pfx.labels(kind="cached", tenant="*",
                                             **lbl)
        self._g_prefix_shared = g_pfx.labels(kind="shared", tenant="*",
                                             **lbl)
        self._g_prefix_tenant: Dict[str, object] = {}
        # hierarchical tiering (docs/serving.md "Hierarchical KV-cache
        # tiering"): per-tier residency, demote/promote lifecycle
        # counters and the promotion-latency histogram
        g_tier = obs.gauge(
            "serving_prefix_tier_blocks",
            "prefix-cache residency per tier: device (trie-indexed HBM "
            "blocks) | host (demoted host-RAM payloads)",
            labels=("engine", "tier"), unit="blocks")
        self._g_tier_device = g_tier.labels(tier="device", **lbl)
        self._g_tier_host = g_tier.labels(tier="host", **lbl)
        self._c_demotions = obs.counter(
            "serving_tier_demotions_total",
            "device->host spills (demote-instead-of-free evictions)",
            labels=("engine",)).labels(**lbl)
        pr = obs.counter(
            "serving_tier_promotions_total",
            "host->device promotion attempts by outcome: hit (filled, "
            "digest verified) | timeout (killed/over budget/pool hot — "
            "entry stays resident) | integrity (sha256 mismatch, "
            "dropped) | raced (store evicted first, dropped)",
            labels=("engine", "outcome"))
        self._promotions = {o: pr.labels(outcome=o, **lbl)
                            for o in ("hit", "timeout",
                                      "integrity", "raced")}
        self._promote_hist = obs.histogram(
            "serving_tier_promote_seconds",
            "wall time of one host->device promotion run (all blocks "
            "promoted for one request probe)",
            labels=("engine",), unit="seconds").labels(**lbl)

    # -------------------------------------------------- record helpers
    def observe_ttft(self, dt: float) -> None:
        self._ttft.observe(dt)

    def observe_token_gap(self, dt: float) -> None:
        self._token_gap.observe(dt)

    def observe_latency(self, dt: float) -> None:
        self._latency.observe(dt)

    def observe_step(self, dt: float) -> None:
        self._step.observe(dt)

    def set_step_gauges(self, running: int, waiting: int,
                        blocks_used: int, blocks_free: int,
                        state_slots_used: int = 0,
                        window_blocks_used: int = 0) -> None:
        self._g_running.set(running)
        self._g_waiting.set(waiting)
        self._g_blocks_used.set(blocks_used)
        self._g_blocks_free.set(blocks_free)
        self._g_state_slots_in_use.set(state_slots_used)
        self._g_window_blocks_in_use.set(window_blocks_used)

    @property
    def cache_bytes_per_token(self) -> int:
        return int(self._g_cache_bytes_per_token.value)

    @property
    def cache_physical_bytes_per_token(self) -> int:
        return int(self._g_cache_physical_bytes_per_token.value)

    @property
    def state_bytes_per_seq(self) -> int:
        return int(self._g_state_bytes_per_seq.value)

    @property
    def state_slots_in_use(self) -> int:
        return int(self._g_state_slots_in_use.value)

    @property
    def window_bytes_per_seq(self) -> int:
        return int(self._g_window_bytes_per_seq.value)

    @property
    def window_blocks_in_use(self) -> int:
        return int(self._g_window_blocks_in_use.value)

    def set_cache_bytes_per_token(self, n: int, physical: int,
                                  state: int = 0, window: int = 0) -> None:
        self._g_cache_bytes_per_token.set(n)
        self._g_cache_physical_bytes_per_token.set(physical)
        self._g_state_bytes_per_seq.set(state)
        self._g_window_bytes_per_seq.set(window)

    def set_prefill_spend(self, tokens: int) -> None:
        self._g_prefill_spend.set(tokens)

    def observe_decode_chunk(self, dt: float) -> None:
        self._decode_chunk.observe(dt)

    def inc_prefill_chunks(self, n: int = 1) -> None:
        self._c_prefill_chunks.inc(n)

    def prefill_chunks(self) -> int:
        return int(self._c_prefill_chunks.value)

    def inc_host_sync(self, phase: str) -> None:
        self._syncs[phase].inc()

    def host_syncs(self, phase: str) -> int:
        """Exact sync count (the chunked-decode acceptance test pins
        decode syncs == number of chunks, not tokens)."""
        return int(self._syncs[phase].value)

    def host_syncs_per_token(self) -> float:
        """Decode host syncs / generated tokens (at most 1 / chunk size):
        the quotient of the two counters, taken when asked for."""
        return self.host_syncs("decode") / self.generated_tokens \
            if self.generated_tokens else 0.0

    def note_hold(self, reason: str) -> None:
        self._holds[reason].inc()

    def admission_holds(self, reason: str) -> int:
        return int(self._holds[reason].value)

    def record_prefix(self, ps: dict) -> None:
        """Publish one prefix-cache snapshot (PagedKVCache.prefix_stats)
        — counters advance by delta (they are lifetime-monotone on the
        cache side), gauges overwrite."""
        for k, child in self._prefix_counters.items():
            delta = ps[k] - child.value
            if delta > 0:
                child.inc(delta)
        self._g_prefix_ratio.set(ps["cached_tokens_ratio"])
        self._g_prefix_cached.set(ps["cached_blocks"])
        self._g_prefix_shared.set(ps["shared_blocks"])
        # per-tenant cached-block census (multi-tenant stacks): children
        # are created lazily but never retired — a tenant that drops to
        # zero blocks must REPORT zero, not go silently stale
        tb = ps.get("tenant_blocks") or {}
        for t in tb:
            if t not in self._g_prefix_tenant:
                self._g_prefix_tenant[t] = self._f_prefix_blocks.labels(
                    kind="cached", tenant=t, engine=self.label)
        for t, child in self._g_prefix_tenant.items():
            child.set(tb.get(t, 0))
        delta = ps["tier_demotions"] - self._c_demotions.value
        if delta > 0:
            self._c_demotions.inc(delta)
        for o, child in self._promotions.items():
            delta = ps[f"promote_{o}"] - child.value
            if delta > 0:
                child.inc(delta)
        self._g_tier_device.set(ps["cached_blocks"])
        self._g_tier_host.set(ps["host_blocks"])

    def prefix_counter(self, kind: str) -> int:
        """Exact published counter value (kind='hits'|'misses'|
        'evictions') — tests pin these against the cache's own
        counters."""
        return int(self._prefix_counters[kind].value)

    def prefix_tenant_blocks(self, tenant: str) -> int:
        """Published per-tenant cached-block gauge (reconciliation tests
        pin this against the trie's lifetime counters)."""
        child = self._g_prefix_tenant.get(tenant)
        return int(child.value) if child is not None else 0

    def observe_promote(self, dt: float) -> None:
        self._promote_hist.observe(dt)

    def promote_quantile(self, q: float) -> float:
        """Exact promotion-latency quantile (tiered_prefix reports
        p99 here)."""
        return self._promote_hist.quantile(q)

    def tier_demotions(self) -> int:
        return int(self._c_demotions.value)

    def promotion_counter(self, outcome: str) -> int:
        """Published promotion count for one outcome ('hit'|'timeout'|
        'integrity'|'raced') — tests pin these against the cache."""
        return int(self._promotions[outcome].value)

    def ttft_quantile(self, q: float) -> float:
        """Exact TTFT quantile (bench / load suite read p50/p99 here)."""
        return self._ttft.quantile(q)

    def token_gap_quantile(self, q: float) -> float:
        """Exact inter-token-gap quantile (load suite decode_heavy
        reports p99 here)."""
        return self._token_gap.quantile(q)

    def as_dict(self) -> dict:
        d = {f: getattr(self, f) for f in _STAT_EVENTS}
        for f in _STAT_PHASES:
            d[f] = getattr(self, f)
        for f in _STAT_REQ_SUMS:
            d[f] = getattr(self, f)
        done = max(self.completed, 1)
        d["avg_ttft_s"] = self.ttft_sum / done
        d["avg_request_latency_s"] = self.latency_sum / done
        busy = self.time_prefill + self.time_decode
        d["decode_tokens_per_sec"] = (
            self.generated_tokens / busy if busy > 0 else 0.0)
        d["host_syncs_per_token"] = self.host_syncs_per_token()
        return d


def _stats_property(table: str, f: str, as_int: bool):
    """Generated accessor pair: reads pull the registry child's value,
    writes inc() the monotonic delta — so the historical `stats.x += 1`
    call sites keep working verbatim against counter-backed storage."""

    def _get(self):
        v = getattr(self, table)[f].value
        return int(v) if as_int else v

    def _set(self, new):
        child = getattr(self, table)[f]
        delta = new - child.value
        if delta:
            child.inc(delta)             # counters refuse to go down

    return property(_get, _set)


for _f in _STAT_EVENTS:
    setattr(EngineStats, _f, _stats_property("_events", _f, as_int=True))
for _f in _STAT_PHASES:
    setattr(EngineStats, _f, _stats_property("_phases", _f, as_int=False))
for _f in _STAT_REQ_SUMS:
    setattr(EngineStats, _f, _stats_property("_req_sums", _f,
                                             as_int=False))
del _f


def _live_trips(req, k: int) -> int:
    """Trips of a k-trip chunk in which the row is live: as many as its
    prompt feed and its max_tokens leave it. A row that stops early at EOS
    is counted to its max_tokens: the host cannot know before the fetch."""
    feed = max(0, req.pf_target - req.prefill_pos)
    left = req.params.max_tokens - len(req.output_ids)
    # the trip that eats the last prompt token also samples
    return min(k, max(0, feed - 1) + left)


def _context_tokens(reqs, k: int) -> int:
    """KV positions a k-trip chunk attends to, summed over rows and trips:
    a row whose first reserved position is p reads p + j + 1 at trip j
    (the fused chunk's `att_lens`), for each of its `_live_trips`."""
    total = 0
    for req in reqs:
        trips = _live_trips(req, k)
        total += trips * req.slot[2] + trips * (trips + 1) // 2
    return total


def _live_blocks(reqs, k: int, block_size: int) -> int:
    """Table entries a k-trip chunk attends through, summed over rows and
    trips: cdiv(p + j + 1, block_size) at trip j, the blocks that hold the
    p + j + 1 positions `_context_tokens` counts (what the ragged kernel
    visits of `num_seqs x max_blocks_per_seq x k` entries)."""
    def upto(n):    # sum of cdiv(x, block_size) over x = 1 .. n
        whole, rest = divmod(n, block_size)
        return block_size * whole * (whole + 1) // 2 + rest * (whole + 1)
    return sum(upto(req.slot[2] + _live_trips(req, k)) - upto(req.slot[2])
               for req in reqs)


def _window_context_tokens(reqs, k: int, window: int) -> int:
    """KV positions a k-trip chunk attends to in ONE window layer, summed
    over rows and trips: min(p + j + 1, window) at trip j, where
    `_context_tokens` counts p + j + 1."""
    total = 0
    for req in reqs:
        p, trips = req.slot[2], _live_trips(req, k)
        # trips whose context p + j + 1 is still short of the window
        short = min(trips, max(0, window - p - 1))
        total += short * p + short * (short + 1) // 2 \
            + (trips - short) * window
    return total


def _bucket(n: int, cap: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return min(b, cap)


class LLMEngine:
    """Continuous-batching engine over (params, spec): a parameter dict
    and the `models.spec.ModelSpec` of its family, served paged. `geom`
    may also be the (L, H, D, S) tuple of models.generation, which names
    the GPT-2 spec (`attention.as_spec`). The layout and dtype of the
    cache are the spec's, not the config's.

    Thread contract (checked by ptlint PT-C001 via _GUARDED_BY): the
    fields below are shared between the serving loop (step/run) and
    intake threads (add_request/cancel) and are only touched under
    self._lock. Public entry points take the lock; internal helpers are
    @holds_lock("_lock") — called only from a locked frame. Lock order
    is engine → scheduler (the engine calls scheduler methods while
    locked, never the reverse), so the pair cannot deadlock."""

    _GUARDED_BY = {
        "_requests": "_lock",
        "_rngs": "_lock",
        "_next_id": "_lock",
        "_next_trace": "_lock",
        "_pending_outputs": "_lock",
        "_flights": "_lock",
        "stats": "_lock",
        "_step_start": "_lock",
    }

    def __init__(self, params, geom, config: EngineConfig = None,
                 faults=None):
        config = config or EngineConfig()
        spec = as_spec(geom)
        S = spec.max_seq_len
        if S % config.block_size != 0:
            # divisibility keeps the gathered context bitwise-identical
            # to the dense cache layout (and write_prefill rectangular)
            raise ValueError(
                f"block_size {config.block_size} must divide "
                f"max_seq_len {S}")
        if config.decode_chunk_size < 1:
            raise ValueError(
                f"decode_chunk_size must be >= 1, got "
                f"{config.decode_chunk_size}")
        self.params = params
        self.geom = geom                  # as given: what the programs key on
        self.spec = spec
        self.config = config
        # the stat of `serving.prefill` by which a device trace's reader
        # finds the expert products: (held experts, hidden, expert width)
        # of their weights, "64x2304x896" (the profiler cuts a stat's
        # value at a comma)
        self._moe_shape = {"moe_shape": "x".join(
            map(str, spec.expert_shape))} if spec.expert_shape else {}
        self.max_blocks_per_seq = S // config.block_size
        self.cache = PagedKVCache(
            spec.num_layers, spec.cache_shape, config.num_blocks,
            config.block_size, dtype=jnp.dtype(spec.cache_dtype),
            enable_prefix_cache=config.enable_prefix_cache,
            host_tier_blocks=config.host_tier_blocks,
            promote_timeout_s=config.promote_timeout_s,
            kv_cache_dtype=config.kv_cache_dtype,
            layer_caches=spec.layer_caches, state_shapes=spec.state_shapes,
            # a sequence that holds cache is a running one
            num_state_slots=config.max_num_seqs, window=spec.window,
            # and holds at most a window and a chunk's look-ahead of them
            num_window_blocks=config.max_num_seqs * window_blocks_per_seq(
                spec.window, config.block_size, config.decode_chunk_size))
        cost_model = config.prefill_cost_model
        if cost_model == "auto":
            # committed-plan admission pricing; a repo without a plan
            # file degrades to the flat token budget
            from ...analysis import jaxplan
            cost_model = jaxplan.default_admission_model()
        self.scheduler = Scheduler(
            SchedulerConfig(
                max_num_seqs=config.max_num_seqs,
                max_prefill_tokens=config.max_prefill_tokens,
                decode_chunk_size=config.decode_chunk_size,
                max_waiting=config.max_waiting,
                admission_policy=config.admission_policy,
                cache_high_watermark=config.cache_high_watermark,
                prefill_cost_model=cost_model,
                prefill_chunk_threshold=config.prefill_chunk_threshold,
                tenants=config.tenants),
            self.cache)
        # RLock: step() holds it across the whole iteration and the
        # helpers it calls re-enter (e.g. _emit under _recover)
        self._lock = threading.RLock()
        self.stats = EngineStats(config.obs_label)
        self.stats.set_cache_bytes_per_token(
            spec.cache_bytes_per_token, self.cache.physical_bytes_per_token,
            self.cache.state_bytes_per_seq, self.cache.window_bytes_per_seq)
        # (model, revision) event tag (serving/deploy.py): emission and
        # terminal events carry the serving revision so the causality
        # checker can prove no token was emitted by a revision other
        # than the one the request was admitted under (invariant 8).
        # Default-keyed engines stay untagged — pre-deploy dump schema.
        self._rev_tag: Optional[Dict[str, str]] = None
        if (config.model, config.revision) != ("default", "r0"):
            self._rev_tag = {"model": config.model,
                             "revision": config.revision}
        self._requests: Dict[str, Request] = {}
        self._rngs: Dict[str, np.random.RandomState] = {}
        self._next_id = 0
        self._next_trace = 0
        self._pending_outputs: List[RequestOutput] = []
        self._flights: List[tuple] = []   # deferred flight-recorder dumps
        self._step_start = 0.0
        if faults is None:
            # env-driven (PADDLE_TPU_SERVE_FAULTS), inert without a spec
            # — same unconditional-call contract as training's
            # FaultInjector. Lazy import: testing pulls the op harness.
            from ...testing.faults import ServingFaultInjector
            faults = ServingFaultInjector()
        self.faults = faults
        # ptlint: disable=PT-C004  fault injector (see step())
        self.cache.arm_tier_faults(self.faults, 0)

    @classmethod
    def from_model(cls, model, config: EngineConfig = None, faults=None):
        """Serve `model`: the family says how (`model.serving_spec()`), its
        named parameters are the programs' `params`."""
        return cls(gen.extract_params(model), model.serving_spec(), config,
                   faults=faults)

    # ------------------------------------------------------------ intake
    def add_request(self, prompt_ids, sampling: SamplingParams = None,
                    request_id: str = None, arrival_time: float = None,
                    arrival: int = None, resume_tokens=None,
                    readmit: bool = False,
                    trace_id: str = None) -> str:
        """Queue one request. Raises EngineOverloaded when the bounded
        waiting queue is full under admission_policy='reject'; under
        'shed_oldest' the oldest waiting request is evicted instead
        (terminal RequestOutput with finish_reason='shed', streamed from
        the next step()).

        The keyword extensions are the replica-failover re-admission
        surface (router.py; docs/serving.md "Multi-replica serving"):
        `arrival_time`/`arrival` carry the request's ORIGINAL wall-clock
        arrival and FCFS ticket across engines — deadline_s/queue_ttl_s
        stay measured from the original arrival (a re-admitted request
        that already blew its deadline finishes as 'timeout', never as a
        silent retry), and the requeue keeps its original place in line.
        `resume_tokens` seeds the output log with the tokens the failed
        replica already streamed, so re-prefill continues the SAME token
        stream (sampling keys depend only on request progress) and
        max_tokens accounting never restarts. `readmit=True` inserts
        arrival-ordered and bypasses the max_waiting bound (backpressure
        applies to new arrivals, not to recovered in-flight work).
        `trace_id` is the per-request causal-trace id (obs/reqtrace.py);
        the router mints one and passes it through dispatch so a
        failover hop stays ONE timeline — a standalone engine mints its
        own (`tr-<engine-label>-N`)."""
        sampling = sampling or SamplingParams()
        ids = np.asarray(prompt_ids, np.int32).reshape(-1)
        if ids.size == 0:
            raise ValueError("empty prompt")
        S = self.spec.max_seq_len
        if ids.size + sampling.max_tokens > S:
            raise ValueError(
                f"prompt {ids.size} + max_tokens {sampling.max_tokens} "
                f"exceeds max_seq_len {S}")
        tenants = self.config.tenants
        if tenants is not None:
            # unknown tenant ids are caller bugs, refused loudly before
            # any engine state is touched
            tenants.resolve(sampling.tenant)
        with self._lock, contextlib.ExitStack() as scope:
            if request_id is None:
                request_id = f"req-{self._next_id}"
                self._next_id += 1
            # entered here and not round the lock: the id is minted above
            scope.enter_context(RecordEvent(
                "serving.add_request", cat="serving",
                args={"request_id": request_id,
                      "prompt_tokens": int(ids.size)}))
            old = self._requests.get(request_id)
            if old is not None and old.state != RequestState.MIGRATED:
                # a migrated-out tombstone does NOT block re-admission:
                # a request can legitimately come back to an engine it
                # once left (failover after its new home died, drain
                # round trip) — only a live or truly-terminal record is
                # a duplicate
                raise ValueError(f"duplicate request_id {request_id!r}")
            now = time.perf_counter()
            req = Request(request_id=request_id, prompt_ids=ids,
                          params=sampling,
                          arrival_time=now if arrival_time is None
                          else arrival_time)
            if arrival is not None:
                req.arrival = arrival
            if trace_id is None:
                trace_id = f"tr-{self.stats.label}-{self._next_trace}"
                self._next_trace += 1
            req.trace_id = trace_id
            if tenants is not None:
                # bind the tenant to the trace so EVERY subsequent event
                # on this timeline auto-carries the tag (ring-level map;
                # single-tenant stacks without a registry stay untagged)
                obs.reqtrace.bind_tenant(req.tid, sampling.tenant)
            if resume_tokens is not None and len(resume_tokens):
                req.output_ids = [int(t) for t in resume_tokens]
                # TTFT was already observed on the replica that emitted
                # the first token; the re-admitting engine records only
                # token gaps (from now) for the resumed stream
                req.first_token_time = req.arrival_time
                req.last_token_time = now
            charged = 0
            if tenants is not None and not readmit:
                # sliding-window token quota, charged for the WORST CASE
                # (prompt + max_tokens) before any engine state commits;
                # readmissions never re-charge — failover must not burn
                # quota twice for one request
                try:
                    # ptlint: disable=PT-C004  TenantRegistry sits
                    # BELOW LLMEngine in lockgraph.json; charge() takes
                    # only the registry lock, no re-entry
                    tenants.charge(sampling.tenant,
                                   ids.size + sampling.max_tokens,
                                   model=self.config.model)
                except EngineOverloaded as e:
                    self.stats.rejected += 1
                    obs.reqtrace.record(
                        "rejected", req.tid, request_id, reason="quota",
                        tenant=sampling.tenant, spent=e.depth,
                        quota=e.limit, retry_after_s=e.retry_after_s)
                    e.request_id = request_id
                    raise
                charged = ids.size + sampling.max_tokens
            try:
                if readmit:
                    self.scheduler.readmit(req)
                    shed = []
                else:
                    shed = self.scheduler.add(req)  # validates pool fit
            except EngineOverloaded:
                if charged:
                    # ptlint: disable=PT-C004  registry call below the
                    # engine lock in lockgraph.json (see charge above)
                    tenants.refund(sampling.tenant, charged,
                                   model=self.config.model)
                self.stats.rejected += 1
                raise
            except ValueError:
                if charged:
                    # ptlint: disable=PT-C004  same as refund above
                    tenants.refund(sampling.tenant, charged,
                                   model=self.config.model)
                raise
            for victim in shed:
                victim.finish_time = time.perf_counter()
                self.stats.shed += 1
                self._pending_outputs.append(RequestOutput(
                    victim.request_id, None, list(victim.output_ids),
                    True, "shed"))
                obs.reqtrace.record("finish", victim.tid,
                                    victim.request_id, reason="shed",
                                    **(self._rev_tag or {}))
            self._requests[request_id] = req
            self._rngs[request_id] = np.random.RandomState(
                sampling.seed & 0x7FFFFFFF)
            obs.reqtrace.record(
                "engine_admit", req.tid, request_id,
                engine=self.stats.label, arrival=req.arrival,
                readmit=bool(readmit), resume=len(req.output_ids),
                waiting=self.scheduler.num_waiting(),
                **(self._rev_tag or {}))
            if self.cache.host_tier is not None:
                # enqueue-time prefetch: promote the host-resident
                # prefix while the request queues, overlapping the fill
                # with queue wait instead of serialising it into the
                # admission step
                self._prefetch_promote(req)
            return request_id

    @holds_lock("_lock")
    def _prefetch_promote(self, req: Request) -> None:
        """Asynchronous-in-spirit host→device prefetch at enqueue (the
        scheduler's admission probe is the retry for anything this run
        leaves behind). Never raises: ensure_promoted degrades every
        failure to re-prefill of the missing suffix."""
        tokens = req.all_token_ids()
        host = self.cache.host_match_len(tokens)
        if not host:
            return
        cached = self.cache.match_len(tokens)
        obs.reqtrace.record("prefix_match", req.tid, req.request_id,
                            cached_tokens=cached, host_tokens=host,
                            probe=cached)
        with RecordEvent("serving.promote", cat="promote") as ev:
            promo = self.cache.ensure_promoted(tokens)
            ev.args = {"request_id": req.request_id,
                       "host_tokens": host,
                       "promoted": 0 if promo is None
                       else promo["promoted_blocks"],
                       "outcomes": [] if promo is None
                       else promo["outcomes"]}
        record_promotion_events(req.tid, req.request_id, promo)

    def cancel(self, request_id: str) -> bool:
        with self._lock:
            ok = self.scheduler.cancel(request_id)
            if ok:
                self.stats.cancelled += 1
                req = self._requests[request_id]
                req.finish_time = time.perf_counter()
                self._pending_outputs.append(RequestOutput(
                    request_id, None, list(req.output_ids), True,
                    "cancelled"))
                obs.reqtrace.record("finish", req.tid, request_id,
                                    reason="cancelled")
            return ok

    def has_unfinished(self) -> bool:
        return self.scheduler.has_unfinished()

    def get_request(self, request_id: str) -> Request:
        with self._lock:
            return self._requests[request_id]

    # ----------------------------------------------- router-facing surface
    def shed_oldest_waiting(self) -> Optional[str]:
        """Evict this engine's oldest waiting request (the router's
        cross-replica 'shed_oldest' acts on whichever replica holds the
        globally-oldest waiting request). Streams the terminal 'shed'
        output from the next step(); returns the shed request_id or
        None when nothing waits."""
        with self._lock:
            victim = self.scheduler.shed_oldest()
            if victim is None:
                return None
            victim.finish_time = time.perf_counter()
            self.stats.shed += 1
            self._pending_outputs.append(RequestOutput(
                victim.request_id, None, list(victim.output_ids),
                True, "shed"))
            obs.reqtrace.record("finish", victim.tid, victim.request_id,
                                reason="shed")
            return victim.request_id

    def oldest_waiting_arrival(self) -> Optional[int]:
        return self.scheduler.oldest_waiting_arrival()

    def load_info(self) -> dict:
        """Host-side load snapshot the ReplicaSet balances on:
        free_blocks MINUS the engine's outstanding block demand is the
        effective headroom, prefill_cost prices the queued re-prefills
        with the committed cost model (docs/serving.md "Multi-replica
        serving")."""
        with self._lock:
            info = self.scheduler.backlog()
            info["free_blocks"] = self.cache.num_free()
            info["running"] = self.scheduler.num_running()
            return info

    def waiting_by_tenant(self) -> dict:
        """Per-tenant queue depth (autoscaler pressure signal)."""
        return self.scheduler.waiting_by_tenant()

    # ------------------------------------------- block migration surface
    # (serving/migration.py; docs/serving.md "Disaggregated serving and
    # block migration"). All four methods run at step boundaries only —
    # the BlockMigration coordinator calls them through the owning
    # replica's lock from the router's step frame, where the per-request
    # invariant holds that every reserved cache slot has written KV.

    def migratable_requests(self, decode_only: bool = True) -> List[str]:
        """Request ids safe to export at this step boundary: RUNNING and
        unfinished. `decode_only=True` (handoff/rebalance) keeps only
        requests PAST prefill — the prefill→decode handoff point;
        `decode_only=False` (drain) also includes mid-prefill rows,
        whose committed prefix migrates and finishes prefilling at the
        destination."""
        with self._lock:
            out = []
            for req in self.scheduler.running_requests():
                if req.finished:
                    continue
                if decode_only and req.pf_target \
                        and req.prefill_pos < req.pf_target:
                    continue
                out.append(req.request_id)
            return out

    def export_request(self, request_id: str) -> dict:
        """Snapshot one RUNNING request for migration: the full request
        record (prompt, params, token log, FCFS ticket, deadline clock,
        prefill progress, trace id) plus its KV payload gathered from
        the pool (PagedKVCache.export_blocks — a COPY; source state is
        untouched, so a failed migration just keeps running here).
        Sampling needs no extra state: in-scan keys are
        fold_in(seed, tokens_generated), a function of progress the
        snapshot already carries."""
        with self._lock:
            req = self._requests[request_id]
            if req.state != RequestState.RUNNING:
                raise ValueError(
                    f"export_request: {request_id!r} is {req.state}, "
                    f"not running")
            payload, num_tokens = self.cache.export_blocks(request_id)
            if req.pf_target and req.prefill_pos < req.pf_target:
                valid = req.prefill_pos
            else:
                valid = len(req.prompt_ids) \
                    + max(0, len(req.output_ids) - 1)
            if num_tokens != valid:
                # only clean step boundaries satisfy written-KV == length
                raise ValueError(
                    f"export_request: {request_id!r} cache length "
                    f"{num_tokens} != written KV {valid} — not at a "
                    f"clean step boundary")
            return {
                "request_id": request_id,
                # (model, revision) key: the destination's
                # admit_migrated refuses a payload keyed for different
                # weights — KV is only valid under the parameters that
                # wrote it, so it must never cross a rollout boundary
                "model": self.config.model,
                "revision": self.config.revision,
                "prompt_ids": np.array(req.prompt_ids, np.int32),
                "params": req.params,
                "arrival": req.arrival,
                "arrival_time": req.arrival_time,
                "first_token_time": req.first_token_time,
                "last_token_time": req.last_token_time,
                "output_ids": list(req.output_ids),
                "pf_target": req.pf_target,
                "prefill_pos": req.prefill_pos,
                "trace_id": req.trace_id,
                "payload": payload,
                "num_tokens": num_tokens,
                "blocks": len(self.cache.block_table(request_id)),
                "bytes": self.cache.payload_bytes(payload),
            }

    def admit_migrated(self, snap: dict) -> str:
        """Destination half of a migration: import the KV payload into
        fresh private blocks, register its clean prefix into this
        engine's trie (hit rates survive the hop), and adopt the
        request straight into the RUNNING set — no re-prefill, no
        waiting-queue pass, FCFS ticket and deadline clock preserved.
        Raises CacheExhausted with NO side effects when the pool can't
        hold the table (the coordinator aborts; the request keeps
        running at the source)."""
        rid = snap["request_id"]
        key = (snap.get("model", self.config.model),
               snap.get("revision", self.config.revision))
        if key != (self.config.model, self.config.revision):
            # cross-revision refusal (serving/deploy.py): KV written by
            # other weights is garbage under these — raised BEFORE any
            # state is touched, so the coordinator aborts cleanly and
            # the request keeps running at its source
            raise ValueError(
                f"admit_migrated: {rid!r} payload is keyed "
                f"{key} but this engine serves "
                f"{(self.config.model, self.config.revision)} — "
                f"cross-revision KV refused")
        with self._lock:
            old = self._requests.get(rid)
            if old is not None and not old.finished:
                raise ValueError(
                    f"admit_migrated: {rid!r} already live here")
            req = Request(request_id=rid,
                          prompt_ids=snap["prompt_ids"],
                          params=snap["params"],
                          arrival_time=snap["arrival_time"])
            req.arrival = snap["arrival"]
            req.trace_id = snap["trace_id"]
            req.output_ids = list(snap["output_ids"])
            req.pf_target = snap["pf_target"]
            req.prefill_pos = snap["prefill_pos"]
            # TTFT was observed (once) wherever the first token was
            # emitted; preserving the stamps keeps the gap histograms
            # honest — the next emission's gap includes migration time
            req.first_token_time = snap["first_token_time"]
            req.last_token_time = snap["last_token_time"]
            worst = len(req.prompt_ids) + req.params.max_tokens
            if self.cache.blocks_needed(worst) > self.cache.num_blocks:
                raise ValueError(
                    f"admit_migrated: {rid!r} can never fit this pool "
                    f"({self.cache.blocks_needed(worst)} blocks at its "
                    f"longest vs {self.cache.num_blocks} total)")
            # the decode packing is a FIXED max_num_seqs rows — adopting
            # past it would index off the end of the batch, so sequence
            # slots exhaust with the same clean-abort signal as blocks
            live = sum(1 for r in self.scheduler.running
                       if not r.finished)
            if live >= self.config.max_num_seqs:
                raise CacheExhausted(rid, 1, 0,
                                     self.config.max_num_seqs,
                                     what="sequence slot")
            self.cache.import_blocks(rid, snap["payload"],
                                     snap["num_tokens"])
            self.scheduler.adopt_running(req)
            if self.cache.prefix_index is not None \
                    and snap["num_tokens"]:
                self.cache.register_prefix(
                    rid, req.all_token_ids()[:snap["num_tokens"]])
            self._requests[rid] = req
            self._rngs[rid] = np.random.RandomState(
                req.params.seed & 0x7FFFFFFF)
            return self.stats.label

    def release_migrated(self, request_id: str) -> None:
        """Source half, called only AFTER the destination committed:
        detach the request (state MIGRATED — terminal for this engine,
        no finish output) and free its blocks through the normal
        completion path, registering the clean prefix so the SOURCE
        trie keeps its entries and shared blocks just drop one
        reference."""
        with self._lock:
            req = self._requests[request_id]
            if req.state != RequestState.RUNNING:
                raise ValueError(
                    f"release_migrated: {request_id!r} is {req.state}, "
                    f"not running")
            self.scheduler.release_running(req)
            self._rngs.pop(request_id, None)

    def abort_migrated(self, request_id: str) -> None:
        """Destination rollback for a migration that failed AFTER
        admit_migrated (source died before releasing): drop the adopted
        request and free its imported blocks. The router re-admits the
        victim from its authoritative token log via the failover path —
        zero blocks leak on either end."""
        with self._lock:
            req = self._requests.pop(request_id, None)
            self._rngs.pop(request_id, None)
            if req is not None and req.state == RequestState.RUNNING:
                self.scheduler.abort_adopted(req)

    def release_waiting(self, request_id: str) -> Optional[Request]:
        """Drain evacuation of QUEUED work: pull a waiting request out
        without a terminal output (it has no KV to migrate — the router
        re-dispatches it to another replica from its token log).
        Returns the request, or None when it is not waiting."""
        with self._lock:
            req = self.scheduler.remove_waiting(request_id)
            if req is not None:
                self._rngs.pop(request_id, None)
            return req

    # --------------------------------------------- peer prefix fetch
    # (docs/serving.md "Hierarchical KV-cache tiering": a replica
    # missing a prefix pulls its blocks from a peer that holds them —
    # a BlockMigration-shaped transactional pull — before falling back
    # to re-prefill, so prefix-affinity routing degrades gracefully
    # after rebalance/failover instead of cliff-ing into cold caches.)

    def prefix_probe(self, prompt_ids) -> int:
        """Leading tokens of `prompt_ids` this engine could serve from
        its prefix cache, across BOTH tiers (device match + promotable
        host run) — the router compares probes to pick the donor."""
        with self._lock:
            toks = np.asarray(prompt_ids, np.int32).reshape(-1)
            return self.cache.match_len(toks) \
                + self.cache.host_match_len(toks)

    def export_prefix(self, prompt_ids) -> Optional[dict]:
        """Donor half of a peer prefix fetch: snapshot the longest
        cached full-block prefix of `prompt_ids` (both tiers, digests
        included), keyed by this engine's (model, revision). Read-only;
        None when nothing matches."""
        with self._lock:
            snap = self.cache.export_prefix(
                np.asarray(prompt_ids, np.int32).reshape(-1))
            if snap is not None:
                snap["model"] = self.config.model
                snap["revision"] = self.config.revision
            return snap

    def admit_prefix(self, prompt_ids, blocks, model: str = None,
                     revision: str = None) -> int:
        """Receiver half: verify and install a peer's prefix snapshot
        as locally cached (evictable) blocks. Raises ValueError on an
        integrity mismatch OR on a payload keyed for a different
        (model, revision) — stale prefix KV must never serve another
        revision's requests — and CacheExhausted when the pool cannot
        hold it; all with atomic-abort semantics (nothing mutated)."""
        key = (self.config.model if model is None else model,
               self.config.revision if revision is None else revision)
        if key != (self.config.model, self.config.revision):
            raise ValueError(
                f"admit_prefix: payload keyed {key} but this engine "
                f"serves {(self.config.model, self.config.revision)} — "
                f"cross-revision prefix refused")
        with self._lock:
            return self.cache.admit_prefix(
                np.asarray(prompt_ids, np.int32).reshape(-1), blocks)

    # ---------------------------------------------------------- sampling
    @holds_lock("_lock")
    def _sample(self, req: Request, logits: np.ndarray) -> int:
        p = req.params
        if p.temperature <= 0.0:
            return int(np.argmax(logits))
        lg = logits.astype(np.float64) / p.temperature
        if p.top_k:
            kth = np.sort(lg)[-p.top_k]
            lg = np.where(lg < kth, -np.inf, lg)
        if 0.0 < p.top_p < 1.0:
            srt = np.sort(lg)[::-1]
            probs = np.exp(srt - srt.max())
            probs /= probs.sum()
            excl = np.cumsum(probs) - probs
            kth = srt[int((excl < p.top_p).sum()) - 1]
            lg = np.where(lg < kth, -np.inf, lg)
        probs = np.exp(lg - lg.max())
        probs /= probs.sum()
        return int(self._rngs[req.request_id].choice(len(probs), p=probs))

    @holds_lock("_lock")
    def _emit(self, req: Request, tok: int, outs: List[RequestOutput]):
        """Record one sampled token, handle completion, stream it out."""
        now = time.perf_counter()
        if req.first_token_time is None:
            req.first_token_time = now
            # TTFT is recorded HERE, exactly once per request at its
            # first token (tests/test_observability.py pins once-ness);
            # ttft_sum below stays the completed-only accumulator
            self.stats.observe_ttft(now - req.arrival_time)
            obs.reqtrace.record("first_token", req.tid, req.request_id,
                                ttft_s=now - req.arrival_time,
                                **(self._rev_tag or {}))
        else:
            # per-token latency: gap since this request's previous token
            self.stats.observe_token_gap(now - req.last_token_time)
        req.last_token_time = now
        req.output_ids.append(tok)
        self.stats.generated_tokens += 1
        finished, reason = False, None
        if req.params.eos_token_id is not None \
                and tok == req.params.eos_token_id:
            finished, reason = True, "stop"
            state = RequestState.FINISHED_STOPPED
        elif len(req.output_ids) >= req.params.max_tokens:
            finished, reason = True, "length"
            state = RequestState.FINISHED_LENGTH
        if finished:
            self.scheduler.finish(req, state)
            req.finish_time = now
            self.stats.completed += 1
            self.stats.ttft_sum += req.first_token_time - req.arrival_time
            self.stats.latency_sum += now - req.arrival_time
            self.stats.observe_latency(now - req.arrival_time)
            obs.reqtrace.record("finish", req.tid, req.request_id,
                                reason=reason,
                                tokens=len(req.output_ids),
                                **(self._rev_tag or {}))
        outs.append(RequestOutput(req.request_id, tok,
                                  list(req.output_ids), finished, reason))

    # --------------------------------------------- robustness primitives
    def _finish_abnormal(self, req: Request, state: str, reason: str,
                         outs: List[RequestOutput], scrub: bool = False):
        """Terminal path for timeout/shed/error: detach (freeing blocks
        iff running), stamp, stream the terminal RequestOutput."""
        if req.state == RequestState.RUNNING:
            self.scheduler.finish(req, state, scrub=scrub)
        else:
            req.state = state
        req.finish_time = time.perf_counter()
        outs.append(RequestOutput(req.request_id, None,
                                  list(req.output_ids), True, reason))
        obs.reqtrace.record("finish", req.tid, req.request_id,
                            reason=reason, tokens=len(req.output_ids),
                            **(self._rev_tag or {}))

    @holds_lock("_lock")
    def _expire_and_abort(self, outs: List[RequestOutput]):
        """Step-boundary deadline enforcement: expire queued requests
        past queue_ttl_s/deadline_s, abort running ones past
        deadline_s."""
        now = time.perf_counter()
        for req in self.scheduler.expire_waiting(now):
            self.stats.expired += 1
            req.finish_time = now
            outs.append(RequestOutput(req.request_id, None,
                                      list(req.output_ids), True,
                                      "timeout"))
            obs.reqtrace.record("finish", req.tid, req.request_id,
                                reason="timeout",
                                **(self._rev_tag or {}))
        for req in self.scheduler.overdue_running(now):
            self.stats.timeouts += 1
            self._finish_abnormal(req, RequestState.FINISHED_TIMEOUT,
                                  "timeout", outs)

    @holds_lock("_lock")
    def _wedged(self) -> bool:
        """Watchdog check at phase boundaries: has this step overrun its
        step_timeout_s budget? (A hard device hang blocks Python
        entirely — that is what the elastic supervisor's heartbeat
        catches; this watchdog handles the soft case where a phase
        returns but has already blown the step's latency budget.)"""
        t = self.config.step_timeout_s
        return t is not None and \
            (time.perf_counter() - self._step_start) > t

    @holds_lock("_lock")
    def _quarantine(self, req: Request, outs: List[RequestOutput],
                    why: str):
        """One poisoned/wedged request costs one request: error-terminal,
        blocks scrubbed (NaN survives the attention mask) and freed."""
        self.stats.errors += 1
        obs.reqtrace.record("quarantine", req.tid, req.request_id,
                            why=why, engine=self.stats.label)
        self._finish_abnormal(req, RequestState.FINISHED_ERROR, "error",
                              outs, scrub=True)
        # flight recorder: a quarantine is a postmortem trigger — when
        # armed, ship the victim's full timeline + registry snapshot.
        # The dump is file I/O, so it is only QUEUED here; step() writes
        # it after the engine lock is released (PT-C003) — a slow disk
        # must not stall intake threads mid-step.
        self._flights.append((
            "quarantine", [req.tid],
            {"why": why, "engine": self.stats.label,
             "request_id": req.request_id}))

    @holds_lock("_lock")
    def _recover(self, decode: List[Request], offenders: List[Request],
                 outs: List[RequestOutput], why: str):
        """Crash recovery for a poisoned/wedged decode step: the step's
        outputs are already discarded (nothing was emitted); quarantine
        the offenders and rebuild every surviving decode request by
        scrub-freeing its blocks and requeueing it (arrival-ordered) for
        re-prefill from its token log — proven bitwise-equivalent to an
        unfaulted run for the survivors (tests/test_serving_robustness)."""
        self.stats.recoveries += 1
        for req in offenders:
            self._quarantine(req, outs, why)
        survivors = [r for r in decode if r not in offenders]
        for req in survivors:
            self.scheduler.requeue_for_recovery(req)
            self.stats.rebuilt += 1

    # -------------------------------------------------------------- step
    def step(self) -> List[RequestOutput]:
        """One engine iteration: expire/abort overdue requests, schedule,
        prefill admitted requests, decode every running sequence, stream
        the new tokens — under the anomaly guard + watchdog (module
        docstring)."""
        from ...distributed import elastic
        elastic.heartbeat()                  # no-op when unsupervised
        with self._lock:
            outs = self._step_locked()
            flights, self._flights = self._flights, []
        # flight-recorder dumps queued by _quarantine are written here,
        # AFTER the engine lock is released (PT-C003). In fleet mode
        # this still rides under the owning replica's lock — that lock
        # is per-replica, so the blast radius of slow disk I/O is one
        # replica, not the router or its siblings.
        for reason, ids, extra in flights:
            obs.reqtrace.maybe_flight(reason, ids, extra=extra)
        return outs

    @holds_lock("_lock")
    def _step_locked(self) -> List[RequestOutput]:
        outs: List[RequestOutput] = list(self._pending_outputs)
        self._pending_outputs.clear()
        self.stats.steps += 1
        step_no = self.stats.steps
        self._step_start = time.perf_counter()
        with RecordEvent("serving.engine_step", cat="serving",
                         args={"step": step_no}):
            # ptlint: disable=PT-C004  fault injector: inert no-op in
            # production (env-gated); chaos tests NEED it inside the lock
            # to corrupt state at the exact point a real fault would
            self.faults.corrupt_cache(step_no, self.cache)
            # ptlint: disable=PT-C004  fault injector (see above)
            self.faults.corrupt_host_block(step_no, self.cache)
            # re-arm the cache's demote/promote fault hooks at this
            # step so kill_promotion/kill_demotion specs fire on the
            # engine-step clock like every other serving fault
            # ptlint: disable=PT-C004  fault injector (see above)
            self.cache.arm_tier_faults(self.faults, step_no)
            self._expire_and_abort(outs)
            t0 = time.perf_counter()
            with RecordEvent("serving.schedule", cat="schedule") as ev:
                batch = self.scheduler.schedule()
                ev.set_stats(
                    prefill=len(batch.prefill),
                    prefill_tokens=sum(
                        len(r.prompt_ids) + len(r.output_ids)
                        for r in batch.prefill),
                    chunked=batch.chunked, decode=len(batch.decode),
                    waiting=self.scheduler.num_waiting(),
                    preempted=len(batch.preempted),
                    free_blocks=self.cache.num_free(),
                    held_by=batch.held_by)
            self.stats.note_hold(batch.held_by)
            self.stats.preemptions += len(batch.preempted)
            self.stats.time_schedule += time.perf_counter() - t0

            prefill_spend = 0
            for req in batch.prefill:
                t0 = time.perf_counter()
                tokens = req.all_token_ids()
                with RecordEvent("serving.prefill", cat="prefill",
                                 args={"request_id": req.request_id,
                                       "tokens": int(tokens.size),
                                       **self._moe_shape}) as ev:
                    try:
                        logits, counts = self._prefill(req, tokens)
                    except Exception as e:
                        self._quarantine(req, outs, f"prefill raised: {e}")
                        continue
                    ev.set_stats(**counts)
                    self.stats.prefill_tokens += int(tokens.size)
                    prefill_spend += int(tokens.size)
                    self.stats.time_prefill += time.perf_counter() - t0
                    # ptlint: disable=PT-C004  fault injector (see step())
                    logits = self.faults.poison_logits(step_no, logits)
                    # logits are already host numpy (_prefill fetched
                    # them); the host-side check avoids re-uploading them
                    # through a jnp reduction every step (ptlint PT-T002's
                    # defect class: a device round-trip per prefill)
                    if anomaly.any_not_finite_host(logits):
                        self._quarantine(req, outs,
                                         "non-finite prefill logits")
                        continue
                    obs.reqtrace.record("prefill", req.tid, req.request_id,
                                        tokens=int(tokens.size))
                    with RecordEvent("serving.prefill.sample",
                                     cat="prefill"):
                        self._emit(req, self._sample(req, logits), outs)
                    if not req.finished and self._wedged():
                        # prefill attribution is exact: the request whose
                        # forward blew the budget is the one in hand
                        self.stats.watchdog_trips += 1
                        self._quarantine(req, outs, "wedged prefill")

            # requests finished right at prefill release their blocks
            # before the decode gather builds its tables
            decode = [r for r in batch.decode if not r.finished]
            if decode:
                t0 = time.perf_counter()
                k = self.config.decode_chunk_size
                context = _context_tokens(decode, k)
                row_trips = sum(_live_trips(r, k) for r in decode)
                blocks = _live_blocks(decode, k, self.config.block_size)
                self.stats.context_tokens += context
                self.stats.live_row_trips += row_trips
                self.stats.live_blocks += blocks
                in_window = {}
                if self.spec.window:
                    in_window["window_context_tokens"] = \
                        _window_context_tokens(decode, k, self.spec.window)
                    self.stats.window_context_tokens += \
                        in_window["window_context_tokens"]
                sampled_rows = sum(1 for r in decode
                                   if r.params.temperature > 0)
                if sampled_rows:
                    self.stats.sampled_chunks += 1
                with RecordEvent("serving.decode", cat="decode", args={
                        "num_seqs": len(decode), "chunk": k,
                        "context_tokens": context,
                        "live_row_trips": row_trips, "live_blocks": blocks,
                        "sampled_rows": sampled_rows, **in_window}) as ev:
                    # ptlint: disable=PT-C004  fault injector: stalls ON
                    # PURPOSE under the lock to exercise the watchdog
                    self.faults.stall(step_no)
                    try:
                        toks, bad, counts = self._decode_chunk(decode, k)
                        ev.set_stats(**counts)
                    except Exception as e:
                        toks = None
                        self._recover(decode, [decode[0]], outs,
                                      f"decode raised: {e}")
                    dt = time.perf_counter() - t0
                    self.stats.time_decode += dt
                    self.stats.observe_decode_chunk(dt)
                    if toks is not None:
                        self._drain_chunk(step_no, decode, toks, bad, outs)
                        if self.spec.window:
                            ev.set_stats(
                                window_blocks_freed=self._release_windows(
                                    decode))
        # per-step telemetry: all host values already in hand (scheduler
        # counters, cache free lists) — recording adds no device work
        step_dt = time.perf_counter() - self._step_start
        self.stats.observe_step(step_dt)
        # feed the measured service rate to the scheduler's deadline
        # early-reject estimator (inert without a tenant registry)
        self.scheduler.note_step_seconds(step_dt)
        self.stats.set_prefill_spend(prefill_spend)
        self.stats.set_step_gauges(
            running=self.scheduler.num_running(),
            waiting=self.scheduler.num_waiting(),
            blocks_used=self.cache.num_used(),
            blocks_free=self.cache.num_free(),
            state_slots_used=self.cache.num_state_slots_used(),
            window_blocks_used=self.cache.num_window_used())
        if self.cache.prefix_index is not None:
            self.stats.record_prefix(self.cache.prefix_stats())
            for dt in self.cache.drain_promote_seconds():
                self.stats.observe_promote(dt)
        return outs

    @holds_lock("_lock")
    def _drain_chunk(self, step_no: int, decode: List[Request], toks, bad,
                     outs: List[RequestOutput]) -> None:
        """What the step does with a fetched chunk: discard it and recover
        if a row went bad or the watchdog tripped, else emit its tokens."""
        # the not-finite flags were computed IN-SCAN and
        # arrived with the chunk fetch — anomaly attribution
        # costs no extra sync (and no host re-reduction)
        # ptlint: disable=PT-C004  fault injector (see step())
        bad = self.faults.poison_chunk(step_no, bad)
        if bad.any():
            # a bad row poisons the whole chunk: every
            # emission is discarded, offenders quarantined,
            # survivors requeued — replay is bitwise because
            # sampling keys depend only on request progress
            self._recover(
                decode,
                [r for i, r in enumerate(decode) if bad[i]],
                outs, "non-finite decode logits in chunk")
        elif self._wedged():
            # a wedged batched chunk cannot be attributed;
            # quarantine its head (deterministic) and rebuild
            # the rest — the whole chunk's tokens are dropped
            # so survivors stay bitwise on the replay
            self.stats.watchdog_trips += 1
            self._recover(decode, [decode[0]], outs,
                          "wedged decode chunk (watchdog)")
        else:
            # step-major drain of the fetched chunk: row j of
            # toks is scan step j; -1 marks a frozen row.
            # _emit re-derives eos/max_tokens terminals on
            # host — the same conditions the device froze on
            # — so telemetry and finish_reason stay exact.
            emitted: Dict[str, int] = {}
            with RecordEvent("serving.decode.drain", cat="decode"):
                for j in range(toks.shape[0]):
                    for i, req in enumerate(decode):
                        t = int(toks[j, i])
                        if t >= 0 and not req.finished:
                            self._emit(req, t, outs)
                            emitted[req.request_id] = \
                                emitted.get(req.request_id, 0) + 1
                # chunk-boundary trace events: tokens emitted
                # per row + the finish latch (host values only)
                for req in decode:
                    n_emit = emitted.get(req.request_id, 0)
                    if n_emit:
                        obs.reqtrace.record(
                            "decode_chunk", req.tid,
                            req.request_id, n=n_emit,
                            total=len(req.output_ids),
                            finished=req.finished,
                            **(self._rev_tag or {}))

    @holds_lock("_lock")
    def _release_windows(self, decode: List[Request]) -> int:
        """After a chunk's drain: every row that still holds cache gives
        back the window blocks its window has moved past (host arithmetic
        only; a row that finished, or was requeued by a recovery, has
        already returned all it held). Returns the blocks freed."""
        with RecordEvent("serving.decode.release_window", cat="decode"):
            freed = sum(self.cache.release_behind(req.request_id)
                        for req in decode)
        self.stats.window_blocks_freed += freed
        return freed

    @holds_lock("_lock")
    def _prefill(self, req: Request, tokens: np.ndarray):
        """Dense prefill (the spec's; for GPT-2 the jitted program shared
        with generate()), scattered into the sequence's blocks. One
        upload (the prompt), one fetch (the last-position logits [V], and
        with them the spec's counts where it has any) — already the
        minimal host/device traffic for a prompt forward. Returns
        (logits, {counter: value})."""
        with RecordEvent("serving.prefill.forward", cat="prefill"):
            logits, dense_cache, counts = self.spec.prefill(
                self.params, jnp.asarray(tokens[None], jnp.int32))
        # span serving.prefill.write_cache is write_prefill's own
        self.cache.write_prefill(req.request_id, dense_cache, tokens.size)
        if self.cache.prefix_index is not None:
            # every prompt position's KV is now written — index the
            # full blocks immediately so template siblings queued
            # behind this request already hit
            self.cache.register_prefix(req.request_id, tokens)
        with RecordEvent("serving.prefill.fetch", cat="prefill"):
            # fetch, then index on the host: an eager logits[0] is one
            # more device program, queued BEHIND the scatter, so the
            # first token would wait for the scatter too
            out = np.asarray(logits)[0]
            if counts is not None:
                counts = np.asarray(counts)
        self.stats.inc_host_sync("prefill")
        return out, self._count(counts)

    @holds_lock("_lock")
    def _count(self, counts) -> dict:
        """The spec's counts of one program (a prefill, a chunk) into the
        engine's counters; returns them by name for the span."""
        if counts is None:
            return {}
        named = {n: int(c) for n, c in zip(self.spec.counters, counts)}
        for name, count in named.items():
            # a count the engine keeps no counter for (a maximum, as
            # `moe_max_load`) goes to the span alone
            if name in _STAT_EVENTS:
                setattr(self.stats, name, getattr(self.stats, name) + count)
        return named

    @holds_lock("_lock")
    def _decode_chunk(self, reqs: List[Request], k: int):
        """Fused k-token device-resident decode for all running
        sequences — padded to the ONE fixed max_num_seqs width under the
        default ragged kernel (dead rows cost zero kernel work, so a
        single compilation covers every batch mix), or to the power-of-
        two bucket under kernel="bucketed". The per-sequence control
        state (last token, position, sampling knobs, prefill feed, block
        table) travels as ONE packed int32 upload; the result — k
        sampled tokens per row plus the finished and not-finite masks —
        comes back in ONE fetch. Mid-prefill rows (chunked prefill) get
        their next min(k, remaining-prompt) tokens packed into the feed
        columns and advance prefill_pos iff the chunk came back clean.
        Returns (tokens [k, len(reqs)] int32 with -1 on frozen rows,
        bad [len(reqs)] bool, the span's stats of the chunk: the spec's
        counts by name, `rows` and `feeding_rows`)."""
        ragged = self.config.kernel == "ragged"
        n = self.config.max_num_seqs if ragged \
            else _bucket(len(reqs), self.config.max_num_seqs)
        mb = self.max_blocks_per_seq
        # a spec with state layers: one more column, the row's state slot;
        # one with window layers: the row's window table and the logical
        # index of its first block, between the block table and the slot
        stateful = bool(self.spec.state_layers)
        wb = window_blocks_per_seq(self.spec.window, self.config.block_size, k)
        at_window = PACK_COLS + k + mb
        with RecordEvent("serving.decode.pack", cat="decode"):
            packed = np.zeros(
                (n, at_window + (wb + 1 if wb else 0) + stateful), np.int32)
            fed = []                         # (req, tokens consumed)
            for i, req in enumerate(reqs):
                p = req.params
                packed[i, 0] = req.last_token
                packed[i, 1] = req.slot[2]   # first reserved position
                packed[i, 2] = 1             # active (padding rows: 0)
                packed[i, 3] = len(req.output_ids)
                packed[i, 4] = p.max_tokens
                packed[i, 5] = -1 if p.eos_token_id is None \
                    else int(p.eos_token_id)
                packed[i, 6] = pack_f32(p.temperature)
                packed[i, 7] = int(p.top_k)
                packed[i, 8] = pack_f32(p.top_p)
                packed[i, 9] = p.seed & 0x7FFFFFFF
                if req.prefill_pos < req.pf_target:
                    pf_rem = req.pf_target - req.prefill_pos
                    f = min(k, pf_rem)
                    packed[i, 10] = f
                    packed[i, 11] = 1 if pf_rem > k else 0
                    prompt = req.all_token_ids()
                    packed[i, PACK_COLS:PACK_COLS + f] = \
                        prompt[req.prefill_pos:req.prefill_pos + f]
                    fed.append((req, f))
                table = self.cache.block_table(req.request_id)
                packed[i, PACK_COLS + k:PACK_COLS + k + len(table)] = table
                if wb:
                    wtable, first = self.cache.window_table(req.request_id)
                    packed[i, at_window:at_window + len(wtable)] = wtable
                    packed[i, at_window + wb] = first
                if stateful:
                    packed[i, -1] = self.cache.state_slot(req.request_id)
        with RecordEvent("serving.decode.dispatch", cat="decode"):
            out, pools = fused_decode_chunk(
                self.params, self.cache.pools, jnp.asarray(packed),
                self.geom, k, self.config.kernel)
            self.cache.pools = pools
        with RecordEvent("serving.decode.fetch", cat="decode"):
            fetched = np.asarray(out)        # the chunk's ONE host sync
        self.stats.inc_host_sync("decode")
        live = len(reqs)
        if fed:
            self.stats.inc_prefill_chunks(len(fed))
        bad = fetched[k + 1, :live].astype(bool)
        if not bad.any():
            # a bad chunk is discarded wholesale (offenders quarantined,
            # survivors requeued with pf state reset), so prefill
            # progress only commits on a clean fetch
            for req, f in fed:
                req.prefill_pos += f
                obs.reqtrace.record(
                    "prefill_chunk", req.tid, req.request_id, fed=f,
                    pos=req.prefill_pos, target=req.pf_target)
                if self.cache.prefix_index is not None:
                    # committed prefill progress is valid KV: index the
                    # newly completed full blocks so concurrent template
                    # siblings share them while this row still prefills
                    self.cache.register_prefix(
                        req.request_id,
                        req.all_token_ids()[:req.prefill_pos])
        counts = self._count(fetched[k + 2:, 0]) if self.spec.counters \
            else {}
        # the program's row width and the rows that fed prompt tokens
        counts.update(rows=n, feeding_rows=len(fed))
        return fetched[:k, :live], bad, counts

    # ------------------------------------------------------- convenience
    def run(self, max_steps: int = None) -> Dict[str, np.ndarray]:
        """Drive every queued request to completion; returns
        {request_id: np.ndarray of generated token ids}."""
        steps = 0
        # NOTE: the drain loop itself runs unlocked — each step() takes
        # the lock for one iteration, so intake threads (add_request /
        # cancel) interleave at step boundaries instead of blocking for
        # the whole drain
        while self.has_unfinished():
            self.step()
            steps += 1
            if max_steps is not None and steps >= max_steps:
                raise RuntimeError(
                    f"engine did not drain within {max_steps} steps")
        with self._lock:
            # MIGRATED tombstones hold a PARTIAL stream — the request's
            # real output finishes on its destination engine (the router
            # record is the place to read it)
            return {rid: np.asarray(r.output_ids, np.int64)
                    for rid, r in self._requests.items()
                    if r.state not in (RequestState.CANCELLED,
                                       RequestState.MIGRATED)}


class ServingPredictor:
    """Paddle-parity predictor facade over LLMEngine (the serving twin
    of inference.Predictor, dispatched by create_predictor when
    Config.enable_llm_engine was called — mirroring how
    AnalysisPredictor picks its engine off config flags).

    IO surface: input 'input_ids' [B, T] (right-padded) + optional
    'prompt_lens' [B]; output 'sequences' [B, T_out] right-padded with
    the pad token (eos when set, else 0).
    """

    def __init__(self, config):
        model = getattr(config, "_llm_model", None)
        if model is None:
            raise ValueError(
                "Config.enable_llm_engine(model=...) must receive the "
                "model object; serving runs live parameters, not a "
                "serialized artifact")
        opts = dict(getattr(config, "_llm_options", {}) or {})
        self._sampling = SamplingParams(**{
            k: opts.pop(k) for k in list(opts)
            if k in SamplingParams.__dataclass_fields__})
        self.engine = LLMEngine.from_model(model, EngineConfig(**opts))
        from .. import Tensor
        self._inputs = {n: Tensor(n)
                        for n in ("input_ids", "prompt_lens")}
        self._outputs = {}

    def get_input_names(self):
        return ["input_ids", "prompt_lens"]

    def get_input_handle(self, name):
        return self._inputs[name]

    def get_output_names(self):
        return ["sequences"]

    def get_output_handle(self, name):
        return self._outputs[name]

    def run(self, inputs: Optional[list] = None):
        from ...distributed import elastic
        elastic.heartbeat()                  # no-op when unsupervised
        if inputs is not None:
            self._inputs["input_ids"].copy_from_cpu(
                np.asarray(inputs[0]))
            if len(inputs) > 1:
                self._inputs["prompt_lens"].copy_from_cpu(
                    np.asarray(inputs[1]))
        ids_h = self._inputs["input_ids"]
        if ids_h._arr is None:
            raise RuntimeError("input 'input_ids' not set")
        ids = np.asarray(ids_h._arr)
        lens_h = self._inputs["prompt_lens"]
        lens = (np.asarray(lens_h._arr).astype(int).reshape(-1)
                if lens_h._arr is not None
                else np.full(ids.shape[0], ids.shape[1]))
        rids = [self.engine.add_request(ids[b, :lens[b]], self._sampling)
                for b in range(ids.shape[0])]
        results = self.engine.run()
        pad = self._sampling.eos_token_id
        pad = 0 if pad is None else int(pad)
        width = max(int(lens[b]) + len(results[r].tolist())
                    for b, r in enumerate(rids))
        out = np.full((ids.shape[0], width), pad, np.int64)
        for b, rid in enumerate(rids):
            seq = np.concatenate([ids[b, :lens[b]].astype(np.int64),
                                  results[rid]])
            out[b, :seq.size] = seq
        from .. import Tensor
        t = Tensor("sequences")
        t._arr = jnp.asarray(out)
        self._outputs = {"sequences": t}
        if inputs is not None:
            return [out]
        return None

    # Predictor-surface parity no-ops
    def clear_intermediate_tensor(self):
        pass

    def try_shrink_memory(self):
        pass
