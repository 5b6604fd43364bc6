"""Continuous-batching scheduler: FCFS admission, decode interleaving,
preemption under block-pool pressure.

Reference shape: vLLM's scheduler (and the fluid inference executor's
batch dispatch, reference paddle/fluid/inference/), specialised to the
paged cache in serving/paged_cache.py. Per engine step:

1. DECODE — every RUNNING sequence reserves the slots for its next
   decode chunk (cache.reserve_slots, up to decode_chunk_size tokens),
   earliest arrival first. If the pool is
   exhausted, the LATEST-arrived running sequence is preempted: its
   blocks are freed and it re-queues at the FRONT of the waiting line
   with prompt := prompt + generated-so-far (recompute-style preemption
   — cheap on TPU where prefill is one fused forward). FCFS priority is
   therefore strict: an earlier request can never be starved by a later
   one.
2. PREFILL/ADMIT — waiting requests are admitted in arrival order while
   the running set is under max_num_seqs, the per-step prefill token
   budget holds (at least one admission may overflow the budget so a
   long prompt is never starved), the pool can hold their tokens, AND
   post-admission occupancy stays under `cache_high_watermark` — the
   backpressure valve that keeps decode headroom so admission can never
   strand running sequences into a preemption storm. Admission never
   preempts: running sequences outrank new ones.

Robustness surface (the hardened-serving layer):

- the waiting queue is bounded (`max_waiting`): a full queue either
  rejects new arrivals with `EngineOverloaded` (policy 'reject') or
  evicts the oldest waiting request (policy 'shed_oldest');
- queued requests expire (`expire_waiting`) once their `queue_ttl_s` /
  `deadline_s` elapses, and running requests past `deadline_s` are
  reported by `overdue_running` for the engine to abort at the step
  boundary;
- every requeue (preemption, engine crash recovery) goes through
  `_requeue`, an arrival-ordered insert, so a repeatedly-preempted
  request keeps its FCFS priority and can never be starved by later
  arrivals.

The scheduler only does host-side accounting; all device work (prefill
forward, paged decode) belongs to the engine.
"""
from __future__ import annotations

import itertools
import threading
from collections import deque
from dataclasses import dataclass, field
from typing import List, Optional

import numpy as np

from ...analysis import holds_lock
from ...obs import reqtrace
from .paged_cache import CacheExhausted, PagedKVCache

__all__ = ["EngineOverloaded", "SamplingParams", "Request", "RequestState",
           "Scheduler", "SchedulerConfig", "ScheduledBatch", "HOLD_REASONS",
           "record_promotion_events"]

ADMISSION_POLICIES = ("reject", "shed_oldest")


def record_promotion_events(tid: str, request_id: str,
                            promo: Optional[dict]) -> None:
    """Translate one `PagedKVCache.ensure_promoted` result into reqtrace
    events (shared by the engine's enqueue-time prefetch and the
    scheduler's admission-time retry). A partial promotion emits BOTH a
    `promote` (for the blocks that landed) and a `promote_abort` (for
    the failure that stopped the run); `promo is None` means the host
    run vanished between probe and promotion — raced, nothing landed.
    The causality checker requires every tiered prefix_match to be
    resolved by one of these before the request may emit."""
    if promo is None:
        reqtrace.record("promote_abort", tid, request_id,
                        outcome="raced", promoted=0)
        return
    if promo["promoted_blocks"]:
        reqtrace.record("promote", tid, request_id,
                        blocks=promo["promoted_blocks"],
                        tokens=promo["promoted_tokens"],
                        seconds=round(promo["seconds"], 6))
    if promo["outcomes"] and promo["outcomes"][-1] != "hit":
        reqtrace.record("promote_abort", tid, request_id,
                        outcome=promo["outcomes"][-1],
                        promoted=promo["promoted_blocks"])


class EngineOverloaded(RuntimeError):
    """Admission refused: the bounded waiting queue is full (policy
    'reject'). Carries the queue depth and, when the raiser can estimate
    one, a `retry_after_s` hint — the ReplicaSet router fills it from
    its observed drain rate so clients can back off instead of hammering
    a saturated fleet."""

    def __init__(self, request_id, depth: int, limit: int,
                 retry_after_s: Optional[float] = None):
        self.request_id = request_id
        self.depth = depth
        self.limit = limit
        self.retry_after_s = retry_after_s
        hint = "" if retry_after_s is None \
            else f"; retry after ~{retry_after_s:.2f}s"
        super().__init__(
            f"engine overloaded: request {request_id!r} rejected, waiting "
            f"queue at {depth}/{limit} (admission_policy='reject'; use "
            f"'shed_oldest' to evict instead){hint}")


@dataclass(frozen=True)
class SamplingParams:
    """Per-request decode knobs (vLLM SamplingParams analogue).

    deadline_s: wall-clock budget for the WHOLE request (queue + decode),
        measured from arrival; the engine aborts an overdue request at
        the next step boundary with finish_reason='timeout'.
    queue_ttl_s: how long the request may sit in the waiting queue before
        it expires unserved (finish_reason='timeout'); unlike deadline_s
        it only guards queueing, so an admitted request never re-arms it.
    tenant: which tenant's fair share this request spends (serving/
        tenancy.py). Resolved against the TenantRegistry when the stack
        is built with one; ignored (and left at "default") otherwise.
    model: which model this request wants (serving/deploy.ModelRegistry).
        Resolved by the ReplicaSet front-end against the registry when
        the fleet is built with one — the request is admitted to a
        replica pool serving that model's currently-routed revision.
        Ignored (and left at "default") on single-model stacks.
    """
    max_tokens: int = 16
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    eos_token_id: Optional[int] = None
    seed: int = 0
    deadline_s: Optional[float] = None
    queue_ttl_s: Optional[float] = None
    tenant: str = "default"
    model: str = "default"


class RequestState:
    WAITING = "waiting"
    RUNNING = "running"
    FINISHED_STOPPED = "finished_stopped"    # sampled eos
    FINISHED_LENGTH = "finished_length"      # hit max_tokens
    FINISHED_TIMEOUT = "finished_timeout"    # deadline_s / queue_ttl_s hit
    FINISHED_SHED = "finished_shed"          # evicted by admission control
    FINISHED_ERROR = "finished_error"        # quarantined by the watchdog
    CANCELLED = "cancelled"
    MIGRATED = "migrated"                    # handed off to another engine

    # terminal FOR THIS ENGINE: a MIGRATED request lives on at its
    # destination (the router's record tracks it there), but this
    # engine will never step it again
    FINISHED = (FINISHED_STOPPED, FINISHED_LENGTH, FINISHED_TIMEOUT,
                FINISHED_SHED, FINISHED_ERROR, CANCELLED, MIGRATED)


_arrival_counter = itertools.count()


@dataclass
class Request:
    request_id: str
    prompt_ids: np.ndarray                   # int32 [T], never mutated
    params: SamplingParams
    output_ids: List[int] = field(default_factory=list)
    state: str = RequestState.WAITING
    arrival: int = field(default_factory=lambda: next(_arrival_counter))
    num_preemptions: int = 0
    # engine bookkeeping
    slot: Optional[tuple] = None             # (block, offset, pos)
    arrival_time: float = 0.0
    first_token_time: Optional[float] = None
    last_token_time: Optional[float] = None  # previous emit (token gap)
    finish_time: Optional[float] = None
    # chunked prefill (docs/serving.md "Ragged paged attention and
    # chunked prefill"): a long prompt admitted chunked feeds the fused
    # decode scan k prompt tokens per step instead of running a dense
    # prefill dispatch. pf_target is len(all_token_ids()) at admission;
    # prefill_pos advances per good chunk; the row is mid-prefill while
    # prefill_pos < pf_target. Both reset on every requeue (recompute
    # discipline: re-admission re-prefills from the token log).
    pf_target: int = 0
    prefill_pos: int = 0
    # per-request causal tracing (obs/reqtrace.py): stable id minted at
    # admission (router for fleet runs, engine for standalone) that
    # survives preemption, requeue, and cross-engine failover
    trace_id: Optional[str] = None

    @property
    def tid(self) -> str:
        """Trace id for reqtrace events (request_id for bare Requests
        built directly in tests)."""
        return self.trace_id or self.request_id

    def all_token_ids(self) -> np.ndarray:
        """prompt + generated — the effective prompt after preemption."""
        if not self.output_ids:
            return self.prompt_ids
        return np.concatenate(
            [self.prompt_ids, np.asarray(self.output_ids, np.int32)])

    @property
    def last_token(self) -> int:
        return int(self.output_ids[-1]) if self.output_ids \
            else int(self.prompt_ids[-1])

    @property
    def finished(self) -> bool:
        return self.state in RequestState.FINISHED


@dataclass
class SchedulerConfig:
    max_num_seqs: int = 8                    # decode bucket ceiling
    max_prefill_tokens: int = 2048           # per-step admission budget
    # static-cost admission: an object with .cost(num_tokens) and
    # .budget(max_prefill_tokens) (analysis/jaxplan.PrefillCostModel).
    # When set, each admission is charged its modelled prefill FLOPs
    # (quadratic in prompt length — attention) against
    # budget(max_prefill_tokens), so one long prompt pays super-linearly
    # instead of the same per-token rate as many short ones. None keeps
    # the flat token count.
    prefill_cost_model: Optional[object] = None
    # tokens decoded per fused device chunk: each scheduled decode
    # reserves min(decode_chunk_size, tokens-remaining) cache slots so
    # the fused scan (serving/attention.py) can write k tokens without
    # a host round-trip. 1 reproduces the classic one-token step.
    decode_chunk_size: int = 1
    # ------------------------------ admission control / backpressure
    max_waiting: Optional[int] = None        # waiting-queue bound (None=∞)
    admission_policy: str = "reject"         # 'reject' | 'shed_oldest'
    # pause prefill admission once post-admission pool occupancy would
    # exceed this fraction — reserves decode headroom so CacheExhausted
    # cannot strand running sequences. 1.0 disables the watermark.
    cache_high_watermark: float = 1.0
    # chunked prefill: prompts STRICTLY longer than this are admitted
    # chunked — they join the running set with an empty block table and
    # consume decode_chunk_size prompt tokens per step inside the fused
    # decode scan, so a long prompt never monopolises a step. Admission
    # charges only the first chunk against the prefill budget (later
    # chunks are inherently rate-limited at k tokens/step). None
    # disables chunking (every prompt takes the dense prefill path).
    prefill_chunk_threshold: Optional[int] = None
    # multi-tenant WFQ (serving/tenancy.TenantRegistry). When set, the
    # admission head is chosen by weighted fair queuing over per-tenant
    # virtual finish times priced in jaxplan FLOPs (full prompt cost, so
    # one 8k prompt charges its quadratic cost against its tenant's
    # share), with strict FCFS inside each tenant; deadline-aware early
    # reject also arms. None (the default) keeps the historical global
    # FCFS path untouched, and a single active tenant degenerates WFQ to
    # exactly that path (pinned by tests/test_tenancy.py).
    tenants: Optional[object] = None


#: why admission ended in a step (`ScheduledBatch.held_by`): the queue was
#: empty, every row was taken (`max_num_seqs`), the prefill budget was
#: spent, the cache stood above its watermark, the window group had too
#: few free blocks, or an allocation raised `CacheExhausted`
HOLD_REASONS = ("none", "rows", "budget", "watermark", "window", "blocks")


@dataclass
class ScheduledBatch:
    prefill: List[Request] = field(default_factory=list)
    decode: List[Request] = field(default_factory=list)
    preempted: List[Request] = field(default_factory=list)
    #: requests admitted to ride the decode scan (chunked prefill, prefix
    #: hits); they are in `decode` too
    chunked: int = 0
    held_by: str = "none"                # one of HOLD_REASONS


class Scheduler:
    """FCFS scheduler (module docstring). Thread contract (checked by
    ptlint PT-C001 via _GUARDED_BY): the queue/running structures are
    shared between the engine's step loop and intake threads and are
    only touched under self._lock. Public methods take the lock (RLock:
    safe to call from the engine's own locked frame — lock order is
    engine → scheduler, never the reverse); _requeue/_preempt are
    @holds_lock("_lock") helpers called from schedule()/
    requeue_for_recovery's locked frames."""

    _GUARDED_BY = {
        "waiting": "_lock",
        "running": "_lock",
        "num_preemptions": "_lock",
        "_vtime": "_lock",
        "_vfinish": "_lock",
        "_wfq_weights": "_lock",
        "_weights_version": "_lock",
        "_step_ewma": "_lock",
        "deadline_rejects": "_lock",
    }

    def __init__(self, config: SchedulerConfig, cache: PagedKVCache):
        if config.admission_policy not in ADMISSION_POLICIES:
            raise ValueError(
                f"admission_policy must be one of {ADMISSION_POLICIES}, "
                f"got {config.admission_policy!r}")
        if not 0.0 < config.cache_high_watermark <= 1.0:
            raise ValueError(
                f"cache_high_watermark must be in (0, 1], got "
                f"{config.cache_high_watermark}")
        self.config = config
        self.cache = cache
        self._lock = threading.RLock()
        self.waiting: deque = deque()
        self.running: List[Request] = []
        self.num_preemptions = 0
        # multi-tenant WFQ state (inert when config.tenants is None):
        # start-time fair queuing over per-tenant virtual finish times.
        # _vtime is the system virtual clock (last admission's virtual
        # start); _vfinish[t] the tenant's last virtual finish. Prices
        # are jaxplan FLOPs of the FULL prompt (quadratic), weights the
        # registry's effective WFQ weights, snapshotted by version.
        self.tenants = config.tenants
        self._vtime = 0.0
        self._vfinish: dict = {}
        self._wfq_weights: dict = {}
        self._weights_version = -1
        # measured service rate for deadline-aware early reject: EWMA of
        # engine step wall seconds (note_step_seconds). 0.0 until the
        # first step — the estimator abstains rather than guess.
        self._step_ewma = 0.0
        self.deadline_rejects = 0            # statically-hopeless refusals

    # ------------------------------------------------------------- intake
    def add(self, req: Request) -> List[Request]:
        """Queue a request; returns the waiting requests shed to make
        room (empty normally). Raises EngineOverloaded when the bounded
        queue is full under the 'reject' policy."""
        # a request that can never fit the pool would livelock the
        # preemption loop — refuse it up front, loudly
        worst = len(req.prompt_ids) + req.params.max_tokens
        if self.cache.blocks_needed(worst) > self.cache.num_blocks:
            raise ValueError(
                f"request {req.request_id!r} needs "
                f"{self.cache.blocks_needed(worst)} blocks at its longest"
                f" ({worst} tokens) but the pool only has "
                f"{self.cache.num_blocks}; grow num_blocks or shrink the"
                f" request")
        with self._lock:
            # deadline-aware early reject (multi-tenant stacks only):
            # refuse a request that statically cannot meet its deadline
            # at the measured service rate BEFORE it burns prefill —
            # checked ahead of shed_oldest so a doomed arrival never
            # evicts viable queued work to make room for itself
            self._deadline_early_reject(req)
            shed: List[Request] = []
            limit = self.config.max_waiting
            if limit is not None:
                if self.config.admission_policy == "reject":
                    if len(self.waiting) >= limit:
                        raise EngineOverloaded(req.request_id,
                                               len(self.waiting), limit)
                else:                        # shed_oldest
                    while len(self.waiting) >= limit:
                        victim = self.waiting.popleft()
                        victim.state = RequestState.FINISHED_SHED
                        shed.append(victim)
            req.state = RequestState.WAITING
            self.waiting.append(req)
            self._note_tenant(req)
            return shed

    def readmit(self, req: Request):
        """Failover re-admission (docs/serving.md "Multi-replica
        serving"): insert a request recovered from a failed replica into
        THIS scheduler's waiting queue at its ORIGINAL arrival position —
        the same arrival-ordered requeue discipline `_requeue` applies to
        preemption and crash recovery, but crossing engines. Bypasses
        `max_waiting` deliberately: the bound is backpressure against NEW
        arrivals, and bouncing a recovered in-flight request would break
        the zero-lost-request guarantee (the transient overshoot drains
        at FCFS priority)."""
        worst = len(req.prompt_ids) + req.params.max_tokens
        if self.cache.blocks_needed(worst) > self.cache.num_blocks:
            raise ValueError(
                f"request {req.request_id!r} needs "
                f"{self.cache.blocks_needed(worst)} blocks at its longest"
                f" ({worst} tokens) but the pool only has "
                f"{self.cache.num_blocks}")
        with self._lock:
            self._requeue(req)
            self._note_tenant(req)

    # ----------------------------------------------------- block migration
    def adopt_running(self, req: Request):
        """Migration admission (serving/migration.py): install an
        in-flight request straight into the RUNNING set — its KV blocks
        were already imported into this scheduler's cache
        (PagedKVCache.import_blocks), so unlike `readmit` there is
        nothing to re-prefill: the next schedule() reserves its decode
        chunk and the fused scan continues exactly where the source
        stopped. Bypasses max_waiting for the same reason readmit does
        (the bound is backpressure against NEW arrivals)."""
        worst = len(req.prompt_ids) + req.params.max_tokens
        if self.cache.blocks_needed(worst) > self.cache.num_blocks:
            raise ValueError(
                f"request {req.request_id!r} needs "
                f"{self.cache.blocks_needed(worst)} blocks at its longest"
                f" ({worst} tokens) but the pool only has "
                f"{self.cache.num_blocks}")
        if not self.cache.has_seq(req.request_id):
            raise ValueError(
                f"adopt_running: seq {req.request_id!r} has no imported "
                f"cache state — import_blocks must run first")
        with self._lock:
            req.slot = None
            req.state = RequestState.RUNNING
            self.running.append(req)
            self._note_tenant(req)

    def release_running(self, req: Request):
        """Migration release (source side): detach a RUNNING request
        whose KV payload has been committed at the destination. Frees
        its blocks through the normal completion path — `cache_tokens`
        registers the clean prefix, so the SOURCE trie keeps (or gains)
        the entries this sequence wrote and shared blocks just drop one
        reference. No terminal output, no finish event: the request is
        still live, it just lives somewhere else now."""
        with self._lock:
            self.running.remove(req)
            self.cache.free(req.request_id,
                            cache_tokens=self._cache_tokens(req))
            req.slot = None
            req.state = RequestState.MIGRATED

    def remove_waiting(self, request_id: str) -> Optional[Request]:
        """Pull a WAITING request out of the queue without a terminal
        state (drain evacuation: queued work has no KV to migrate, so
        the router re-dispatches it to another replica from its token
        log). Returns the request, or None when it is not waiting."""
        with self._lock:
            for req in list(self.waiting):
                if req.request_id == request_id:
                    self.waiting.remove(req)
                    req.state = RequestState.MIGRATED
                    return req
            return None

    def abort_adopted(self, req: Request):
        """Roll back an adopt_running whose migration failed before the
        source released (kill-mid-migration): drop the request from the
        RUNNING set and free its imported blocks WITHOUT registering a
        prefix — the destination never decoded a token, and the victim
        re-prefills elsewhere from the router's token log."""
        with self._lock:
            if req in self.running:
                self.running.remove(req)
            if self.cache.has_seq(req.request_id):
                self.cache.free(req.request_id)
            req.slot = None
            req.state = RequestState.MIGRATED

    def running_requests(self) -> List[Request]:
        """Stable snapshot of the RUNNING set (migration coordinator
        scans it at step boundaries)."""
        with self._lock:
            return list(self.running)

    def shed_oldest(self) -> Optional[Request]:
        """Evict the oldest waiting request (router-level 'shed_oldest'
        spanning replicas: the ReplicaSet finds the globally-oldest
        waiting request and sheds it from whichever replica holds it).
        Returns it with state FINISHED_SHED, or None when nothing
        waits."""
        with self._lock:
            if not self.waiting:
                return None
            victim = self.waiting.popleft()
            victim.state = RequestState.FINISHED_SHED
            return victim

    def oldest_waiting_arrival(self) -> Optional[int]:
        """Arrival ticket of the head of the waiting line (None when
        empty) — the router's cross-replica shed_oldest scans these."""
        with self._lock:
            return self.waiting[0].arrival if self.waiting else None

    def backlog(self) -> dict:
        """Load snapshot for the router's free-block balancer:
        `waiting` (queue depth), `block_demand` (worst-case ADDITIONAL
        blocks needed to finish every admitted and queued request — the
        growth headroom this engine still owes), and `prefill_cost`
        (modelled cost of the re-prefills waiting in line, priced by the
        jaxplan cost model when configured, flat tokens otherwise)."""
        with self._lock:
            cost_model = self.config.prefill_cost_model
            demand = 0
            cost = 0.0
            for req in self.waiting:
                tokens = len(req.prompt_ids) + len(req.output_ids)
                remaining = max(0, req.params.max_tokens
                                - len(req.output_ids))
                demand += self.cache.blocks_needed(tokens + remaining)
                # ptlint: disable=PT-C004  admission cost model: pure
                # arithmetic over committed-plan coefficients (jaxplan),
                # contractually non-blocking and non-reentrant
                cost += cost_model.cost(tokens) if cost_model else tokens
            for req in self.running:
                tokens = len(req.prompt_ids) + len(req.output_ids)
                remaining = max(0, req.params.max_tokens
                                - len(req.output_ids))
                held = len(self.cache.block_table(req.request_id)) \
                    if self.cache.has_seq(req.request_id) else 0
                demand += max(
                    0, self.cache.blocks_needed(tokens + remaining) - held)
            return {"waiting": len(self.waiting),
                    "block_demand": demand,
                    "prefill_cost": cost}

    def cancel(self, request_id: str) -> bool:
        with self._lock:
            for req in list(self.waiting):
                if req.request_id == request_id:
                    self.waiting.remove(req)
                    req.state = RequestState.CANCELLED
                    return True
            for req in self.running:
                if req.request_id == request_id:
                    self.running.remove(req)
                    self.cache.free(request_id,
                                    cache_tokens=self._cache_tokens(req))
                    req.state = RequestState.CANCELLED
                    return True
            return False

    def has_unfinished(self) -> bool:
        with self._lock:
            return bool(self.waiting or self.running)

    def num_waiting(self) -> int:
        """Queue depth snapshot (the engine's step telemetry reads this
        instead of reaching into self.waiting unlocked)."""
        with self._lock:
            return len(self.waiting)

    def num_running(self) -> int:
        """Running-set size snapshot (same telemetry contract as
        num_waiting: the engine's step gauges read it locked)."""
        with self._lock:
            return len(self.running)

    # ----------------------------------------------------- expiry / abort
    def expire_waiting(self, now: float) -> List[Request]:
        """Remove waiting requests whose queue_ttl_s or deadline_s has
        elapsed (both measured from arrival_time). Returns them with
        state FINISHED_TIMEOUT; the engine emits the terminal outputs."""
        with self._lock:
            expired = []
            for req in list(self.waiting):
                p = req.params
                age = now - req.arrival_time
                if (p.queue_ttl_s is not None and age > p.queue_ttl_s) \
                        or (p.deadline_s is not None
                            and age > p.deadline_s):
                    self.waiting.remove(req)
                    req.state = RequestState.FINISHED_TIMEOUT
                    expired.append(req)
            return expired

    def overdue_running(self, now: float) -> List[Request]:
        """Running requests past their deadline_s; the engine aborts them
        (finish + terminal output) at the step boundary."""
        with self._lock:
            return [r for r in self.running
                    if r.params.deadline_s is not None
                    and (now - r.arrival_time) > r.params.deadline_s]

    # ---------------------------------------------------------- scheduling
    def _cache_tokens(self, req: Request):
        """Tokens whose KV the sequence has actually WRITTEN — what a
        release may index into the prefix trie (docs/serving.md "Prefix
        caching"). Mid-prefill that is the committed prefill_pos; after
        prefill it is everything except the last sampled token, which
        is emitted but never fed back (its KV slot is only written by
        the step that would have sampled its successor). None when the
        prefix cache is off."""
        if self.cache.prefix_index is None:
            return None
        toks = req.all_token_ids()
        if req.pf_target and req.prefill_pos < req.pf_target:
            valid = req.prefill_pos
        else:
            valid = len(req.prompt_ids) + max(0, len(req.output_ids) - 1)
        return toks[:valid]

    @holds_lock("_lock")
    def _requeue(self, req: Request):
        """Arrival-ordered insert into the waiting queue. Preemption and
        crash recovery both requeue through here so a bumped request
        keeps its ORIGINAL FCFS priority — appendleft would invert the
        relative order of a multi-request requeue and let later arrivals
        starve a repeatedly-preempted earlier one."""
        req.slot = None
        req.state = RequestState.WAITING
        # chunked-prefill progress is cache state; a requeue drops the
        # cache, so re-admission must re-prefill from the token log
        req.pf_target = 0
        req.prefill_pos = 0
        for i, w in enumerate(self.waiting):
            if w.arrival > req.arrival:
                self.waiting.insert(i, req)
                return
        self.waiting.append(req)

    @holds_lock("_lock")
    def _preempt(self, victim: Request, batch: ScheduledBatch):
        """Recompute-style preemption: drop the cache, requeue in arrival
        order with the generated tokens folded into the prompt
        (all_token_ids)."""
        self.running.remove(victim)
        if victim in batch.decode:
            batch.decode.remove(victim)
        # the victim's written KV stays matchable: its re-admission (or
        # any template sibling) re-attaches the cached blocks instead
        # of re-prefilling from token zero
        self.cache.free(victim.request_id,
                        cache_tokens=self._cache_tokens(victim))
        victim.num_preemptions += 1
        self.num_preemptions += 1
        self._requeue(victim)
        batch.preempted.append(victim)
        reqtrace.record("preempt", victim.tid, victim.request_id,
                        arrival=victim.arrival,
                        num_preemptions=victim.num_preemptions,
                        tokens_kept=len(victim.output_ids))

    def requeue_for_recovery(self, req: Request):
        """Crash-recovery rebuild: drop the (possibly tainted) cache
        state of a surviving RUNNING request and requeue it in arrival
        order; the next admission re-prefills it from its token log
        (all_token_ids), which the parity pins prove bitwise-equivalent
        to having never been disturbed. Freed blocks are scrubbed — a
        poisoned step may have scattered NaN into them, and NaN (unlike
        finite garbage) survives the attention length-mask via 0*NaN."""
        with self._lock:
            self.running.remove(req)
            self.cache.free(req.request_id, scrub=True)
            self._requeue(req)
            reqtrace.record("requeue", req.tid, req.request_id,
                            reason="recovery", arrival=req.arrival,
                            tokens_kept=len(req.output_ids))

    # ------------------------------------------------- multi-tenant WFQ
    def note_step_seconds(self, dt: float) -> None:
        """Engine step-time feed for the deadline early-reject service
        rate (EWMA; alpha favours recency so the estimate tracks load
        shifts within a few steps)."""
        with self._lock:
            self._step_ewma = dt if self._step_ewma == 0.0 \
                else 0.8 * self._step_ewma + 0.2 * dt

    def waiting_by_tenant(self) -> dict:
        """Queue depth per tenant (autoscaler pressure signal)."""
        with self._lock:
            out: dict = {}
            for req in self.waiting:
                t = req.params.tenant
                out[t] = out.get(t, 0) + 1
            return out

    @holds_lock("_lock")
    def _note_tenant(self, req: Request) -> None:
        """Tenant bookkeeping on intake (inert without a registry):
        refresh the weight snapshots and tag the sequence's tenant into
        the cache so prefix registration stamps trie nodes."""
        if self.tenants is None:
            return
        self._refresh_weights()
        self.cache.note_seq_tenant(req.request_id, req.params.tenant)

    @holds_lock("_lock")
    def _refresh_weights(self) -> None:
        """Re-snapshot registry weights when its version moved; also
        pushes prefix-share weights into the cache's weighted-eviction
        view so both stay coherent with one registry version."""
        reg = self.tenants
        if reg is None or reg.version == self._weights_version:
            return
        # ptlint: disable=PT-C004  TenantRegistry sits BELOW Scheduler
        # in lockgraph.json; wfq_weights() is a locked read, no re-entry
        self._wfq_weights = reg.wfq_weights()
        self._weights_version = reg.version
        # ptlint: disable=PT-C004  same registry read as above
        self.cache.set_tenant_weights(reg.prefix_shares())

    @holds_lock("_lock")
    def _full_price(self, req: Request) -> float:
        """WFQ price of a request: jaxplan FLOPs of its FULL effective
        prompt (quadratic — an 8k prompt charges its attention cost, not
        one ticket), flat tokens without a cost model. Deliberately NOT
        the per-step admission price (which sees only the first chunk /
        uncached suffix): fairness is about total work commanded."""
        n = len(req.prompt_ids) + len(req.output_ids)
        cost_model = self.config.prefill_cost_model
        # ptlint: disable=PT-C004  admission cost model (see backlog())
        return float(cost_model.cost(n)) if cost_model else float(n)

    @holds_lock("_lock")
    def _deadline_early_reject(self, req: Request) -> None:
        """Static admission check: at the measured service rate, can
        this request's prefill even START before its deadline? The bound
        is optimistic (queue-ahead cost at full budget throughput, zero
        decode time), so a rejection is a certainty, not a guess; raises
        EngineOverloaded with a retry_after_s hint sized to the excess.
        Abstains entirely when there is no registry (single-tenant
        stacks keep their historical semantics: overdue work is expired
        by TTL, not refused at the door) or no measured rate yet."""
        if self.tenants is None or self._step_ewma <= 0.0:
            return
        deadline = req.params.deadline_s
        if deadline is None:
            # ptlint: disable=PT-C004  TenantRegistry sits BELOW
            # Scheduler in lockgraph.json; resolve() is a locked read
            cfg = self.tenants.resolve(req.params.tenant)
            deadline = cfg.deadline_slo_s
        if deadline is None:
            return
        cost_model = self.config.prefill_cost_model
        # ptlint: disable=PT-C004  admission cost model (see backlog())
        budget = cost_model.budget(self.config.max_prefill_tokens) \
            if cost_model else float(self.config.max_prefill_tokens)
        ahead = sum(self._full_price(w) for w in self.waiting)
        own = self._full_price(req)
        steps = max(1.0, (ahead + own) / max(budget, 1.0))
        est = steps * self._step_ewma
        if est <= deadline:
            return
        self.deadline_rejects += 1
        retry = round(est - deadline + self._step_ewma, 3)
        reqtrace.record("rejected", req.tid, req.request_id,
                        reason="deadline", deadline_s=deadline,
                        estimate_s=round(est, 3),
                        tenant=req.params.tenant)
        raise EngineOverloaded(req.request_id, len(self.waiting),
                               self.config.max_waiting or 0,
                               retry_after_s=retry)

    @holds_lock("_lock")
    def _select_waiting(self) -> Request:
        """WFQ head selection: per-tenant FCFS heads (first waiting
        request of each tenant, in arrival order — intra-tenant order is
        inviolable), ranked by virtual finish time F = max(vtime,
        vfinish[tenant]) + price/weight, ties broken by arrival ticket.
        With zero or one active tenant this returns self.waiting[0]
        unconditionally — the exact object the historical FCFS path
        would take, so single-tenant scheduling stays bitwise-identical."""
        heads: dict = {}
        for req in self.waiting:
            t = req.params.tenant
            if t not in heads:
                heads[t] = req
        if len(heads) <= 1:
            return self.waiting[0]
        self._refresh_weights()
        best = None
        best_key = None
        for t, req in heads.items():
            w = max(self._wfq_weights.get(t, 1.0), 1e-9)
            start = max(self._vtime, self._vfinish.get(t, 0.0))
            key = (start + self._full_price(req) / w, req.arrival)
            if best is None or key < best_key:
                best, best_key = req, key
        return best

    @holds_lock("_lock")
    def _dequeue(self, req: Request) -> None:
        """Remove the admitted request from the waiting queue (the WFQ
        head need not be the deque head) and advance the virtual clock:
        the tenant's vfinish absorbs the full price over its weight, and
        vtime moves to the admission's virtual start so idle tenants
        re-enter at the current clock instead of a stale past."""
        if self.waiting and self.waiting[0] is req:
            self.waiting.popleft()
        else:
            self.waiting.remove(req)
        if self.tenants is None:
            return
        self._refresh_weights()
        t = req.params.tenant
        w = max(self._wfq_weights.get(t, 1.0), 1e-9)
        start = max(self._vtime, self._vfinish.get(t, 0.0))
        self._vfinish[t] = start + self._full_price(req) / w
        self._vtime = start

    def schedule(self) -> ScheduledBatch:
        with self._lock:
            return self._schedule_locked()

    @holds_lock("_lock")
    def _schedule_locked(self) -> ScheduledBatch:
        batch = ScheduledBatch()
        # 1. decode slots, earliest arrival first; preempt from the back.
        # Each sequence reserves its whole next CHUNK (up to
        # decode_chunk_size tokens, capped by its remaining budget) so
        # the fused device scan never needs a mid-chunk allocation; a
        # sequence that stops early (EOS) frees the unwritten tail with
        # the rest of its table.
        chunk = max(1, self.config.decode_chunk_size)
        for req in sorted(self.running, key=lambda r: r.arrival):
            if req not in self.running:      # preempted below, this step
                continue
            remaining = req.params.max_tokens - len(req.output_ids)
            if req.prefill_pos < req.pf_target:
                # mid-prefill row: the chunk consumes up to pf_rem fed
                # prompt tokens, then may sample/decode for the rest of
                # its k trips — every consumed trip writes one KV slot
                pf_rem = req.pf_target - req.prefill_pos
                n = min(chunk, pf_rem + max(0, remaining))
            else:
                n = min(chunk, remaining)
            n = max(1, n)
            while True:
                try:
                    req.slot = self.cache.reserve_slots(req.request_id, n)
                    batch.decode.append(req)
                    break
                except CacheExhausted:
                    victim = max(self.running, key=lambda r: r.arrival)
                    self._preempt(victim, batch)
                    if victim is req:
                        break                # preempted itself; move on
        # 2. FCFS admission under seq count + prefill cost budget +
        #    the cache occupancy high-watermark (decode headroom).
        #    With a cost model the budget is FLOPs (each request priced
        #    by the static model); without, the flat token count. Either
        #    way the head of line may overflow an untouched budget so a
        #    maximal request cannot starve.
        cost_model = self.config.prefill_cost_model
        # ptlint: disable=PT-C004  admission cost model (see backlog())
        budget = cost_model.budget(self.config.max_prefill_tokens) \
            if cost_model else self.config.max_prefill_tokens
        mark = self.config.cache_high_watermark
        thr = self.config.prefill_chunk_threshold
        admitted = 0
        held_by = "none"                     # the queue ran empty
        while self.waiting:
            if len(self.running) >= self.config.max_num_seqs:
                held_by = "rows"
                break
            req = self.waiting[0] if self.tenants is None \
                else self._select_waiting()
            tokens = req.all_token_ids()
            # prefix caching: probe the longest cached prefix first —
            # a hit is admitted CHUNKED regardless of length (the
            # chunked path writes only uncached suffix positions, so
            # shared blocks are never touched; dense write_prefill
            # would scatter the WHOLE table), and admission is priced
            # on the uncached tokens only: a fully-templated prompt
            # admits at near-zero cost
            cached_probe = self.cache.match_len(tokens)
            # tier-aware pricing: a host-resident run behind the device
            # match is promotable before prefill — promote it NOW (the
            # admission-time retry of the engine's enqueue prefetch;
            # covers entries a timed-out promotion left behind) and
            # re-probe so the price reflects what actually landed
            host_probe = self.cache.host_match_len(tokens)
            if host_probe:
                reqtrace.record(
                    "prefix_match", req.tid, req.request_id,
                    cached_tokens=cached_probe, host_tokens=host_probe,
                    probe=cached_probe)
                promo = self.cache.ensure_promoted(tokens)
                record_promotion_events(req.tid, req.request_id, promo)
                cached_probe = self.cache.match_len(tokens)
            uncached = len(tokens) - cached_probe
            # chunked prefill: a long prompt is admitted with an empty
            # table and fed to the fused decode scan k tokens per step —
            # it is priced (and block-checked) per chunk, not per prompt
            chunked = (thr is not None and len(tokens) > thr) \
                or cached_probe > 0 or host_probe > 0
            eff = min(chunk, uncached) if chunked else len(tokens)
            # ptlint: disable=PT-C004  admission cost model (see backlog())
            price = cost_model.cost(eff) if cost_model else eff
            if price > budget and admitted:
                held_by = "budget"           # spent; next step
                break
            needed = self.cache.blocks_needed(eff)
            used = self.cache.num_used() - self.cache.num_evictable()
            if (used + needed) > mark * self.cache.num_blocks \
                    and self.running:
                # above the watermark with live decodes: hold admission
                # so their growth can't hit CacheExhausted (evictable
                # cached blocks count as headroom — they reclaim on
                # demand). With nothing running there is nothing to
                # strand — admit (the head alone may legitimately
                # exceed the watermark).
                held_by = "watermark"
                break
            # a cache with window layers: the request's need in the second
            # group (the blocks of its last window, or of its first chunk)
            # against that group's free count; 0 > 0 without such layers
            if (self.cache.window_blocks_needed(0, eff) if chunked
                    else self.cache.window_blocks_needed(len(tokens))) \
                    > self.cache.num_window_free():
                held_by = "window"
                break
            if chunked:
                remaining = max(0, req.params.max_tokens
                                - len(req.output_ids))
                d0 = self.cache.tier_demotions
                try:
                    got = self.cache.allocate_with_prefix(
                        req.request_id, tokens)
                    req.slot = self.cache.reserve_slots(
                        req.request_id,
                        min(chunk, (len(tokens) - got) + remaining))
                except CacheExhausted:
                    if self.cache.has_seq(req.request_id):
                        self.cache.free(req.request_id)
                    held_by = "blocks"       # never preempt to admit
                    break
                dd = self.cache.tier_demotions - d0
                if dd:
                    reqtrace.record("demote", req.tid, req.request_id,
                                    blocks=dd)
                req.pf_target = len(tokens)
                req.prefill_pos = got
                self._dequeue(req)
                req.state = RequestState.RUNNING
                self.running.append(req)
                # rides THIS step's fused decode dispatch: first chunk
                # of prompt feed goes out alongside the decode slots
                batch.decode.append(req)
                batch.chunked += 1
                if got:
                    bs = self.cache.block_size
                    reqtrace.record(
                        "prefix_match", req.tid, req.request_id,
                        cached_tokens=got, blocks=-(-got // bs),
                        cow_fork=bool(got % bs), probe=cached_probe)
                reqtrace.record(
                    "scheduled", req.tid, req.request_id, mode="chunked",
                    price=float(price), budget=float(budget),
                    arrival=req.arrival, cached=got,
                    target=req.pf_target)
            else:
                d0 = self.cache.tier_demotions
                try:
                    self.cache.allocate(req.request_id, len(tokens))
                except CacheExhausted:
                    held_by = "blocks"       # never preempt to admit
                    break
                dd = self.cache.tier_demotions - d0
                if dd:
                    reqtrace.record("demote", req.tid, req.request_id,
                                    blocks=dd)
                self.cache.note_prefix_miss(len(tokens))
                self._dequeue(req)
                req.state = RequestState.RUNNING
                self.running.append(req)
                batch.prefill.append(req)
                reqtrace.record(
                    "scheduled", req.tid, req.request_id, mode="dense",
                    price=float(price), budget=float(budget),
                    arrival=req.arrival, tokens=len(tokens))
            admitted += 1
            budget -= price
        batch.held_by = held_by
        return batch

    # ------------------------------------------------------------ results
    def finish(self, req: Request, state: str, scrub: bool = False):
        """Completion path: release blocks, detach from running. `scrub`
        zeroes the freed blocks device-side — required when quarantining
        a poisoned request whose blocks may hold NaN (see
        requeue_for_recovery)."""
        with self._lock:
            self.running.remove(req)
            self.cache.free(
                req.request_id, scrub=scrub,
                cache_tokens=None if scrub else self._cache_tokens(req))
            req.slot = None
            req.state = state
