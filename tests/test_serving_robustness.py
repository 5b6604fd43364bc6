"""Hardened serving runtime — deadlines, admission control, watchdog +
crash recovery, and fault-injected chaos (paddle_tpu/inference/serving/
+ paddle_tpu/testing/faults.ServingFaultInjector).

The load-bearing pins (docs/serving.md "Failure semantics"):
- every abnormal exit is a terminal RequestOutput with a taxonomy
  finish_reason ('timeout' | 'shed' | 'error'), never a lost request;
- a poisoned/wedged step costs the offending request only: survivors
  are rebuilt by re-prefill and their tokens stay BITWISE-identical to
  an unfaulted run;
- the block pool never leaks across any mix of completion, expiry,
  cancellation, shedding and crash recovery (check_integrity after
  every scenario, including a 200-event random churn).
"""
import os
import time

import numpy as np
import pytest
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.models.gpt import GPT, GPTConfig
import paddle_tpu.models.generation as gen
from paddle_tpu.inference.serving import (EngineConfig, EngineOverloaded,
                                          LLMEngine, SamplingParams)
from paddle_tpu.inference.serving.scheduler import (Request, RequestState,
                                                    Scheduler,
                                                    SchedulerConfig)
from paddle_tpu.inference.serving.paged_cache import PagedKVCache
from paddle_tpu.testing.faults import ServingFaultInjector

VOCAB = 97


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=VOCAB, hidden_size=32, num_layers=2,
                    num_heads=4, max_seq_len=24)
    m = GPT(cfg)
    m.eval()
    return m


def _engine(model, faults=None, **kw):
    kw.setdefault("block_size", 4)
    kw.setdefault("num_blocks", 16)
    kw.setdefault("max_num_seqs", 4)
    return LLMEngine.from_model(model, EngineConfig(**kw), faults=faults)


def _prompts(n, seed=7, lo=3, hi=8):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, VOCAB, int(rng.randint(lo, hi))).astype(np.int32)
            for _ in range(n)]


def _reference_tokens(model, prompt, max_new):
    out = np.asarray(gen.generate(
        model, jnp.asarray(np.asarray(prompt)[None], jnp.int32), max_new))
    return out[0, len(prompt):]


# --------------------------------------------------------- deadlines / TTL
def test_queue_ttl_expires_waiting_request(model):
    eng = _engine(model, max_num_seqs=1)
    p = _prompts(2)
    eng.add_request(p[0], SamplingParams(max_tokens=3))
    doomed = eng.add_request(p[1], SamplingParams(max_tokens=3,
                                                 queue_ttl_s=0.0))
    time.sleep(0.01)
    outs = eng.step()
    t = [o for o in outs if o.request_id == doomed]
    assert len(t) == 1 and t[0].finished
    assert t[0].finish_reason == "timeout" and t[0].new_token is None
    assert eng.get_request(doomed).state == RequestState.FINISHED_TIMEOUT
    assert eng.stats.expired == 1
    eng.run()
    eng.cache.check_integrity()


def test_deadline_aborts_running_request(model):
    eng = _engine(model, max_num_seqs=1)
    rid = eng.add_request(_prompts(1)[0],
                          SamplingParams(max_tokens=16, deadline_s=0.05))
    eng.step()                               # admit + prefill + first token
    assert eng.get_request(rid).state == RequestState.RUNNING
    time.sleep(0.08)
    outs = eng.step()                        # step boundary: overdue abort
    t = [o for o in outs if o.request_id == rid]
    assert t and t[-1].finish_reason == "timeout"
    assert eng.get_request(rid).state == RequestState.FINISHED_TIMEOUT
    assert eng.stats.timeouts == 1
    # partial progress is reported in the terminal output
    assert t[-1].token_ids == list(eng.get_request(rid).output_ids)
    assert not eng.has_unfinished()
    eng.cache.check_integrity()


# --------------------------------------------------------- admission control
def test_bounded_queue_rejects_when_full(model):
    eng = _engine(model, max_num_seqs=1, max_waiting=2)
    p = _prompts(3)
    eng.add_request(p[0], SamplingParams(max_tokens=2))
    eng.add_request(p[1], SamplingParams(max_tokens=2))
    with pytest.raises(EngineOverloaded) as ei:
        eng.add_request(p[2], SamplingParams(max_tokens=2))
    assert ei.value.depth == 2 and ei.value.limit == 2
    eng.run()
    eng.cache.check_integrity()


def test_shed_oldest_evicts_and_streams_terminal(model):
    eng = _engine(model, max_num_seqs=1, max_waiting=1,
                  admission_policy="shed_oldest")
    p = _prompts(2)
    victim = eng.add_request(p[0], SamplingParams(max_tokens=2))
    keeper = eng.add_request(p[1], SamplingParams(max_tokens=2))
    assert eng.get_request(victim).state == RequestState.FINISHED_SHED
    outs = eng.step()
    t = [o for o in outs if o.request_id == victim]
    assert t and t[0].finish_reason == "shed" and t[0].new_token is None
    assert eng.stats.shed == 1
    eng.run()
    assert eng.get_request(keeper).state == RequestState.FINISHED_LENGTH
    eng.cache.check_integrity()


def test_cache_high_watermark_pauses_admission(model):
    # 8 blocks, watermark 0.45 → hold above 3.6 blocks: the head's
    # 7-token prompt (2 blocks) admits freely (nothing running yet), the
    # second's 2 more would cross the mark with a live decode → held
    eng = _engine(model, num_blocks=8, max_num_seqs=4,
                  cache_high_watermark=0.45)
    p = _prompts(2, lo=7, hi=8)              # 2 blocks each at admission
    a = eng.add_request(p[0], SamplingParams(max_tokens=8))
    b = eng.add_request(p[1], SamplingParams(max_tokens=8))
    eng.step()
    # head admitted (nothing was running), second held by the watermark
    assert eng.get_request(a).state == RequestState.RUNNING
    assert eng.get_request(b).state == RequestState.WAITING
    assert eng.stats.admission_holds("watermark") >= 1
    eng.run()
    assert eng.get_request(b).finished
    eng.cache.check_integrity()


# ------------------------------------------------- anomaly guard + recovery
def test_prefill_nan_quarantines_only_offender(model):
    # nan_logits fires on the FIRST logits at/after step 1 = the first
    # prefill; its request errors out, the rest run to completion clean
    fi = ServingFaultInjector("nan_logits@1")
    eng = _engine(model, faults=fi)
    p = _prompts(3)
    rids = [eng.add_request(q, SamplingParams(max_tokens=5)) for q in p]
    res = eng.run()
    assert eng.get_request(rids[0]).state == RequestState.FINISHED_ERROR
    assert eng.stats.errors == 1 and eng.stats.recoveries == 0
    for q, rid in zip(p[1:], rids[1:]):
        np.testing.assert_array_equal(res[rid], _reference_tokens(model, q, 5))
    eng.cache.check_integrity()


def test_decode_nan_recovery_keeps_survivors_bitwise(model):
    # all four prefill at step 1; step 2 is pure decode (one fused
    # chunk drains the remaining tokens), so the poison lands on decode
    # row 1 of that chunk → the WHOLE chunk is discarded, that request
    # quarantined, the other three rebuilt by re-prefill and
    # BITWISE-equal to the unfaulted reference (chunk-invariant
    # sampling keys make the replay exact)
    fi = ServingFaultInjector("nan_logits@2:1")
    eng = _engine(model, faults=fi)
    p = _prompts(4)
    rids = [eng.add_request(q, SamplingParams(max_tokens=6)) for q in p]
    res = eng.run()
    errored = [r for r in rids
               if eng.get_request(r).state == RequestState.FINISHED_ERROR]
    assert len(errored) == 1
    assert eng.stats.errors == 1 and eng.stats.recoveries == 1
    assert eng.stats.rebuilt == 3
    assert ("nan_logits", 2) in fi.fired_log
    for q, rid in zip(p, rids):
        if rid in errored:
            continue
        np.testing.assert_array_equal(res[rid],
                                      _reference_tokens(model, q, 6))
    eng.cache.check_integrity()


def test_cache_corruption_detected_and_recovered(model):
    # NaN scribbled into a live block surfaces as non-finite decode
    # logits on that sequence; recovery scrubs + rebuilds, and the pool
    # must come back clean (a NaN left in a freed block would poison
    # whoever gets it next via 0*NaN through the attention mask)
    fi = ServingFaultInjector("cache_corrupt@2")
    eng = _engine(model, faults=fi)
    p = _prompts(4)
    rids = [eng.add_request(q, SamplingParams(max_tokens=6)) for q in p]
    res = eng.run()
    assert eng.stats.errors >= 1 and eng.stats.recoveries >= 1
    errored = {r for r in rids
               if eng.get_request(r).state == RequestState.FINISHED_ERROR}
    for q, rid in zip(p, rids):
        if rid not in errored:
            np.testing.assert_array_equal(
                res[rid], _reference_tokens(model, q, 6))
    eng.cache.check_integrity()
    for kp, vp in eng.cache.pools:           # scrub left no NaN behind
        assert bool(jnp.isfinite(kp).all()) and bool(jnp.isfinite(vp).all())


def test_stall_trips_watchdog_and_engine_drains(model):
    # generous timeout (2s) so tiny-model compiles can't trip it; the
    # injected stall (2.5s) must. Warm the jit caches with a clean run
    # first so compile time never lands inside the guarded step.
    clean = _engine(model)
    for q in _prompts(4):
        clean.add_request(q, SamplingParams(max_tokens=4))
    clean.run()
    fi = ServingFaultInjector("stall@2:2.5")
    eng = _engine(model, faults=fi, step_timeout_s=2.0)
    rids = [eng.add_request(q, SamplingParams(max_tokens=4))
            for q in _prompts(4)]
    eng.run()
    assert eng.stats.watchdog_trips >= 1
    assert eng.stats.errors >= 1            # the quarantined head
    assert all(eng.get_request(r).finished for r in rids)
    eng.cache.check_integrity()


# -------------------------------------------------------- heartbeat wiring
def test_engine_step_beats_elastic_heartbeat(model, tmp_path):
    hb = tmp_path / "beat"
    os.environ["PADDLE_ELASTIC_HEARTBEAT_FILE"] = str(hb)
    try:
        eng = _engine(model)
        eng.add_request(_prompts(1)[0], SamplingParams(max_tokens=2))
        eng.step()
        assert hb.exists()
        before = hb.stat().st_mtime_ns
        time.sleep(0.01)
        eng.step()
        assert hb.stat().st_mtime_ns > before
    finally:
        del os.environ["PADDLE_ELASTIC_HEARTBEAT_FILE"]


# ------------------------------------------------------ starvation / FCFS
def test_requeue_preserves_arrival_order():
    """A preempted-and-requeued request re-enters the waiting queue at
    its ORIGINAL FCFS position, ahead of later arrivals (appendleft
    would also pass this one, but inverts multi-request recovery order —
    covered below)."""
    cache = PagedKVCache(num_layers=1, cache_shape=(2, 4),
                         num_blocks=16, block_size=4)
    sched = Scheduler(SchedulerConfig(max_num_seqs=4), cache)
    reqs = [Request(request_id=f"r{i}", prompt_ids=np.ones(3, np.int32),
                    params=SamplingParams(max_tokens=4)) for i in range(4)]
    for r in reqs:
        sched.add(r)
    sched.schedule()                         # all running
    assert [r.request_id for r in sched.running] == ["r0", "r1", "r2", "r3"]
    late = Request(request_id="late", prompt_ids=np.ones(3, np.int32),
                   params=SamplingParams(max_tokens=4))
    sched.add(late)
    # recovery requeue of r1 then r3 (any order) must land them BEFORE
    # the later arrival and in arrival order relative to each other
    sched.requeue_for_recovery(reqs[3])
    sched.requeue_for_recovery(reqs[1])
    assert [r.request_id for r in sched.waiting] == ["r1", "r3", "late"]
    cache.check_integrity()


def test_repeatedly_preempted_request_not_starved(model):
    """Engine-level regression: under constant pool pressure with a
    stream of later arrivals, the earliest request still finishes no
    later than any later arrival (strict FCFS despite preemptions)."""
    eng = _engine(model, num_blocks=6, max_num_seqs=2)
    first = eng.add_request(_prompts(1, seed=3, lo=6, hi=7)[0],
                            SamplingParams(max_tokens=10))
    later = []
    steps = 0
    while eng.has_unfinished():
        eng.step()
        steps += 1
        assert steps < 200
        if steps % 2 == 0 and len(later) < 6:
            later.append(eng.add_request(
                _prompts(1, seed=40 + steps, lo=4, hi=6)[0],
                SamplingParams(max_tokens=6)))
    t_first = eng.get_request(first).finish_time
    for rid in later:
        assert t_first <= eng.get_request(rid).finish_time
    eng.cache.check_integrity()


# ----------------------------------------------------- cancellation races
def test_cancel_waiting_request_before_prefill(model):
    eng = _engine(model, max_num_seqs=1)
    p = _prompts(2)
    eng.add_request(p[0], SamplingParams(max_tokens=3))
    queued = eng.add_request(p[1], SamplingParams(max_tokens=3))
    assert eng.cancel(queued)                # still WAITING: never ran
    outs = eng.step()
    t = [o for o in outs if o.request_id == queued]
    assert t and t[0].finish_reason == "cancelled"
    eng.run()
    assert eng.get_request(queued).output_ids == []
    eng.cache.check_integrity()


def test_cancel_expired_request_is_noop(model):
    eng = _engine(model, max_num_seqs=1)
    p = _prompts(2)
    eng.add_request(p[0], SamplingParams(max_tokens=3))
    doomed = eng.add_request(p[1], SamplingParams(max_tokens=3,
                                                 queue_ttl_s=0.0))
    time.sleep(0.01)
    eng.step()                               # expires `doomed`
    assert eng.get_request(doomed).state == RequestState.FINISHED_TIMEOUT
    assert not eng.cancel(doomed)            # lost the race: no double-free
    assert eng.stats.cancelled == 0
    eng.run()
    eng.cache.check_integrity()


def test_churn_cancel_expire_complete_leaks_nothing(model):
    """200 random request fates (complete / cancel / expire / shed) with
    recovery faults mixed in: the pool must end with every block free and
    lifetime counters balanced."""
    fi = ServingFaultInjector("nan_logits@9,cache_corrupt@21,nan_logits@33")
    eng = _engine(model, num_blocks=32, max_num_seqs=4, max_waiting=8,
                  admission_policy="shed_oldest")
    rng = np.random.RandomState(0)
    submitted = []
    n_target = 200
    steps = 0
    while len(submitted) < n_target or eng.has_unfinished():
        if len(submitted) < n_target and rng.rand() < 0.7:
            ttl = 0.0 if rng.rand() < 0.1 else None
            rid = eng.add_request(
                rng.randint(0, VOCAB, int(rng.randint(3, 7))).astype(
                    np.int32),
                SamplingParams(max_tokens=int(rng.randint(2, 5)),
                               queue_ttl_s=ttl))
            submitted.append(rid)
        if submitted and rng.rand() < 0.15:
            eng.cancel(submitted[int(rng.randint(len(submitted)))])
        eng.step()
        steps += 1
        assert steps < 3000
    assert len(submitted) == n_target
    for rid in submitted:
        assert eng.get_request(rid).finished, f"lost request {rid}"
    assert eng.cache.num_free() == eng.cache.num_blocks
    assert eng.cache.blocks_allocated == eng.cache.blocks_freed
    eng.cache.check_integrity()


# ----------------------------------------------------- chaos acceptance
@pytest.mark.chaos
def test_chaos_sixteen_requests_through_faults(model):
    """The PR's acceptance pin: 16 staggered requests through a seeded
    nan/stall/cache-corrupt schedule — every request terminal, zero
    leaked blocks, at least one quarantine, and every surviving request
    bitwise-identical to generate()."""
    fi = ServingFaultInjector(
        "nan_logits@4,stall@7:0.1,cache_corrupt@10,nan_logits@13")
    eng = _engine(model, faults=fi, num_blocks=64, max_num_seqs=4,
                  max_waiting=16, admission_policy="shed_oldest",
                  cache_high_watermark=0.9)
    rng = np.random.RandomState(0)
    specs = [(rng.randint(0, VOCAB, int(rng.randint(3, 9))).astype(np.int32),
              int(rng.randint(4, 10))) for _ in range(16)]
    pending = list(specs)
    rids = []
    for p, mt in pending[:4]:
        rids.append(eng.add_request(p, SamplingParams(max_tokens=mt)))
    pending = pending[4:]
    steps = 0
    while eng.has_unfinished() or pending:
        eng.step()
        steps += 1
        assert steps < 400
        if steps % 2 == 0 and pending:
            p, mt = pending.pop(0)
            rids.append(eng.add_request(p, SamplingParams(max_tokens=mt)))
    assert len(rids) == 16
    for rid in rids:
        assert eng.get_request(rid).finished, f"lost request {rid}"
    assert eng.stats.errors >= 1             # the schedule really bit
    assert len(fi.fired_log) == 4            # every fault fired
    eng.cache.check_integrity()
    survivors = 0
    for (p, mt), rid in zip(specs, rids):
        req = eng.get_request(rid)
        if req.state in (RequestState.FINISHED_STOPPED,
                         RequestState.FINISHED_LENGTH):
            survivors += 1
            np.testing.assert_array_equal(
                np.asarray(req.output_ids, np.int64),
                _reference_tokens(model, p, mt))
    assert survivors >= 8                    # faults cost few, not most
