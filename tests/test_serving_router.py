"""Multi-replica serving: ReplicaSet router + EngineReplica supervisor
(paddle_tpu/inference/serving/router.py, replica.py).

The load-bearing pins (docs/serving.md "Multi-replica serving and
failover"):

- free-block admission balancing spreads skewed prompt lengths better
  than round-robin (the A/B both policies expose);
- a replica crash/wedge loses ZERO requests: in-flight and queued work
  fails over to survivors in ORIGINAL arrival order (FCFS tickets
  preserved), and requests on untouched replicas stay bitwise-identical
  to an unfaulted run (greedy);
- deadlines keep counting from the ORIGINAL arrival across failover —
  a re-admitted request that blew deadline_s finishes 'timeout';
- a killed replica restarts with capped backoff and rejoins only after
  its warmup probe serves a token end-to-end;
- no replica pool leaks blocks across any mix of completion, failover,
  cancellation and churn (check_integrity per replica).
"""
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.gpt import GPT, GPTConfig
from paddle_tpu.inference.serving import (EngineConfig, EngineOverloaded,
                                          ReplicaSet, ReplicaState,
                                          RouterConfig, SamplingParams)
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


def _ecfg(**kw):
    kw.setdefault("block_size", 4)
    kw.setdefault("num_blocks", 16)
    kw.setdefault("max_num_seqs", 4)
    kw.setdefault("decode_chunk_size", 2)   # keep requests in flight
    return EngineConfig(**kw)


def _router(model, n=2, faults=None, ecfg=None, **rkw):
    rkw.setdefault("backoff_base", 0.01)
    rkw.setdefault("backoff_max", 0.05)
    rkw.setdefault("backoff_jitter", 0.0)
    return ReplicaSet.from_model(
        model, RouterConfig(num_replicas=n, **rkw),
        engine_config=ecfg or _ecfg(),
        faults=faults or ServingFaultInjector(""))


def _prompts(n, seed=7, lo=3, hi=8):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, VOCAB, int(rng.randint(lo, hi))).astype(np.int32)
            for _ in range(n)]


def _await_rejoin(rs):
    """`rs.run()` returns when the requests are done, and the survivor can
    manage that before a killed replica's 10 ms backoff has passed: the
    chaos harness's wait for the replica to be back in rotation."""
    import tools.chaos_serve as cs
    cs._await_rejoin(rs, 0, 3000)


def _assert_no_leaks(rs):
    for idx, audit in rs.check_integrity().items():
        assert audit is not None, f"replica {idx} has no live engine"
        assert audit["leaked"] == 0, (idx, audit)


# ---------------------------------------------------------- balancing
def test_free_block_balancing_beats_round_robin(model):
    # L,S,L,S is adversarial to round-robin (both longs land on the
    # same replica) while free-block scoring absorbs each long before
    # routing the next; no stepping, so demand is purely admission-time
    long_p = np.arange(1, 15, dtype=np.int32)        # 14 tokens
    short_p = np.arange(1, 4, dtype=np.int32)        # 3 tokens
    order = [long_p, short_p, long_p, short_p]

    def imbalance(balance):
        rs = ReplicaSet.from_model(
            model, RouterConfig(num_replicas=2, balance=balance),
            engine_config=_ecfg(num_blocks=32))
        for p in order:
            rs.add_request(p, SamplingParams(max_tokens=4))
        d = [r.load_info()["block_demand"] for r in rs.replicas]
        rs.run(max_steps=500)
        _assert_no_leaks(rs)
        return abs(d[0] - d[1])

    fb, rr = imbalance("free_blocks"), imbalance("round_robin")
    assert fb < rr, (fb, rr)


def test_round_robin_rotates(model):
    rs = ReplicaSet.from_model(
        model, RouterConfig(num_replicas=3, balance="round_robin"),
        engine_config=_ecfg())
    homes = []
    for p in _prompts(6):
        rid = rs.add_request(p, SamplingParams(max_tokens=2))
        homes.append(rs.get_request(rid).replica)
    assert homes == [0, 1, 2, 0, 1, 2]
    rs.run(max_steps=500)
    _assert_no_leaks(rs)


# ----------------------------------------------------------- failover
def test_failover_zero_lost_and_bitwise_untouched(model):
    prompts = _prompts(6)
    sp = lambda: SamplingParams(max_tokens=8)  # noqa: E731

    faults = ServingFaultInjector("kill_replica@3:1")
    rs = _router(model, n=3, faults=faults)
    rids = [rs.add_request(p, sp()) for p in prompts]
    homes = {r: rs.get_request(r).replica for r in rids}
    rs.run(max_steps=3000)
    assert faults.fired_log, "kill fault never fired"

    st = rs.router_stats()
    assert st["unfinished"] == 0                     # zero lost
    assert st["requeues"] >= 1                       # failover happened
    assert all(rs.get_request(r).finish_reason == "length" for r in rids)
    _assert_no_leaks(rs)

    ref = _router(model, n=3)
    ref_rids = [ref.add_request(p, sp()) for p in prompts]
    ref.run(max_steps=1500)
    untouched = 0
    for r, rr in zip(rids, ref_rids):
        rec = rs.get_request(r)
        if rec.requeues == 0 and homes[r] != 1:
            untouched += 1
            assert rec.tokens == ref.get_request(rr).tokens
    assert untouched > 0
    # greedy decode is bitwise across failover too (re-prefill +
    # fold_in(seed, progress) sampling keys): ALL requests must match
    for r, rr in zip(rids, ref_rids):
        assert rs.get_request(r).tokens == ref.get_request(rr).tokens


def test_fcfs_arrival_order_preserved_across_requeue(model):
    # all six requests land on replica 1 of 2 after filling replica 0's
    # score down is fiddly; instead kill r1 and inspect the SURVIVOR's
    # scheduler: readmitted requests must carry their ORIGINAL tickets
    # and sit in arrival order
    faults = ServingFaultInjector("kill_replica@1:1")
    rs = _router(model, n=2, faults=faults)
    rids = [rs.add_request(p, SamplingParams(max_tokens=6))
            for p in _prompts(6)]
    tickets = {r: rs.get_request(r).arrival for r in rids}
    rs.step()                                        # fires the kill
    assert rs.states()[1] in (ReplicaState.DOWN, ReplicaState.FAILED)
    # every request now lives on replica 0 with its original ticket
    for r in rids:
        rec = rs.get_request(r)
        if rec.finished:
            continue
        assert rec.replica == 0
        assert rec.arrival == tickets[r]
    sched = rs.replicas[0].engine.scheduler
    waiting = [q.arrival for q in sched.waiting]
    assert waiting == sorted(waiting), \
        "requeue must keep the waiting queue in original arrival order"
    rs.run(max_steps=3000)
    assert rs.router_stats()["unfinished"] == 0
    _await_rejoin(rs)           # the survivor can finish inside the backoff
    _assert_no_leaks(rs)


def test_deadline_counts_from_original_arrival_across_failover(model):
    # satellite regression: a request whose replica dies does NOT get a
    # fresh deadline on re-admission — deadline_s is measured from the
    # ORIGINAL arrival_time, so one that blew its budget during the
    # failover finishes 'timeout'
    faults = ServingFaultInjector("kill_replica@1:1")
    rs = _router(model, n=2, faults=faults)
    keep = rs.add_request(_prompts(1)[0], SamplingParams(max_tokens=4))
    doomed = rs.add_request(
        _prompts(2)[1], SamplingParams(max_tokens=16, deadline_s=0.05))
    assert rs.get_request(doomed).replica == 1
    t_orig = rs.get_request(doomed).arrival_time
    rs.step()                                        # kill + readmit
    assert rs.get_request(doomed).requeues == 1
    assert rs.get_request(doomed).replica == 0
    # the engine-side clone must carry the ORIGINAL arrival stamp
    eng_req = rs.replicas[0].engine.get_request(doomed)
    assert eng_req.arrival_time == t_orig
    time.sleep(0.06)                                 # blow the budget
    rs.run(max_steps=3000)
    assert rs.get_request(doomed).finish_reason == "timeout"
    assert rs.get_request(keep).finish_reason == "length"
    _assert_no_leaks(rs)


def test_wedge_failover_via_heartbeat(model):
    faults = ServingFaultInjector("wedge_replica@2:0")
    rs = _router(model, n=2, faults=faults, heartbeat_timeout_s=0.01)
    rids = [rs.add_request(p, SamplingParams(max_tokens=6))
            for p in _prompts(6)]
    steps = 0
    while rs.has_unfinished():
        rs.step()
        steps += 1
        assert steps < 3000
        time.sleep(0.002)    # let the wedged replica's silence accrue
    st = rs.router_stats()
    assert st["unfinished"] == 0
    assert st["requeues"] >= 1
    assert any("wedge" in reason
               for r in rs.replicas for _, reason in r.history)
    assert all(rs.get_request(r).finished for r in rids)


# ----------------------------------------------------- restart/rejoin
def test_killed_replica_rejoins_after_warmup_probe(model):
    faults = ServingFaultInjector("kill_replica@2:1")
    rs = _router(model, n=2, faults=faults)
    rids = [rs.add_request(p, SamplingParams(max_tokens=8))
            for p in _prompts(6)]
    rs.run(max_steps=3000)
    _await_rejoin(rs)
    rep = rs.replicas[1]
    assert rep.state == ReplicaState.UP
    assert rep.restarts == 1
    assert rep.probe_tokens >= 1          # the probe actually served
    assert len(rs.recovery_times) == 1
    assert rs.router_stats()["unfinished"] == 0
    # the rejoined replica serves real traffic: drain the other one so
    # routing has a single destination
    rs.drain(0)
    canary = rs.add_request(_prompts(1)[0], SamplingParams(max_tokens=2))
    assert rs.get_request(canary).replica == 1
    rs.run(max_steps=1000)
    assert rs.get_request(canary).finish_reason == "length"
    rs.undrain(0)
    _assert_no_leaks(rs)
    assert all(rs.get_request(r).finished for r in rids)


def test_probe_failure_counts_against_restart_budget(model):
    # an engine factory whose second incarnation cannot serve sends the
    # replica through quarantine → restart → failed probe → FAILED once
    # the budget is spent; the orphans terminalize 'error', never lost
    from paddle_tpu.inference.serving.engine import LLMEngine

    calls = []

    def factory(index, incarnation):
        calls.append(incarnation)
        if incarnation > 0:
            raise RuntimeError("fresh engine refuses to boot")
        return LLMEngine.from_model(model, _ecfg())

    faults = ServingFaultInjector("kill_replica@2:0")
    rs = ReplicaSet(factory,
                    RouterConfig(num_replicas=1, max_restarts=2,
                                 backoff_base=0.005, backoff_max=0.01,
                                 backoff_jitter=0.0),
                    faults=faults)
    rids = [rs.add_request(p, SamplingParams(max_tokens=6))
            for p in _prompts(3)]
    steps = 0
    while rs.has_unfinished():
        rs.step()
        steps += 1
        assert steps < 3000
        time.sleep(0.002)
    assert rs.states()[0] == ReplicaState.FAILED
    for r in rids:
        assert rs.get_request(r).finish_reason == "error"
    assert len(calls) >= 2                # the restart path did run


# ------------------------------------------------------- backpressure
def test_router_reject_carries_retry_after_hint(model):
    rs = _router(model, n=1, max_waiting=1, admission_policy="reject",
                 ecfg=_ecfg(max_num_seqs=1))
    rs.add_request(_prompts(1)[0], SamplingParams(max_tokens=4))
    rs.step()                            # admit it to running
    rs.add_request(_prompts(2)[1], SamplingParams(max_tokens=4))
    with pytest.raises(EngineOverloaded) as ei:
        rs.add_request(_prompts(3)[2], SamplingParams(max_tokens=4))
    assert ei.value.retry_after_s is not None
    assert ei.value.retry_after_s > 0
    assert "retry after" in str(ei.value)
    rs.run(max_steps=1000)
    _assert_no_leaks(rs)


def test_router_shed_oldest_spans_replicas(model):
    rs = _router(model, n=2, max_waiting=2,
                 admission_policy="shed_oldest",
                 ecfg=_ecfg(max_num_seqs=1))
    prompts = _prompts(6)
    rids = [rs.add_request(p, SamplingParams(max_tokens=8))
            for p in prompts[:2]]
    rs.step()                           # both running, waiting empty
    rids += [rs.add_request(p, SamplingParams(max_tokens=8))
             for p in prompts[2:4]]     # one waiting per replica
    victim = min((r for r in rids[2:]),
                 key=lambda r: rs.get_request(r).arrival)
    extra = rs.add_request(prompts[4], SamplingParams(max_tokens=4))
    rs.run(max_steps=1000)
    assert rs.get_request(victim).finish_reason == "shed"
    assert rs.get_request(extra).finish_reason == "length"
    _assert_no_leaks(rs)


def test_no_up_replica_rejects_with_hint(model):
    rs = _router(model, n=1)
    rs.drain(0)
    with pytest.raises(EngineOverloaded) as ei:
        rs.add_request(_prompts(1)[0], SamplingParams(max_tokens=2))
    assert ei.value.retry_after_s is not None


# ------------------------------------------------------------- churn
def test_churn_zero_leak_with_failover(model):
    # 200-request churn (small generations, staggered arrivals, random
    # cancels) across 3 replicas with one kill mid-stream: everything
    # terminal, zero leaks on every replica
    rng = np.random.RandomState(3)
    n = 200
    specs = [(rng.randint(0, VOCAB, int(rng.randint(3, 8)))
              .astype(np.int32), int(rng.randint(2, 5)))
             for _ in range(n)]
    faults = ServingFaultInjector("kill_replica@8:2")
    rs = _router(model, n=3, faults=faults,
                 ecfg=_ecfg(decode_chunk_size=4, num_blocks=24))
    pending = list(specs)
    rids, cancelled = [], 0
    steps = 0
    while pending or rs.has_unfinished():
        for _ in range(min(2, len(pending))):
            p, mt = pending.pop(0)
            rids.append(rs.add_request(p, SamplingParams(max_tokens=mt)))
        rs.step()
        steps += 1
        assert steps < 6000
        if steps % 7 == 0 and rids:
            live = [r for r in rids
                    if not rs.get_request(r).finished]
            if live:
                if rs.cancel(live[int(rng.randint(len(live)))]):
                    cancelled += 1
        if not any(r.has_unfinished() for r in rs.replicas) \
                and rs.has_unfinished():
            time.sleep(0.002)
    assert len(rids) == n
    assert faults.fired_log, "kill fault never fired"
    st = rs.router_stats()
    assert st["unfinished"] == 0
    assert st["requeues"] >= 1
    assert cancelled > 0
    _assert_no_leaks(rs)


# -------------------------------------------------- chaos acceptance
@pytest.mark.chaos
def test_replica_chaos_acceptance(model):
    # the PR's acceptance gate, in-process: 3 replicas, kill_replica
    # mid-traffic + engine-level poison — every request terminal,
    # untouched-replica requests bitwise vs unfaulted, zero leaks per
    # replica, killed replica rejoins and serves a canary in-run
    import tools.chaos_serve as cs
    report = cs.run_chaos_replicas(seed=0, n_requests=12, replicas=3)
    assert report["requeues"] >= 1
    assert report["canaries_served"] >= 1
    assert report["untouched_survivors"] > 0
    for audit in report["integrity"].values():
        assert audit["leaked"] == 0


# ------------------------------------------------------------- obs
def test_router_metrics_families(model):
    from paddle_tpu import obs
    faults = ServingFaultInjector("kill_replica@2:0")
    rs = _router(model, n=2, faults=faults)
    for p in _prompts(4):
        rs.add_request(p, SamplingParams(max_tokens=6))
    rs.run(max_steps=3000)
    _await_rejoin(rs)
    fams = {f["name"]: f for f in obs.snapshot()["metrics"]}
    for name in ("serving_replica_up", "serving_failovers_total",
                 "serving_requeued_total", "serving_router_ttft_seconds",
                 "serving_failover_recovery_seconds"):
        assert name in fams, name
    ups = [s["value"] for s in fams["serving_replica_up"]["series"]
           if s["labels"]["router"] == rs.label]
    assert len(ups) == 2 and all(v == 1 for v in ups)
    fo = [s for s in fams["serving_failovers_total"]["series"]
          if s["labels"]["router"] == rs.label]
    assert sum(s["value"] for s in fo) >= 1
    assert any(s["labels"]["reason"] == "crash" for s in fo)
    req = [s for s in fams["serving_requeued_total"]["series"]
           if s["labels"]["router"] == rs.label]
    assert sum(s["value"] for s in req) >= 1
    rec = [s for s in fams["serving_failover_recovery_seconds"]["series"]
           if s["labels"]["router"] == rs.label]
    assert sum(s["count"] for s in rec) == 1
