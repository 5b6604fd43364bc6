#!/usr/bin/env python
"""Chaos harness for the hardened serving engine.

Drives a seeded mixed workload (staggered arrivals, random
cancellations, deadlines) through an LLMEngine while a deterministic
ServingFaultInjector schedule poisons logits, stalls decode steps and
corrupts paged-cache blocks — then audits the invariants the hardening
layer promises (docs/serving.md "Failure semantics"):

- every submitted request reaches a terminal state (none lost);
- the block pool's free list + live tables exactly partition the pool
  (PagedKVCache.check_integrity — zero leaked blocks);
- every request that survived the faults produced tokens
  bitwise-identical to an unfaulted engine run of the same workload.

Exit status is nonzero on any violation, so CI can run this directly:

    JAX_PLATFORMS=cpu python tools/chaos_serve.py --seed 0 \
        --faults "nan_logits@4,stall@7:0.1,cache_corrupt@10" --requests 16

`run_chaos` is importable — the chaos-marked acceptance test in
tests/test_serving_robustness.py asserts the same invariants in-process.

`--replicas N` switches to the multi-replica harness (`run_chaos_replicas`):
the same seeded workload flows through a ReplicaSet while replica-targeted
faults (kill_replica@step:r, wedge_replica@step:r) crash/wedge whole
engines mid-traffic, and the audit gates widen to the router's promises
(docs/serving.md "Multi-replica serving and failover"):

- every submitted request reaches a terminal state (failover loses none);
- every live replica's pool audits zero leaked blocks;
- requests on UNTOUCHED replicas produce tokens bitwise-identical to an
  unfaulted router run (greedy decode — failover must not perturb
  survivors);
- every killed/wedged replica rejoins after its warmup probe AND serves
  a canary request within the same run.

    JAX_PLATFORMS=cpu python tools/chaos_serve.py --replicas 3 \
        --faults "kill_replica@6:1,nan_logits@10,stall@12:0.05"

`--disagg` switches to the disaggregated-serving harness
(`run_chaos_disagg`): replica 0 becomes a prefill tier that hands every
prefill-complete request to decode replicas via live KV-block migration
(paddle_tpu/inference/serving/migration.py), while `kill_migration@step:0`
kills the source INSIDE the commit window — between destination admit
and source release, the one window plain kill_replica can never reach.
Gates: zero lost requests (the half-migrated victim re-prefills from
the router's authoritative token log), zero leaked blocks on BOTH ends,
every completed request bitwise-identical to the unfaulted
disaggregated run, non-vacuous handoffs + rollback, and the migration
coordinator's cross-replica lock edges cycle-free and statically
predicted.

    JAX_PLATFORMS=cpu python tools/chaos_serve.py --disagg --seed 0

`--tiering` switches to the hierarchical KV-tiering harness
(`run_chaos_tiering`): templated traffic against a device pool far
smaller than the prefix working set, a host-RAM tier behind the trie
(docs/serving.md "Hierarchical KV-cache tiering"), and tier-targeted
faults — `kill_demotion@step` (die mid-spill), `kill_promotion@step`
(die mid-fill) and `corrupt_host_block@step` (flip bytes in a spilled
block; the next promotion must fail sha256 verification and re-prefill
instead). Gates: zero lost requests, zero leaked blocks on BOTH tiers
(cross-tier check_integrity + drain-to-empty), bitwise survivors vs
the unfaulted tiering-on run, non-vacuous demote/promote churn, a
forced-promotion integrity catch on a corrupted host entry, and a
clean lock witness including the HostTierStore leaf lock.
`--kv-cache-dtype int8` reruns all of it over the quantized pool +
quantized spill (docs/serving.md "int8 KV blocks"), pinning that the
sha256 digest covers the codes+scales payload too.

    JAX_PLATFORMS=cpu python tools/chaos_serve.py --tiering --seed 0

`--tenants` switches to the multi-tenant autoscaling harness
(`run_chaos_tenants`): tenant-tagged traffic (WFQ admission, token
quotas) flows through a 3-replica fleet with the telemetry-driven
Autoscaler in the loop. The quiet opening parks one replica through an
evacuating autoscale shrink; `kill_replica` then lands on a SERVING
replica while the fleet is in that shrunken state — the one-survivor
window autoscaling creates — and a quota-exhaustion burst slams the
'burst' tenant's token window while the failover is still settling.
Gates: zero lost requests across park/kill/rejoin, zero leaked blocks
AND zero per-tenant census drift on every live pool
(check_integrity's tenant reconciliation), intra-tenant FCFS verified
from the recorded traces (reqtrace check_causality — WFQ may reorder
ACROSS tenants, never within one), non-vacuous quota rejects, the
shrink strictly before the kill and a probe-rejoin grow after it, and
a clean lock witness that actually saw the Autoscaler and
TenantRegistry locks.

    JAX_PLATFORMS=cpu python tools/chaos_serve.py --tenants --seed 0

`--deploy` switches to the rolling-deploy harness
(`run_chaos_deploy`): a 3-replica single-model fleet built over a
ModelRegistry runs TWO rollouts of a genuinely-different candidate
revision while traffic keeps flowing (docs/serving.md "Multi-model
serving and rolling deploys"). `kill_deploy@tick:r` kills replica r in
the one window plain kill_replica can't isolate — after the new
engine swapped in but BEFORE the canary parity gate ran — and it is
scheduled to land after another slot already swapped AND rejoined, so
the rollback must unwind a live serving slot (evict its new-revision
requests through the zero-lost failover, restore the warm old-weight
engine) and not just the corpse. The second rollout runs with the
fault budget exhausted and must commit. Gates: both deploys reach
their required terminal, the registry stays on the old revision after
the rollback and lands on the new one after the commit, zero lost
requests, zero leaked blocks, non-vacuous evacuating-drain KV
migrations, reqtrace causality clean (incl. the revision-pinning
invariant: no token from a revision the request was not admitted
under), and a lock witness that actually saw the DeployController and
ModelRegistry locks.

    JAX_PLATFORMS=cpu python tools/chaos_serve.py --deploy --seed 0

`--prefix-cache` reruns either harness on TEMPLATED prompts with
radix-trie block sharing enabled (docs/serving.md "Prefix caching") —
multi-replica mode additionally routes by prefix affinity so the
scheduled kill lands on the replica holding the shared blocks
mid-decode. All of the gates above must hold with refcounted sharing
active (scrub-frees taint instead of scrubbing blocks siblings still
hold; failover re-admission neither double-frees nor double-counts),
and the run asserts it was non-vacuous: zero trie hits is a failure.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import tempfile

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

DEFAULT_FAULTS = "nan_logits@4,stall@7:0.1,cache_corrupt@10,nan_logits@13"


def _lock_witness():
    """Fresh runtime lock witness + the statically predicted DAG
    (paddle_tpu/analysis/lockgraph.py over the committed
    lockgraph.json). Chaos runs execute entirely under the witness; the
    report gates on (a) the witnessed graph being cycle-free and (b)
    every witnessed edge being statically predicted."""
    import paddle_tpu
    from paddle_tpu.analysis import lockgraph
    from paddle_tpu.testing.locktrace import LockWitness

    root = os.path.dirname(os.path.dirname(
        os.path.abspath(paddle_tpu.__file__)))
    predicted = lockgraph.predicted_edges(root)
    return LockWitness(), predicted


def _audit_witness(witness, predicted, report: dict,
                   spans_path: str = "") -> None:
    """Fold the lock-order audit into a chaos report and gate on it.
    `spans_path` additionally persists the witnessed acquisition spans
    (perf_counter clock — the same clock reqtrace events use) so
    `tools/reqtrace.py --chrome OUT --locks spans.json` can overlay
    lock wait/hold tracks on the per-request timeline."""
    lock_rep = witness.report(predicted)
    report["lockgraph"] = {
        "acquisitions": lock_rep["acquisitions"],
        "witnessed_edges": [f"{e['src']} -> {e['dst']}"
                            for e in lock_rep["edges"]],
        "cycles": lock_rep["cycles"],
        "unpredicted_edges": lock_rep["unpredicted_edges"],
    }
    if spans_path:
        # written BEFORE the asserts: a failing run's spans are exactly
        # the ones the postmortem wants
        with open(spans_path, "w") as f:
            json.dump({"kind": "locktrace", "clock": "perf_counter",
                       "spans": witness.span_list()}, f)
        report["lockgraph"]["spans_path"] = spans_path
    assert not lock_rep["cycles"], \
        f"witnessed lock graph has cycles: {lock_rep['cycles']}"
    assert not lock_rep["unpredicted_edges"], \
        "witnessed lock edges the static analyzer did not predict " \
        f"(stale lockgraph model?): {lock_rep['unpredicted_edges']}"


def _build_model(vocab=97, hidden=32, layers=2, heads=4, seq=48):
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPT, GPTConfig
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=vocab, hidden_size=hidden,
                    num_layers=layers, num_heads=heads, max_seq_len=seq)
    m = GPT(cfg)
    m.eval()
    return m, cfg


def _await_rejoin(rs, steps: int, max_steps: int) -> None:
    """The survivors can drain a run's requests before a killed replica's
    restart backoff has passed (the faster the decode step, the sooner):
    keep the router's housekeeping ticking until no replica is DOWN or
    STARTING any more. A FAILED one never rejoins; the gates name it."""
    import time
    while any(str(s) in ("down", "starting") for s in rs.states().values()):
        rs.step()
        steps += 1
        assert steps <= max_steps, \
            f"a killed replica failed to rejoin in {max_steps} steps " \
            f"(states {rs.states()})"
        time.sleep(0.002)


def run_chaos(seed: int = 0, n_requests: int = 16,
              faults: str = DEFAULT_FAULTS, max_steps: int = 400,
              cancel_every: int = 0, prefix_cache: bool = False,
              witness_out: str = "") -> dict:
    """One seeded chaos run; returns the audit report dict. Raises
    AssertionError on a lost request, a leaked block, or a survivor
    whose tokens diverge from the unfaulted reference run.
    `prefix_cache=True` switches the workload to templated prompts and
    enables radix-trie block sharing, so the same gates now also cover
    refcounted shared blocks under faults: scrub-frees (cache_corrupt
    recovery) must taint, not scrub, blocks other requests still hold,
    and the audit's refcount/trie invariants must survive the churn.
    The run asserts the sharing was non-vacuous (hits > 0)."""
    from paddle_tpu.inference.serving import (EngineConfig, LLMEngine,
                                              SamplingParams)
    from paddle_tpu.testing.faults import ServingFaultInjector
    from paddle_tpu.testing.locktrace import (instrument_engine,
                                              instrument_obs)

    witness, predicted = _lock_witness()
    instrument_obs(witness)
    model, cfg = _build_model()
    rng = np.random.RandomState(seed)
    if prefix_cache:
        # templated mix: 2 fixed 16-token templates (4 full blocks),
        # unique 2..6-token suffixes — every other request shares a
        # prefix with a live or recently-freed sibling
        tpls = [rng.randint(0, cfg.vocab_size, (16,), dtype=np.int32)
                for _ in range(2)]
        specs = [(np.concatenate(
                    [tpls[i % 2],
                     rng.randint(0, cfg.vocab_size,
                                 (int(rng.randint(2, 6)),),
                                 dtype=np.int32)]),
                  int(rng.randint(4, 10))) for i in range(n_requests)]
    else:
        specs = [(rng.randint(0, cfg.vocab_size,
                              (int(rng.randint(3, 9)),), dtype=np.int32),
                  int(rng.randint(4, 10))) for _ in range(n_requests)]
    ecfg = EngineConfig(block_size=4, num_blocks=64, max_num_seqs=4,
                        max_waiting=n_requests,
                        admission_policy="shed_oldest",
                        cache_high_watermark=0.9,
                        enable_prefix_cache=prefix_cache)

    def drive(injector, do_cancel):
        eng = LLMEngine.from_model(model, ecfg, faults=injector)
        instrument_engine(eng, witness)
        # cancellation draws come from their own stream so the faulted
        # pass sees the same workload spec whether or not the reference
        # pass ran first
        crng = np.random.RandomState(seed + 1)
        pending = list(enumerate(specs))
        rids = {}
        cancelled = set()
        for i, (p, mt) in pending[:ecfg.max_num_seqs]:
            rids[i] = eng.add_request(p, SamplingParams(max_tokens=mt))
        pending = pending[ecfg.max_num_seqs:]
        steps = 0
        while eng.has_unfinished() or pending:
            eng.step()
            steps += 1
            assert steps <= max_steps, \
                f"engine failed to drain within {max_steps} steps"
            if steps % 2 == 0 and pending:      # staggered arrivals
                i, (p, mt) = pending.pop(0)
                rids[i] = eng.add_request(p, SamplingParams(max_tokens=mt))
            if do_cancel and cancel_every and steps % cancel_every == 0:
                live = [i for i, r in rids.items()
                        if not eng.get_request(r).finished
                        and i not in cancelled]
                if live:
                    victim = live[int(crng.randint(len(live)))]
                    eng.cancel(rids[victim])
                    cancelled.add(victim)
        return eng, rids, cancelled

    # reference pass: same workload, no faults and NO cancellations (it
    # defines the full-length expected tokens; also warms every jit
    # bucket so the faulted pass's watchdog never sees compile time)
    ref_eng, ref_rids, _ = drive(ServingFaultInjector(""), do_cancel=False)
    ref_eng.cache.check_integrity()
    ref_tokens = {i: list(ref_eng.get_request(r).output_ids)
                  for i, r in ref_rids.items()}

    injector = ServingFaultInjector(faults)
    eng, rids, cancelled = drive(injector, do_cancel=True)

    d = eng.stats.as_dict()
    unserved = d["shed"] + d["errors"] + d["timeouts"] + d["expired"]
    p99 = eng.stats.ttft_quantile(0.99)
    report = {
        "seed": seed, "requests": n_requests, "faults": faults,
        "fired": list(injector.fired_log),
        "stats": {k: v for k, v in d.items()
                  if isinstance(v, int) and v},
        "cache": eng.cache.stats(),
        # serving SLO view (same definitions as tools/load_suite.py):
        # reject_rate counts every submitted request the engine did not
        # serve to completion for an engine-side reason
        "slo": {"ttft_p99_s": None if math.isnan(p99) else round(p99, 4),
                "reject_rate": round(unserved / max(n_requests, 1), 4)},
    }
    if prefix_cache:
        ps = eng.cache.prefix_stats()
        report["prefix"] = {k: ps[k] for k in
                           ("hits", "misses", "evictions", "cow_forks",
                            "cached_tokens_total", "prompt_tokens_total",
                            "shared_blocks", "evictable_blocks")}
        assert ps["hits"] > 0, \
            "prefix-cache chaos run was vacuous: zero trie hits"
    # 1. no lost requests: every id terminal
    lost = [i for i, r in rids.items() if not eng.get_request(r).finished]
    assert not lost, f"non-terminal requests after drain: {lost}"
    # 2. zero leaked blocks (with prefix_cache this also audits
    #    refcount-vs-table drift, taint hygiene and trie structure)
    report["integrity"] = eng.cache.check_integrity()
    # 3. survivors (normal completions, not cancelled here or there)
    #    match the unfaulted run bitwise
    mismatched = []
    survivors = 0
    for i, r in rids.items():
        req = eng.get_request(r)
        if req.state not in ("finished_stopped", "finished_length") \
                or i in cancelled:
            continue
        survivors += 1
        if list(req.output_ids) != ref_tokens[i]:
            # the trace id names the request's causal timeline in the
            # flight dump — the postmortem starts from here
            mismatched.append({"request": i, "trace_id": req.tid})
    report["survivors"] = survivors
    assert not mismatched, \
        f"survivor token divergence vs unfaulted run: {mismatched}"
    # 4. lock-order witness: cycle-free, and every witnessed edge was
    #    statically predicted (docs/static_analysis.md, PT-C002)
    _audit_witness(witness, predicted, report,
                   spans_path=witness_out)
    return report


DEFAULT_TIERING_FAULTS = \
    "kill_demotion@4,kill_promotion@8,corrupt_host_block@12"


def run_chaos_tiering(seed: int = 0, n_requests: int = 20,
                      faults: str = DEFAULT_TIERING_FAULTS,
                      max_steps: int = 600, cancel_every: int = 0,
                      witness_out: str = "",
                      kv_cache_dtype: str = "float32") -> dict:
    """One seeded hierarchical-tiering chaos run (docs/serving.md
    "Hierarchical KV-cache tiering"): templated traffic against a
    device pool far smaller than the prefix working set, with a host
    KV tier behind the trie, while tier-targeted faults kill demotions
    mid-spill (`kill_demotion`), kill promotions mid-fill
    (`kill_promotion`) and silently flip bytes in a spilled host block
    (`corrupt_host_block`). The audit gates:

    - zero lost requests: every id terminal — a failed demotion simply
      frees the block, a failed/corrupted promotion degrades to
      ordinary re-prefill of the missing suffix;
    - zero leaked blocks on BOTH tiers: cross-tier check_integrity
      clean (host_orphans/host_leaked included), and after
      clear_prefix_cache the run asserts blocks_allocated ==
      blocks_freed AND an empty host store;
    - bitwise survivors: completed requests match the unfaulted
      tiering-on run token-for-token (a promoted prefix restores the
      exact spilled bytes; anything less fails digest verification and
      re-prefills);
    - non-vacuous: the run must demote, attempt promotions, and fire
      every scheduled tier fault;
    - a corrupted host payload must be CAUGHT: the in-traffic
      corrupt_host_block flips the LRU-oldest spill (which this
      workload may never re-request), so after the drive the harness
      ALSO corrupts a still-resident host entry and forces promotion
      of its exact token path — the sha256 check must trip and drop
      the entry; with kv_cache_dtype="int8" this pins that the
      QUANTIZED spill payload (codes + scale rows under one digest)
      still trips the integrity check, not just the f32 layout;
    - lock-order witness (HostTierStore leaf lock included):
      cycle-free, statically predicted."""
    from paddle_tpu.inference.serving import (EngineConfig, LLMEngine,
                                              SamplingParams)
    from paddle_tpu.testing.faults import ServingFaultInjector
    from paddle_tpu.testing.locktrace import (instrument_engine,
                                              instrument_obs)

    witness, predicted = _lock_witness()
    instrument_obs(witness)
    model, cfg = _build_model()
    rng = np.random.RandomState(seed)
    # 4 templates x 16 tokens = 16 full trie blocks of working set
    # against a 32-block device pool that also holds 4 live requests'
    # tables. Phased revisit order: seed templates 0/1, churn on 2/3
    # long enough that pool pressure demotes 0/1 to the host tier,
    # then revisit 0/1 — their blocks must come back via promotion
    # (n_requests=20 is tuned to make both phases non-vacuous)
    tpls = [rng.randint(0, cfg.vocab_size, (16,), dtype=np.int32)
            for _ in range(4)]
    order = ([0, 0, 1, 1]
             + [2, 3] * max((n_requests - 8) // 2, 1)
             + [0, 1, 0, 1])
    order = (order + [i % 4 for i in range(n_requests)])[:n_requests]
    specs = [(np.concatenate(
                [tpls[order[i]],
                 rng.randint(0, cfg.vocab_size,
                             (int(rng.randint(2, 6)),),
                             dtype=np.int32)]),
              int(rng.randint(4, 10))) for i in range(n_requests)]
    ecfg = EngineConfig(block_size=4, num_blocks=32, max_num_seqs=4,
                        max_waiting=n_requests,
                        admission_policy="shed_oldest",
                        cache_high_watermark=0.9,
                        enable_prefix_cache=True,
                        host_tier_blocks=64,
                        kv_cache_dtype=kv_cache_dtype)

    def drive(injector, do_cancel):
        eng = LLMEngine.from_model(model, ecfg, faults=injector)
        instrument_engine(eng, witness)
        crng = np.random.RandomState(seed + 1)
        pending = list(enumerate(specs))
        rids = {}
        cancelled = set()
        for i, (p, mt) in pending[:ecfg.max_num_seqs]:
            rids[i] = eng.add_request(p, SamplingParams(max_tokens=mt))
        pending = pending[ecfg.max_num_seqs:]
        steps = 0
        while eng.has_unfinished() or pending:
            eng.step()
            steps += 1
            assert steps <= max_steps, \
                f"engine failed to drain within {max_steps} steps"
            if steps % 2 == 0 and pending:      # staggered arrivals
                i, (p, mt) = pending.pop(0)
                rids[i] = eng.add_request(p, SamplingParams(max_tokens=mt))
            if do_cancel and cancel_every and steps % cancel_every == 0:
                live = [i for i, r in rids.items()
                        if not eng.get_request(r).finished
                        and i not in cancelled]
                if live:
                    victim = live[int(crng.randint(len(live)))]
                    eng.cancel(rids[victim])
                    cancelled.add(victim)
        return eng, rids, cancelled

    # reference pass: same workload, tiering ON, no faults — survivors
    # compare against healthy demote/promote cycles, so the comparison
    # also pins promotion bitwise-invariance
    ref_eng, ref_rids, _ = drive(ServingFaultInjector(""),
                                 do_cancel=False)
    ref_eng.cache.check_integrity()
    ref_ps = ref_eng.cache.prefix_stats()
    assert ref_ps["tier_demotions"] > 0, \
        "tiering reference run never demoted — device pool too large " \
        "for the working set (vacuous)"
    ref_tokens = {i: list(ref_eng.get_request(r).output_ids)
                  for i, r in ref_rids.items()}

    injector = ServingFaultInjector(faults)
    scheduled = {k for k, _s, _a in injector.faults}
    eng, rids, cancelled = drive(injector, do_cancel=True)

    d = eng.stats.as_dict()
    unserved = d["shed"] + d["errors"] + d["timeouts"] + d["expired"]
    p99 = eng.stats.ttft_quantile(0.99)
    ps = eng.cache.prefix_stats()
    promotes = {k: ps[f"promote_{k}"]
                for k in ("hit", "timeout", "integrity", "raced")}
    pp99 = eng.stats.promote_quantile(0.99)
    report = {
        "seed": seed, "requests": n_requests, "faults": faults,
        "fired": list(injector.fired_log),
        "stats": {k: v for k, v in d.items()
                  if isinstance(v, int) and v},
        "cache": eng.cache.stats(),
        "host_tier": eng.cache.host_tier.stats(),
        "prefix": {k: ps[k] for k in
                   ("hits", "misses", "evictions", "cow_forks",
                    "host_blocks", "tier_demotions")},
        "promotions": promotes,
        "slo": {"ttft_p99_s": None if math.isnan(p99) else round(p99, 4),
                "promote_p99_s": None if math.isnan(pp99)
                else round(pp99, 4),
                "reject_rate": round(unserved / max(n_requests, 1), 4)},
    }
    # 1. no lost requests: every id terminal — a misbehaving cache
    #    tier must degrade to re-prefill, never wedge a request
    lost = [i for i, r in rids.items()
            if not eng.get_request(r).finished]
    assert not lost, f"non-terminal requests after drain: {lost}"
    # 2. cross-tier zero-leak: device audit + host_orphans/host_leaked
    report["integrity"] = eng.cache.check_integrity()
    # 3. bitwise survivors vs the unfaulted tiering-on run
    mismatched, survivors = [], 0
    for i, r in rids.items():
        req = eng.get_request(r)
        if req.state not in ("finished_stopped", "finished_length") \
                or i in cancelled:
            continue
        survivors += 1
        if list(req.output_ids) != ref_tokens[i]:
            mismatched.append({"request": i, "trace_id": req.tid})
    report["survivors"] = survivors
    assert not mismatched, \
        f"survivor token divergence vs unfaulted run: {mismatched}"
    # 4. non-vacuous: tier churn happened and every scheduled tier
    #    fault actually fired (a corrupt_host_block that never found a
    #    resident host block, or a kill_promotion that never saw a
    #    promotion, tested nothing)
    assert ps["tier_demotions"] > 0, \
        "faulted tiering run never demoted — vacuous"
    assert sum(promotes.values()) > 0, \
        "faulted tiering run never attempted a promotion — vacuous"
    fired_kinds = {k for k, _s in injector.fired_log}
    missing = scheduled - fired_kinds
    assert not missing, \
        f"scheduled tier faults never fired: {sorted(missing)}"
    # 4b. the corruption contract must be CAUGHT, deterministically:
    #    the in-traffic fault flips the LRU-oldest spill, which this
    #    workload may never re-request, so corrupt a still-resident
    #    host entry (shortest host run, so its promotion is attempted
    #    first) and force-promote its exact token path — the sha256
    #    check must trip and drop the entry. Under
    #    kv_cache_dtype="int8" this pins that the QUANTIZED payload
    #    (codes + trailing scale rows) is covered by the digest.
    if "corrupt_host_block" in scheduled:
        idx = eng.cache.prefix_index
        best = None
        for hid in eng.cache.host_tier.ids():
            node = idx.node_of_host(hid)
            if node is None:
                continue
            path, n = [], node
            while n is not None and n.key is not None:
                path.append(n)
                n = n.parent
            host_run = 0
            for n in path:                     # leaf-ward: node first
                if n.tier == "host":
                    host_run += 1
                else:
                    break
            if best is None or host_run < best[0]:
                best = (host_run, hid, list(reversed(path)))
        assert best is not None, \
            "corrupt_host_block scheduled but no host entry still " \
            "resident to pin the integrity contract on"
        _hr, hid, path = best
        toks = [t for n in path for t in n.key]
        k0 = eng.cache.host_tier.get(hid)["payload"][0][0]
        k0.flat[0] = k0.flat[0] + 1.0          # torn RAM, stale digest
        pre = eng.cache.tier_promotions["integrity"]
        # +1 sentinel: ensure_promoted drops the trailing (uncached)
        # decode token before matching
        res = eng.cache.ensure_promoted(toks + [0])
        assert res is not None and "integrity" in res["outcomes"], \
            f"forced promotion of a corrupted host payload was " \
            f"silently admitted (outcomes: " \
            f"{res and res['outcomes']}) — digest does not cover " \
            f"the {eng.cache.kv_cache_dtype} payload"
        assert eng.cache.tier_promotions["integrity"] == pre + 1
        ps = eng.cache.prefix_stats()
        report["promotions"] = {
            k: ps[f"promote_{k}"]
            for k in ("hit", "timeout", "integrity", "raced")}
        report["forced_integrity_catch"] = {
            "host_id": hid, "blocks_deep": len(path),
            "kv_cache_dtype": eng.cache.kv_cache_dtype}
    # 5. both tiers drain to empty: the trie releases every cached
    #    device block, the host store every spilled payload, and the
    #    free-list crossing counters must balance exactly
    eng.cache.clear_prefix_cache()
    assert eng.cache.blocks_allocated == eng.cache.blocks_freed, \
        f"device-tier leak after drain+clear: allocated " \
        f"{eng.cache.blocks_allocated} != freed {eng.cache.blocks_freed}"
    assert len(eng.cache.host_tier) == 0, \
        f"host-tier leak after clear: {len(eng.cache.host_tier)} " \
        f"entries still resident"
    # 6. lock-order witness (HostTierStore._lock rides as a leaf under
    #    the engine/scheduler frame): cycle-free, statically predicted
    _audit_witness(witness, predicted, report,
                   spans_path=witness_out)
    return report


DEFAULT_REPLICA_FAULTS = "kill_replica@6:1,nan_logits@10,stall@12:0.05"


def run_chaos_replicas(seed: int = 0, n_requests: int = 24,
                       replicas: int = 3,
                       faults: str = DEFAULT_REPLICA_FAULTS,
                       max_steps: int = 4000,
                       prefix_cache: bool = False,
                       witness_out: str = "") -> dict:
    """One seeded multi-replica chaos run (module docstring). Raises
    AssertionError on a lost request, a leaked block on any live
    replica, an untouched-replica token divergence, or a faulted
    replica that fails to rejoin and serve again. `prefix_cache=True`
    runs templated traffic with trie sharing on and routes by prefix
    affinity, so the kill lands on a replica holding SHARED blocks
    mid-decode: failover re-admission must neither double-free nor
    double-count them (the zero-lost + zero-leak gates now cover
    refcounted sharing), and the run must record trie hits."""
    import time

    from paddle_tpu.inference.serving import (EngineConfig, ReplicaSet,
                                              RouterConfig,
                                              SamplingParams)
    from paddle_tpu.testing.faults import ServingFaultInjector
    from paddle_tpu.testing.locktrace import instrument_fleet

    witness, predicted = _lock_witness()
    model, cfg = _build_model()
    rng = np.random.RandomState(seed)
    if prefix_cache:
        # templated mix (see run_chaos): with prefix-affinity routing
        # each template's requests pile onto ONE replica, so the
        # scheduled kill hits live shared-prefix decodes, not strays
        tpls = [rng.randint(0, cfg.vocab_size, (16,), dtype=np.int32)
                for _ in range(2)]
        specs = [(np.concatenate(
                    [tpls[i % 2],
                     rng.randint(0, cfg.vocab_size,
                                 (int(rng.randint(2, 6)),),
                                 dtype=np.int32)]),
                  int(rng.randint(6, 12))) for i in range(n_requests)]
    else:
        specs = [(rng.randint(0, cfg.vocab_size,
                              (int(rng.randint(3, 9)),), dtype=np.int32),
                  int(rng.randint(6, 12))) for _ in range(n_requests)]
    # decode_chunk_size=2 keeps requests in flight across many router
    # steps so mid-traffic faults land on live work
    ecfg = EngineConfig(block_size=4, num_blocks=32, max_num_seqs=4,
                        decode_chunk_size=2,
                        enable_prefix_cache=prefix_cache)

    def router_config():
        # tight backoff so a killed replica's restart lands inside the
        # run; heartbeat small enough that a wedged replica is caught
        # while survivors still hold its failed-over work
        return RouterConfig(num_replicas=replicas,
                            heartbeat_timeout_s=0.02,
                            backoff_base=0.01, backoff_max=0.05,
                            backoff_jitter=0.0,
                            balance=("prefix_affinity" if prefix_cache
                                     else "free_blocks"))

    def drive(injector):
        rs = ReplicaSet.from_model(model, router_config(),
                                   engine_config=ecfg, faults=injector)
        instrument_fleet(rs, witness)
        pending = list(enumerate(specs))
        rids, homes = {}, {}
        for i, (p, mt) in pending[:2 * replicas]:
            rids[i] = rs.add_request(p, SamplingParams(max_tokens=mt))
            homes[i] = rs.get_request(rids[i]).replica
        pending = pending[2 * replicas:]
        steps = 0
        while rs.has_unfinished() or pending:
            rs.step()
            steps += 1
            assert steps <= max_steps, \
                f"router failed to drain within {max_steps} steps"
            if steps % 2 == 0 and pending:      # staggered arrivals
                i, (p, mt) = pending.pop(0)
                rids[i] = rs.add_request(p, SamplingParams(max_tokens=mt))
                homes[i] = rs.get_request(rids[i]).replica
            if not any(r.has_unfinished() for r in rs.replicas) \
                    and rs.has_unfinished():
                time.sleep(0.002)               # restart backoff pending
        _await_rejoin(rs, steps, max_steps)
        return rs, rids, homes

    # reference pass: same workload through an unfaulted router (defines
    # expected tokens; greedy tokens depend only on the prompt, so the
    # comparison is routing-independent)
    ref_rs, ref_rids, _ = drive(ServingFaultInjector(""))
    for idx, audit in ref_rs.check_integrity().items():
        assert audit is not None, f"reference replica {idx} lost engine"
    ref_tokens = {i: list(ref_rs.get_request(r).tokens)
                  for i, r in ref_rids.items()}

    injector = ServingFaultInjector(faults)
    targeted = sorted({(0 if arg is None or arg != arg else int(arg))
                       for k, s, arg in injector.faults
                       if k in ("kill_replica", "wedge_replica")})
    rs, rids, homes = drive(injector)

    st = rs.router_stats()
    p99 = rs.ttft_quantile(0.99)
    unserved = sum(v for k, v in st["finish_reasons"].items()
                   if k not in ("stop", "length"))
    report = {
        "seed": seed, "requests": n_requests, "replicas": replicas,
        "faults": faults, "fired": list(injector.fired_log),
        "targeted_replicas": targeted,
        "requeues": st["requeues"],
        "finish_reasons": st["finish_reasons"],
        "replica_states": {k: str(v)
                           for k, v in st["replica_states"].items()},
        "recovery_times_s": st["recovery_times_s"],
        # router-level SLO view, same definitions as the single-engine
        # report: TTFT is client-visible (across failovers)
        "slo": {"ttft_p99_s": None if math.isnan(p99) else round(p99, 4),
                "reject_rate": round(unserved / max(n_requests, 1), 4)},
    }
    if prefix_cache:
        fps = rs.prefix_stats()
        report["prefix"] = {k: fps[k] for k in
                            ("hits", "misses", "evictions", "cow_forks",
                             "cached_tokens_total",
                             "prompt_tokens_total")}
        assert fps["hits"] > 0, \
            "prefix-cache replica chaos run was vacuous: zero trie hits"
    # 1. no lost requests: every id terminal
    lost = [i for i, r in rids.items()
            if not rs.get_request(r).finished]
    assert not lost, f"non-terminal requests after drain: {lost}"
    # 2. zero leaked blocks on every live replica (a faulted replica
    #    must be live again by now — gate 4 — so None is a failure)
    report["integrity"] = rs.check_integrity()
    for idx, audit in report["integrity"].items():
        assert audit is not None, \
            f"replica {idx} ended the run without a live engine"
    # 3. untouched-replica requests match the unfaulted run bitwise
    #    (never requeued AND homed on a never-faulted replica)
    mismatched, untouched = [], 0
    for i, r in rids.items():
        rec = rs.get_request(r)
        if rec.requeues or homes[i] in targeted \
                or rec.finish_reason not in ("stop", "length"):
            continue
        untouched += 1
        if list(rec.tokens) != ref_tokens[i]:
            # trace id = the request's causal timeline in the flight
            # dump (tools/reqtrace.py --timeline <id>)
            mismatched.append({"request": i, "trace_id": rec.trace_id})
    report["untouched_survivors"] = untouched
    assert not mismatched, \
        f"untouched-replica token divergence vs unfaulted run: {mismatched}"
    # 4. every faulted replica rejoined (warmup probe passed) and serves
    #    a canary request end-to-end in this same run
    for idx in targeted:
        assert str(rs.states()[idx]) == "up", \
            f"faulted replica {idx} did not rejoin (state " \
            f"{rs.states()[idx]})"
    for other in range(replicas):
        if other not in targeted:
            rs.drain(other)
    canaries = {}
    for idx in targeted:
        rid = rs.add_request(specs[0][0], SamplingParams(max_tokens=2))
        canaries[idx] = rid
        assert rs.get_request(rid).replica == idx, \
            f"canary for rejoined replica {idx} routed to " \
            f"{rs.get_request(rid).replica}"
    steps = 0
    while rs.has_unfinished():
        rs.step()
        steps += 1
        assert steps <= max_steps, "canary requests failed to drain"
    for idx, rid in canaries.items():
        reason = rs.get_request(rid).finish_reason
        assert reason in ("stop", "length"), \
            f"rejoined replica {idx} canary ended {reason!r}"
    for other in range(replicas):
        if other not in targeted:
            rs.undrain(other)
    report["canaries_served"] = len(canaries)
    # 5. lock-order witness over the whole fleet (incl. the restarted
    #    incarnations the traced factories instrumented): cycle-free
    #    and fully predicted by the static DAG
    _audit_witness(witness, predicted, report,
                   spans_path=witness_out)
    return report


DEFAULT_DISAGG_FAULTS = "kill_migration@3:0,kill_migration@7:0"


def run_chaos_disagg(seed: int = 0, n_requests: int = 18,
                     replicas: int = 3,
                     faults: str = DEFAULT_DISAGG_FAULTS,
                     max_steps: int = 4000,
                     witness_out: str = "") -> dict:
    """One seeded disaggregated-serving chaos run: a prefill-tier
    replica 0 hands every prefill-complete request off to the decode
    tier via live KV-block migration, while `kill_migration@step:0`
    kills the SOURCE inside the commit window (between destination
    admit and source release — the one window `kill_replica` can never
    reach, because the replica's own step claims that fault first).
    The audit gates on docs/serving.md "Disaggregated serving and
    block migration":

    - zero lost requests: the half-migrated victim's destination copy
      is rolled back and the router re-prefills it from its
      authoritative token log, so every id still reaches a terminal
      state;
    - zero leaked blocks on BOTH ends of every migration (router-wide
      check_integrity — the rolled-back destination must not strand
      its freshly imported blocks, the dead source's restart must come
      up clean);
    - bitwise survivors: EVERY completed request — migrated, re-
      prefilled after the mid-migration kill, or untouched — matches
      the unfaulted disaggregated run token-for-token (migration
      invariance + replay invariance compose);
    - non-vacuous: the run must commit handoffs AND roll at least one
      migration back when the spec schedules a kill_migration;
    - lock-order witness: the migration coordinator's cross-replica
      edges (BlockMigration -> EngineReplica -> ...) are cycle-free
      and statically predicted."""
    import time

    from paddle_tpu.inference.serving import (EngineConfig, ReplicaSet,
                                              RouterConfig,
                                              SamplingParams)
    from paddle_tpu.testing.faults import ServingFaultInjector
    from paddle_tpu.testing.locktrace import instrument_fleet

    if replicas < 2:
        raise ValueError("disaggregated chaos needs >= 2 replicas "
                         "(one prefill, one+ decode)")
    witness, predicted = _lock_witness()
    model, cfg = _build_model()
    rng = np.random.RandomState(seed)
    specs = [(rng.randint(0, cfg.vocab_size,
                          (int(rng.randint(4, 12)),), dtype=np.int32),
              int(rng.randint(8, 16))) for _ in range(n_requests)]
    # decode_chunk_size=2 keeps migrated requests decoding across many
    # router steps, so the scheduled kill lands on live handoffs
    ecfg = EngineConfig(block_size=4, num_blocks=48, max_num_seqs=4,
                        decode_chunk_size=2, enable_prefix_cache=True)
    roles = ("prefill",) + ("decode",) * (replicas - 1)

    def router_config():
        return RouterConfig(num_replicas=replicas, roles=roles,
                            heartbeat_timeout_s=0.02,
                            backoff_base=0.01, backoff_max=0.05,
                            backoff_jitter=0.0)

    def drive(injector):
        rs = ReplicaSet.from_model(model, router_config(),
                                   engine_config=ecfg, faults=injector)
        instrument_fleet(rs, witness)
        pending = list(enumerate(specs))
        rids = {}
        for i, (p, mt) in pending[:2 * replicas]:
            rids[i] = rs.add_request(p, SamplingParams(max_tokens=mt))
        pending = pending[2 * replicas:]
        steps = 0
        while rs.has_unfinished() or pending:
            rs.step()
            steps += 1
            assert steps <= max_steps, \
                f"router failed to drain within {max_steps} steps"
            if steps % 2 == 0 and pending:      # staggered arrivals
                i, (p, mt) = pending.pop(0)
                rids[i] = rs.add_request(p, SamplingParams(max_tokens=mt))
            if not any(r.has_unfinished() for r in rs.replicas) \
                    and rs.has_unfinished():
                time.sleep(0.002)               # restart backoff pending
        _await_rejoin(rs, steps, max_steps)
        return rs, rids

    # reference pass: same workload, same tiers, no faults — handoffs
    # still happen, so the comparison also pins migration invariance
    ref_rs, ref_rids = drive(ServingFaultInjector(""))
    assert ref_rs.migrator.stats()["migrations"] > 0, \
        "disagg reference run committed no handoffs — vacuous tiering"
    ref_tokens = {i: list(ref_rs.get_request(r).tokens)
                  for i, r in ref_rids.items()}

    injector = ServingFaultInjector(faults)
    scheduled_kills = sum(1 for k, _s, _a in injector.faults
                          if k == "kill_migration")
    rs, rids = drive(injector)

    st = rs.router_stats()
    mig = rs.migrator.stats()
    p99 = rs.ttft_quantile(0.99)
    unserved = sum(v for k, v in st["finish_reasons"].items()
                   if k not in ("stop", "length"))
    report = {
        "seed": seed, "requests": n_requests, "replicas": replicas,
        "roles": list(roles), "faults": faults,
        "fired": list(injector.fired_log),
        "migrations": mig,
        "requeues": st["requeues"],
        "finish_reasons": st["finish_reasons"],
        "replica_states": {k: str(v)
                           for k, v in st["replica_states"].items()},
        "slo": {"ttft_p99_s": None if math.isnan(p99) else round(p99, 4),
                "reject_rate": round(unserved / max(n_requests, 1), 4)},
    }
    # 1. no lost requests — and stronger than the failover harness:
    #    every id must actually COMPLETE (stop/length), because the
    #    only faults here are mid-migration kills and the victim always
    #    re-prefills from the router's authoritative token log
    lost = [i for i, r in rids.items()
            if rs.get_request(r).finish_reason not in ("stop", "length")]
    assert not lost, f"requests lost or errored after drain: {lost}"
    # 2. zero leaked blocks on BOTH ends: check_integrity raises on any
    #    violation, and a replica that ended without a live engine is
    #    itself a failure (the killed source must have restarted)
    report["integrity"] = rs.check_integrity()
    for idx, audit in report["integrity"].items():
        assert audit is not None, \
            f"replica {idx} ended the run without a live engine"
    # 3. bitwise survivors: every completed request matches the
    #    unfaulted disaggregated run — migrated, re-prefilled or not
    mismatched, survivors = [], 0
    for i, r in rids.items():
        rec = rs.get_request(r)
        if rec.finish_reason not in ("stop", "length"):
            continue
        survivors += 1
        if list(rec.tokens) != ref_tokens[i]:
            mismatched.append({"request": i, "trace_id": rec.trace_id})
    report["survivors"] = survivors
    assert not mismatched, \
        f"survivor token divergence vs unfaulted run: {mismatched}"
    # 4. non-vacuous: handoffs committed, and the scheduled
    #    mid-migration kill actually rolled a destination back
    assert mig["migrations"] > 0, \
        "disagg chaos run committed no handoffs — vacuous tiering"
    if scheduled_kills:
        assert mig["rolled_back"] > 0, \
            "kill_migration was scheduled but no migration rolled " \
            "back — the fault never landed in the commit window"
    # 5. lock-order witness across the migration coordinator's
    #    cross-replica call path: cycle-free, statically predicted
    _audit_witness(witness, predicted, report,
                   spans_path=witness_out)
    return report


DEFAULT_TENANT_FAULTS = "kill_replica@26:1"


def run_chaos_tenants(seed: int = 0, n_requests: int = 24,
                      replicas: int = 3,
                      faults: str = DEFAULT_TENANT_FAULTS,
                      max_steps: int = 4000,
                      witness_out: str = "") -> dict:
    """One seeded multi-tenant autoscaling chaos run (module
    docstring). The schedule is built so the fault lands in the window
    the autoscaler itself creates: a quiet opening lets the idle-shrink
    park replica 0 (evacuating drain), the kill then takes a SERVING
    replica while the fleet is shrunken, and a quota-exhaustion burst
    arrives while the failover is still settling — forcing a
    probe-rejoin grow of the parked slot. Raises AssertionError on a
    lost request, a leaked block or per-tenant census drift on any live
    pool, an intra-tenant FCFS violation in the recorded traces, a
    vacuous run (no shrink / no grow / no quota reject / kill before
    the shrink), or a lock-order finding that misses the Autoscaler and
    TenantRegistry locks."""
    import time

    from paddle_tpu import obs
    from paddle_tpu.inference.serving import (
        Autoscaler, AutoscalerConfig, EngineConfig, ReplicaSet,
        RouterConfig, SamplingParams, TenantConfig, TenantQuotaExceeded,
        TenantRegistry)
    from paddle_tpu.testing.faults import ServingFaultInjector
    from paddle_tpu.testing.locktrace import instrument_autoscaler

    witness, predicted = _lock_witness()
    model, cfg = _build_model()
    rng = np.random.RandomState(seed)
    obs.reqtrace.enable()

    # three contracts: a latency tenant, a batch tenant, and a
    # quota-bounded tenant whose burst is MEANT to overdraw its window
    reg = TenantRegistry([
        TenantConfig("alpha", priority="latency"),
        TenantConfig("bulk", priority="batch"),
        TenantConfig("burst", quota_tokens=80, quota_window_s=300.0),
    ])
    ecfg = EngineConfig(block_size=4, num_blocks=48, max_num_seqs=4,
                        decode_chunk_size=2, max_waiting=64,
                        enable_prefix_cache=True, tenants=reg)
    rcfg = RouterConfig(num_replicas=replicas,
                        heartbeat_timeout_s=0.02,
                        backoff_base=0.01, backoff_max=0.05,
                        backoff_jitter=0.0)

    # arrival schedule keyed by ROUTER step. Steps 0..3 are silent so
    # the idle-shrink parks a slot before any work exists; a
    # latency/batch trickle then keeps the shrunken fleet busy through
    # the scheduled kill; the burst-tenant flood (templated prompts —
    # the trie census gates stay non-vacuous) lands two steps after it.
    tpl = rng.randint(0, cfg.vocab_size, (8,), dtype=np.int32)
    schedule = {}
    # trickle arrivals every 2 steps past the kill step, so the fault
    # hits a replica holding LIVE decodes and the failover is real
    n_trickle = max(12, n_requests - 10)
    for j in range(n_trickle):
        tenant = "alpha" if j % 2 == 0 else "bulk"
        plen = int(rng.randint(4, 8)) if tenant == "alpha" \
            else int(rng.randint(10, 15))
        p = rng.randint(0, cfg.vocab_size, (plen,), dtype=np.int32)
        schedule.setdefault(4 + 2 * j, []).append(
            (tenant, p, int(rng.randint(6, 11))))
    n_burst = 10
    for j in range(n_burst):
        sfx = rng.randint(0, cfg.vocab_size,
                          (int(rng.randint(2, 5)),), dtype=np.int32)
        schedule.setdefault(28, []).append(
            ("burst", np.concatenate([tpl, sfx]), 6))
    last_arrival = max(schedule)

    injector = ServingFaultInjector(faults)
    kill_targets = sorted({(0 if arg is None or arg != arg else int(arg))
                           for k, s, arg in injector.faults
                           if k == "kill_replica"})
    rs = ReplicaSet.from_model(model, rcfg, engine_config=ecfg,
                               faults=injector)
    asc = Autoscaler(rs, AutoscalerConfig(
        min_replicas=max(1, replicas - 1), max_replicas=replicas,
        target_waiting_per_replica=3.0, low_waiting_per_replica=1.0,
        min_headroom_frac=0.05, cooldown_steps=4))
    instrument_autoscaler(asc, witness)

    rids, quota_rejects, retry_hints = {}, 0, []
    submitted = 0
    kill_obs = None
    fleet_series = [(0, rs.num_up())]
    step = 0
    while step <= last_arrival or rs.has_unfinished():
        for tenant, p, mt in schedule.get(step, ()):
            submitted += 1
            try:
                rid = rs.add_request(
                    p, SamplingParams(max_tokens=mt, tenant=tenant))
                rids[(tenant, len(rids))] = rid
            except TenantQuotaExceeded as e:
                quota_rejects += 1
                retry_hints.append(e.retry_after_s)
        kills_before = sum(1 for k, _s in injector.fired_log
                           if k == "kill_replica")
        rs.step()
        if sum(1 for k, _s in injector.fired_log
               if k == "kill_replica") > kills_before:
            kill_obs = {
                "step": step,
                "parked_at_kill": sum(
                    1 for r in rs.replicas
                    if str(rs.states()[r.index]) == "drained"),
                "shrinks_before_kill": asc.shrink_events,
            }
        decision = asc.step()
        if decision["enacted"]:
            fleet_series.append((step, rs.num_up()))
        step += 1
        assert step <= max_steps, \
            f"router failed to drain within {max_steps} steps"
        if not any(r.has_unfinished() for r in rs.replicas) \
                and rs.has_unfinished():
            time.sleep(0.002)               # restart backoff pending
    # the killed replica must restart and rejoin within the run: keep
    # the housekeeping loop (and the autoscaler) ticking until it does
    for idx in kill_targets:
        while str(rs.states()[idx]) not in ("up", "drained"):
            rs.step()
            asc.step()
            step += 1
            assert step <= max_steps, \
                f"killed replica {idx} failed to rejoin in " \
                f"{max_steps} steps (state {rs.states()[idx]})"
            time.sleep(0.002)

    st = rs.router_stats()
    p99 = rs.ttft_quantile(0.99)
    unserved = sum(v for k, v in st["finish_reasons"].items()
                   if k not in ("stop", "length"))
    report = {
        "seed": seed, "requests": submitted, "replicas": replicas,
        "faults": faults, "fired": list(injector.fired_log),
        "tenants": sorted(reg.names()),
        "quota_rejects": quota_rejects,
        "retry_after_hints": [round(h, 4) for h in retry_hints
                              if h is not None],
        "autoscaler": {"grow_events": asc.grow_events,
                       "shrink_events": asc.shrink_events,
                       "final_active": rs.num_up(),
                       "fleet_series": fleet_series},
        "kill": kill_obs,
        "requeues": st["requeues"],
        "finish_reasons": st["finish_reasons"],
        "replica_states": {k: str(v)
                           for k, v in st["replica_states"].items()},
        "slo": {"ttft_p99_s": None if math.isnan(p99) else round(p99, 4),
                "reject_rate": round((unserved + quota_rejects)
                                     / max(submitted, 1), 4)},
    }
    # 1. zero lost: every ADMITTED request is terminal and served —
    #    across the autoscale park, the kill's failover, and the rejoin
    lost = [k for k, r in rids.items()
            if rs.get_request(r).finish_reason not in ("stop", "length")]
    assert not lost, f"admitted requests not served after drain: {lost}"
    # 2. zero leaked blocks AND zero per-tenant census drift on every
    #    pool that is still live (parked slots keep their engine warm;
    #    the killed slot's fresh incarnation audits clean by gate 5)
    report["integrity"] = rs.check_integrity()
    for idx, audit in report["integrity"].items():
        assert audit is not None, \
            f"replica {idx} ended the run without a live engine"
        assert not audit.get("tenant_drift"), \
            f"replica {idx}: per-tenant census drift {audit['tenant_drift']}"
    # 3. quota enforcement was non-vacuous and actionable: the burst
    #    tenant overdrew its window, every refusal carried a retry hint
    assert quota_rejects > 0, \
        "quota chaos run was vacuous: burst tenant never hit its window"
    assert len(report["retry_after_hints"]) == quota_rejects, \
        "quota refusal without a retry_after_s hint"
    # 4. the autoscaler actually exercised both directions, and the kill
    #    landed while the fleet was in the autoscale-shrunken state
    assert asc.shrink_events >= 1, "no autoscale shrink happened"
    assert asc.grow_events >= 1, \
        "no probe-rejoin grow happened (burst should have forced one)"
    assert kill_obs is not None, "kill_replica fault never fired"
    assert kill_obs["shrinks_before_kill"] >= 1 \
        and kill_obs["parked_at_kill"] >= 1, \
        f"kill missed the shrunken-fleet window: {kill_obs}"
    # 5. the killed replica rejoined
    for idx in kill_targets:
        assert str(rs.states()[idx]) in ("up", "drained"), \
            f"killed replica {idx} did not rejoin " \
            f"(state {rs.states()[idx]})"
    # 6. intra-tenant FCFS, machine-checked over the recorded traces:
    #    WFQ + failover may reorder ACROSS tenants, never within one
    dump = {"reason": "tenants_chaos", "complete": True,
            "events": [e.as_dict() for e in obs.reqtrace.events(
                prefix=f"tr-{rs.label}-")]}
    assert dump["events"], "reqtrace recorded nothing for this router"
    violations = obs.reqtrace.check_causality(dump)
    assert not violations, \
        f"causality violations (incl. intra-tenant FCFS): {violations}"
    report["causality_events"] = len(dump["events"])
    # 7. lock-order witness — and it must have actually SEEN the two
    #    locks this PR added to the order (a witness that never touched
    #    them would vacuously pass)
    _audit_witness(witness, predicted, report, spans_path=witness_out)
    seen = " ".join(report["lockgraph"]["witnessed_edges"])
    assert "Autoscaler._lock" in seen, \
        "witness never saw Autoscaler._lock"
    assert "TenantRegistry._lock" in seen, \
        "witness never saw TenantRegistry._lock"
    return report


DEFAULT_DEPLOY_FAULTS = "kill_deploy@1:1"


def run_chaos_deploy(seed: int = 0, n_requests: int = 24,
                     replicas: int = 3,
                     faults: str = DEFAULT_DEPLOY_FAULTS,
                     max_steps: int = 4000,
                     witness_out: str = "") -> dict:
    """One seeded rolling-deploy chaos run (module docstring). Two
    rollouts of the same candidate revision under continuous traffic:
    the first is killed in the swap->canary window (`kill_deploy` —
    replica 1 dies AFTER replica 0 already swapped and rejoined, so
    the rollback has a live rejoined slot to unwind) and must roll
    back atomically; the second runs with the fault budget exhausted
    and must commit. Raises AssertionError on a lost request, a leaked
    block on any live pool, a deploy missing its required terminal,
    the registry activating the candidate after the rollback, a
    vacuous run (kill never fired / nothing swapped before the kill /
    zero mid-rollout KV migrations) or a lock-order finding that never
    saw the DeployController and ModelRegistry locks."""
    import time

    import paddle_tpu as paddle
    from paddle_tpu import obs
    from paddle_tpu.inference.serving import (
        DeployConfig, DeployController, EngineConfig, ModelRegistry,
        ReplicaSet, RouterConfig, SamplingParams)
    from paddle_tpu.models.gpt import GPT, GPTConfig
    from paddle_tpu.testing.faults import ServingFaultInjector
    from paddle_tpu.testing.locktrace import instrument_deploy

    witness, predicted = _lock_witness()
    rng = np.random.RandomState(seed)
    obs.reqtrace.enable()

    # two GENUINELY different revisions of one architecture (different
    # init seeds -> different weights -> different sha256 manifests;
    # identical weights would publish idempotently as ONE revision).
    # The canary tolerance is opened to the full prompt set because the
    # candidate is MEANT to diverge: this harness gates the kill
    # window and the rollback machinery, while the parity gate's
    # poisoned-revision rejection has its own coverage
    # (tools/load_suite.py rolling_deploy, pass 2).
    gcfg = GPTConfig(vocab_size=97, hidden_size=32, num_layers=2,
                     num_heads=4, max_seq_len=48)

    def _rev_model(init_seed):
        paddle.seed(init_seed)
        m = GPT(gcfg)
        m.eval()
        return m

    ecfg = EngineConfig(block_size=4, num_blocks=48, max_num_seqs=4,
                        decode_chunk_size=2, max_waiting=64,
                        enable_prefix_cache=True)
    reg = ModelRegistry()
    rev_old = reg.publish("m", _rev_model(0), engine_config=ecfg)
    rev_new = reg.publish("m", _rev_model(1), engine_config=ecfg)
    assert rev_new != rev_old, "seeded revisions collided"

    injector = ServingFaultInjector(faults)
    rcfg = RouterConfig(num_replicas=replicas,
                        heartbeat_timeout_s=0.02,
                        backoff_base=0.01, backoff_max=0.05,
                        backoff_jitter=0.0)
    rs = ReplicaSet.from_registry(reg, ("m",) * replicas, config=rcfg,
                                  faults=injector)
    dcfg = DeployConfig(canary_tolerance=3)   # = len(canary_prompts)

    rids = []
    submitted = 0
    step = 0
    ctl = None
    done_deploys = []
    kill_obs = None
    next_deploy_at = 3                 # traffic in flight before it
    while (submitted < n_requests or rs.has_unfinished()
           or len(done_deploys) < 2):
        if submitted < n_requests and step % 2 == 0:
            plen = int(rng.randint(4, 10))
            p = rng.randint(0, gcfg.vocab_size, (plen,), dtype=np.int32)
            rids.append(rs.add_request(
                p, SamplingParams(max_tokens=int(rng.randint(6, 11)),
                                  model="m")))
            submitted += 1
        rs.step()
        if ctl is not None:
            kills_before = sum(1 for k, _s in injector.fired_log
                               if k == "kill_deploy")
            ctl.tick()
            if sum(1 for k, _s in injector.fired_log
                   if k == "kill_deploy") > kills_before:
                kill_obs = {
                    "step": step, "tick": ctl.status()["ticks"],
                    "swapped_before_kill":
                        len(ctl.status()["swapped"]) - 1,
                }
            if ctl.done():
                done_deploys.append(ctl.status())
                next_deploy_at = step + 2
                ctl = None
        elif len(done_deploys) < 2 and step >= next_deploy_at:
            ctl = DeployController(rs, "m", rev_new, config=dcfg,
                                   faults=injector)
            instrument_deploy(ctl, witness)
            ctl.start()
        step += 1
        assert step <= max_steps, \
            f"run incomplete after {max_steps} steps " \
            f"(deploys {len(done_deploys)}/2, " \
            f"unfinished {rs.has_unfinished()})"
        if not any(r.has_unfinished() for r in rs.replicas) \
                and rs.has_unfinished():
            time.sleep(0.002)           # restart backoff pending

    st = rs.router_stats()
    p99 = rs.ttft_quantile(0.99)
    unserved = sum(v for k, v in st["finish_reasons"].items()
                   if k not in ("stop", "length"))
    report = {
        "seed": seed, "requests": submitted, "replicas": replicas,
        "faults": faults, "fired": list(injector.fired_log),
        "revisions": {"old": rev_old, "new": rev_new},
        "deploys": done_deploys,
        "kill": kill_obs,
        "requeues": st["requeues"],
        "migrations": st["migrations"],
        "finish_reasons": st["finish_reasons"],
        "pools": st["pools"],
        "replica_states": {k: str(v)
                           for k, v in st["replica_states"].items()},
        "slo": {"ttft_p99_s": None if math.isnan(p99) else round(p99, 4),
                "reject_rate": round(unserved / max(submitted, 1), 4)},
    }
    # 1. deploy #1 rolled back (kill in the swap->canary window) and
    #    left the registry on the old revision; deploy #2 committed
    assert len(done_deploys) == 2, f"deploys: {done_deploys}"
    assert done_deploys[0]["outcome"] == "rolled_back", \
        f"killed deploy did not roll back: {done_deploys[0]}"
    assert done_deploys[1]["outcome"] == "committed", \
        f"clean deploy did not commit: {done_deploys[1]}"
    assert reg.active("m") == rev_new, \
        "registry not on the new revision after the committed deploy"
    # 2. the kill was non-vacuous AND landed after a real swap — the
    #    rollback had a rejoined new-revision slot to unwind, not just
    #    the freshly-killed one
    assert kill_obs is not None, "kill_deploy fault never fired"
    assert kill_obs["swapped_before_kill"] >= 1, \
        f"kill landed before any other slot swapped: {kill_obs}"
    # 3. zero lost: every admitted request is terminal and served,
    #    across the rollout drains, the kill, the rollback eviction and
    #    the second rollout
    lost = [r for r in rids
            if rs.get_request(r).finish_reason not in ("stop", "length")]
    assert not lost, f"requests not served: {lost}"
    # 4. the fleet converged: every slot is back in rotation on the
    #    committed revision, and every live pool audits zero leaks
    for idx, state in rs.states().items():
        assert str(state) == "up", \
            f"replica {idx} did not converge (state {state})"
    report["integrity"] = rs.check_integrity()
    for idx, audit in report["integrity"].items():
        assert audit is not None, \
            f"replica {idx} ended the run without a live engine"
    # 5. the rollout drains actually MOVED live KV (evacuating drain —
    #    a run where every request finished before its replica drained
    #    never exercised migration)
    assert st["migrations"]["migrations"] > 0, \
        "no KV migrations during the rollout drains (vacuous run)"
    # 6. per-request causality (incl. invariant 8: no token from a
    #    revision the request was not admitted under) and the deploy
    #    lifecycle invariant (every started deploy ends in exactly one
    #    commit XOR rollback), machine-checked over the recorded traces
    evs = [e.as_dict() for e in obs.reqtrace.events(
        prefix=f"tr-{rs.label}-")]
    evs += [e.as_dict() for e in obs.reqtrace.events(prefix="deploy-")]
    evs.sort(key=lambda d: d["seq"])
    dump = {"reason": "deploy_chaos", "complete": True, "events": evs}
    assert dump["events"], "reqtrace recorded nothing for this router"
    violations = obs.reqtrace.check_causality(dump)
    assert not violations, \
        f"causality violations (incl. revision pinning): {violations}"
    report["causality_events"] = len(dump["events"])
    # 7. lock-order witness — and it must have actually SEEN the two
    #    locks this PR added to the declared order
    _audit_witness(witness, predicted, report, spans_path=witness_out)
    seen = " ".join(report["lockgraph"]["witnessed_edges"])
    assert "DeployController._lock" in seen, \
        "witness never saw DeployController._lock"
    assert "ModelRegistry._lock" in seen, \
        "witness never saw ModelRegistry._lock"
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--replicas", type=int, default=0,
                    help="run the multi-replica harness with N engine "
                         "replicas behind a ReplicaSet (0 = single-"
                         "engine mode); default faults become "
                         f"{DEFAULT_REPLICA_FAULTS!r}")
    ap.add_argument("--disagg", action="store_true",
                    help="disaggregated-serving harness: replica 0 is "
                         "a prefill tier handing off to decode "
                         "replicas via live KV-block migration, with "
                         "kill-mid-migration coverage (default faults "
                         f"{DEFAULT_DISAGG_FAULTS!r}; --replicas "
                         "defaults to 3)")
    ap.add_argument("--tiering", action="store_true",
                    help="hierarchical KV-tiering harness: host-RAM "
                         "tier behind the prefix trie, device pool "
                         "sized below the working set, tier-targeted "
                         "faults (default "
                         f"{DEFAULT_TIERING_FAULTS!r})")
    ap.add_argument("--kv-cache-dtype", choices=("float32", "int8"),
                    default="float32",
                    help="--tiering only: KV-block pool storage dtype; "
                         "'int8' runs the harness over the quantized "
                         "pool + quantized host-tier spill, pinning "
                         "that the sha256 integrity contract holds for "
                         "the codes+scales payload")
    ap.add_argument("--tenants", action="store_true",
                    help="multi-tenant autoscaling harness: WFQ-"
                         "admitted tenant traffic, the autoscaler in "
                         "the loop, a replica kill landing in the "
                         "autoscale-shrunken window and a quota-"
                         "exhaustion burst (default faults "
                         f"{DEFAULT_TENANT_FAULTS!r}; --replicas "
                         "defaults to 3)")
    ap.add_argument("--deploy", action="store_true",
                    help="rolling-deploy harness: two weight rollouts "
                         "under continuous traffic — the first killed "
                         "in the swap->canary window (kill_deploy) "
                         "must roll back atomically with zero lost "
                         "requests, the second must commit (default "
                         f"faults {DEFAULT_DEPLOY_FAULTS!r}; "
                         "--replicas defaults to 3)")
    ap.add_argument("--faults", default=None,
                    help="ServingFaultInjector spec (see testing/faults.py)")
    ap.add_argument("--cancel-every", type=int, default=0,
                    help="cancel a random live request every N steps")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="templated workload with radix-trie prefix "
                         "caching on (multi-replica mode also routes "
                         "by prefix affinity): the zero-lost/zero-leak "
                         "gates must hold with refcounted shared "
                         "blocks, and the run must record trie hits")
    ap.add_argument("--max-steps", type=int, default=400)
    ap.add_argument("--snapshot", metavar="PATH",
                    default=os.path.join(tempfile.gettempdir(),
                                         "chaos_serve_obs.json"),
                    help="obs registry snapshot dumped on exit "
                         "(pass or fail); '' disables")
    ap.add_argument("--witness-out", metavar="PATH",
                    default=os.path.join(tempfile.gettempdir(),
                                         "chaos_serve_locks.json"),
                    help="lock-witness acquisition spans dumped after "
                         "the run (perf_counter clock) — overlay them "
                         "on the per-request timeline with "
                         "tools/reqtrace.py --chrome OUT --locks PATH; "
                         "'' disables")
    ap.add_argument("--slo", action="store_true",
                    help="exit nonzero on TTFT-p99 / reject-rate breach")
    ap.add_argument("--max-ttft-p99", type=float, default=10.0,
                    help="--slo threshold, seconds")
    ap.add_argument("--max-reject-rate", type=float, default=0.5,
                    help="--slo threshold, fraction of submitted")
    args = ap.parse_args(argv)
    # per-request flight recorder (obs/reqtrace.py): record every
    # lifecycle event, arm auto dumps (quarantine / failover /
    # integrity triggers, capped so a chaotic run can't spray files),
    # and ALWAYS write one complete end-of-run dump —
    # tools/reqtrace.py reconstructs each victim's single causal
    # timeline from it and --check machine-verifies the invariants
    from paddle_tpu import obs
    obs.reqtrace.enable()
    flight_dir = tempfile.mkdtemp(prefix="chaos-flight-")
    obs.reqtrace.arm(flight_dir, max_dumps=4)
    flight_path = os.path.join(flight_dir, "flightrec-exit.json")
    try:
        if args.tiering:
            report = run_chaos_tiering(
                seed=args.seed, n_requests=args.requests,
                faults=(args.faults if args.faults is not None
                        else DEFAULT_TIERING_FAULTS),
                max_steps=max(args.max_steps, 600),
                cancel_every=args.cancel_every,
                witness_out=args.witness_out,
                kv_cache_dtype=args.kv_cache_dtype)
        elif args.disagg:
            report = run_chaos_disagg(
                seed=args.seed, n_requests=args.requests,
                replicas=(args.replicas if args.replicas > 0 else 3),
                faults=(args.faults if args.faults is not None
                        else DEFAULT_DISAGG_FAULTS),
                max_steps=args.max_steps,
                witness_out=args.witness_out)
        elif args.deploy:
            report = run_chaos_deploy(
                seed=args.seed, n_requests=args.requests,
                replicas=(args.replicas if args.replicas > 0 else 3),
                faults=(args.faults if args.faults is not None
                        else DEFAULT_DEPLOY_FAULTS),
                max_steps=max(args.max_steps, 600),
                witness_out=args.witness_out)
        elif args.tenants:
            report = run_chaos_tenants(
                seed=args.seed, n_requests=args.requests,
                replicas=(args.replicas if args.replicas > 0 else 3),
                faults=(args.faults if args.faults is not None
                        else DEFAULT_TENANT_FAULTS),
                max_steps=args.max_steps,
                witness_out=args.witness_out)
        elif args.replicas > 0:
            report = run_chaos_replicas(
                seed=args.seed, n_requests=args.requests,
                replicas=args.replicas,
                faults=(args.faults if args.faults is not None
                        else DEFAULT_REPLICA_FAULTS),
                max_steps=args.max_steps,
                prefix_cache=args.prefix_cache,
                witness_out=args.witness_out)
        else:
            report = run_chaos(
                seed=args.seed, n_requests=args.requests,
                faults=(args.faults if args.faults is not None
                        else DEFAULT_FAULTS),
                max_steps=args.max_steps,
                cancel_every=args.cancel_every,
                prefix_cache=args.prefix_cache,
                witness_out=args.witness_out)
    except AssertionError as e:
        print(f"CHAOS FAIL: {e}", file=sys.stderr)
        print(json.dumps({"chaos_fail": str(e),
                          "flight_dump": flight_path,
                          "auto_flight_dumps": obs.reqtrace.RING.dumps()},
                         indent=2))
        return 1
    finally:
        # post-mortem telemetry: full obs snapshot (both engines' metric
        # series — the labels differ, so ref vs faulted stay separate)
        # + the complete flight dump (pass or fail)
        obs.reqtrace.flight_dump("chaos_exit", path=flight_path,
                                 complete=True)
        obs.reqtrace.disarm()
        print(f"flight dump: {flight_path}", file=sys.stderr)
        if args.snapshot:
            obs.dump_snapshot(args.snapshot)
            print(f"obs snapshot: {args.snapshot}", file=sys.stderr)
    report["flight_dump"] = flight_path
    report["auto_flight_dumps"] = obs.reqtrace.RING.dumps()
    rc = 0
    if args.slo:
        viol = []
        p99 = report["slo"]["ttft_p99_s"]
        if p99 is None or p99 > args.max_ttft_p99:
            viol.append(f"ttft_p99 {p99} > {args.max_ttft_p99}s")
        if report["slo"]["reject_rate"] > args.max_reject_rate:
            viol.append(f"reject_rate {report['slo']['reject_rate']} > "
                        f"{args.max_reject_rate}")
        if viol:
            print(f"SLO FAIL: {'; '.join(viol)}", file=sys.stderr)
            rc = 1
    print(json.dumps(report, indent=2, default=str))
    return rc


if __name__ == "__main__":
    sys.exit(main())
