"""Hierarchical KV-cache tiering: the host-RAM tier behind
PrefixCacheIndex (paddle_tpu/inference/serving/host_tier.py + the
demote/promote paths in PagedKVCache, ISSUE 16).

The load-bearing pins (docs/serving.md "Hierarchical KV-cache
tiering"):

- tiering is INVISIBLE to outputs: a prefix that round-trips
  device -> host -> device is bitwise-identical to a device hit and to
  cache-off, for greedy decode AND seeded stochastic sampling (both
  engines pinned to the chunked path, the PR-11 parity contract);
- promotion is fault-bounded: a killed promotion (injected
  kill_promotion), a deadline (promote_timeout_s) or a torn host
  payload (sha256 mismatch) degrades to re-prefill of the missing
  suffix — the request finishes with correct output, never wedges,
  and the reqtrace timeline pairs every tiered prefix_match with a
  promote or promote_abort (check_causality invariants 6/7);
- a timeout leaves the entry host-resident (retryable); an integrity
  failure drops the subtree (never promoted);
- scrub-taint crosses tiers: a taint raised while descendants are
  host-resident POISONS the spilled copies (dropped, counted, never
  promoted), and a tainted block never reaches the host store;
- peer prefix fetch is transactional: a replica missing a prefix pulls
  it from a peer bitwise-intact, and a digest mismatch or a full pool
  aborts with the destination untouched;
- batched demotion selects the exact victim sequence the
  one-at-a-time loop would (the `pending` contract of
  lru_demotable);
- zero-leak spans tiers: cross-tier check_integrity stays clean and
  clear_prefix_cache reconciles blocks_allocated == blocks_freed with
  an empty host store.
"""
import os
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import obs
from paddle_tpu.models.gpt import GPT, GPTConfig
from paddle_tpu.inference.serving import (EngineConfig, LLMEngine,
                                          PagedKVCache, PrefixCacheIndex,
                                          ReplicaSet, RouterConfig,
                                          SamplingParams)
from paddle_tpu.obs.reqtrace import check_causality
from paddle_tpu.testing.faults import ServingFaultInjector

VOCAB = 97
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=VOCAB, hidden_size=32, num_layers=2,
                    num_heads=4, max_seq_len=64)
    m = GPT(cfg)
    m.eval()
    return m


@pytest.fixture(autouse=True)
def recording():
    """Fresh, enabled process ring per test (the promote/demote event
    pairing assertions read it); always disarmed after."""
    obs.reqtrace.clear()
    obs.reqtrace.enable()
    yield
    obs.reqtrace.disarm()
    obs.reqtrace.enable()
    obs.reqtrace.clear()


def _engine(model, faults=None, **kw):
    kw.setdefault("block_size", 4)
    kw.setdefault("num_blocks", 20)
    kw.setdefault("max_num_seqs", 4)
    kw.setdefault("decode_chunk_size", 4)
    kw.setdefault("enable_prefix_cache", True)
    kw.setdefault("host_tier_blocks", 64)
    return LLMEngine.from_model(model, EngineConfig(**kw),
                                faults=faults or ServingFaultInjector(""))


def _drain(eng, max_steps=600):
    steps = 0
    while eng.has_unfinished():
        eng.step()
        steps += 1
        assert steps <= max_steps, "engine failed to drain"


def _run_sequential(eng, prompts, params_fn):
    """One request at a time, fully drained before the next arrives —
    the deterministic arrival shape that makes the demote/promote
    schedule identical across the compared engines."""
    out = {}
    for i, p in enumerate(prompts):
        r = eng.add_request(p, params_fn(i))
        _drain(eng)
        out[i] = list(eng.get_request(r).output_ids)
    return out


def _tiering_prompts(seed=0):
    """4 templates x 24 tokens revisited after enough churn that a
    20-block pool must demote the early templates to host — the
    revisits (last two prompts) then promote them back."""
    rng = np.random.RandomState(seed)
    tpls = [rng.randint(1, VOCAB, (24,), dtype=np.int32)
            for _ in range(4)]
    order = [0, 1, 2, 3, 0, 1]
    return [np.concatenate(
                [tpls[t], rng.randint(1, VOCAB, (4,), dtype=np.int32)])
            for t in order]


def _audit_clean(cache):
    cache.check_integrity()
    cache.clear_prefix_cache()
    r = cache.check_integrity()
    assert r["leaked"] == 0 and r["host_leaked"] == 0 \
        and r["host_orphans"] == 0
    s = cache.stats()
    assert s["blocks_allocated"] == s["blocks_freed"]
    assert len(cache.host_tier) == 0


# ------------------------------------------------------ bitwise parity

def test_demote_promote_bitwise_parity_greedy(model):
    prompts = _tiering_prompts()
    params = lambda i: SamplingParams(max_tokens=6)  # noqa: E731
    tiered = _engine(model)
    out_t = _run_sequential(tiered, prompts, params)
    ps = tiered.cache.prefix_stats()
    assert ps["tier_demotions"] >= 1, f"no demotion pressure: {ps}"
    assert ps["promote_hit"] >= 1, f"tiering was vacuous: {ps}"
    # the reqtrace timeline carries the tier lifecycle and stays causal
    kinds = {e.kind for e in obs.reqtrace.events()}
    assert {"demote", "promote"} <= kinds, kinds
    dump = obs.reqtrace.dump_payload(
        "test", trace_ids=sorted(obs.reqtrace.traces(
            prefix=f"tr-{tiered.stats.label}-")))
    assert check_causality(dump) == []
    # device-hit reference: same workload, pool big enough that the
    # revisits hit device-resident blocks (no tier round-trip)
    device = _engine(model, num_blocks=64)
    out_d = _run_sequential(device, prompts, params)
    dps = device.cache.prefix_stats()
    assert dps["tier_demotions"] == 0 and dps["hits"] >= 2, dps
    off = _engine(model, enable_prefix_cache=False, host_tier_blocks=0)
    out_o = _run_sequential(off, prompts, params)
    assert out_t == out_d == out_o
    _audit_clean(tiered.cache)


def test_demote_promote_bitwise_parity_stochastic(model):
    # all engines pinned to the CHUNKED path (prefill_chunk_threshold=0)
    # so the first sampled token comes from the in-scan sampler on every
    # side — the PR-11 parity contract; the only difference left is the
    # tier round-trip, which must not change a single seeded draw
    prompts = _tiering_prompts(seed=1)
    params = lambda i: SamplingParams(  # noqa: E731
        max_tokens=6, temperature=0.8, top_k=20, seed=100 + i)
    tiered = _engine(model, prefill_chunk_threshold=0)
    out_t = _run_sequential(tiered, prompts, params)
    ps = tiered.cache.prefix_stats()
    assert ps["tier_demotions"] >= 1 and ps["promote_hit"] >= 1, ps
    device = _engine(model, num_blocks=64, prefill_chunk_threshold=0)
    out_d = _run_sequential(device, prompts, params)
    off = _engine(model, enable_prefix_cache=False, host_tier_blocks=0,
                  prefill_chunk_threshold=0)
    out_o = _run_sequential(off, prompts, params)
    assert out_t == out_d == out_o
    _audit_clean(tiered.cache)


# ----------------------------------------------- degraded promotion

def test_failed_promotion_degrades_to_reprefill(model):
    """kill_promotion cuts the first fill short: the entry stays
    host-resident, the request re-prefills and finishes with the same
    greedy output, and the timeline pairs the tiered prefix_match with
    a promote_abort followed by re-prefill (invariants 6/7)."""
    prompts = _tiering_prompts(seed=2)
    params = lambda i: SamplingParams(max_tokens=6)  # noqa: E731
    faulted = _engine(model, faults=ServingFaultInjector("kill_promotion@0"))
    out_f = _run_sequential(faulted, prompts, params)
    ps = faulted.cache.prefix_stats()
    assert ps["tier_demotions"] >= 1, ps
    assert ps["promote_timeout"] >= 1, \
        f"kill_promotion never landed on a fill: {ps}"
    kinds = [e.kind for e in obs.reqtrace.events()]
    assert "promote_abort" in kinds, set(kinds)
    dump = obs.reqtrace.dump_payload(
        "test", trace_ids=sorted(obs.reqtrace.traces(
            prefix=f"tr-{faulted.stats.label}-")))
    assert check_causality(dump) == []
    off = _engine(model, enable_prefix_cache=False, host_tier_blocks=0)
    out_o = _run_sequential(off, prompts, params)
    assert out_f == out_o
    _audit_clean(faulted.cache)


# -------------------------------------------------- cache-level tiers

def _demoted_chain(host_blocks=8, promote_timeout_s=None):
    """A PagedKVCache whose 4-block template chain has been fully
    demoted to the host tier, with recognizable per-block payloads.
    Returns (cache, tokens, template_blocks)."""
    import jax.numpy as jnp
    cache = PagedKVCache(num_layers=1, cache_shape=(1, 2),
                         num_blocks=8, block_size=4,
                         enable_prefix_cache=True,
                         host_tier_blocks=host_blocks,
                         promote_timeout_s=promote_timeout_s)
    ta = np.arange(1, 18, dtype=np.int32)           # 17 tokens, 4 full blocks
    assert cache.allocate_with_prefix("a", ta) == 0
    cache.reserve_slots("a", len(ta))
    blocks = list(cache.block_table("a")[:4])
    kp, vp = cache.pools[0]
    for j, b in enumerate(blocks):                  # distinct payloads
        kp = kp.at[b].set(float(j + 1))
        vp = vp.at[b].set(-float(j + 1))
    cache.pools = ((kp, vp),)
    cache.free("a", cache_tokens=ta)                # 4 retained, evictable
    # two waves of pool pressure demote the whole chain leaf-ward
    cache.allocate("f", 24)                         # 6 blocks: demotes 2
    cache.free("f")
    cache.allocate("g", 32)                         # 8 blocks: demotes 2 more
    cache.free("g")
    assert cache.match_len(ta) == 0
    assert cache.host_match_len(ta) == 16
    assert cache.tier_demotions == 4
    return cache, ta, blocks


def test_cache_promote_roundtrip_is_bitwise():
    cache, ta, _old = _demoted_chain()
    promo = cache.ensure_promoted(ta)
    assert promo["outcomes"] == ["hit"] * 4
    assert promo["promoted_blocks"] == 4
    assert cache.match_len(ta) == 16
    assert len(cache.host_tier) == 0
    # the promoted chain carries the exact spilled bytes
    path, _ = cache.prefix_index.match([int(t) for t in ta[:16]])
    assert len(path) == 4
    kp, vp = cache.pools[0]
    for j, node in enumerate(path):
        assert bool(np.all(np.asarray(kp[node.block]) == float(j + 1)))
        assert bool(np.all(np.asarray(vp[node.block]) == -float(j + 1)))
    _audit_clean(cache)


def test_cache_promote_timeout_is_retryable():
    cache, ta, _old = _demoted_chain(promote_timeout_s=0.0)
    promo = cache.ensure_promoted(ta)
    assert promo["outcomes"] == ["timeout"]
    assert promo["promoted_blocks"] == 0
    assert cache.tier_promotions["timeout"] == 1
    # deadline left the entries host-resident: a retry without the
    # deadline promotes the full chain
    assert cache.host_match_len(ta) == 16
    cache.promote_timeout_s = None
    assert cache.ensure_promoted(ta)["outcomes"] == ["hit"] * 4
    assert cache.match_len(ta) == 16
    _audit_clean(cache)


def test_cache_corrupt_host_block_fails_integrity_and_drops():
    cache, ta, _old = _demoted_chain()
    # flip one byte of the LRU-oldest entry (the leaf-most spill)
    # without updating its digest — the fill must catch it
    assert cache.host_tier.corrupt_oldest()
    promo = cache.ensure_promoted(ta)
    assert promo["outcomes"] == ["hit"] * 3 + ["integrity"]
    assert cache.tier_promotions["integrity"] == 1
    # the torn entry is gone (never promoted); the intact prefix is
    # device-resident and the tail re-prefills
    assert cache.match_len(ta) == 12
    assert cache.host_match_len(ta) == 0
    assert len(cache.host_tier) == 0
    _audit_clean(cache)


def test_taint_poisons_host_copy_and_never_spills():
    """Satellite 1 (the PR-11 scrub pin across tiers): scrub-freeing
    one sharer of a prefix whose descendants were demoted must POISON
    the host copies — dropped immediately, never promoted — while the
    surviving sharer's device blocks are not zeroed under it; tainted
    blocks never reach the host store."""
    import jax.numpy as jnp
    cache = PagedKVCache(num_layers=1, cache_shape=(1, 2),
                         num_blocks=8, block_size=4,
                         enable_prefix_cache=True, host_tier_blocks=8)
    ta = np.arange(1, 18, dtype=np.int32)
    assert cache.allocate_with_prefix("a", ta) == 0
    cache.reserve_slots("a", len(ta))
    blocks = list(cache.block_table("a")[:4])
    cache.free("a", cache_tokens=ta)
    # demote the two leaf-most chain blocks host-side
    cache.allocate("f", 24)
    cache.free("f")
    assert cache.tier_demotions == 2
    assert cache.host_tier.stats()["puts"] == 2
    # give the still-device blocks recognizable nonzero KV, then attach
    # two sharers to them
    dev = np.array(blocks[:2])
    cache.pools = tuple((kp.at[dev].set(1.0), vp.at[dev].set(1.0))
                        for kp, vp in cache.pools)
    tb = np.concatenate([ta[:8], [50, 51]]).astype(np.int32)
    tc = np.concatenate([ta[:8], [60, 61]]).astype(np.int32)
    assert cache.allocate_with_prefix("b", tb) == 8
    cache.reserve_slots("b", 2)
    assert cache.allocate_with_prefix("c", tc) == 8
    cache.reserve_slots("c", 2)
    cache.free("b", scrub=True)                     # faulted sharer
    hs = cache.host_tier.stats()
    assert hs["poisoned"] == 2, hs                  # host copies poisoned
    assert len(cache.host_tier) == 0
    assert hs["puts"] == 2, "a tainted block reached the host store"
    # the whole prefix is distrusted on both tiers...
    assert cache.match_len(ta) == 0
    assert cache.host_match_len(ta) == 0
    # ...but c still reads the device blocks: NOT zeroed under it
    assert bool(jnp.all(cache.pools[0][0][dev] == 1.0))
    cache.free("c")                                 # LAST free: scrub
    assert bool(jnp.all(cache.pools[0][0][dev] == 0.0))
    r = cache.check_integrity()
    assert r["leaked"] == 0 and r["stale_tainted"] == 0
    s = cache.stats()
    assert s["blocks_allocated"] == s["blocks_freed"]


def test_lru_demotable_batched_matches_sequential():
    """The `pending` contract: selecting N victims with pending
    accumulation (batched demotion) yields the exact node sequence the
    demote-one-at-a-time loop produces."""
    def build():
        idx = PrefixCacheIndex(block_size=2)
        idx.insert(list(range(1, 9)), [10, 11, 12, 13])     # 4-deep chain
        idx.insert([1, 2, 3, 4, 9, 9], [10, 11, 20])        # branch
        return idx

    batched = build()
    pending, order = set(), []
    while True:
        n = batched.lru_demotable(lambda b: True, pending=pending)
        if n is None:
            break
        pending.add(n)
        order.append(n.block)
    sequential = build()
    order_seq, hid = [], 0
    while True:
        n = sequential.lru_demotable(lambda b: True)
        if n is None:
            break
        order_seq.append(n.block)
        sequential.demote(n, hid)
        hid += 1
    assert order == order_seq
    assert sorted(order) == [10, 11, 12, 13, 20]
    assert batched.audit() == 0 and sequential.audit() == 0


# ---------------------------------------------------- peer prefix fetch

def _fleet(model, num_replicas=2, **ekw):
    ekw.setdefault("block_size", 4)
    ekw.setdefault("num_blocks", 32)
    ekw.setdefault("max_num_seqs", 4)
    ekw.setdefault("decode_chunk_size", 4)
    ekw.setdefault("enable_prefix_cache", True)
    ekw.setdefault("host_tier_blocks", 32)
    rc = RouterConfig(num_replicas=num_replicas, balance="round_robin",
                      peer_prefix_fetch=True, backoff_base=0.01,
                      backoff_max=0.05, backoff_jitter=0.0)
    return ReplicaSet.from_model(model, rc, engine_config=EngineConfig(**ekw))


def _drain_fleet(rs, max_steps=600):
    steps = 0
    while rs.has_unfinished():
        rs.step()
        steps += 1
        assert steps <= max_steps


def test_peer_fetch_fills_cold_replica_bitwise(model):
    rng = np.random.RandomState(7)
    tpl = rng.randint(1, VOCAB, (24,), dtype=np.int32)
    leader = np.concatenate([tpl, rng.randint(1, VOCAB, (4,),
                                              dtype=np.int32)])
    follower = np.concatenate([tpl, rng.randint(1, VOCAB, (4,),
                                                dtype=np.int32)])
    params = SamplingParams(max_tokens=6)
    rs = _fleet(model)
    r0 = rs.add_request(leader, params)             # round-robin: replica 0
    _drain_fleet(rs)
    r1 = rs.add_request(follower, params)           # replica 1: cold, pulls
    _drain_fleet(rs)
    ms = rs.migrator.stats()
    assert ms["prefix_fetches"] >= 1, ms
    assert ms["prefix_aborted"] == 0 and ms["prefix_bytes"] > 0, ms
    assert {rs.get_request(r0).replica, rs.get_request(r1).replica} \
        == {0, 1}
    # the peer-fetched blocks decode bitwise like a local prefill
    off = _engine(model, enable_prefix_cache=False, host_tier_blocks=0,
                  num_blocks=32)
    out_off = _run_sequential(off, [leader, follower],
                              lambda i: params)
    assert list(rs.get_request(r0).tokens) == out_off[0]
    assert list(rs.get_request(r1).tokens) == out_off[1]
    kinds = {e.kind for e in obs.reqtrace.events()}
    assert "peer_fetch" in kinds, kinds
    for audit in rs.check_integrity().values():
        assert audit is None or (audit["leaked"] == 0
                                 and audit["host_leaked"] == 0)


def test_peer_fetch_aborts_atomically(model):
    """Both abort legs leave the destination untouched: a digest
    mismatch raises out of admit_prefix before any block is claimed,
    and a full destination pool aborts the transactional pull
    (prefix_aborted) so the request degrades to re-prefill."""
    rng = np.random.RandomState(8)
    tpl = rng.randint(1, VOCAB, (24,), dtype=np.int32)
    params = SamplingParams(max_tokens=4)
    rs = _fleet(model, num_blocks=16)
    src, dst = rs.replicas[0], rs.replicas[1]
    # warm the donor directly
    src.engine.add_request(tpl, params)
    _drain(src.engine)
    snap = src.export_prefix(tpl)
    assert snap is not None and len(snap["blocks"]) >= 1
    # leg 1: tamper one payload byte — every digest is re-verified
    # before a single block is claimed
    free_before = dst.engine.cache.num_free()
    payload0, _digest0 = snap["blocks"][0]
    payload0[0][0].flat[0] += 1.0                   # layer-0 K, one value
    with pytest.raises(ValueError):
        dst.admit_prefix(tpl, snap["blocks"])
    assert dst.engine.cache.num_free() == free_before
    dst.engine.cache.check_integrity()
    # leg 2: fill the destination pool so the pull cannot fit — the
    # coordinator aborts and counts it, destination still untouched
    hog = rng.randint(1, VOCAB, (48,), dtype=np.int32)  # 12 of 16 blocks
    dst.engine.add_request(hog, SamplingParams(max_tokens=8))
    dst.engine.step()
    assert rs.migrator.fetch_prefix(src, dst, "rq-abort", "tr-abort",
                                    tpl) is None
    ms = rs.migrator.stats()
    assert ms["prefix_aborted"] >= 1, ms
    dst.engine.cache.check_integrity()


# ------------------------------------------------- checker invariants

def _ev(seq, kind, tid="t0", **attrs):
    return {"seq": seq, "ts": float(seq), "trace_id": tid,
            "request_id": "r0", "kind": kind, "attrs": attrs}


def test_checker_tiering_invariants_on_synthetic_dumps():
    # clean: tiered match resolved by promote before tokens flow
    clean = {"complete": True, "events": [
        _ev(0, "engine_admit", engine="e0", arrival=1.0),
        _ev(1, "prefix_match", cached_tokens=0, host_tokens=8),
        _ev(2, "promote", blocks=2, tokens=8),
        _ev(3, "scheduled"),
        _ev(4, "prefill", tokens=12),
        _ev(5, "first_token"),
        _ev(6, "finish", reason="length"),
    ]}
    assert check_causality(clean) == []
    # invariant 6: tokens while matched blocks are still host-resident
    unresolved = {"complete": True, "events": [
        _ev(0, "engine_admit", engine="e0", arrival=1.0),
        _ev(1, "prefix_match", cached_tokens=0, host_tokens=8),
        _ev(2, "scheduled"),
        _ev(3, "prefill", tokens=12),
        _ev(4, "first_token"),
        _ev(5, "finish", reason="length"),
    ]}
    v = check_causality(unresolved)
    assert any("host-resident" in x for x in v), v
    # invariant 7: a degraded promotion must be followed by re-prefill
    # progress or a terminal — a bare promote_abort is a wedged request
    wedged = {"complete": True, "events": [
        _ev(0, "engine_admit", engine="e0", arrival=1.0),
        _ev(1, "prefix_match", cached_tokens=0, host_tokens=8),
        _ev(2, "promote_abort", outcome="timeout"),
    ]}
    v = check_causality(wedged)
    assert any("wedged" in x for x in v), v
    # ...and promote_abort -> prefill -> terminal is the healthy
    # degraded path
    degraded = {"complete": True, "events": [
        _ev(0, "engine_admit", engine="e0", arrival=1.0),
        _ev(1, "prefix_match", cached_tokens=0, host_tokens=8),
        _ev(2, "promote_abort", outcome="integrity"),
        _ev(3, "scheduled"),
        _ev(4, "prefill", tokens=12),
        _ev(5, "first_token"),
        _ev(6, "finish", reason="length"),
    ]}
    assert check_causality(degraded) == []


# ------------------------------------------------------- chaos smoke

@pytest.mark.slow
def test_chaos_tiering_runner_cpu():
    """tools/chaos_serve.py --tiering smoke: the seeded tier-fault
    schedule drains with zero lost requests, zero leaks on both tiers
    and bitwise survivors (exit 0)."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import chaos_serve
    rc = chaos_serve.main(["--tiering", "--seed", "0"])
    assert rc == 0
