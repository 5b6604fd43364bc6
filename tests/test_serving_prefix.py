"""Prefix cache subsystem: radix-trie block sharing with copy-on-write
(paddle_tpu/inference/serving/prefix_cache.py + the refcounted
PagedKVCache sharing mode, ISSUE 11).

The load-bearing pins (docs/serving.md "Prefix caching"):

- caching is INVISIBLE to outputs: greedy decode is bitwise-identical
  cache-on vs cache-off, and stochastic sampling under per-request
  seeds is identical too (both engines pinned to the chunked path —
  the dense path samples its first token on host, the chunked path
  in-scan, so the comparison isolates sharing, not sampler siting);
- mid-block divergence forks via copy-on-write: the donor block stays
  cached and byte-intact for later full hits;
- refcounts never leak: hundreds of allocate/attach/free churns with
  cancels and preemption end with (free list + live blocks) exactly
  partitioning the pool, and clear_prefix_cache() reconciles
  blocks_allocated == blocks_freed;
- eviction under pressure frees only unreferenced cached blocks and
  never perturbs outputs;
- scrub is refcount-aware (the PR's bugfix): scrub-freeing one sharer
  must NOT zero a block another sequence still reads — the block is
  tainted, dropped from the trie, and scrubbed only at its LAST free;
- prefix-affinity routing keeps a template's followers on the replica
  that cached it: the 3-replica fleet retains >= 80% of the
  single-engine hit rate.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.gpt import GPT, GPTConfig
from paddle_tpu.inference.serving import (EngineConfig, LLMEngine,
                                          PagedKVCache, PrefixCacheIndex,
                                          ReplicaSet, RouterConfig,
                                          SamplingParams)

VOCAB = 97


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=VOCAB, hidden_size=32, num_layers=2,
                    num_heads=4, max_seq_len=64)
    m = GPT(cfg)
    m.eval()
    return m


def _engine(model, **kw):
    kw.setdefault("block_size", 4)
    kw.setdefault("num_blocks", 64)
    kw.setdefault("max_num_seqs", 4)
    kw.setdefault("decode_chunk_size", 4)
    kw.setdefault("enable_prefix_cache", True)
    return LLMEngine.from_model(model, EngineConfig(**kw))


def _drain(eng, max_steps=600):
    steps = 0
    while eng.has_unfinished():
        eng.step()
        steps += 1
        assert steps <= max_steps, "engine failed to drain"


def _run_staggered(eng, prompts, params_fn, lead=1):
    """Leaders first (they register the template blocks as they
    prefill), then the followers — the arrival shape that produces
    trie hits. Returns {index: output token list}."""
    rids = {}
    for i in range(lead):
        rids[i] = eng.add_request(prompts[i], params_fn(i))
    for _ in range(6):
        if eng.has_unfinished():
            eng.step()
    for i in range(lead, len(prompts)):
        rids[i] = eng.add_request(prompts[i], params_fn(i))
    _drain(eng)
    return {i: list(eng.get_request(r).output_ids)
            for i, r in rids.items()}


def _templated_prompts(rng, n, tpl_len=24, n_tpl=1):
    tpls = [rng.randint(1, VOCAB, (tpl_len,), dtype=np.int32)
            for _ in range(n_tpl)]
    return [np.concatenate(
                [tpls[i % n_tpl],
                 rng.randint(1, VOCAB, (int(rng.randint(2, 6)),),
                             dtype=np.int32)]) for i in range(n)]


# ------------------------------------------------------------ parity

def test_greedy_parity_cache_on_vs_off(model):
    rng = np.random.RandomState(0)
    prompts = _templated_prompts(rng, 4)
    params = lambda i: SamplingParams(max_tokens=8)  # noqa: E731
    on = _engine(model, enable_prefix_cache=True)
    out_on = _run_staggered(on, prompts, params)
    ps = on.cache.prefix_stats()
    assert ps["hits"] >= 3, f"sharing was vacuous: {ps}"
    off = _engine(model, enable_prefix_cache=False)
    out_off = _run_staggered(off, prompts, params)
    assert out_on == out_off
    on.cache.check_integrity()


def test_stochastic_parity_cache_on_vs_off(model):
    # both engines pinned to the CHUNKED path: prefill_chunk_threshold=0
    # makes every admission chunked, so the first sampled token comes
    # from the in-scan sampler on both sides and the only difference
    # left is block sharing — which must not change a single draw
    rng = np.random.RandomState(1)
    prompts = _templated_prompts(rng, 4)
    params = lambda i: SamplingParams(  # noqa: E731
        max_tokens=8, temperature=0.8, top_k=20, seed=100 + i)
    on = _engine(model, enable_prefix_cache=True,
                 prefill_chunk_threshold=0)
    out_on = _run_staggered(on, prompts, params)
    assert on.cache.prefix_stats()["hits"] >= 3
    off = _engine(model, enable_prefix_cache=False,
                  prefill_chunk_threshold=0)
    out_off = _run_staggered(off, prompts, params)
    assert out_on == out_off


# ------------------------------------------------------------ COW

def test_cow_fork_on_mid_block_divergence(model):
    rng = np.random.RandomState(2)
    base = rng.randint(1, VOCAB, (28,), dtype=np.int32)
    diverged = base.copy()
    diverged[22:] = (diverged[22:] + 7) % (VOCAB - 1) + 1
    # leader registers 7 full blocks of `base`; the diverged follower
    # fully matches blocks 0..4 (20 tokens) and shares only 2 of block
    # 5's 4 tokens -> copy-on-write fork mid-block; the third request
    # repeats `base` verbatim and must take a FULL hit on the donor
    # chain — proving the fork wrote its copy, never the donor
    prompts = [base, diverged, base]
    params = lambda i: SamplingParams(max_tokens=6)  # noqa: E731
    on = _engine(model, enable_prefix_cache=True)
    rids = {0: on.add_request(prompts[0], params(0))}
    for _ in range(6):
        if on.has_unfinished():
            on.step()
    rids[1] = on.add_request(prompts[1], params(1))
    for _ in range(8):
        if on.has_unfinished():
            on.step()
    rids[2] = on.add_request(prompts[2], params(2))
    _drain(on)
    out_on = {i: list(on.get_request(r).output_ids)
              for i, r in rids.items()}
    ps = on.cache.prefix_stats()
    assert ps["cow_forks"] >= 1, f"divergence did not fork: {ps}"
    assert ps["hits"] >= 2
    off = _engine(model, enable_prefix_cache=False)
    out_off = {}
    for i, p in enumerate(prompts):
        r = off.add_request(p, params(i))
        _drain(off)
        out_off[i] = list(off.get_request(r).output_ids)
    assert out_on == out_off
    on.cache.check_integrity()


# ------------------------------------------------------------ refcounts

def test_refcount_zero_leak_under_churn():
    """200 cache-level sequence lifetimes over a small shared pool:
    allocate-with-prefix, grow, free (randomly scrubbed, randomly
    registered) — then the audit must reconcile to the empty state."""
    rng = np.random.RandomState(3)
    cache = PagedKVCache(num_layers=2, cache_shape=(2, 4),
                         num_blocks=48, block_size=4,
                         enable_prefix_cache=True)
    tpls = [rng.randint(1, 50, (16,)).tolist() for _ in range(5)]
    live = {}
    for i in range(200):
        sid = f"s{i}"
        toks = np.array(tpls[i % 5]
                        + rng.randint(1, 50, (int(rng.randint(1, 9)),))
                        .tolist(), dtype=np.int32)
        try:
            got = cache.allocate_with_prefix(sid, toks)
        except Exception:
            continue
        cache.reserve_slots(sid, len(toks) - got)
        live[sid] = toks
        if len(live) >= 6 or rng.rand() < 0.5:
            victim = list(live)[int(rng.randint(len(live)))]
            vt = live.pop(victim)
            scrub = rng.rand() < 0.3          # cancels/faulted frees
            cache.free(victim, scrub=scrub,
                       cache_tokens=None if scrub else vt)
        if i % 25 == 0:
            cache.check_integrity()
    for sid, vt in live.items():
        cache.free(sid, cache_tokens=vt)
    cache.check_integrity()
    cache.clear_prefix_cache()
    r = cache.check_integrity()
    assert r["leaked"] == 0
    s = cache.stats()
    assert s["blocks_allocated"] == s["blocks_freed"]
    assert s["free"] == cache.num_blocks


def test_engine_churn_with_cancel_and_preemption(model):
    rng = np.random.RandomState(4)
    prompts = _templated_prompts(rng, 16, tpl_len=20, n_tpl=2)
    # small pool + long generations: decode growth forces preemption
    # while cancels cut sharers loose mid-flight
    eng = _engine(model, num_blocks=32, max_waiting=20,
                  enable_prefix_cache=True)
    rids = []
    cancelled = 0
    step = 0
    pending = list(prompts)
    while pending or eng.has_unfinished():
        if pending:                       # staggered: one arrival/step
            rids.append(eng.add_request(
                pending.pop(0), SamplingParams(max_tokens=12)))
        if eng.has_unfinished():
            eng.step()
        step += 1
        if step % 5 == 0:
            alive = [r for r in rids if not eng.get_request(r).finished]
            if alive:
                eng.cancel(alive[int(rng.randint(len(alive)))])
                cancelled += 1
        assert step <= 800
    assert cancelled > 0
    eng.cache.check_integrity()
    eng.cache.clear_prefix_cache()
    r = eng.cache.check_integrity()
    assert r["leaked"] == 0
    s = eng.cache.stats()
    assert s["blocks_allocated"] == s["blocks_freed"]


# ------------------------------------------------------------ eviction

def test_eviction_under_pressure(model):
    rng = np.random.RandomState(5)
    # pool far smaller than the retained-prefix working set: serving 12
    # distinct templates through 28 blocks forces LRU eviction of
    # unreferenced cached blocks — and must not perturb outputs
    prompts = _templated_prompts(rng, 12, tpl_len=20, n_tpl=12)
    params = lambda i: SamplingParams(max_tokens=4)  # noqa: E731
    on = _engine(model, num_blocks=28, enable_prefix_cache=True)
    out_on = {}
    for i, p in enumerate(prompts):
        r = on.add_request(p, params(i))
        _drain(on)
        out_on[i] = list(on.get_request(r).output_ids)
    ps = on.cache.prefix_stats()
    assert ps["evictions"] > 0, f"no eviction pressure: {ps}"
    on.cache.check_integrity()
    off = _engine(model, num_blocks=28, enable_prefix_cache=False)
    out_off = {}
    for i, p in enumerate(prompts):
        r = off.add_request(p, params(i))
        _drain(off)
        out_off[i] = list(off.get_request(r).output_ids)
    assert out_on == out_off


# ------------------------------------------------------------ scrub fix

def test_scrub_is_refcount_aware():
    """The PR's bugfix: scrub-freeing one sharer of a block must not
    zero it under the other sharer — the block is tainted (dropped from
    the trie, never re-indexed) and scrubbed only at its LAST free."""
    import jax.numpy as jnp
    cache = PagedKVCache(num_layers=1, cache_shape=(1, 2),
                         num_blocks=8, block_size=4,
                         enable_prefix_cache=True)
    tpl = np.arange(1, 9, dtype=np.int32)           # 8 tokens, 2 blocks
    ta = np.append(tpl, 50).astype(np.int32)        # distinct tails so
    tb = np.append(tpl, 60).astype(np.int32)        # the L-1 probe cap
    tc = np.append(tpl, 70).astype(np.int32)        # covers the template
    assert cache.allocate_with_prefix("a", ta) == 0
    cache.reserve_slots("a", len(ta))
    blocks = np.array(cache.block_table("a")[:2])   # the template blocks
    # give the to-be-shared blocks recognizable nonzero KV
    cache.pools = tuple((kp.at[blocks].set(1.0), vp.at[blocks].set(1.0))
                        for kp, vp in cache.pools)
    cache.free("a", cache_tokens=ta)                # retained + indexed
    assert cache.allocate_with_prefix("b", tb) == 8
    assert cache.allocate_with_prefix("c", tc) == 8
    assert cache.prefix_stats()["shared_blocks"] == 2
    cache.free("b", scrub=True)                     # faulted sharer
    # c still reads those blocks: they must NOT have been zeroed
    assert bool(jnp.all(cache.pools[0][0][blocks] == 1.0))
    # but they are distrusted: a fresh probe finds no cached prefix
    assert cache.match_len(tb) == 0
    cache.free("c")                                 # LAST free: scrub
    assert bool(jnp.all(cache.pools[0][0][blocks] == 0.0))
    r = cache.check_integrity()
    assert r["leaked"] == 0 and r["stale_tainted"] == 0
    s = cache.stats()
    assert s["blocks_allocated"] == s["blocks_freed"]


# ------------------------------------------------------------ trie unit

def test_prefix_index_match_insert_evict():
    idx = PrefixCacheIndex(block_size=4)
    toks = list(range(1, 13))                       # 3 full blocks
    assert idx.insert(toks, [10, 11, 12]) == 3
    path, partial = idx.match(toks)
    assert [n.block for n in path] == [10, 11, 12] and partial is None
    # longest-prefix: 2 full blocks + mid-block divergence -> COW
    # candidate (node for block 12, 2 matching tokens)
    q = toks[:10] + [99, 99]
    path, partial = idx.match(q)
    assert [n.block for n in path] == [10, 11]
    assert partial is not None and partial[0].block == 12 \
        and partial[1] == 2
    # first-wins dedupe: re-inserting the same content adds nothing
    assert idx.insert(toks, [20, 21, 22]) == 0
    # LRU: the leaf is the eviction candidate, never the root path
    leaf = idx.pop_lru_leaf(lambda b: True)
    assert leaf is not None and leaf.block == 12
    assert idx.audit() == 0


def test_prefix_index_remove_subtree():
    idx = PrefixCacheIndex(block_size=2)
    idx.insert([1, 2, 3, 4, 5, 6], [7, 8, 9])
    idx.insert([1, 2, 3, 4, 8, 8], [7, 8, 5])
    node = idx.node_of(8)
    gone = idx.remove_subtree(node)
    assert sorted(n.block for n in gone) == [5, 8, 9]
    assert gone[0].block == 8                       # node first
    path, _ = idx.match([1, 2, 3, 4, 5, 6])
    assert [n.block for n in path] == [7]
    assert idx.audit() == 0


# ------------------------------------------------------------ affinity

def test_affinity_retains_hit_rate_across_replicas(model):
    rng = np.random.RandomState(6)
    prompts = _templated_prompts(rng, 12, tpl_len=24, n_tpl=2)
    params = SamplingParams(max_tokens=4)
    rc = RouterConfig(num_replicas=3, balance="prefix_affinity",
                      backoff_base=0.01, backoff_max=0.05,
                      backoff_jitter=0.0)
    ecfg = EngineConfig(block_size=4, num_blocks=64, max_num_seqs=4,
                        decode_chunk_size=4, enable_prefix_cache=True)
    rs = ReplicaSet.from_model(model, rc, engine_config=ecfg)
    rids = []
    for i, p in enumerate(prompts[:2]):             # template leaders
        rids.append(rs.add_request(p, params))
    steps = 0
    while rs.has_unfinished():
        rs.step()
        steps += 1
        assert steps <= 600
    for p in prompts[2:]:
        rids.append(rs.add_request(p, params))
    while rs.has_unfinished():
        rs.step()
        steps += 1
        assert steps <= 600
    # every follower landed on its template's home replica...
    homes = {}
    for i, r in enumerate(rids):
        homes.setdefault(i % 2, set()).add(rs.get_request(r).replica)
    assert all(len(v) == 1 for v in homes.values()), homes
    # ...so the fleet keeps >= 80% of the single-engine hit rate
    # (single-engine: 1 miss per template -> (n-2)/n)
    fps = rs.prefix_stats()
    fleet_rate = fps["hits"] / (fps["hits"] + fps["misses"])
    single_rate = (len(prompts) - 2) / len(prompts)
    assert fleet_rate >= 0.8 * single_rate, (fleet_rate, single_rate)
    for audit in rs.check_integrity().values():
        assert audit is None or audit["leaked"] == 0
