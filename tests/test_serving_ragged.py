"""Ragged paged attention + chunked prefill (ISSUE 10).

The load-bearing pins:

- the pallas kernel (interpret mode) is BITWISE-identical to its
  lax.scan reference and matches a dense softmax oracle to float32
  tolerance, dead rows included;
- engine output under kernel="ragged" is bitwise-identical to
  kernel="bucketed" and to the dense generate() reference — greedy AND
  stochastic. Off-TPU both kernels lower to the same gather path and
  row-wise results are batch-width-invariant, so CPU equality is
  structural; on TPU the kernel-level tolerance above is the bound and
  the greedy token streams still match exactly;
- ONE compilation of fused_decode_chunk covers every batch mix under
  ragged (the jit-cache pin that retires the per-bucket compile axis),
  while the bucketed fallback compiles per power-of-two bucket;
- chunked prefill (prefill_chunk_threshold) emits the same greedy
  tokens as the dense one-shot prefill path, invariant under chunk
  size, with EOS-mid-chunk, preemption/requeue and chaos recovery
  holding the zero-leak / zero-lost / survivor-bitwise contracts.
"""
import functools

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.models.gpt import GPT, GPTConfig
import paddle_tpu.models.generation as gen
from paddle_tpu.inference.serving import (EngineConfig, LLMEngine,
                                          SamplingParams)
from paddle_tpu.inference.serving.attention import fused_decode_chunk
from paddle_tpu.ops.pallas import ragged_paged_attention as rpa
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
    return LLMEngine.from_model(model, EngineConfig(**kw),
                                faults=faults)


def _reference_tokens(model, prompt, max_new):
    out = np.asarray(gen.generate(
        model, jnp.asarray(np.asarray(prompt)[None], jnp.int32), max_new))
    return out[0, len(prompt):]


def _run_engine(model, prompts, samplings, **kw):
    eng = _engine(model, **kw)
    rids = [eng.add_request(p, s) for p, s in zip(prompts, samplings)]
    res = eng.run(max_steps=500)
    return eng, rids, res


# ------------------------------------------------------- kernel parity
def _random_paged(seed, n, nb, bs, h, d):
    """Random pools + valid block tables + mixed lengths (one dead
    row, one single-token row, one near-capacity row)."""
    rng = np.random.RandomState(seed)
    mb = 5
    k_pool = rng.randn(nb, bs, h, d).astype(np.float32)
    v_pool = rng.randn(nb, bs, h, d).astype(np.float32)
    q = rng.randn(n, h, d).astype(np.float32)
    lengths = np.array([0, 1, bs * mb - 1, 7][:n], np.int32)
    tables = _block_tables(rng, lengths, nb, bs, mb)
    return (jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
            jnp.asarray(tables), jnp.asarray(lengths))


def _block_tables(rng, lengths, nb, bs, mb, sentinel=-1):
    """[N, mb] tables: each row's live blocks drawn from a permutation of
    the pool, `sentinel` in every entry past them."""
    tables = np.full((len(lengths), mb), sentinel, np.int32)
    perm, used = rng.permutation(nb), 0
    for i, n in enumerate(-(-np.asarray(lengths) // bs)):
        tables[i, :n] = perm[used:used + n]
        used += n
    return tables


def _dense_oracle(q, k_pool, v_pool, tables, lengths):
    """Per-row gather + full softmax, float32."""
    n, h, d = q.shape
    bs = k_pool.shape[1]
    out = np.zeros((n, h, d), np.float32)
    for i in range(n):
        ln = int(lengths[i])
        if ln == 0:
            continue
        blocks = [int(b) for b in np.asarray(tables[i]) if b >= 0]
        kc = np.concatenate([np.asarray(k_pool[b]) for b in blocks])[:ln]
        vc = np.concatenate([np.asarray(v_pool[b]) for b in blocks])[:ln]
        s = np.einsum("hd,shd->hs", np.asarray(q[i]), kc) / np.sqrt(d)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        out[i] = np.einsum("hs,shd->hd", p, vc)
    return out


def test_kernel_interpret_bitwise_matches_reference():
    """The pallas kernel (interpret mode, runs on CPU) is bitwise-equal
    to the lax.scan reference — same flash update, same block order —
    and float32-close to a dense softmax oracle. The dead row (length
    0) returns exact zeros, the kernel-level form of 'dead rows cost
    zero work'."""
    args = _random_paged(0, 4, nb=16, bs=4, h=4, d=8)
    got = np.asarray(rpa.ragged_decode_attention(*args, interpret=True))
    ref = np.asarray(rpa.ragged_attention_reference(*args))
    np.testing.assert_array_equal(got, ref)
    oracle = _dense_oracle(*args)
    np.testing.assert_allclose(got, oracle, rtol=2e-6, atol=2e-6)
    assert np.all(got[0] == 0.0)          # lengths[0] == 0: dead row


# the serving tile: blocks of 32 positions whose stored block pads to 128
# KiB of VMEM, so a DMA group is 4 blocks and a table of 9 holds two whole
# groups and one more block
_BS, _MB, _NB = 32, 9, 24
_ROW_LENGTHS = {
    "dead": 0, "one-position": 1, "a-block": _BS, "a-block-and-one": _BS + 1,
    "a-whole-group": 4 * _BS, "one-more-than-a-group": 4 * _BS + 1,
    "not-a-multiple-of-the-group": 7 * _BS - 5, "two-groups": 8 * _BS,
    "the-whole-table": _MB * _BS,
}


@pytest.mark.parametrize("sentinel", ["minus-one", "num_blocks"])
@pytest.mark.parametrize("stored", ["logical", "packed"])
@pytest.mark.parametrize("length", list(_ROW_LENGTHS.values()),
                         ids=list(_ROW_LENGTHS))
def test_kernel_walks_a_rows_live_blocks(length, stored, sentinel):
    """The row under test between two live rows of 3 and 6 blocks (for
    length 0: a dead row between two live ones, whose first group the row
    before it starts): what a row leaves in the two buffer slots and on
    their semaphores must not reach the next. Table entries past a row's
    live blocks hold a sentinel that no copy may dereference (the pool has
    no such block). Logical [4, 16] pools: bitwise the lax.scan reference;
    16 x 64 packed as [8, 128]: inside float32 rounding of it; both against
    the dense softmax oracle."""
    heads, head_dim = (4, 16) if stored == "logical" else (16, 64)
    tile = (heads, head_dim) if stored == "logical" else (8, 128)
    assert rpa.blocks_per_group((_BS,) + tile, np.float32, _MB) == 4
    rng = np.random.RandomState(length)
    lengths = np.array([2 * _BS + 5, length, 5 * _BS + 3], np.int32)
    k, v = (rng.randn(_NB, _BS, heads, head_dim).astype(np.float32)
            for _ in range(2))
    q = jnp.asarray(rng.randn(3, heads, head_dim).astype(np.float32))
    tables = _block_tables(rng, lengths, _NB, _BS, _MB,
                           -1 if sentinel == "minus-one" else _NB)
    args = (q, jnp.asarray(k).reshape((_NB, _BS) + tile),
            jnp.asarray(v).reshape((_NB, _BS) + tile), jnp.asarray(tables),
            jnp.asarray(lengths))
    got = np.asarray(rpa.ragged_decode_attention(*args, interpret=True))
    ref = np.asarray(rpa.ragged_attention_reference(*args))
    if stored == "logical":
        np.testing.assert_array_equal(got, ref)
    else:
        np.testing.assert_allclose(got, ref, rtol=2e-6, atol=2e-6)
    oracle = _dense_oracle(q, k, v, np.where(tables == _NB, -1, tables),
                           lengths)
    np.testing.assert_allclose(got, oracle, rtol=2e-6, atol=2e-6)
    assert length or np.all(got[1] == 0.0)


@pytest.mark.parametrize("lengths", [
    (3 * _BS + 1, 0, 6 * _BS), (_MB * _BS, 1, 0), (0, 0, 5 * _BS - 1)],
    ids=["dead-between", "whole-table-then-one", "dead-rows-first"])
def test_kernel_waits_for_the_copies_it_reads(lengths):
    """Under the TPU interpreter with every DMA carried out only when it is
    waited on (`interpret=True` copies at the start): a block multiplied
    before its copy was waited on, or a wait on the wrong slot's semaphore,
    reads what the buffer held before and shows here."""
    from jax.experimental.pallas import tpu as pltpu
    rng = np.random.RandomState(7)
    lengths = np.asarray(lengths, np.int32)
    k, v = (jnp.asarray(rng.randn(_NB, _BS, 8, 128).astype(np.float32))
            for _ in range(2))
    q = jnp.asarray(rng.randn(3, 16, 64).astype(np.float32))
    tables = _block_tables(rng, lengths, _NB, _BS, _MB, _NB)
    args = (q, k, v, jnp.asarray(tables), jnp.asarray(lengths))
    got = np.asarray(rpa.ragged_decode_attention(
        *args, interpret=pltpu.InterpretParams(dma_execution_mode="on_wait")))
    np.testing.assert_allclose(
        got, np.asarray(rpa.ragged_attention_reference(*args)),
        rtol=2e-6, atol=2e-6)
    assert np.all(got[lengths == 0] == 0.0)


def test_one_row_alone_walks_its_blocks():
    """A batch of one: no row before it starts its first group and none
    after it takes a hand-over; seven blocks are a whole group and one that
    is not full."""
    rng = np.random.RandomState(3)
    lengths = np.array([7 * _BS - 2], np.int32)
    k, v = (jnp.asarray(rng.randn(_NB, _BS, 4, 16).astype(np.float32))
            for _ in range(2))
    q = jnp.asarray(rng.randn(1, 4, 16).astype(np.float32))
    args = (q, k, v, jnp.asarray(_block_tables(rng, lengths, _NB, _BS, _MB)),
            jnp.asarray(lengths))
    np.testing.assert_array_equal(
        np.asarray(rpa.ragged_decode_attention(*args, interpret=True)),
        np.asarray(rpa.ragged_attention_reference(*args)))


def _equations(jaxpr) -> int:
    """Equations of a jaxpr and of every jaxpr inside it (loop and branch
    bodies, the kernel under its `pallas_call`)."""
    def inner(v):
        if hasattr(v, "eqns"):
            yield v
        elif hasattr(v, "jaxpr"):
            yield v.jaxpr
        elif isinstance(v, (tuple, list)):
            for x in v:
                yield from inner(x)
    return sum(1 + sum(_equations(j) for v in e.params.values()
                       for j in inner(v)) for e in jaxpr.eqns)


def test_the_kernels_program_does_not_grow_with_the_group():
    """The set-up budget's guard that needs no chip (PERF.md section 6, PR
    39): a group's blocks are loops the kernel runs, so the program traced
    at a group of 4 blocks is as long as at a group of 1. Unrolled in
    Python it was four times as long in the part that repeats, and every
    GPT-2 serving cell's set-up paid the host time of tracing it."""
    def traced(table_len, group):
        block = (_BS, 8, 128)
        assert rpa.blocks_per_group(block, np.float32, table_len) == group
        pool = jax.ShapeDtypeStruct((_NB,) + block, jnp.float32)
        return _equations(jax.make_jaxpr(
            functools.partial(rpa.ragged_decode_attention, interpret=True))(
            jax.ShapeDtypeStruct((3, 16, 64), jnp.float32), pool, pool,
            jax.ShapeDtypeStruct((3, table_len), jnp.int32),
            jax.ShapeDtypeStruct((3,), jnp.int32)).jaxpr)
    one, four = traced(1, 1), traced(_MB, 4)
    assert one > 100                    # the kernel's body was counted
    assert abs(four - one) <= 4


# ------------------------------------------------------- engine parity
def test_live_blocks_sum_to_the_entries_the_tables_hold(model, monkeypatch):
    """Chunks of ONE trip: a row's table then holds exactly the blocks of
    the positions that trip attends to, so `live_blocks` of every
    `serving.decode` span is the table entries packed into its upload, and
    `EngineStats.live_blocks` their sum over the run."""
    from paddle_tpu import obs
    from paddle_tpu.inference.serving import engine as engine_mod
    eng = _engine(model, decode_chunk_size=1)
    table_of, chunk = eng.cache.block_table, engine_mod.fused_decode_chunk
    asked, uploaded = [], []

    def recording_table(rid):
        asked.append(len(table_of(rid)))
        return table_of(rid)

    def recording_chunk(params, pools, packed, *rest):
        live = int(np.asarray(packed)[:, 2].sum())
        uploaded.append(sum(asked[-live:]))     # the pack's own calls
        return chunk(params, pools, packed, *rest)

    monkeypatch.setattr(eng.cache, "block_table", recording_table)
    monkeypatch.setattr(engine_mod, "fused_decode_chunk", recording_chunk)
    obs.trace.enable()
    try:
        for n, new in ((3, 9), (7, 5), (4, 12)):    # across block edges
            eng.add_request(np.arange(1, n + 1, dtype=np.int32),
                            SamplingParams(max_tokens=new))
        eng.run(max_steps=100)
        spans = [e.args["live_blocks"] for e in obs.trace.events()
                 if e.name == "serving.decode"]
    finally:
        obs.trace.disable()
    assert spans == uploaded and len(spans) > 10
    assert eng.stats.live_blocks == sum(uploaded)


def test_greedy_ragged_bucketed_dense_bitwise(model):
    """THE tentpole pin: kernel='ragged' output == kernel='bucketed'
    output == dense generate(), token-exact, on a mixed-length
    workload."""
    prompts = [np.arange(1, 4, dtype=np.int32),
               np.arange(5, 12, dtype=np.int32),
               np.asarray([9, 1, 7, 3], np.int32)]
    samp = [SamplingParams(max_tokens=mt) for mt in (9, 5, 12)]
    _, rr, res_r = _run_engine(model, prompts, samp, kernel="ragged")
    _, rb, res_b = _run_engine(model, prompts, samp, kernel="bucketed")
    for r_r, r_b, p, s in zip(rr, rb, prompts, samp):
        np.testing.assert_array_equal(res_r[r_r], res_b[r_b])
        np.testing.assert_array_equal(
            res_r[r_r], _reference_tokens(model, p, s.max_tokens))


def test_stochastic_ragged_bucketed_parity(model):
    """Temperature/top-k/top-p streams match across kernels. Off-TPU
    this is bitwise (same lowered path, row-invariant padding); the
    TPU kernel's numeric envelope is bounded by the oracle test above,
    so any divergence here is a routing bug, not noise."""
    prompts = [np.arange(1, 4, dtype=np.int32),
               np.asarray([9, 1, 7, 3], np.int32),
               np.arange(5, 10, dtype=np.int32)]
    samp = [SamplingParams(max_tokens=10, temperature=0.9, top_k=9,
                           top_p=0.8, seed=11),
            SamplingParams(max_tokens=8, temperature=0.7, seed=22),
            SamplingParams(max_tokens=12, temperature=1.1, top_p=0.95,
                           seed=33)]
    _, rr, res_r = _run_engine(model, prompts, samp, kernel="ragged",
                               num_blocks=32)
    _, rb, res_b = _run_engine(model, prompts, samp, kernel="bucketed",
                               num_blocks=32)
    if jax.default_backend() == "tpu":
        pytest.skip("stochastic streams are knife-edge under the "
                    "kernel's 1e-6 envelope; the greedy test and the "
                    "kernel oracle carry the TPU contract")
    for r_r, r_b in zip(rr, rb):
        np.testing.assert_array_equal(res_r[r_r], res_b[r_b])
        assert np.all(res_r[r_r] >= 0) and np.all(res_r[r_r] < VOCAB)


# --------------------------------------------------- compile-count pin
def test_one_compilation_covers_all_batch_mixes(model):
    """The acceptance pin that retires the bucket-recompile axis:
    driving the ragged engine through batch sizes 1..4 (staggered
    arrivals + drains) adds exactly ONE fused_decode_chunk cache entry;
    the bucketed fallback adds one per power-of-two bucket it walks."""
    def drive(kern):
        # num_blocks=28 is used by NO other test: the pool aval is
        # unique to this one, so the jit-cache deltas below count this
        # test's compilations only, whatever ran before
        before = fused_decode_chunk._cache_size()
        # k=2 so requests stay in flight across the staggered arrivals:
        # live counts genuinely walk 1 -> 2 -> 3 -> 4 -> drain, so the
        # bucketed fallback visits buckets 1, 2 AND 4
        eng = _engine(model, kernel=kern, num_blocks=28,
                      decode_chunk_size=2)
        eng.add_request(np.arange(1, 4, dtype=np.int32),
                        SamplingParams(max_tokens=14))
        eng.step()
        for i in range(3):
            eng.add_request(np.arange(2 + i, 7 + i, dtype=np.int32),
                            SamplingParams(max_tokens=12 - 3 * i))
            eng.step()
        eng.run(max_steps=100)
        return fused_decode_chunk._cache_size() - before

    assert drive("ragged") == 1   # THE program: all mixes, one compile
    assert drive("ragged") == 0   # a second engine reuses it
    assert drive("bucketed") == 3  # one per power-of-two bucket walked


def test_the_decode_span_says_how_wide_the_program_ran(model):
    """3 live rows the whole run: the ragged kernel's chunk runs at the
    one fixed `max_num_seqs` width, 8 (dead rows cost no kernel work), the
    bucketed fallback at its power-of-two pad, 4. `rows` beside `num_seqs`
    on `serving.decode` says so (PR 37: it took the place of the gauge
    `serving_padding_waste_ratio`, the same quotient of the last chunk)."""
    from paddle_tpu import obs
    prompts = [np.arange(1, 4, dtype=np.int32)] * 3
    samp = [SamplingParams(max_tokens=6)] * 3
    widths = {}
    for kernel in ("ragged", "bucketed"):
        obs.trace.enable()
        try:
            _run_engine(model, prompts, samp, kernel=kernel, max_num_seqs=8)
            widths[kernel] = {
                (e.args["num_seqs"], e.args["rows"], e.args["feeding_rows"])
                for e in obs.trace.events() if e.name == "serving.decode"}
        finally:
            obs.trace.disable()
    assert widths == {"ragged": {(3, 8, 0)}, "bucketed": {(3, 4, 0)}}


# ------------------------------------------------------ chunked prefill
def test_chunked_prefill_greedy_matches_dense_prefill(model):
    """Prompts above the threshold stream through the fused scan in
    k-token chunks instead of one-shot generation.prefill; greedy
    output is token-identical to the dense reference (the first token
    comes from in-scan argmax over logits that match the dense
    prefill's row to float32 tolerance — equal argmax, pinned here).
    Short prompts still take the dense path in the same engine."""
    prompts = [np.arange(1, 15, dtype=np.int32),   # chunked (14 > 6)
               np.arange(3, 13, dtype=np.int32),   # chunked (10 > 6)
               np.asarray([9, 1, 7], np.int32)]    # dense   (3 <= 6)
    samp = [SamplingParams(max_tokens=mt) for mt in (8, 10, 6)]
    eng, rids, res = _run_engine(model, prompts, samp, kernel="ragged",
                                 prefill_chunk_threshold=6,
                                 num_blocks=32)
    for rid, p, s in zip(rids, prompts, samp):
        np.testing.assert_array_equal(
            res[rid], _reference_tokens(model, p, s.max_tokens))
    assert eng.stats.prefill_chunks() >= 3   # 14 and 10 tokens at k=8
    eng.cache.check_integrity()


@pytest.mark.parametrize("k", [1, 3, 8])
def test_chunked_prefill_chunk_size_invariant(model, k):
    """The chunked stream does not depend on chunk geometry: feeding a
    prompt 1, 3 or 8 tokens per chunk yields the same output (sampling
    keys are fold_in(seed, progress) — progress-based, so the first
    token's key is identical no matter which trip samples it)."""
    prompts = [np.arange(1, 14, dtype=np.int32),
               np.arange(2, 12, dtype=np.int32)]
    samp = [SamplingParams(max_tokens=7, temperature=0.8, top_k=11,
                           seed=5),
            SamplingParams(max_tokens=7)]
    _, rids, res = _run_engine(model, prompts, samp, kernel="ragged",
                               prefill_chunk_threshold=4,
                               decode_chunk_size=k, num_blocks=32)
    _, rids8, res8 = _run_engine(model, prompts, samp, kernel="ragged",
                                 prefill_chunk_threshold=4,
                                 decode_chunk_size=8, num_blocks=32)
    for r, r8 in zip(rids, rids8):
        np.testing.assert_array_equal(res[r], res8[r8])


def test_eos_mid_chunk_during_chunked_prefill(model):
    """EOS sampled on the very first output of a chunked prompt — the
    trip right after the last fed prompt token, mid-chunk — freezes the
    row in-scan: exactly one token emitted, blocks all returned."""
    p = np.arange(1, 14, dtype=np.int32)
    ref = _reference_tokens(model, p, 4)
    eos = int(ref[0])                     # first output IS the stop
    eng = _engine(model, kernel="ragged", prefill_chunk_threshold=6,
                  num_blocks=32)
    rid = eng.add_request(p, SamplingParams(max_tokens=4,
                                            eos_token_id=eos))
    outs = []
    while eng.has_unfinished():
        outs.extend(eng.step())
    req = eng.get_request(rid)
    np.testing.assert_array_equal(np.asarray(req.output_ids), ref[:1])
    assert outs[-1].finished and outs[-1].finish_reason == "stop"
    assert eng.cache.num_free() == eng.config.num_blocks
    eng.cache.check_integrity()


def test_chunked_prefill_preemption_requeue(model):
    """A pool too small for everyone forces recompute preemption while
    chunked prefills are in flight: the preempted row requeues with its
    pf state reset, re-feeds from the start, and every request still
    completes with the dense-reference tokens — zero leaks."""
    prompts = [np.arange(1, 12, dtype=np.int32),
               np.arange(2, 13, dtype=np.int32),
               np.arange(3, 11, dtype=np.int32)]
    samp = [SamplingParams(max_tokens=mt) for mt in (10, 8, 9)]
    # watermark 1.0 admits everyone off their cheap first chunk (a
    # chunked admission only reserves min(k, ...) slots); the pool then
    # cannot hold all three grown sequences, so growth preempts
    eng, rids, res = _run_engine(model, prompts, samp, kernel="ragged",
                                 prefill_chunk_threshold=4,
                                 num_blocks=10, cache_high_watermark=1.0)
    assert eng.stats.preemptions >= 1
    for rid, p, s in zip(rids, prompts, samp):
        np.testing.assert_array_equal(
            res[rid], _reference_tokens(model, p, s.max_tokens))
    assert eng.cache.num_free() == eng.config.num_blocks
    eng.cache.check_integrity()


def test_chunked_chaos_zero_leak_zero_lost(model):
    """NaN fault lands while a chunked prefill is mid-stream: the
    poisoned chunk is discarded (prefill progress does NOT commit), the
    offender is quarantined, survivors — mid-prefill rows included —
    are rebuilt by requeue and replay bitwise; nothing is lost and no
    block leaks."""
    fi = ServingFaultInjector("nan_logits@2:1")
    eng = LLMEngine.from_model(
        model, EngineConfig(block_size=4, num_blocks=32, max_num_seqs=4,
                            kernel="ragged", prefill_chunk_threshold=4),
        faults=fi)
    prompts = [np.arange(1, 12, dtype=np.int32),
               np.asarray([9, 1, 7, 3, 2, 8, 4, 6, 5], np.int32),
               np.arange(5, 15, dtype=np.int32)]
    rids = [eng.add_request(p, SamplingParams(max_tokens=7))
            for p in prompts]
    res = eng.run(max_steps=200)
    assert ("nan_logits", 2) in fi.fired_log
    states = [eng.get_request(r).state for r in rids]
    assert all(str(s).startswith("finished") for s in states)
    errored = [r for r, s in zip(rids, states) if s == "finished_error"]
    assert len(errored) == 1
    for p, rid in zip(prompts, rids):
        if rid in errored:
            continue
        np.testing.assert_array_equal(
            res[rid], _reference_tokens(model, p, 7))
    assert eng.cache.num_free() == eng.config.num_blocks
    eng.cache.check_integrity()
