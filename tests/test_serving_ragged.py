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
    # the flat grouped tile multiplies a whole group at once: its program
    # is as long at a group of 16 blocks as at 1
    flat = [_flat_equations(table_len, group)
            for table_len, group in ((1, 1), (_FLAT_MB, 16))]
    assert flat[0] > 100 and flat[0] == flat[1]


def _flat_equations(table_len, group):
    block = (_BS, 512)
    assert rpa.blocks_per_group(block, jnp.bfloat16, table_len) == group
    pool = jax.ShapeDtypeStruct((_FLAT_NB,) + block, jnp.bfloat16)
    return _equations(jax.make_jaxpr(
        functools.partial(rpa.ragged_decode_attention, interpret=True))(
        jax.ShapeDtypeStruct((3, 16, 256), jnp.bfloat16), pool, pool,
        jax.ShapeDtypeStruct((3, table_len), jnp.int32),
        jax.ShapeDtypeStruct((3,), jnp.int32)).jaxpr)


# sha1 of the jaxpr text of the kernel at GPT-2 medium's stored block
# [32, 8, 128] float32 (cells 2, 4 and 8: 16 rows, 512 blocks, tables of 32),
# taken with jax 0.9.0 on the tree before the flat grouped tile existed
GPT2M_KERNEL_JAXPR_SHA1 = "acb0045eded51df1adb5318aa3e3e123b48ba946"


@pytest.mark.skipif(jax.__version__ != "0.9.0",
                    reason="the recorded text is jax 0.9.0's")
def test_the_one_head_a_query_head_tile_traces_as_it_did():
    """The flat grouped tile is chosen when the kernel is traced, from the
    pool's rank: the tile GPT-2's cells read gains no equation and traces
    to the same text, so its lowering and its set-up stay what they were."""
    import hashlib
    pool = jax.ShapeDtypeStruct((512, 32, 8, 128), jnp.float32)
    text = str(jax.make_jaxpr(rpa.ragged_decode_attention)(
        jax.ShapeDtypeStruct((16, 16, 64), jnp.float32), pool, pool,
        jax.ShapeDtypeStruct((16, 32), jnp.int32),
        jax.ShapeDtypeStruct((16,), jnp.int32)))
    assert hashlib.sha1(text.encode()).hexdigest() == GPT2M_KERNEL_JAXPR_SHA1


# ------------------------------------------- the flat grouped tile
# blocks [32, 512] in a table of 20: a DMA group is 16 blocks in bfloat16
# (32 KiB a block) and 8 in float32, so the tables hold a whole group and a
# partial one (two and a partial one in float32)
_FLAT_NB, _FLAT_MB = 160, 20
_FLAT_LENGTHS = (0, 1, _BS, _BS + 1, 17 * _BS + 3, _FLAT_MB * _BS)
# against the composed path on the same pools: float32 rounding of two
# orders of summation; in bfloat16 both round the probabilities and the
# result to bfloat16 (2 ** -8 relative), at different points of the sum
_FLAT_TOL = {"float32": 2e-6, "bfloat16": 2 ** -6}


def _flat_case(kv, head_dim, dtype, seed=0):
    """Rows of every length in _FLAT_LENGTHS (a dead row first) over pools
    of W = kv * head_dim lanes: each row's live blocks drawn from the pool,
    its sentinel entries pointing at a block of NaN, and NaN in every block
    no table holds. Returns the kernel's arguments and the same pools with
    zeros for NaN, for the composed path."""
    rng = np.random.RandomState(seed)
    lengths = np.asarray(_FLAT_LENGTHS, np.int32)
    lanes = kv * head_dim
    k, v = (rng.randn(_FLAT_NB, _BS, lanes).astype(np.float32)
            for _ in range(2))
    sentinel = _FLAT_NB - 1
    tables = _block_tables(rng, lengths, sentinel, _BS, _FLAT_MB, sentinel)
    outside = np.setdiff1d(np.arange(_FLAT_NB), tables[tables != sentinel])
    assert sentinel in outside and len(outside) > 1
    k[outside] = np.nan
    v[outside] = np.nan
    q = rng.randn(len(lengths), 8 * kv, head_dim).astype(np.float32)
    k, v, q = (jnp.asarray(a, dtype) for a in (k, v, q))
    clean = [jnp.where(jnp.isnan(p), 0, p) for p in (k, v)]
    return (q, k, v, jnp.asarray(tables), jnp.asarray(lengths)), clean


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("kv,head_dim", [(2, 256), (4, 128)],
                         ids=["2x256", "4x128"])
def test_the_flat_grouped_tile_is_the_composed_attention(kv, head_dim,
                                                         dtype):
    """8 query heads a KV head over pools stored as 512 flat lanes, against
    `qwen3_next._rows_attention` over `gather_rows` (what cells 6 and 7
    composed before), row lengths 0, 1, a block, a block and one, a whole
    DMA group and part of the next, the whole table. NaN in every block the
    kernel must not read reaches no result; a dead row returns zeros."""
    from paddle_tpu.inference.serving.paged_cache import gather_rows
    from paddle_tpu.models.qwen3_next import _rows_attention
    args, (k, v) = _flat_case(kv, head_dim, dtype)
    q, _, _, tables, lengths = args
    got = np.asarray(rpa.ragged_decode_attention(*args, interpret=True),
                     np.float32)
    flat = (kv * head_dim,)
    want = np.asarray(_rows_attention(
        q, gather_rows(k, tables, flat), gather_rows(v, tables, flat),
        lengths, kv), np.float32)
    assert np.isfinite(got).all()
    assert np.all(got[0] == 0.0)
    tol = _FLAT_TOL[dtype]
    np.testing.assert_allclose(got[1:], want[1:], rtol=tol, atol=tol)


def test_the_flat_grouped_tile_waits_for_the_copies_it_reads():
    """As the other tile's test: every DMA carried out only when it is
    waited on, so a group multiplied before its copies land reads what the
    slot held before (NaN among it)."""
    from jax.experimental.pallas import tpu as pltpu
    args, _ = _flat_case(2, 256, "float32", seed=1)
    got = np.asarray(rpa.ragged_decode_attention(
        *args, interpret=pltpu.InterpretParams(dma_execution_mode="on_wait")))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(
        got, np.asarray(rpa.ragged_decode_attention(*args, interpret=True)),
        rtol=_FLAT_TOL["float32"], atol=_FLAT_TOL["float32"])


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


# ------------------------ full layers of the grouped families, kernel forced
# toy widths whose pools are stored as the flat grouped tile (2 KV heads of
# 128 -> 256 lanes, `physical_shape`), 4 query heads a KV head
_GROUPED = dict(vocab_size=128, hidden_size=64, num_hidden_layers=4,
                num_attention_heads=8, num_key_value_heads=2, head_dim=128,
                moe_intermediate_size=32, num_experts=8,
                num_experts_per_tok=2, max_seq_len=64)


def _grouped_family(name):
    from paddle_tpu.models import mellum, qwen3_next
    from paddle_tpu.models.generation import extract_params
    if name == "qwen3_next":
        cfg = qwen3_next.Qwen3NextConfig(
            **_GROUPED, linear_num_key_heads=2, linear_num_value_heads=4,
            linear_key_head_dim=8, linear_value_head_dim=8,
            shared_expert_intermediate_size=32)
        paddle.seed(3)
        model, spec = qwen3_next.Qwen3Next(cfg), qwen3_next.serving_spec(cfg)
    else:
        cfg = mellum.MellumConfig(**_GROUPED, sliding_window=12)
        paddle.seed(3)
        model, spec = mellum.Mellum(cfg), mellum.serving_spec(cfg)
    return spec, extract_params(model)


@pytest.fixture
def kernel_forced(monkeypatch):
    """The ragged kernel's gate open off the chip, the kernel run by the
    interpreter; jit's caches emptied on both sides, so no program traced
    the other way is reused."""
    def forced():
        monkeypatch.setattr(rpa, "route_gate", lambda *a: True)
        monkeypatch.setattr(rpa, "ragged_decode_attention", functools.partial(
            rpa.ragged_decode_attention, interpret=True))
        jax.clear_caches()
    yield forced
    monkeypatch.undo()
    jax.clear_caches()


def _grouped_engine(spec, params):
    return LLMEngine(params, spec, EngineConfig(
        block_size=8, num_blocks=40, max_num_seqs=4))


def _grouped_prompts():
    rng = np.random.default_rng(11)
    return [rng.integers(0, 128, (n,)).astype(np.int32)
            for n in (3, 14, 25, 9)]


def _grouped_stream(spec, params):
    eng = _grouped_engine(spec, params)
    for i, p in enumerate(_grouped_prompts()):
        eng.add_request(p, SamplingParams(max_tokens=20),
                        request_id=f"r{i}")
    out = eng.run(max_steps=200)
    assert not any(eng.cache.check_integrity().values())
    return [out[f"r{i}"].tolist() for i in range(4)]


def _hand_decoded_logits(spec, params, prompt, steps):
    """[1 + steps, V] logits of the prefill and `steps` paged decode steps
    of one sequence, greedy, the tokens taken from the composed path's
    logits; each step runs twice on two caches, composed and with every
    layer's `ragged` set. Returns both rows."""
    import dataclasses
    from paddle_tpu.inference.serving.attention import paged_decode_step

    def ragged_layer(*args):
        return spec.decode_layer(*args[:10], True, *args[11:])

    specs = (spec, dataclasses.replace(spec, decode_layer=ragged_layer))
    steps_of = [jax.jit(functools.partial(paged_decode_step, geom=s),
                        donate_argnums=(1,)) for s in specs]
    caches = [_grouped_engine(spec, params).cache for _ in specs]
    logits, dense, _ = spec.prefill(params, jnp.asarray(prompt[None]))
    rows = [[np.asarray(logits, np.float32)[0]] for _ in specs]
    for cache in caches:
        cache.allocate("s", len(prompt))
        cache.write_prefill("s", dense, len(prompt))
    for _ in range(steps):
        tok = np.asarray([rows[0][-1].argmax()], np.int32)
        for step, cache, out in zip(steps_of, caches, rows):
            block, offset, pos = cache.append_slot("s")
            table = np.zeros((1, 8), np.int32)
            table[0, :len(cache.block_table("s"))] = cache.block_table("s")
            kw = {}
            if spec.state_layers:
                kw["state_slots"] = np.asarray([cache.state_slot("s")],
                                               np.int32)
            if spec.window:
                wtable, first = cache.window_table("s")
                kw["window_tables"] = np.zeros((1, 8), np.int32)
                kw["window_tables"][0, :len(wtable)] = wtable
                kw["window_firsts"] = np.asarray([first], np.int32)
            logits, cache.pools = step(
                params, cache.pools, tok, np.asarray([pos], np.int32),
                table, np.asarray([block], np.int32),
                np.asarray([offset], np.int32), **kw)
            out.append(np.asarray(logits, np.float32)[0])
            if spec.window:
                cache.release_behind("s")
    return np.stack(rows[0]), np.stack(rows[1])


def _gathers_and_kernel_calls(jaxpr):
    """Shapes of the arrays every `gather` of a jaxpr (its loop bodies
    included) reads from, and the number of `ragged_decode_attention`
    calls."""
    shapes, calls = [], 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "gather":
            shapes.append(tuple(eqn.invars[0].aval.shape))
        if eqn.params.get("name") == "ragged_decode_attention":
            calls += 1
            continue
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    more, n = _gathers_and_kernel_calls(inner)
                    shapes += more
                    calls += n
    return shapes, calls


@pytest.mark.parametrize("family", ["qwen3_next", "mellum"])
def test_full_layers_through_the_kernel_stream_the_composed_tokens(
        family, kernel_forced, monkeypatch):
    """The families of cells 6 and 7 at toy widths stored as the flat
    grouped tile, with the kernel's gate forced open (the interpreter runs
    it): the same greedy tokens as the composed path over several chunks,
    logits within the kernel test's tolerance, and in the chunk's program
    the full layers' pools read by one kernel call a layer and never
    gathered, the window layers' pools still gathered."""
    from paddle_tpu.inference.serving import engine as engine_mod
    spec, params = _grouped_family(family)
    assert spec.cache_shape == (2, 128)
    composed = _grouped_stream(spec, params)
    kernel_forced()

    plain, ragged = _hand_decoded_logits(spec, params,
                                         _grouped_prompts()[2], 16)
    tol = _FLAT_TOL["float32"]
    np.testing.assert_allclose(ragged, plain, rtol=tol, atol=tol)

    chunks = []

    def recording_chunk(*args):
        chunks.append(jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args[:3]))
        return chunk(*args)

    chunk = engine_mod.fused_decode_chunk
    monkeypatch.setattr(engine_mod, "fused_decode_chunk", recording_chunk)
    assert _grouped_stream(spec, params) == composed
    assert len(chunks) >= 3

    k, kernel = 8, "ragged"
    jaxpr = jax.make_jaxpr(fused_decode_chunk, static_argnums=(3, 4, 5))(
        *chunks[0], spec, k, kernel).jaxpr
    gathered, calls = _gathers_and_kernel_calls(jaxpr)
    rows_layers = [i for i, c in enumerate(spec.layer_caches or
                                           ("rows",) * spec.num_layers)
                   if c == "rows"]
    pools = chunks[0][1]
    full = {tuple(pools[i][0].shape) for i in rows_layers}
    assert calls == len(rows_layers) > 0
    assert not full & set(gathered)
    windowed = {tuple(pools[i][0].shape) for i, c in
                enumerate(spec.layer_caches) if c == "window"}
    assert (family == "mellum") == bool(windowed)
    assert windowed <= set(gathered) and not windowed & full
