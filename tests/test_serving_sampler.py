"""The sampler inside the fused decode scan does only what a chunk's rows
ask for (serving/attention._sample_rows, ISSUE 34).

Two scalar predicates, taken from the chunk's control columns before the
scan, choose among three dataflows under `lax.cond`: no row samples ->
the argmax and nothing else; rows sample, none truncates -> the scaled
categorical draw; a row truncates -> ONE descending sort serves top-k and
top-p. The pins:
- every row's token is bit for bit what the old branchless form (two
  sorts, every row through every path; kept below as the oracle) gave,
  whatever branch the row's neighbours put it in;
- a greedy row's stream does not depend on which branch ran;
- the greedy branch of the traced chunk holds no sort, cumsum or random
  bits, and the whole chunk holds one sort;
- the host counts what it packed: stat `sampled_rows` on
  `serving.decode`, counter `sampled_chunks` on `EngineStats`.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import obs
from paddle_tpu.analysis.jaxpr_audit import _iter_eqns
from paddle_tpu.models.gpt import GPT, GPTConfig
import paddle_tpu.models.generation as gen
from paddle_tpu.inference.serving import (EngineConfig, LLMEngine,
                                          PagedKVCache, SamplingParams,
                                          fused_decode_chunk)
from paddle_tpu.inference.serving.attention import (PACK_COLS, _sample_rows,
                                                    pack_f32)

VOCAB = 101


@jax.jit
def _two_sort_sample_rows(logits, keys, temps, top_ks, top_ps):
    """The sampler as it stood before ISSUE 34, verbatim: every row runs
    top-k's sort, top-p's sort, the softmax, the cumsum and the draw."""
    vocab = logits.shape[-1]
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    lg = logits.astype(jnp.float32) / jnp.where(temps > 0, temps, 1.0)[:, None]
    srt = jnp.sort(lg, axis=-1)[:, ::-1]
    kth = jnp.take_along_axis(
        srt, jnp.clip(top_ks - 1, 0, vocab - 1)[:, None], axis=1)
    lg = jnp.where((top_ks[:, None] > 0) & (lg < kth), -1e30, lg)
    srt = jnp.sort(lg, axis=-1)[:, ::-1]
    probs = jax.nn.softmax(srt, axis=-1)
    excl = jnp.cumsum(probs, axis=-1) - probs
    n_keep = jnp.sum(excl < top_ps[:, None], axis=-1)
    pth = jnp.take_along_axis(
        srt, jnp.clip(n_keep - 1, 0, vocab - 1)[:, None], axis=1)
    use_p = (top_ps > 0.0) & (top_ps < 1.0)
    lg = jnp.where(use_p[:, None] & (lg < pth), -1e30, lg)
    sampled = jax.vmap(jax.random.categorical)(keys, lg).astype(jnp.int32)
    return jnp.where(temps <= 0.0, greedy, sampled)


# compiled, as in the chunk: the predicates are traced scalars
sample_rows = jax.jit(_sample_rows)


#: rows of (temperature, top_k, top_p) a case's batch cycles through
ROWS = {
    "greedy": [(0.0, 0, 1.0), (0.0, 5, 0.5)],   # knobs without a temperature
    "temperature": [(0.7, 0, 1.0), (1.3, 0, 0.0)],
    "top_k": [(0.9, 1, 1.0), (0.9, 7, 1.0), (1.0, VOCAB + 5, 1.0)],
    "top_p": [(0.8, 0, 0.9), (1.1, 0, 0.3), (1.0, 0, 1e-6)],
    "both": [(0.9, 9, 0.8), (0.6, 3, 0.95)],
    "mixed": [(0.0, 0, 1.0), (0.7, 0, 1.0), (0.9, 7, 1.0), (0.8, 0, 0.9),
              (0.9, 9, 0.8), (0.0, 4, 0.5)],
}


def _logits(kind, n, rng):
    lg = rng.normal(0.0, 2.0, (n, VOCAB)).astype(np.float32)
    if kind == "tied":
        # five distinct values a row: ties at every k-th value and at every
        # nucleus crossing, and at the argmax
        lg = np.round(lg / 2.0) * 2.0
    return jnp.asarray(lg)


@pytest.mark.parametrize("logit_kind", ["distinct", "tied"])
@pytest.mark.parametrize("rows", sorted(ROWS))
def test_one_sort_sampler_bitwise_matches_the_two_sort_form(rows, logit_kind):
    n = 12
    knobs = [ROWS[rows][i % len(ROWS[rows])] for i in range(n)]
    temps = jnp.asarray([t for t, _, _ in knobs], jnp.float32)
    top_ks = jnp.asarray([k for _, k, _ in knobs], jnp.int32)
    top_ps = jnp.asarray([p for _, _, p in knobs], jnp.float32)
    base_keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(n, dtype=jnp.int32))
    # what fused_decode_chunk takes from the control columns
    samples = temps > 0
    any_sampled = jnp.any(samples)
    any_truncated = jnp.any(
        samples & ((top_ks > 0) | ((top_ps > 0) & (top_ps < 1))))
    assert bool(any_sampled) == (rows != "greedy")
    assert bool(any_truncated) == (rows not in ("greedy", "temperature"))
    rng = np.random.default_rng(len(rows) + len(logit_kind))
    for trip in range(12):
        logits = _logits(logit_kind, n, rng)
        out_cnt = jnp.full((n,), trip, jnp.int32)
        keys = jax.vmap(jax.random.fold_in)(base_keys, out_cnt)
        want = np.asarray(
            _two_sort_sample_rows(logits, keys, temps, top_ks, top_ps))
        got = np.asarray(sample_rows(
            logits, base_keys, out_cnt, temps, top_ks, top_ps,
            any_sampled, any_truncated))
        np.testing.assert_array_equal(got, want)
        # and the same tokens from the branch that does everything: a
        # row's token does not depend on its neighbours' knobs
        forced = np.asarray(sample_rows(
            logits, base_keys, out_cnt, temps, top_ks, top_ps,
            jnp.asarray(True), jnp.asarray(True)))
        np.testing.assert_array_equal(forced, want)
        if rows != "greedy":
            half = np.asarray(sample_rows(
                logits, base_keys, out_cnt, temps, top_ks, top_ps,
                jnp.asarray(True), any_truncated))
            np.testing.assert_array_equal(half, want)


# ------------------------------------------------------ the chunk itself
@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    m = GPT(GPTConfig(vocab_size=VOCAB, hidden_size=32, num_layers=2,
                      num_heads=4, max_seq_len=24))
    m.eval()
    return m


def _geom(m):
    cfg = m.cfg
    return (cfg.num_layers, cfg.num_heads,
            cfg.hidden_size // cfg.num_heads, cfg.max_seq_len)


PROMPTS = [[1, 2, 3], [5, 6, 7, 8], [9, 1, 7]]


def _chunk(model, third, k=8, bs=4, nb=16):
    """One k-trip chunk over PROMPTS, all rows prefilled: rows 0 and 1
    greedy; row 2 is padding when `third` is None, else active with the
    knobs (temperature, top_k, top_p). Returns the [k, 3] tokens."""
    geom = _geom(model)
    L, H, D, S = geom
    params = gen.extract_params(model)
    cache = PagedKVCache(num_layers=L, cache_shape=(H, D), num_blocks=nb,
                         block_size=bs)
    packed = np.zeros((len(PROMPTS), PACK_COLS + k + S // bs), np.int32)
    for i, p in enumerate(PROMPTS):
        sid = str(i)
        cache.allocate(sid, len(p))
        logits, kvs = gen.prefill(
            params, jnp.asarray(np.asarray(p)[None], jnp.int32), geom)
        cache.write_prefill(sid, kvs, len(p))
        cache.reserve_slots(sid, k)
        t, tk, tp = (0.0, 0, 1.0) if i < 2 or third is None else third
        packed[i, :PACK_COLS] = [
            int(np.argmax(np.asarray(logits[0]))), len(p),
            0 if i == 2 and third is None else 1, 1, 1 + k, -1,
            pack_f32(t), tk, pack_f32(tp), 17 + i, 0, 0]
        table = cache.block_table(sid)
        packed[i, PACK_COLS + k:PACK_COLS + k + len(table)] = table
    out, _ = fused_decode_chunk(params, cache.pools, jnp.asarray(packed),
                                geom, k)
    return np.asarray(out)[:k]


@pytest.mark.parametrize("third", [(0.8, 0, 1.0), (0.8, 7, 0.9)],
                         ids=["temperature", "truncated"])
def test_greedy_rows_do_not_depend_on_the_branch_their_chunk_took(model,
                                                                  third):
    """An all-greedy chunk (the argmax branch) and the same chunk with one
    more, sampled, row (the sampled branch, with and without the sort)
    give the greedy rows the same streams."""
    alone = _chunk(model, None)
    assert np.all(alone[:, 2] == -1) and np.all(alone[:, :2] >= 0)
    beside = _chunk(model, third)
    np.testing.assert_array_equal(beside[:, :2], alone[:, :2])
    assert np.all((beside[:, 2] >= 0) & (beside[:, 2] < VOCAB))
    # the same row as a greedy one: the argmax of either branch
    greedy = _chunk(model, (0.0, 0, 1.0))
    np.testing.assert_array_equal(greedy[:, :2], alone[:, :2])


# -------------------------------------------------- what the trace holds
def _eqns(jaxpr):
    """Every equation under `jaxpr`, sub-jaxprs included."""
    return [e for kind, e, _ in _iter_eqns(jaxpr, "") if kind == "__eqn__"]


def _primitives(jaxpr):
    return [e.primitive.name for e in _eqns(jaxpr)]


def test_the_greedy_branch_of_the_chunk_sorts_nothing(model):
    geom = _geom(model)
    L, H, D, S = geom
    k, bs = 8, 4
    params = gen.extract_params(model)
    cache = PagedKVCache(num_layers=L, cache_shape=(H, D), num_blocks=16,
                         block_size=bs)
    packed = jnp.zeros((4, PACK_COLS + k + S // bs), jnp.int32)
    traced = jax.make_jaxpr(
        lambda prm, pools, pk: fused_decode_chunk(prm, pools, pk, geom, k))(
            params, cache.pools, packed)
    (scan,) = [e for e in _eqns(traced.jaxpr) if e.primitive.name == "scan"]
    body = scan.params["jaxpr"].jaxpr
    # ONE cond at the top of the body's sampler, a second inside it
    outer = [e for e in body.eqns if e.primitive.name == "cond"]
    assert len(outer) == 1
    greedy, sampled = (b.jaxpr for b in outer[0].params["branches"])
    costly = {"sort", "cumsum", "random_bits", "exp", "cond"}
    assert "argmax" in _primitives(greedy)
    assert not costly & set(_primitives(greedy))
    assert {"random_bits", "cond", "argmax"} <= set(_primitives(sampled))
    (inner,) = [e for e in sampled.eqns if e.primitive.name == "cond"]
    plain, truncated = (b.jaxpr for b in inner.params["branches"])
    assert not {"sort", "cumsum", "exp"} & set(_primitives(plain))
    assert "random_bits" in _primitives(plain)
    assert _primitives(truncated).count("sort") == 1
    assert {"cumsum", "random_bits"} <= set(_primitives(truncated))
    # and nowhere else in the chunk
    assert _primitives(traced.jaxpr).count("sort") == 1


# ------------------------------------------------- what the host counts
def _decode_spans(work):
    assert not obs.trace.is_enabled()
    obs.trace.enable()
    try:
        work()
        return [e.args for e in obs.trace.events()
                if e.name == "serving.decode"]
    finally:
        obs.trace.disable()
        obs.trace.clear()


@pytest.mark.parametrize("traffic, rows_by_chunk", [
    ("greedy", [0, 0, 0]),
    # the sampled requests leave after 4 and 12 tokens: the first chunk
    # holds two sampling rows, the second one, the third none
    ("mixed", [2, 1, 0]),
])
def test_sampled_rows_and_sampled_chunks_count_what_was_packed(
        model, traffic, rows_by_chunk):
    eng = LLMEngine.from_model(model, EngineConfig(
        block_size=4, num_blocks=32, max_num_seqs=4, decode_chunk_size=8))
    hot = traffic == "mixed"
    samp = [SamplingParams(max_tokens=20),
            SamplingParams(max_tokens=4, temperature=0.8 if hot else 0.0,
                           seed=3),
            SamplingParams(max_tokens=12, temperature=0.9 if hot else 0.0,
                           top_k=5, top_p=0.9, seed=4)]
    for p, s in zip(PROMPTS, samp):
        eng.add_request(np.asarray(p, np.int32), s)
    spans = _decode_spans(lambda: eng.run(max_steps=50))
    assert [s["sampled_rows"] for s in spans] == rows_by_chunk
    assert [s["num_seqs"] for s in spans] == [3, 2, 1]
    assert eng.stats.sampled_chunks == sum(r > 0 for r in rows_by_chunk)
    assert eng.stats.as_dict()["sampled_chunks"] == eng.stats.sampled_chunks
    assert eng.stats.host_syncs("decode") == len(rows_by_chunk)
