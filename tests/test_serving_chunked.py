"""Device-resident chunked decode (serving/attention.fused_decode_chunk
+ the LLMEngine chunk path, ISSUE 7).

The load-bearing pins:
- one fused k-token chunk is BITWISE-identical to k sequential
  single-token chunks — at the kernel level (same pools, same packed
  state) AND end-to-end through the engine (decode_chunk_size=8 vs 1),
  on the greedy path and on temperature/top-k/top-p under shared
  per-request PRNG seeds (sampling keys are fold_in(seed, progress),
  a function of request progress, never of chunk geometry);
- host syncs in steady-state decode are 1 per chunk, not 1 per token
  (the obs serving_host_syncs_total counter, the ISSUE acceptance
  metric);
- chunk-boundary semantics: EOS mid-chunk stops exactly at the eos
  token, deadlines abort at the next chunk boundary, and a NaN row
  inside a chunk poisons only that chunk — offender quarantined,
  survivors rebuilt bitwise, zero leaked blocks.
"""
import time

import numpy as np
import pytest
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.models.gpt import GPT, GPTConfig
import paddle_tpu.models.generation as gen
from paddle_tpu.inference.serving import (EngineConfig, LLMEngine,
                                          PagedKVCache, SamplingParams,
                                          fused_decode_chunk)
from paddle_tpu.inference.serving.attention import PACK_COLS, pack_f32
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


def _geom(m):
    cfg = m.cfg
    return (cfg.num_layers, cfg.num_heads,
            cfg.hidden_size // cfg.num_heads, cfg.max_seq_len)


def _engine(model, **kw):
    kw.setdefault("block_size", 4)
    kw.setdefault("num_blocks", 16)
    kw.setdefault("max_num_seqs", 4)
    return LLMEngine.from_model(model, EngineConfig(**kw))


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
def _packed_state(cache, seqs, mb, k):
    """Build the fused-chunk control array for live sequences
    [(seq_id, tok, pos, out_cnt, max_out, temp, top_k, top_p, seed)] —
    pure-decode rows (pf_feed=0, empty feed columns)."""
    packed = np.zeros((len(seqs), PACK_COLS + k + mb), np.int32)
    for i, (sid, tok, pos, out_cnt, max_out, t, tk, tp, seed) in \
            enumerate(seqs):
        table = cache.block_table(sid)
        packed[i, :PACK_COLS] = [tok, pos, 1, out_cnt, max_out, -1,
                                 pack_f32(t), tk, pack_f32(tp), seed,
                                 0, 0]
        packed[i, PACK_COLS + k:PACK_COLS + k + len(table)] = table
    return packed


@pytest.mark.parametrize("sampling", ["greedy", "stochastic"])
def test_fused_k_step_bitwise_matches_k_single_steps(model, sampling):
    """THE tentpole pin: one fused k=8 chunk emits bitwise-identical
    tokens to 8 sequential k=1 chunks from the same starting state —
    greedy and temperature/top-k/top-p (shared PRNG seeds) alike."""
    geom = _geom(model)
    L, H, D, S = geom
    params = gen.extract_params(model)
    bs, nb = 4, 16
    mb = S // bs
    prompts = [[1, 2, 3], [5, 6, 7, 8]]
    knobs = [(0.0, 0, 1.0, 0), (0.9, 9, 0.8, 7)] \
        if sampling == "stochastic" else [(0.0, 0, 1.0, 0)] * 2
    k = 8

    def run(chunks):
        cache = PagedKVCache(num_layers=L, cache_shape=(H, D),
                             num_blocks=nb, block_size=bs)
        state = []
        for i, p in enumerate(prompts):
            sid = str(i)
            cache.allocate(sid, len(p))
            logits, kvs = gen.prefill(
                params, jnp.asarray(np.asarray(p)[None], jnp.int32), geom)
            cache.write_prefill(sid, kvs, len(p))
            t, tk, tp, seed = knobs[i]
            # first token greedy off prefill logits in both runs
            tok = int(np.argmax(np.asarray(logits[0])))
            state.append([sid, tok, len(p), 1, 1 + k, t, tk, tp, seed])
        emitted = [[] for _ in prompts]
        for step_k in chunks:
            for s in state:
                cache.reserve_slots(s[0], step_k)
            packed = _packed_state(cache, state, mb, step_k)
            out, pools = fused_decode_chunk(
                params, cache.pools, jnp.asarray(packed), geom, step_k)
            cache.pools = pools
            fetched = np.asarray(out)
            for j in range(step_k):
                for i, s in enumerate(state):
                    t = int(fetched[j, i])
                    if t >= 0:
                        emitted[i].append(t)
                        s[1], s[2], s[3] = t, s[2] + 1, s[3] + 1
        return emitted

    assert run([k]) == run([1] * k)


# ------------------------------------------------------- engine parity
def test_engine_chunked_greedy_bitwise_matches_single_step(model):
    prompts = [np.arange(1, 4, dtype=np.int32),
               np.arange(5, 12, dtype=np.int32),
               np.asarray([9, 1, 7, 3], np.int32)]
    samp = [SamplingParams(max_tokens=mt) for mt in (9, 5, 12)]
    _, rids8, res8 = _run_engine(model, prompts, samp,
                                 decode_chunk_size=8)
    _, rids1, res1 = _run_engine(model, prompts, samp,
                                 decode_chunk_size=1)
    for r8, r1, p, s in zip(rids8, rids1, prompts, samp):
        np.testing.assert_array_equal(res8[r8], res1[r1])
        # and both match the dense generate() reference
        np.testing.assert_array_equal(
            res8[r8], _reference_tokens(model, p, s.max_tokens))


def test_engine_chunked_stochastic_bitwise_matches_single_step(model):
    """Temperature/top-k/top-p streams are invariant under chunk size:
    sampling keys thread fold_in(seed, tokens-generated), so the same
    request samples the same token at the same progress point whether
    the device ran 1 or 8 steps per dispatch. Ample blocks keep the
    two runs preemption-free (identical schedules)."""
    prompts = [np.arange(1, 4, dtype=np.int32),
               np.asarray([9, 1, 7, 3], np.int32),
               np.arange(5, 10, dtype=np.int32)]
    samp = [SamplingParams(max_tokens=10, temperature=0.9, top_k=9,
                           top_p=0.8, seed=11),
            SamplingParams(max_tokens=8, temperature=0.7, seed=22),
            SamplingParams(max_tokens=12, temperature=1.1, top_p=0.95,
                           seed=33)]
    _, rids8, res8 = _run_engine(model, prompts, samp,
                                 decode_chunk_size=8, num_blocks=32)
    _, rids4, res4 = _run_engine(model, prompts, samp,
                                 decode_chunk_size=4, num_blocks=32)
    _, rids1, res1 = _run_engine(model, prompts, samp,
                                 decode_chunk_size=1, num_blocks=32)
    for r8, r4, r1 in zip(rids8, rids4, rids1):
        np.testing.assert_array_equal(res8[r8], res1[r1])
        np.testing.assert_array_equal(res8[r8], res4[r4])
        assert np.all(res8[r8] >= 0) and np.all(res8[r8] < VOCAB)


# ------------------------------------------------- host-sync accounting
def test_host_syncs_per_chunk_not_per_token(model):
    """The ISSUE acceptance metric on a real engine: steady-state
    decode costs ONE host sync per k tokens. One request, max_tokens=17
    -> 1 prefill sync + 2 decode chunks (8 + 8 tokens after the
    host-sampled first token)."""
    k = 8
    eng = _engine(model, decode_chunk_size=k)
    rid = eng.add_request(np.arange(1, 5, dtype=np.int32),
                          SamplingParams(max_tokens=17))
    eng.run(max_steps=50)
    assert len(eng.get_request(rid).output_ids) == 17
    assert eng.stats.host_syncs("prefill") == 1
    assert eng.stats.host_syncs("decode") == 2      # ceil(16 / 8)
    # the gauge the dashboards watch: decode syncs / generated tokens
    assert eng.stats.host_syncs_per_token() <= 1.0 / k + 1e-9
    assert eng.stats.as_dict()["host_syncs_per_token"] == \
        pytest.approx(2 / 17)


def test_chunk_histogram_and_span_recorded(model):
    from paddle_tpu import obs
    eng = _engine(model, decode_chunk_size=8)
    eng.add_request(np.arange(1, 5, dtype=np.int32),
                    SamplingParams(max_tokens=9))
    eng.run(max_steps=50)
    fam = obs.histogram("serving_decode_chunk_seconds",
                        labels=("engine",), unit="seconds")
    child = fam.labels(engine=eng.stats.label)
    assert child.count >= 1 and child.sum >= 0.0


# --------------------------------------------- chunk-boundary semantics
def test_eos_mid_chunk_stops_exactly_at_eos(model):
    """EOS landing mid-chunk freezes the row in-scan: the engine emits
    the eos token and nothing after it, even though the chunk had slots
    reserved past it (freed with the table, zero leaks)."""
    p = np.arange(1, 6, dtype=np.int32)
    ref = _reference_tokens(model, p, 8)
    # a token greedy emits mid-chunk (index 0 is the prefill's, the first
    # chunk's scan emits 1..8) and has not emitted before: the engine
    # rightly stops at an eos's FIRST occurrence
    at = next(i for i in range(2, 7) if ref[i] not in ref[:i])
    eos = int(ref[at])
    eng = _engine(model, decode_chunk_size=8)
    rid = eng.add_request(p, SamplingParams(max_tokens=8,
                                            eos_token_id=eos))
    outs = []
    while eng.has_unfinished():
        outs.extend(eng.step())
    req = eng.get_request(rid)
    np.testing.assert_array_equal(np.asarray(req.output_ids),
                                  ref[:at + 1])
    assert outs[-1].finished and outs[-1].finish_reason == "stop"
    assert eng.cache.num_free() == eng.config.num_blocks
    eng.cache.check_integrity()


def test_deadline_expires_at_chunk_boundary(model):
    """Deadlines act at chunk boundaries: a request whose deadline
    elapses mid-drain is aborted by the NEXT step's expiry sweep with
    finish_reason='timeout', and its blocks come back."""
    eng = _engine(model, decode_chunk_size=8)
    rid = eng.add_request(
        np.arange(1, 4, dtype=np.int32),
        SamplingParams(max_tokens=16, deadline_s=0.05))
    out1 = eng.step()                 # prefill + first token
    assert not out1[-1].finished
    time.sleep(0.08)                  # deadline elapses between chunks
    outs = []
    while eng.has_unfinished():
        outs.extend(eng.step())
    assert outs[-1].finish_reason == "timeout"
    assert eng.get_request(rid).state == "finished_timeout"
    assert eng.stats.timeouts == 1
    assert eng.cache.num_free() == eng.config.num_blocks
    eng.cache.check_integrity()


def test_nan_mid_chunk_quarantines_offender_survivors_bitwise(model):
    """A NaN row inside a chunk is latched by the in-scan anomaly flags
    and poisons the WHOLE chunk: nothing from it is emitted, the
    offender is quarantined, survivors are rebuilt by re-prefill and
    stay bitwise — and the chunk-invariant sampling keys make the
    replayed tokens identical to an unfaulted run."""
    fi = ServingFaultInjector("nan_logits@2:1")
    eng = LLMEngine.from_model(
        model, EngineConfig(block_size=4, num_blocks=16, max_num_seqs=4,
                            decode_chunk_size=8), faults=fi)
    prompts = [np.arange(1, 4, dtype=np.int32),
               np.asarray([9, 1, 7, 3], np.int32),
               np.arange(5, 10, dtype=np.int32)]
    rids = [eng.add_request(p, SamplingParams(max_tokens=7))
            for p in prompts]
    res = eng.run(max_steps=200)
    assert ("nan_logits", 2) in fi.fired_log
    errored = [r for r in rids
               if eng.get_request(r).state == "finished_error"]
    assert errored == [rids[1]]       # the armed row, exactly
    assert eng.stats.errors == 1 and eng.stats.recoveries == 1
    for p, rid in zip(prompts, rids):
        if rid in errored:
            continue
        np.testing.assert_array_equal(
            res[rid], _reference_tokens(model, p, 7))
    assert eng.cache.num_free() == eng.config.num_blocks
    eng.cache.check_integrity()
