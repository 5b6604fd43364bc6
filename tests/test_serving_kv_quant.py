"""int8 KV-cache pool mode (serving/kv_quant.py + PagedKVCache
kv_cache_dtype="int8") against the committed jaxnum bound.

The load-bearing pins:
- the dequantized pool view tracks what was written within the
  committed per-(block, head) relative-error bound from numplan.json —
  the RUNTIME side of the static `serving.kv_block_codec` derivation;
- unchanged blocks are BIT-STABLE across the setter's re-encode
  (monotone scales), so per-chunk pool rebinds never walk stored KV;
- freshly claimed blocks dequantize to exact zeros (scale reset), so
  block reuse can neither leak stale content nor inherit a stale
  (larger) scale that would break the error bound;
- greedy engine output with kv_cache_dtype="int8" token-matches the
  f32 engine on the tiny-GPT recipe, with zero leaked blocks and a
  clean integrity audit;
- the quantized host-tier spill keeps the sha256 integrity contract
  (a corrupted host block trips the digest on promotion) and peers
  receive uniform f32 payloads from export_prefix.
"""
import numpy as np
import pytest
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.models.gpt import GPT, GPTConfig
from paddle_tpu.inference.serving import (EngineConfig, LLMEngine,
                                          PagedKVCache, SamplingParams)
from paddle_tpu.inference.serving import kv_quant
from paddle_tpu.analysis.jaxnum import committed_codec_bound

VOCAB = 97
BOUND = committed_codec_bound()


def _model():
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=VOCAB, hidden_size=32, num_layers=2,
                    num_heads=4, max_seq_len=24)
    m = GPT(cfg)
    m.eval()
    return m


def _tile_rel_err(got, want):
    """Worst per-(block, head) relative error of `got` against `want`
    ([num_blocks, bs, H, D]), fullscale = the tile's absmax in want."""
    absmax = jnp.maximum(jnp.max(jnp.abs(want), axis=(1, 3),
                                 keepdims=True), 1e-30)
    return float(jnp.max(jnp.abs(got - want) / absmax))


def _rand_pools(rng, layers, shape):
    return tuple(
        (jnp.asarray(rng.randn(*shape).astype(np.float32)),
         jnp.asarray(rng.randn(*shape).astype(np.float32)))
        for _ in range(layers))


def test_committed_bound_is_available():
    assert BOUND is not None, "numplan.json must commit the codec bound"
    assert BOUND == pytest.approx(0.5 / kv_quant.KV_INT8_LEVELS,
                                  rel=1e-4)


# --------------------------------------------------------- pool mode
def test_int8_pool_write_read_within_committed_bound():
    rng = np.random.RandomState(0)
    c = PagedKVCache(2, (4, 8), 16, 4, kv_cache_dtype="int8")
    want = _rand_pools(rng, 2, (16, 4, 4, 8))
    c.pools = want
    got = c.pools
    worst = max(_tile_rel_err(g, w)
                for gp, wp in zip(got, want)
                for g, w in zip(gp, wp))
    assert worst <= BOUND * (1 + 1e-6)


def test_int8_unchanged_blocks_are_bit_stable():
    """Assigning the dequantized view straight back (what every decode
    chunk's pool rebind amounts to for untouched blocks) must leave
    codes AND scales bit-identical — the monotone-scale contract."""
    rng = np.random.RandomState(1)
    c = PagedKVCache(2, (4, 8), 16, 4, kv_cache_dtype="int8")
    c.pools = _rand_pools(rng, 2, (16, 4, 4, 8))
    q0 = [(np.asarray(qk), np.asarray(qv)) for qk, qv in c._qpools]
    s0 = [(np.asarray(sk), np.asarray(sv)) for sk, sv in c._scales]
    for _ in range(3):
        c.pools = c.pools
    for (a0, b0), (a1, b1) in zip(q0, c._qpools):
        np.testing.assert_array_equal(a0, np.asarray(a1))
        np.testing.assert_array_equal(b0, np.asarray(b1))
    for (a0, b0), (a1, b1) in zip(s0, c._scales):
        np.testing.assert_array_equal(a0, np.asarray(a1))
        np.testing.assert_array_equal(b0, np.asarray(b1))


def test_int8_reused_blocks_reset_scale_and_content():
    """Free + reclaim must reset the claimed blocks' scales: stale
    codes dequantize to exact zeros (fresh-block invariant) and the
    next write's error is bounded by the NEW content's absmax, not the
    previous tenant's."""
    rng = np.random.RandomState(2)
    c = PagedKVCache(1, (2, 4), 8, 2, kv_cache_dtype="int8")
    ids = c.allocate("big", 16)
    # large-magnitude tenant -> large scales
    c.pools = tuple((jnp.asarray(100.0 * rng.randn(8, 2, 2, 4)
                                 .astype(np.float32)),) * 2
                    for _ in range(1))
    c.free("big")
    ids2 = c.allocate("small", 16)
    assert sorted(ids2) == sorted(ids)       # the same physical blocks
    at = jnp.asarray(ids2, jnp.int32)
    kp, vp = c.pools[0]
    assert float(jnp.max(jnp.abs(kp[at]))) == 0.0
    assert float(jnp.max(jnp.abs(vp[at]))) == 0.0
    # small-magnitude content must meet the bound relative to ITSELF
    want = _rand_pools(rng, 1, (8, 2, 2, 4))
    c.pools = want
    worst = max(_tile_rel_err(g, w)
                for gp, wp in zip(c.pools, want)
                for g, w in zip(gp, wp))
    assert worst <= BOUND * (1 + 1e-6)
    c.free("small")


def test_kv_cache_dtype_validated():
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        PagedKVCache(1, (2, 4), 8, 2, kv_cache_dtype="int4")


def test_float32_mode_keeps_plain_storage():
    """The default mode must stay the historical bitwise path: the
    pools property returns the storage itself, no codec in the loop."""
    c = PagedKVCache(1, (2, 4), 8, 2)
    assert c._qpools is None
    p = c.pools
    assert p is c._pools
    assert p[0][0].dtype == jnp.float32


# ----------------------------------------------------- engine parity
def test_engine_int8_greedy_parity_and_bound():
    """The acceptance pin: greedy serving with kv_cache_dtype="int8"
    token-matches the f32 engine, leaks nothing, audits clean — and
    the real decode KV content round-trips the codec within the
    committed bound (measured <= static, the soundness direction on
    live data)."""
    m = _model()

    def run(kvdt):
        eng = LLMEngine.from_model(m, EngineConfig(
            block_size=4, num_blocks=16, max_num_seqs=4,
            kv_cache_dtype=kvdt))
        rng = np.random.RandomState(0)
        prompts = [rng.randint(0, VOCAB, (n,)).astype(np.int32)
                   for n in (5, 3, 7)]
        for i, p in enumerate(prompts):
            eng.add_request(p, SamplingParams(max_tokens=8),
                            request_id=f"r{i}")
        return eng, eng.run(max_steps=200)

    e32, o32 = run("float32")
    e8, o8 = run("int8")
    assert set(o32) == set(o8)
    for rid in o32:
        np.testing.assert_array_equal(o32[rid], o8[rid])

    st = e8.cache.stats()
    assert st["kv_cache_dtype"] == "int8"
    assert st["blocks_allocated"] == st["blocks_freed"]
    e8.cache.check_integrity()

    # measured codec error on the REAL f32 decode KV content
    worst = 0.0
    for kp, vp in e32.cache.pools:
        for x in (kp, vp):
            worst = max(worst,
                        _tile_rel_err(kv_quant.kv_block_roundtrip(x), x))
    assert worst <= BOUND * (1 + 1e-6)

    # propagated divergence between the engines' pools stays a small
    # multiple of the single-encode bound (1.8x observed; 4x is the
    # alarm threshold for compounding-error regressions)
    div = max(_tile_rel_err(a, b)
              for ap, bp in zip(e8.cache.pools, e32.cache.pools)
              for a, b in zip(ap, bp))
    assert div <= 4 * BOUND


# ---------------------------------------------------- quantized spill
def _spill_cache(**kw):
    kw.setdefault("kv_cache_dtype", "int8")
    return PagedKVCache(2, (2, 4), 8, 2, enable_prefix_cache=True,
                        host_tier_blocks=8, **kw)


def _fill_and_demote(c, rng):
    """Admit + register 8 blocks of content, then hog the pool so every
    cached block demotes to the host tier. Returns (tokens, pre-spill
    dequantized pools, original table)."""
    toks = list(range(1, 17))
    table = c.allocate("a", 16)
    c.pools = _rand_pools(rng, 2, (8, 2, 2, 4))
    before = c.pools
    c.free("a", cache_tokens=toks)
    ids = c._take_blocks("hog", 8)
    assert c.tier_demotions == 8
    for b in ids:                       # hand the blocks back
        del c._refcount[b]
        c._free.append(b)
        c.blocks_freed += 1
    return toks, before, table


def test_int8_spill_payload_is_quantized_and_promotes_within_bound():
    rng = np.random.RandomState(3)
    c = _spill_cache()
    toks, before, table = _fill_and_demote(c, rng)
    # the spilled payload is int8 codes + one trailing f32 scales pair
    entry = c.host_tier.get(0)
    payload = entry["payload"]
    assert len(payload) == c.num_layers + 1
    assert all(p[0].dtype == np.int8 for p in payload[:-1])
    assert payload[-1][0].dtype == np.float32
    assert payload[-1][0].shape == (c.num_layers, c.cache_shape[0])

    res = c.ensure_promoted(toks + [99])
    assert res["outcomes"] == ["hit"] * 8
    path, _ = c.prefix_index.match(toks, touch=False)
    promoted = [n.block for n in path]
    after = c.pools
    # promotion re-encodes the verified payload: one extra encode on
    # top of the original, still within 2x the single-encode bound
    worst = 0.0
    for (ak, av), (bk, bv) in zip(after, before):
        for a, b in ((ak, bk), (av, bv)):
            for pb, ob in zip(promoted, table):
                absmax = jnp.maximum(jnp.max(jnp.abs(b[ob])), 1e-30)
                worst = max(worst, float(
                    jnp.max(jnp.abs(a[pb] - b[ob])) / absmax))
    assert worst <= 2 * BOUND
    c.check_integrity()


def test_int8_corrupted_host_block_trips_sha256():
    """The chaos contract survives quantization: flipping one byte of
    a spilled int8 payload must fail the digest on promotion and
    degrade to re-prefill, never fill garbage."""
    rng = np.random.RandomState(4)
    c = _spill_cache()
    toks, _before, _table = _fill_and_demote(c, rng)
    assert c.host_tier.corrupt_oldest()
    res = c.ensure_promoted(toks + [99])
    assert "integrity" in res["outcomes"]
    assert c.tier_promotions["integrity"] == 1
    c.check_integrity()


def test_int8_export_prefix_ships_uniform_f32_to_peers():
    """Peer fetch must not leak the storage encoding: export_prefix
    decodes quantized host payloads and re-digests, so a plain-f32
    peer admits the snapshot unchanged."""
    rng = np.random.RandomState(5)
    c = _spill_cache()
    toks, _before, _table = _fill_and_demote(c, rng)
    exp = c.export_prefix(toks + [99])
    assert exp is not None and len(exp["blocks"]) == 8
    for payload, digest in exp["blocks"]:
        assert len(payload) == c.num_layers
        assert all(a.dtype == np.float32 for pair in payload
                   for a in pair)
        assert c._payload_digest(payload) == digest
    peer = PagedKVCache(2, (2, 4), 8, 2, enable_prefix_cache=True)
    assert peer.admit_prefix(exp["tokens"], exp["blocks"]) == 8
    peer.check_integrity()
