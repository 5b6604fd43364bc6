"""Mellum 2 at a small size on the CPU (float32): the family against the
plain reference (full forward; prefill, then decoding through the paged
cache past the window's edge), a check that can SEE the mask (a window off
by one, and one ignored, fail it), the YaRN frequencies against the
formula's values, the whole expert layer, the cache manager's window group
(a second table a sequence, blocks freed as the window moves, both groups in
`allocate` / `reserve_slots` / `free` / `check_integrity`), and the family
through `LLMEngine`."""
import dataclasses
import functools
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed.moe import held_experts_mlp
from paddle_tpu.models.pangu_moe import COUNTERS
from paddle_tpu.inference.serving import (EngineConfig, LLMEngine,
                                          SamplingParams)
from paddle_tpu.inference.serving.attention import (PACK_COLS,
                                                    fused_decode_chunk,
                                                    paged_decode_step)
from paddle_tpu.inference.serving.paged_cache import (CacheExhausted,
                                                      PagedKVCache, SeqState,
                                                      window_blocks_per_seq)
from paddle_tpu.models import mellum
from paddle_tpu.models.generation import extract_params

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "benchmarks"))
from lib import reference_mellum2 as ref  # noqa: E402

#: a window of 12 is no multiple of either block size used below
SMALL = dict(vocab_size=256, hidden_size=64, num_hidden_layers=4,
             num_attention_heads=4, num_key_value_heads=2, head_dim=16,
             sliding_window=12, moe_intermediate_size=32, num_experts=8,
             num_experts_per_tok=2, max_seq_len=64)
#: float32 on both sides: what a wrong mask, table or position would exceed
#: by orders of magnitude, and bfloat16 weights by two
LIMIT = 1e-5


def _family(seed=5, **over):
    cfg = mellum.MellumConfig(**{**SMALL, **over})
    paddle.seed(seed)
    model = mellum.Mellum(cfg)
    return model, cfg, extract_params(model)


@functools.lru_cache(maxsize=None)
def _reference_fn(cfg):
    return jax.jit(functools.partial(ref.logits, size=ref.sizes(cfg)))


def _reference_logits(params, ids, cfg):
    """One compilation a configuration: ids padded to max_seq_len (causal,
    so the padding changes nothing before it)."""
    row = np.zeros((cfg.max_seq_len,), np.int32)
    row[:len(ids)] = ids
    return np.asarray(_reference_fn(cfg)(params, row))[:len(ids)]


def _prompt(n, seed=0, vocab=256):
    return np.random.default_rng([seed, n]).integers(
        0, vocab, (n,), dtype=np.int32)


# ------------------------------------------------- the family, no cache
def test_the_layer_pattern_and_the_spec():
    _, cfg, _ = _family()
    assert cfg.kinds == (mellum.SLIDING,) * 3 + (mellum.FULL,)
    spec = mellum.serving_spec(cfg)
    assert spec is mellum.serving_spec(cfg)
    assert (spec.cache_layout, spec.layer_caches, spec.window) == (
        "hybrid", ("window",) * 3 + ("rows",), 12)
    assert (spec.window_layers, spec.state_layers) == (3, 0)
    row = 2 * 2 * 16 * 4                        # k and v of 2 x 16, float32
    assert spec.cache_bytes_per_token == 1 * row
    assert spec.window_bytes_per_seq == 3 * 12 * row
    # the published configuration: 4,096 B a position, 12.6 MB a sequence
    full = mellum.serving_spec(mellum.MellumConfig(
        num_hidden_layers=8, dtype="bfloat16"))
    assert full.cache_bytes_per_token == 2 * 2 * 512 * 2 == 4096
    assert full.window_bytes_per_seq == 6 * 1024 * 2048
    # a model with no sliding layer is a plain heads family
    plain = mellum.serving_spec(mellum.MellumConfig(
        **{**SMALL, "layer_types": (mellum.FULL,) * 4}))
    assert (plain.cache_layout, plain.layer_caches, plain.window) == (
        "heads", (), 0)


@pytest.mark.parametrize("length, query_block", [(40, 512), (40, 8), (9, 4)])
def test_the_forward_is_the_references(monkeypatch, length, query_block):
    """Whole prompts, with the queries in one block and in blocks of 8 (a
    sliding layer's block then multiplies only the keys of its band)."""
    monkeypatch.setattr(mellum, "QUERY_BLOCK", query_block)
    _, cfg, params = _family()
    ids = _prompt(length)
    got = np.asarray(mellum.forward(params, ids[None], cfg))[0]
    want = _reference_logits(params, ids, cfg)
    assert np.abs(want).max() > 0.3
    assert np.abs(got - want).max() < LIMIT


def test_the_nn_layer_runs_the_same_forward():
    model, cfg, params = _family()
    ids = _prompt(20)
    out = model(paddle.to_tensor(ids[None]))
    assert np.abs(np.asarray(out.numpy())[0]
                  - _reference_logits(params, ids, cfg)).max() < LIMIT


def test_yarn_inverse_frequencies_are_the_formulas_for_this_config():
    cfg = mellum.MellumConfig()                 # the published numbers
    inv = mellum.yarn_inv_freq(cfg)
    plain = 500000.0 ** (-np.arange(64) / 64.0)
    # correction dimensions 18.08 and 34.98: floor 18, ceil 35
    low = 128 * math.log(8192 / (32 * 2 * math.pi)) / (2 * math.log(5e5))
    high = 128 * math.log(8192 / (1 * 2 * math.pi)) / (2 * math.log(5e5))
    assert (math.floor(low), math.ceil(high)) == (18, 35)
    np.testing.assert_allclose(inv[:19], plain[:19], rtol=1e-12)
    np.testing.assert_allclose(inv[35:], plain[35:] / 16, rtol=1e-12)
    j = 27                                      # inside the ramp
    ramp = (j - 18) / (35 - 18)
    assert inv[j] == pytest.approx(plain[j] / 16 * ramp
                                   + plain[j] * (1 - ramp), rel=1e-12)
    assert np.all(np.diff(inv) < 0)
    assert cfg.yarn_attention_factor == pytest.approx(0.1 * math.log(16) + 1)
    # the reference computes them from its own code
    mine, scale = ref.inverse_frequencies(ref.sizes(cfg), full=True)
    np.testing.assert_allclose(mine, inv.astype(np.float32), rtol=1e-6)
    assert scale == cfg.yarn_attention_factor
    sliding, one = ref.inverse_frequencies(ref.sizes(cfg), full=False)
    np.testing.assert_allclose(sliding, plain.astype(np.float32), rtol=1e-6)
    assert one == 1.0


def test_the_whole_expert_layer_is_the_references():
    """`held_experts_mlp` with held = (0, E), every expert: the reference's
    whole layer. R >= T x top_k, so there is no compact branch: ONE `cond`
    of two branches, the batched form and the `ragged_dot` fallback (PR 36
    changed this on purpose: the program had no `cond` before the batched
    form)."""
    _, cfg, params = _family()
    x = jnp.asarray(np.random.default_rng(3).normal(0, 1, (24, 64)),
                    jnp.float32)
    pre = "layers.1.moe."

    def layer(x):
        return held_experts_mlp(
            x, params[pre + "router.weight"],
            params[pre + "experts.gate.weight"],
            params[pre + "experts.up.weight"],
            params[pre + "experts.down.weight"], (0, 8), 2, 1.0,
            scoring="softmax")

    routed, counts = layer(x)
    want, pairs = ref._experts(params, pre, x, ref.sizes(cfg), None)
    assert np.abs(np.asarray(routed) - np.asarray(want)).max() < LIMIT
    assert int(counts[0]) == int(pairs) == 24 * 2
    jaxpr = str(jax.make_jaxpr(layer)(x))
    assert jaxpr.count("cond[") == 1
    assert jaxpr.count("ragged_dot_general[") == 3
    assert dict(zip(COUNTERS, np.asarray(counts)))["moe_batched_layers"] == 1


# ------------------------------------------- through the paged cache
def _decode_through_cache(cfg, params, prompt, steps, block_size,
                          num_blocks=32, release=True):
    """Prefill, then `steps` greedy paged decode steps through a
    `PagedKVCache` built from the spec, giving back the blocks behind the
    window after each: (logits rows [1 + steps, V], ids, most window blocks
    held, the cache)."""
    spec = mellum.serving_spec(cfg)
    width = window_blocks_per_seq(spec.window, block_size, 1)
    cache = PagedKVCache(
        spec.num_layers, spec.cache_shape, num_blocks, block_size,
        layer_caches=spec.layer_caches, window=spec.window,
        num_window_blocks=width)
    step = jax.jit(functools.partial(paged_decode_step, geom=spec),
                   donate_argnums=(1,))
    n, ids = len(prompt), prompt
    cache.allocate("s", n)
    logits, dense, _ = spec.prefill(params, jnp.asarray(prompt[None]))
    cache.write_prefill("s", dense, n)
    rows, held = [np.asarray(logits)[0]], []
    for _ in range(steps):
        tok = int(rows[-1].argmax())
        block, offset, pos = cache.append_slot("s")
        ids = np.append(ids, np.int32(tok))
        table = np.zeros((1, cfg.max_seq_len // block_size), np.int32)
        table[0, :len(cache.block_table("s"))] = cache.block_table("s")
        in_window, first = cache.window_table("s")
        held.append(len(in_window))
        window_table = np.zeros((1, max(width, 1)), np.int32)
        window_table[0, :len(in_window)] = in_window
        kw = dict(window_tables=window_table,
                  window_firsts=np.asarray([first], np.int32)) \
            if spec.window else {}
        logits, cache.pools = step(
            params, cache.pools, np.asarray([tok], np.int32),
            np.asarray([pos], np.int32), table,
            np.asarray([block], np.int32), np.asarray([offset], np.int32),
            **kw)
        rows.append(np.asarray(logits)[0])
        if release:
            cache.release_behind("s")
    return np.stack(rows), ids, max(held, default=0), cache


@pytest.mark.parametrize("block_size", [8, 4])
@pytest.mark.parametrize("prompt_len", [5, 12, 13, 30])
def test_prefill_and_decoding_through_the_cache_match_the_forward(
        block_size, prompt_len):
    """Prompts under, at and past the window, then 2 x window decode steps:
    every row of logits against the reference's full forward, while the
    window table never holds more than window // block + 2 blocks."""
    _, cfg, params = _family()
    steps = 2 * cfg.sliding_window
    rows, ids, held, cache = _decode_through_cache(
        cfg, params, _prompt(prompt_len), steps, block_size)
    want = _reference_logits(params, ids, cfg)[prompt_len - 1:]
    assert np.abs(rows - want[:len(rows)]).max() < LIMIT
    assert held <= cfg.sliding_window // block_size + 2
    assert held <= window_blocks_per_seq(12, block_size, 1)
    # the table starts past block 0: the blocks behind the window are gone
    _, first = cache.window_table("s")
    assert first == (prompt_len + steps - 12 + 1) // block_size > 0
    cache.free("s")
    report = cache.check_integrity()
    assert not any(report.values())
    assert cache.window_blocks_allocated == cache.window_blocks_freed > held


@pytest.mark.parametrize("how", ["window_plus_one", "window_ignored"])
def test_a_wrong_window_fails_the_same_tolerance(how):
    """The check can see the mask: the same weights under a window one
    longer, and with the sliding layers attending to everything, are
    orders of magnitude outside the limit, through the forward and through
    the cache."""
    _, cfg, params = _family()
    wrong = dataclasses.replace(cfg, sliding_window=13) \
        if how == "window_plus_one" \
        else dataclasses.replace(cfg, sliding_window=cfg.max_seq_len)
    ids = _prompt(40)
    want = _reference_logits(params, ids, cfg)
    got = np.asarray(mellum.forward(params, ids[None], wrong))[0]
    # positions inside the first window see the same keys either way
    assert np.abs(got[:12] - want[:12]).max() < LIMIT
    assert np.abs(got - want).max() > 100 * LIMIT
    rows, ids, _, _ = _decode_through_cache(wrong, params, _prompt(14), 12,
                                            8)
    want = _reference_logits(params, ids, cfg)[13:]
    assert np.abs(rows - want[:len(rows)]).max() > 100 * LIMIT


# --------------------------------------------------- the cache manager
def _cache(num_blocks=16, num_window_blocks=8, block_size=4, window=10,
           **kw):
    return PagedKVCache(
        3, (2, 8), num_blocks, block_size,
        layer_caches=("window", "rows", "window"), window=window,
        num_window_blocks=num_window_blocks, **kw)


def test_window_pools_are_a_group_of_their_own():
    cache = _cache()
    assert cache.layout == "hybrid" and cache.window == 10
    assert [p[0].shape for p in cache.pools] == [
        (8, 4, 2, 8), (16, 4, 2, 8), (8, 4, 2, 8)]
    assert cache.physical_bytes_per_token == 2 * 2 * 8 * 4
    assert cache.window_bytes_per_seq == 2 * 10 * 2 * 2 * 8 * 4
    # a cache without window layers has no second group
    plain = PagedKVCache(2, (2, 8), 8, 4)
    assert (plain.window, plain.num_window_blocks, plain.num_window_free(),
            plain.window_blocks_needed(100, 8),
            plain.window_bytes_per_seq) == (0, 0, 0, 0, 0)
    assert "window_blocks" not in plain.stats()
    assert plain.release_behind("nobody") == 0


def test_blocks_in_use_stay_bounded_over_a_long_decode_and_reconcile():
    """Chunks of 8 over 400 positions: the window table never holds more
    than `window_blocks_per_seq`, every freed block is back on the free
    list, and the counters reconcile."""
    cache = _cache(num_blocks=128, num_window_blocks=6, window=10)
    most = window_blocks_per_seq(10, 4, 8)
    assert most == 1 + (10 + 8 + 4 - 3) // 4 == 5
    cache.allocate("a", 23)
    table, first = cache.window_table("a")
    # positions 14 .. 22: blocks 3, 4, 5
    assert (len(table), first) == (3, 3)
    freed = 0
    while cache.seq_len("a") < 400:
        _, _, pos = cache.reserve_slots("a", 8)
        table, first = cache.window_table("a")
        assert len(table) <= most
        # the chunk's trips attend from pos - 9 on, and write up to pos + 7
        assert first * 4 <= max(0, pos - 9)
        assert (first + len(table)) * 4 >= pos + 8
        freed += cache.release_behind("a")
        assert cache.num_window_used() == len(cache.window_table("a")[0])
    assert freed > 90 and cache.release_behind("a") == 0
    assert len(cache.block_table("a")) == 102          # the full group grew
    cache.free("a")
    stats = cache.stats()
    assert stats["window_blocks_allocated"] == stats["window_blocks_freed"]
    assert stats["window_free"] == 6 and stats["window_used"] == 0
    assert stats["window_high_water"] <= most
    assert stats["blocks_allocated"] == stats["blocks_freed"] == 102
    assert not any(cache.check_integrity().values())


@pytest.mark.parametrize("group", ["window block", "block"])
def test_exhaustion_of_either_group_leaves_no_side_effect(group):
    cache = _cache(num_blocks=6, num_window_blocks=4) \
        if group == "window block" \
        else _cache(num_blocks=3, num_window_blocks=8)
    cache.allocate("a", 9)                # 3 full blocks; window blocks 0-2
    before = (cache.num_free(), cache.num_window_free(),
              cache.block_table("a"), cache.window_table("a"),
              cache.seq_len("a"))
    with pytest.raises(CacheExhausted) as e:
        cache.allocate("b", 12)           # 3 more of each
    assert group in str(e.value) and ("window" in str(e.value)) == (
        group == "window block")
    assert not cache.has_seq("b")
    with pytest.raises(CacheExhausted):
        cache.reserve_slots("a", 8)       # 2 more of each
    if group == "block":
        with pytest.raises(CacheExhausted):
            cache.append_slot("a")        # position 9 is in block 2: held
            cache.append_slot("a")
            cache.append_slot("a")
            cache.append_slot("a")        # position 12 needs a 4th block
        cache.free("a")
        cache.allocate("a", 9)
    assert (cache.num_free(), cache.num_window_free(),
            cache.block_table("a"), cache.window_table("a"),
            cache.seq_len("a")) == before
    assert cache.alloc_failures >= 2
    cache.free("a")
    assert not any(cache.check_integrity().values())


def test_check_integrity_finds_a_leaked_and_a_double_owned_window_block():
    cache = _cache()
    cache.allocate("a", 9)
    cache.allocate("b", 5)
    assert not any(cache.check_integrity().values())
    lost = cache._wfree.pop()                     # off the list, in no table
    with pytest.raises(RuntimeError, match="'window_blocks_leaked': 1"):
        cache.check_integrity()
    cache._wfree.append(lost)
    cache._wtables["b"].append(cache._wtables["a"][0])    # two owners
    with pytest.raises(RuntimeError,
                       match="'window_blocks_double_owned': 1"):
        cache.check_integrity()
    cache._wtables["b"].pop()
    stolen = cache._wtables.pop("b")              # a table without its twin
    with pytest.raises(RuntimeError,
                       match="'window_blocks_without_table': 1"):
        cache.check_integrity()
    cache._wtables["b"] = stolen
    assert not any(cache.check_integrity().values())


@pytest.mark.parametrize("feature, kwargs", [
    ("int8 KV pools", dict(kv_cache_dtype="int8")),
    ("the prefix cache", dict(enable_prefix_cache=True)),
    ("the host tier", dict(enable_prefix_cache=False, host_tier_blocks=4)),
])
def test_what_the_window_layout_refuses_it_refuses_by_name(feature, kwargs):
    with pytest.raises(NotImplementedError, match=feature):
        _cache(**kwargs)


def test_block_migration_is_refused_by_name_on_the_window_layout():
    cache = _cache()
    cache.allocate("a", 5)
    with pytest.raises(NotImplementedError, match="block migration"):
        cache.export_blocks("a")
    with pytest.raises(NotImplementedError, match="block migration"):
        cache.import_blocks("b", ((None, None),) * 3, 0)
    with pytest.raises(ValueError, match="window > 0"):
        PagedKVCache(2, (2, 8), 8, 4, layer_caches=("window", "rows"))
    with pytest.raises(ValueError, match="'rows' at least once"):
        PagedKVCache(2, (2, 8), 8, 4, layer_caches=("window", "window"),
                     window=4, num_window_blocks=4)


def test_state_slots_and_window_tables_live_in_one_cache():
    """No family here needs both; the manager can hold both: a sequence
    gets blocks, window blocks and a slot together and returns them
    together, and a prefill's three kinds of leaf go to their places."""
    shapes = (((2, 3), "float32"),)
    cache = PagedKVCache(
        3, (2, 8), 8, 4, layer_caches=("state", "window", "rows"),
        state_shapes=shapes, num_state_slots=2, window=6,
        num_window_blocks=6)
    assert isinstance(cache.pools[0], SeqState)
    assert cache.pools[1][0].shape == (6, 4, 2, 8)
    cache.allocate("a", 9)
    cache.allocate("b", 3)
    assert cache.state_slot("a") != cache.state_slot("b")
    assert cache.window_table("a") == ([0, 1], 1)      # positions 4 .. 8
    with pytest.raises(CacheExhausted, match="state slot"):
        cache.allocate("c", 1)
    rng = np.random.default_rng(0)
    final = SeqState(jnp.asarray(rng.normal(size=(1, 2, 3)), jnp.float32))
    last = tuple(jnp.asarray(rng.normal(size=(1, 2, 6, 8)), jnp.float32)
                 for _ in range(2))
    rows = tuple(jnp.asarray(rng.normal(size=(1, 2, 12, 8)), jnp.float32)
                 for _ in range(2))
    cache.write_prefill("a", (final, last, rows), 9)
    np.testing.assert_array_equal(
        np.asarray(cache.pools[0].arrays[0])[cache.state_slot("a")],
        np.asarray(final.arrays[0])[0])
    # dense window row r is position 3 + r: position 8 is row 5, in the
    # window table's entry 8 // 4 - 1 = 1, block 1, offset 0
    np.testing.assert_array_equal(np.asarray(cache.pools[1][0])[1, 0],
                                  np.asarray(last[0])[0, :, 5])
    # position 3 (row 0) lies before the table's first block: dropped
    np.testing.assert_array_equal(np.asarray(cache.pools[1][0])[0, 0],
                                  np.asarray(last[0])[0, :, 1])
    np.testing.assert_array_equal(
        np.asarray(cache.pools[2][1])[cache.block_table("a")[2], 0],
        np.asarray(rows[1])[0, :, 8])
    cache.free("a")
    cache.free("b")
    assert not any(cache.check_integrity().values())
    assert cache.num_window_free() == 6 and cache.num_state_slots_used() == 0


def test_a_quarantined_sequences_window_blocks_are_scrubbed():
    cache = _cache()
    cache.allocate("a", 9)
    cache.pools = tuple(tuple(p + 1 for p in layer) for layer in cache.pools)
    held, _ = cache.window_table("a")
    cache.free("a", scrub=True)
    for layer in (0, 2):
        pool = np.asarray(cache.pools[layer][0])
        assert not pool[held].any() and pool[max(held) + 1:].all()


# ------------------------------------------------- through the engine
def _engine(cfg, params, **over):
    return LLMEngine(params, mellum.serving_spec(cfg), EngineConfig(**{
        "block_size": 8, "max_num_seqs": 4, "num_blocks": 32, **over}))


def _tokens_are_the_references_best(params, cfg, prompt, out):
    ids = np.concatenate([prompt, out])
    want = _reference_logits(params, ids, cfg)
    chosen = np.take_along_axis(want, np.roll(ids, -1)[:, None], -1)[:, 0]
    return float((want.max(-1) - chosen)[len(prompt) - 1:len(ids) - 1].max())


def test_the_engine_serves_the_family_and_frees_blocks_as_windows_move():
    _, cfg, params = _family()
    eng = _engine(cfg, params)
    assert eng.cache.num_window_blocks == 4 * window_blocks_per_seq(12, 8, 8)
    assert eng.stats.window_bytes_per_seq == eng.spec.window_bytes_per_seq
    prompts = {f"r{i}": _prompt(n) for i, n in enumerate(
        (3, 14, 25, 31, 9, 20))}
    for rid, p in prompts.items():
        eng.add_request(p, SamplingParams(max_tokens=30), request_id=rid)
    most = 0
    while eng.has_unfinished():
        eng.step()
        most = max(most, eng.stats.window_blocks_in_use)
        for seq in list(eng.cache._wtables):
            assert len(eng.cache.window_table(seq)[0]) \
                <= window_blocks_per_seq(12, 8, 8)
    out = {rid: np.asarray(eng.get_request(rid).output_ids, np.int32)
           for rid in prompts}
    for rid, p in prompts.items():
        assert len(out[rid]) == 30
        assert _tokens_are_the_references_best(params, cfg, p, out[rid]) \
            < LIMIT
    assert 0 < most <= eng.cache.num_window_blocks
    stats = eng.cache.stats()
    assert stats["window_blocks_allocated"] == stats["window_blocks_freed"]
    assert stats["blocks_allocated"] == stats["blocks_freed"]
    assert 0 < eng.stats.window_blocks_freed < stats["window_blocks_freed"]
    # a window layer reads at most the window: fewer than the full layers
    assert 0 < eng.stats.window_context_tokens < eng.stats.context_tokens
    assert eng.stats.moe_pairs > 0
    assert not any(eng.cache.check_integrity().values())


def test_window_context_tokens_count_what_a_window_layer_reads():
    from paddle_tpu.inference.serving.engine import (_context_tokens,
                                                     _window_context_tokens)

    class Row:
        def __init__(self, pos, left):
            self.slot = (0, 0, pos)
            self.pf_target = self.prefill_pos = 0
            self.output_ids = []
            self.params = SamplingParams(max_tokens=left)

    rows = [Row(0, 100), Row(5, 100), Row(20, 3), Row(11, 100)]
    want = sum(min(r.slot[2] + j + 1, 12)
               for r in rows for j in range(min(8, r.params.max_tokens)))
    assert _window_context_tokens(rows, 8, 12) == want
    assert _window_context_tokens(rows, 8, 10 ** 6) == \
        _context_tokens(rows, 8)


def test_preempt_and_recompute_across_the_windows_edge():
    """A pool too small for every row: the youngest is preempted, its
    blocks of BOTH groups come back, and it is recomputed from prompt +
    output by a prefill that writes only its last window; the tokens are
    those of an engine that never preempted."""
    _, cfg, params = _family()
    prompts = [_prompt(n, seed=1) for n in (20, 26, 17, 23)]

    def run(num_blocks):
        eng = _engine(cfg, params, num_blocks=num_blocks)
        for i, p in enumerate(prompts):
            eng.add_request(p, SamplingParams(max_tokens=28),
                            request_id=f"r{i}")
        out = eng.run()
        assert not any(eng.cache.check_integrity().values())
        stats = eng.cache.stats()
        assert stats["window_blocks_allocated"] \
            == stats["window_blocks_freed"]
        return out, eng.stats.preemptions

    roomy, none = run(32)
    tight, some = run(14)
    assert none == 0 and some > 0
    for rid in roomy:
        np.testing.assert_array_equal(roomy[rid], tight[rid])


def test_chunked_prefill_rides_the_scan_through_both_tables():
    """A prompt past the window fed 8 tokens a chunk: the window table
    starts empty, grows with the feed and gives blocks back on the way."""
    _, cfg, params = _family()
    eng = _engine(cfg, params, prefill_chunk_threshold=8)
    prompt = _prompt(37, seed=2)
    eng.add_request(prompt, SamplingParams(max_tokens=12), request_id="r")
    out = eng.run()["r"]
    assert eng.stats.prefill_chunks() >= 5 and eng.stats.window_blocks_freed > 0
    assert _tokens_are_the_references_best(params, cfg, prompt, out) < LIMIT
    assert not any(eng.cache.check_integrity().values())


def test_the_chunks_upload_carries_the_window_table_and_its_first_block():
    _, cfg, params = _family()
    spec = mellum.serving_spec(cfg)
    width = window_blocks_per_seq(12, 8, 8)
    cache = PagedKVCache(4, (2, 16), 16, 8, layer_caches=spec.layer_caches,
                         window=12, num_window_blocks=2 * width)
    packed = np.zeros((2, PACK_COLS + 8 + 64 // 8 + width + 1), np.int32)
    lowered = fused_decode_chunk.lower(params, cache.pools, packed, spec, 8)
    assert "module @jit_fused_decode_chunk " in lowered.as_text()
    flat_in = jax.tree_util.tree_leaves(lowered.in_avals)
    assert len(flat_in) == len(params) + 4 * 2 + 1
    out = jax.tree_util.tree_leaves(lowered.out_info)
    # the spec's eight counts as rows (five before PR 37's
    # `moe_layer_calls`, `moe_fit_2x`, `moe_fit_4x`)
    assert len(out) == 1 + 8 and out[0].shape == (8 + 2 + 8, 2)
    # every pool of both groups is donated: it aliases its output
    assert lowered.as_text().count("tf.aliasing_output") == 8


def test_the_window_spans_and_stats_are_in_the_profilers_trace(tmp_path):
    """docs/observability.md, "Window layers": the two spans a spec with
    window layers adds, where they nest, and the two stats of
    `serving.decode`."""
    from jax.profiler import ProfileData, ProfileOptions
    _, cfg, params = _family()
    eng = _engine(cfg, params)
    eng.add_request(_prompt(30), SamplingParams(max_tokens=20),
                    request_id="long")
    opts = ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        eng.run()
    finally:
        jax.profiler.stop_trace()
    path = next(tmp_path.rglob("*.xplane.pb"))
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
             for plane in ProfileData.from_file(str(path)).planes
             if plane.name == "/host:CPU"
             for line in plane.lines for e in line.events
             if e.name.startswith("serving.")]

    def inside(name, parent):
        around = [s for s in spans if s[0] == parent]
        mine = [s for s in spans if s[0] == name]
        assert mine and all(any(a <= s[1] and s[2] <= b
                                for _, a, b, _ in around) for s in mine)
        return mine

    (write,) = inside("serving.prefill.write_window", "serving.prefill")
    assert write[3] == {"blocks": 2}        # positions 19 .. 29: blocks 2, 3
    releases = inside("serving.decode.release_window", "serving.decode")
    decodes = [s[3] for s in spans if s[0] == "serving.decode"]
    assert len(releases) == len(decodes) == 3
    # 19 tokens in chunks of 8, 8 and 3 from position 30: every trip's
    # context is past the window of 12
    assert [d["window_context_tokens"] for d in decodes] == [96, 96, 36]
    assert [d["context_tokens"] for d in decodes] == [
        sum(range(31, 39)), sum(range(39, 47)), sum(range(47, 50))]
    # the window moved past one block in each of the first two chunks; the
    # last chunk's row finished and returned its table whole
    assert [d["window_blocks_freed"] for d in decodes] == [1, 1, 0]
    assert eng.stats.window_blocks_freed == 2
    assert eng.stats.window_context_tokens == 228
