"""openPangu-Ultra-MoE at a small size on the CPU (float32): the family
against the plain reference, the latent paged cache, the dropless expert
layer and its shares, and the family through `LLMEngine`."""
import functools
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed.moe import batched_form, held_experts_mlp
from paddle_tpu.inference.serving import (EngineConfig, LLMEngine,
                                          SamplingParams)
from paddle_tpu.inference.serving.attention import (PACK_COLS,
                                                    fused_decode_chunk,
                                                    paged_decode_step)
from paddle_tpu.inference.serving.paged_cache import PagedKVCache
from paddle_tpu.models import pangu_moe as pm
from paddle_tpu.models import generation as gen
from paddle_tpu.models.generation import extract_params

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "benchmarks"))
from lib import reference_pangu_moe as ref  # noqa: E402

SMALL = dict(vocab_size=256, hidden_size=64, num_hidden_layers=3,
             first_k_dense_replace=1, num_attention_heads=4, q_lora_rank=32,
             kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
             v_head_dim=16, intermediate_size=128, moe_intermediate_size=32,
             n_routed_experts=8, num_experts_per_tok=2, max_seq_len=64)


def _family(held=None, seed=5):
    cfg = pm.PanguMoEConfig(**SMALL, held_experts=held)
    paddle.seed(seed)
    model = pm.PanguMoE(cfg)
    return model, cfg, extract_params(model)


@functools.lru_cache(maxsize=None)
def _reference_fn(cfg):
    return jax.jit(functools.partial(ref.logits, size=ref.sizes(cfg)))


def _reference_logits(params, ids, cfg):
    """One compilation a configuration: ids padded to max_seq_len (causal,
    so the padding changes nothing before it)."""
    padded = np.zeros((cfg.max_seq_len,), np.int32)
    padded[:len(ids)] = ids
    return np.asarray(_reference_fn(cfg)(params, jnp.asarray(padded)))[
        :len(ids)]


# ------------------------------------------------------------ the family
@pytest.mark.parametrize("held", [None, (2, 2), (6, 2)])
def test_forward_matches_the_plain_reference(held):
    model, cfg, params = _family(held)
    ids = np.random.default_rng(0).integers(0, 256, (2, 24)).astype(np.int32)
    got = model(paddle.to_tensor(ids)).numpy()
    assert got.shape == (2, 24, 256) and got.dtype == np.float32
    for b in range(2):
        np.testing.assert_allclose(got[b], _reference_logits(
            params, ids[b], cfg), atol=1e-5, rtol=0)


def test_absorbed_attention_equals_expanded_attention():
    """Decode's latent-space form against the published per-head form, on
    the same queries and cached rows (ragged lengths)."""
    _, cfg, params = _family()
    rng = np.random.default_rng(1)
    n, s, pre = 3, 20, "layers.1."
    h = jnp.asarray(rng.normal(size=(n, s, cfg.hidden_size)), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (n, s))
    q_nope, q_pe = pm.mla_queries(params, pre, h, pos, cfg)
    rows = pm.mla_latent(params, pre, h, pos, cfg)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    expanded = np.asarray(pm.mla_expanded(params, pre, q_nope, q_pe, rows,
                                          causal, cfg))
    lens = np.asarray([20, 7, 13], np.int32)
    last = lens - 1
    take = np.arange(n)
    absorbed = np.asarray(pm.mla_absorbed(
        params, pre, q_nope[take, last], q_pe[take, last], rows,
        jnp.asarray(lens), cfg))
    np.testing.assert_allclose(absorbed, expanded[take, last], atol=1e-5,
                               rtol=0)


# ------------------------------------------------------- the expert layer
def _expert_weights(rng, experts, h=32, f=16):
    return (jnp.asarray(rng.normal(size=(h, 8)), jnp.float32),
            *(jnp.asarray(rng.normal(size=shape) * 0.2, jnp.float32)
              for shape in ((experts, h, f), (experts, h, f),
                            (experts, f, h))))


def test_the_shares_add_up_to_the_uncut_layer():
    """8 experts over 4 ranks: the routed parts of all ranks, with the
    shared expert counted once, equal the uncut reference layer."""
    rng = np.random.default_rng(2)
    router, wg, wu, wd = _expert_weights(rng, 8)
    x = jnp.asarray(rng.normal(size=(40, 32)), jnp.float32)
    shared = [jnp.asarray(rng.normal(size=s) * 0.2, jnp.float32)
              for s in ((32, 16), (32, 16), (16, 32))]
    parts, pairs = [], 0
    for rank in range(4):
        held = (2 * rank, 2)
        routed, counts = held_experts_mlp(
            x, router, wg[2 * rank:2 * rank + 2], wu[2 * rank:2 * rank + 2],
            wd[2 * rank:2 * rank + 2], held, 2, 2.5)
        parts.append(np.asarray(routed))
        pairs += int(counts[0])
    assert pairs == 40 * 2                      # every pair, exactly once
    p = {"moe.router.weight": router, "moe.experts.gate.weight": wg,
         "moe.experts.up.weight": wu, "moe.experts.down.weight": wd,
         "moe.shared.gate.weight": shared[0],
         "moe.shared.up.weight": shared[1],
         "moe.shared.down.weight": shared[2]}
    size = {"held": (0, 8), "num_experts_per_tok": 2,
            "routed_scaling_factor": 2.5}
    with jax.default_matmul_precision("highest"):
        whole, ref_pairs = ref._experts(p, "", x, size, None)
        once = ref._mlp(x, *shared, None)
    assert int(ref_pairs) == pairs
    np.testing.assert_allclose(sum(parts) + np.asarray(once),
                               np.asarray(whole), atol=1e-5, rtol=0)


def test_no_token_is_dropped_when_all_route_to_one_held_expert():
    """Every token picks expert 3 first: its group holds all T tokens and
    every one of them is multiplied (no capacity)."""
    rng = np.random.default_rng(3)
    router, wg, wu, wd = _expert_weights(rng, 2)
    x = jnp.abs(jnp.asarray(rng.normal(size=(50, 32)), jnp.float32))
    router = router.at[:, 3].set(5.0)           # x >= 0: column 3 wins
    routed, counts = held_experts_mlp(x, router, wg, wu, wd, (3, 2), 2, 1.0)
    assert int(counts[-1]) == 50 and int(counts[0]) >= 50
    scores = jax.nn.sigmoid(jnp.dot(x, router, precision="highest"))
    top_s, top_i = jax.lax.top_k(scores, 2)
    assert bool((top_i[:, 0] == 3).all())
    want = np.zeros((50, 32), np.float32)
    for e in (3, 4):
        w = np.asarray(jnp.sum(jnp.where(top_i == e, top_s, 0.0), -1)
                       / jnp.sum(top_s, -1))
        with jax.default_matmul_precision("highest"):
            want += w[:, None] * np.asarray(
                ref._mlp(x, wg[e - 3], wu[e - 3], wd[e - 3], None))
    np.testing.assert_allclose(np.asarray(routed), want, atol=1e-5, rtol=0)
    assert np.abs(np.asarray(routed)).min(axis=1).max() > 0


def test_a_token_with_no_held_expert_gets_zeros_and_dead_rows_count_nothing():
    rng = np.random.default_rng(4)
    router, wg, wu, wd = _expert_weights(rng, 2)
    x = jnp.asarray(rng.normal(size=(30, 32)), jnp.float32)
    routed, counts = held_experts_mlp(x, router, wg, wu, wd, (0, 2), 2, 2.5)
    _, top_i = jax.lax.top_k(jax.nn.sigmoid(
        jnp.dot(x, router, precision="highest")), 2)
    none_held = np.asarray((top_i >= 2).all(axis=1))
    assert none_held.any()
    assert not np.asarray(routed)[none_held].any()
    assert int(counts[0]) == int((np.asarray(top_i) < 2).sum())
    live = jnp.arange(30) < 10
    _, few = held_experts_mlp(x, router, wg, wu, wd, (0, 2), 2, 2.5, live)
    assert int(few[0]) == int((np.asarray(top_i)[:10] < 2).sum())


def _routed_by_class(rng, both, one, tokens=256):
    """x [tokens, 32] and a router over 64 experts that sends `both` tokens'
    two picks to the held experts 8 and 9, `one` tokens' first pick to the
    held expert 10 (the second to 40, held elsewhere) and the rest to 50
    and 51: the class is the token's first three features, the router
    reads nothing else, and the tokens are shuffled."""
    kind = rng.permutation(np.repeat(
        [0, 1, 2], [both, one, tokens - both - one]))
    x = rng.normal(size=(tokens, 32))
    x[:, :3] = 4.0 * np.eye(3)[kind]
    router = np.zeros((32, 64))
    router[:3] = rng.uniform(-2.0, -1.0, size=(3, 64))
    for k, picks in enumerate(((8, 9), (10, 40), (50, 51))):
        router[k, picks] = (3.0, 2.0)
    return jnp.asarray(x, jnp.float32), jnp.asarray(router, jnp.float32)


@pytest.mark.parametrize("both, one, live, form", [
    pytest.param(None, None, None, "batched", id="uniform-batched"),
    pytest.param(None, None, 100, "batched",
                 id="uniform-rows-switched-off-batched"),
    pytest.param(32, 0, None, "batched", id="load-equal-C-batched"),
    pytest.param(33, 0, None, "compact", id="load-C-plus-1-compact"),
    pytest.param(16, 30, None, "batched", id="uneven-under-C-batched"),
    pytest.param(256, 0, None, "full", id="every-pick-here-full"),
    pytest.param(0, 256, None, "full", id="every-token-on-one-expert-full"),
    pytest.param(0, 128, None, "compact",
                 id="half-the-tokens-on-one-expert-compact"),
    pytest.param(60, 8, None, "compact", id="pairs-equal-R-compact"),
    pytest.param(60, 9, None, "full", id="pairs-R-plus-1-full"),
    pytest.param(256, 0, 50, "compact", id="rows-switched-off-compact"),
    pytest.param(0, 0, None, "compact", id="no-held-expert-zeros"),
])
def test_every_form_of_the_expert_layer_equals_a_per_token_loop(
        both, one, live, form):
    """256 tokens, top-2 of 64 experts, 4 held (the rest are another
    chip's): 512 pairs against a capacity of C = 32 rows an expert and a
    compact buffer of R = 128 rows. Whichever form the loads choose
    (batched over the experts while the most loaded has at most C rows,
    else `ragged_dot` over R rows while the pairs fit, else over all 512:
    no token is dropped at any routing), the routed part and the counts
    are those of a loop over every token's picks, and the counts say which
    form ran. (PR 36 changed this test on purpose: the batched form and its
    count `moe_batched_layers` are new, six cases more.)"""
    assert batched_form(512, 64, 4, 32, 16) == (32, 1)
    rng = np.random.default_rng(6)
    if both is None:
        x = jnp.asarray(rng.normal(size=(256, 32)), jnp.float32)
        router = jnp.asarray(rng.normal(size=(32, 64)), jnp.float32)
    else:
        x, router = _routed_by_class(rng, both, one)
    wg, wu, wd = _expert_weights(rng, 4)[1:]
    alive = None if live is None else jnp.arange(256) < live
    routed, counts = held_experts_mlp(x, router, wg, wu, wd, (8, 4), 2, 2.5,
                                      alive)
    top_s, top_i = jax.lax.top_k(jax.nn.sigmoid(
        jnp.dot(x, router, precision="highest")), 2)
    top_s, top_i = np.asarray(top_s, np.float64), np.asarray(top_i)
    x64 = np.asarray(x, np.float64)
    want, load = np.zeros((256, 32)), np.zeros(4, int)
    for t in range(256 if live is None else live):
        for s, e in zip(top_s[t], top_i[t] - 8):
            if 0 <= e < 4:
                a = x64[t] @ np.asarray(wg[e], np.float64)
                act = a / (1 + np.exp(-a)) * (x64[t] @ np.asarray(wu[e]))
                want[t] += 2.5 * s / top_s[t].sum() * (act @ np.asarray(wd[e]))
                load[e] += 1
    np.testing.assert_allclose(np.asarray(routed), want, rtol=1e-5,
                               atol=1e-5)
    assert dict(zip(pm.COUNTERS, np.asarray(counts))) == {
        "moe_pairs": load.sum(), "moe_experts_hit": (load > 0).sum(),
        "moe_full_buffer_layers": form == "full",
        "moe_batched_layers": form == "batched", "moe_layer_calls": 1,
        # PR 37: the uniform load is 512 / 64 = 8 rows: twice it is one
        # sublane tile of 16, four times it C = 32
        "moe_fit_2x": load.max() <= 16, "moe_fit_4x": load.max() <= 32,
        "moe_max_load": load.max()}
    assert form == ("batched" if 0 < load.max() <= 32 else
                    "full" if load.sum() > 128 else "compact")
    if both is not None and live is None:
        assert load.sum() == 2 * both + one
        none_held = np.asarray(x[:, 2] > 0)     # the third class
        assert not np.asarray(routed)[none_held].any()
        assert none_held.sum() == 256 - both - one


@pytest.mark.parametrize("pairs, experts, count, hidden, width, form", [
    pytest.param(512, 64, 64, 2304, 896, (32, 16), id="cell-7-decode"),
    pytest.param(8192, 64, 64, 2304, 896, None, id="cell-7-prefill-block"),
    pytest.param(1024, 256, 16, 7680, 2048, (16, 14), id="cell-3-decode"),
    pytest.param(8192, 256, 16, 7680, 2048, (128, 14),
                 id="cell-3-longest-prompt"),
    pytest.param(1280, 512, 128, 2048, 512, (16, 112), id="cell-6-decode"),
    pytest.param(10240, 512, 128, 2048, 512, (80, 112),
                 id="cell-6-prefill-block"),
    pytest.param(8, 64, 64, 2304, 896, (16, 16), id="one-row"),
])
def test_the_batched_form_is_eligible_by_shapes_alone(
        pairs, experts, count, hidden, width, form):
    """C is four times the uniform load in whole sublane tiles, and the
    form is out of the program where C passes the chip's ridge; it is taken
    from 7 in 8 held experts reached where the grouped kernel's 512 tile
    divides both sides of an expert's weights, from 1 in 4 where not."""
    assert batched_form(pairs, experts, count, hidden, width) == form


def test_few_experts_reached_keep_the_grouped_kernel():
    """A batched product reads every held expert, the grouped kernel only
    those a token reached: with 16 held experts of tile-sized weights and
    tokens on two of them, the layer stays on `ragged_dot` whatever the
    loads; with 14 reached it multiplies batched. Same result."""
    rng = np.random.default_rng(9)
    wg, wu, wd = (jnp.asarray(rng.normal(size=s) * 0.05, jnp.float32)
                  for s in ((16, 512, 512), (16, 512, 512), (16, 512, 512)))
    x = rng.normal(size=(32, 512))
    assert batched_form(32, 16, 16, 512, 512) == (16, 14)
    took = {}
    for reached in (2, 14):
        # token t's one pick is expert t % reached
        router = np.zeros((512, 16))
        x[:, :16] = 4.0 * np.eye(16)[np.arange(32) % reached]
        router[:16] = 8.0 * np.eye(16) - 4.0
        routed, counts = held_experts_mlp(
            jnp.asarray(x, jnp.float32), jnp.asarray(router, jnp.float32),
            wg, wu, wd, (0, 16), 1, 1.0)
        counts = dict(zip(pm.COUNTERS, np.asarray(counts)))
        assert counts["moe_experts_hit"] == reached
        took[reached] = counts["moe_batched_layers"]
        with jax.default_matmul_precision("highest"):
            want = np.stack([np.asarray(ref._mlp(
                jnp.asarray(x[t], jnp.float32), wg[t % reached],
                wu[t % reached], wd[t % reached], None)) for t in range(32)])
        np.testing.assert_allclose(np.asarray(routed), want, atol=1e-4,
                                   rtol=1e-5)
    assert took == {2: 0, 14: 1}


# ------------------------------------------------- the latent paged cache
def test_latent_cache_is_one_pool_a_layer_and_counts_its_bytes():
    _, cfg, _ = _family()
    spec = pm.serving_spec(cfg)
    assert spec.cache_layout == "latent" and spec.cache_shape == (24,)
    assert spec.cache_bytes_per_token == 3 * 24 * 4
    full = pm.serving_spec(pm.PanguMoEConfig(
        num_hidden_layers=5, dtype="bfloat16"))
    assert full.cache_shape == (576,)
    assert full.cache_bytes_per_token == 5760
    pc = PagedKVCache(3, (24,), 16, 4)
    assert [p.shape for p in pc.pools] == [(16, 4, 24)] * 3


@pytest.mark.parametrize("family", ["pangu_ultra_moe", "gpt2"])
def test_the_cache_is_built_from_what_the_spec_says_of_a_position(family):
    """`PagedKVCache` takes the spec's `cache_shape` and `cache_dtype` and
    nothing else about layout; `layout` is what the spec calls it. The
    latent pools are stored a whole number of 128-lane rows wide
    (`paged_cache.physical_shape`: 576 -> 640), the toy heads as they
    are."""
    if family == "gpt2":
        spec = gen.serving_spec((2, 4, 8, 32))
        leaf_shapes = [(16, 4, 4, 8)] * 2
    else:
        spec = pm.serving_spec(pm.PanguMoEConfig(
            num_hidden_layers=2, dtype="bfloat16"))
        leaf_shapes = [(16, 4, 640)]
    pc = PagedKVCache(spec.num_layers, spec.cache_shape, 16, 4,
                      dtype=jnp.dtype(spec.cache_dtype))
    assert pc.layout == spec.cache_layout
    assert len(pc.pools) == spec.num_layers
    for leaf in pc.pools:
        arrays = jax.tree_util.tree_leaves(leaf)
        assert len(arrays) == spec.pools_per_layer
        assert [a.shape for a in arrays] == leaf_shapes
        assert all(a.dtype == jnp.dtype(spec.cache_dtype) for a in arrays)


def test_a_cache_shape_that_is_no_layout_is_refused():
    with pytest.raises(ValueError, match="cache_shape"):
        PagedKVCache(1, (2, 4, 8), 16, 4)


@pytest.mark.parametrize("length", [1, 3, 4, 5, 23])
def test_write_prefill_on_the_latent_layout_equals_an_eager_loop(length):
    rng = np.random.default_rng(length)
    dense = tuple(jnp.asarray(np.where(
        np.arange(24)[None, :, None] < length,
        rng.normal(size=(2, 24, 12)), 0.0), jnp.float32) for _ in range(2))
    got = PagedKVCache(2, (12,), 16, 4)
    want = PagedKVCache(2, (12,), 16, 4)
    for pc in (got, want):
        pc.allocate("keep", 6)
        pc.pools = tuple(p + 1.0 for p in pc.pools)
    for b, sid in enumerate(("s0", "s1")):
        for pc in (got, want):
            pc.allocate(sid, length)
        got.write_prefill(sid, dense, length, batch_index=b)
        ids = want.block_table(sid)
        n = len(ids) * 4
        want.pools = tuple(
            pool.at[jnp.asarray(ids)].set(
                jnp.pad(d[b], ((0, max(0, n - 24)), (0, 0)))[:n]
                .reshape(len(ids), 4, 12))
            for pool, d in zip(want.pools, dense))
    for g, w in zip(got.pools, want.pools):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    got.free("s0", scrub=True)
    assert not any(got.check_integrity().values())


def test_prefill_then_paged_decode_matches_the_reference_forward():
    """The engine's prefill program, the scatter into the latent pool and
    ragged `paged_decode_step`s against the reference's full forward."""
    _, cfg, params = _family((0, 4))
    spec = pm.serving_spec(cfg)
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, 256, (n,)).astype(np.int32)
               for n in (5, 11, 8)]
    pc = PagedKVCache(cfg.num_hidden_layers, (cfg.latent_width,), 32, 4)
    toks, seqs = [], []
    for b, p in enumerate(prompts):
        logits, dense, counts = spec.prefill(params,
                                             jnp.asarray(p[None], jnp.int32))
        np.testing.assert_allclose(
            np.asarray(logits)[0], _reference_logits(params, p, cfg)[-1],
            atol=1e-5, rtol=0)
        assert counts.shape == (len(pm.COUNTERS),)
        pc.allocate(b, len(p))
        pc.write_prefill(b, dense, len(p))
        toks.append(int(np.asarray(logits)[0].argmax()))
        seqs.append(list(p))
    tables = np.zeros((3, cfg.max_seq_len // 4), np.int32)
    step = jax.jit(functools.partial(paged_decode_step, geom=spec))
    for _ in range(6):
        slots = [pc.append_slot(b) for b in range(3)]
        for b in range(3):
            seqs[b].append(toks[b])
            t = pc.block_table(b)
            tables[b, :len(t)] = t
        logits, pc.pools = step(
            params, pc.pools, np.asarray(toks, np.int32),
            np.asarray([s[2] for s in slots], np.int32), tables,
            np.asarray([s[0] for s in slots], np.int32),
            np.asarray([s[1] for s in slots], np.int32))
        for b in range(3):
            np.testing.assert_allclose(
                np.asarray(logits)[b],
                _reference_logits(params, np.asarray(seqs[b]), cfg)[-1],
                atol=1e-5, rtol=0)
        toks = [int(r.argmax()) for r in np.asarray(logits)]
    assert not any(pc.check_integrity().values())


# ------------------------------------------------------ through LLMEngine
def _engine(model, k, **kw):
    return LLMEngine.from_model(model, EngineConfig(
        block_size=8, num_blocks=48, max_num_seqs=4, decode_chunk_size=k,
        **kw))


def _serve(model, k, prompts, **kw):
    eng = _engine(model, k, **kw)
    for i, p in enumerate(prompts):
        eng.add_request(p, SamplingParams(max_tokens=12 + i),
                        request_id=f"r{i}")
    out = eng.run()
    assert not any(eng.cache.check_integrity().values())
    return eng, [out[f"r{i}"].tolist() for i in range(len(prompts))]


def test_engine_streams_are_bit_equal_for_chunks_of_8_and_of_1():
    model, cfg, params = _family((0, 4))
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, 256, (n,)).astype(np.int32)
               for n in (5, 17, 9, 30, 3)]
    eng8, got8 = _serve(model, 8, prompts)
    eng1, got1 = _serve(model, 1, prompts)
    assert got8 == got1
    # and each token is the reference's best, to float32 resolution
    for p, toks in zip(prompts, got8):
        ids = np.concatenate([p, toks]).astype(np.int32)
        lg = _reference_logits(params, ids, cfg)[len(p) - 1:-1]
        assert (lg.max(-1) - lg[np.arange(len(toks)), toks]).max() < 1e-5
    assert eng8.stats.moe_pairs == eng1.stats.moe_pairs > 0
    assert eng8.stats.moe_experts_hit > 0
    assert eng8.stats.cache_bytes_per_token == 3 * 24 * 4
    assert eng8.stats.as_dict()["moe_pairs"] == eng8.stats.moe_pairs
    # 8 of 8 experts held: the compact buffer is the whole one, no fallback
    assert eng8.stats.as_dict()["moe_full_buffer_layers"] == 0
    # at most 4 rows x top-2: no load passes the capacity, so every expert
    # layer of every prefill and trip took the batched form (PR 36)
    assert eng8.stats.as_dict()["moe_batched_layers"] \
        == eng8.stats.moe_batched_layers > 0


def test_the_batched_layers_are_on_the_spans_and_in_the_engines_stats():
    """`moe_batched_layers` (PR 36) beside `moe_full_buffer_layers`: a stat
    of `serving.prefill` and `serving.decode`, set from the program's one
    fetch, and an `EngineStats` counter that sums them. All 8 experts held
    and at most 3 rows of top-2: every load fits the capacity and two
    experts at least are reached, so each prefill counts its 2 expert
    layers, a chunk 2 a trip with a live row, and nothing falls back."""
    from paddle_tpu import obs
    model, _, _ = _family()
    eng = _engine(model, 8)
    rng = np.random.default_rng(11)
    for i, n in enumerate((5, 12, 9)):
        eng.add_request(rng.integers(0, 256, (n,)).astype(np.int32),
                        SamplingParams(max_tokens=17), request_id=f"r{i}")
    assert not obs.trace.is_enabled()
    obs.trace.enable()
    try:
        eng.run()
        spans = {name: [e.args for e in obs.trace.events() if e.name == name]
                 for name in ("serving.prefill", "serving.decode")}
    finally:
        obs.trace.disable()
        obs.trace.clear()
    assert [p["moe_batched_layers"] for p in spans["serving.prefill"]] \
        == [2, 2, 2]
    assert len(spans["serving.decode"]) == 2        # 16 tokens: 8 and 8
    for d in spans["serving.decode"]:
        assert d["moe_batched_layers"] == 2 * d["chunk"] == 16
        assert d["moe_full_buffer_layers"] == 0 and 0 < d["moe_max_load"] <= 3
    assert eng.stats.moe_batched_layers == 3 * 2 + 2 * 16
    assert eng.stats.as_dict()["moe_batched_layers"] == 38
    assert eng.stats.moe_full_buffer_layers == 0


def test_chunked_prefill_rides_the_shared_prompt_feed():
    model, _, _ = _family((0, 4))
    rng = np.random.default_rng(8)
    prompts = [rng.integers(0, 256, (n,)).astype(np.int32)
               for n in (21, 6, 28)]
    _, dense = _serve(model, 8, prompts)
    eng, chunked = _serve(model, 8, prompts, prefill_chunk_threshold=8)
    assert chunked == dense and eng.stats.prefill_chunks() > 0


def test_the_chunk_returns_its_counts_as_extra_rows():
    _, cfg, params = _family()                  # all 8 experts held
    spec = pm.serving_spec(cfg)
    pc = PagedKVCache(3, (cfg.latent_width,), 16, 8)
    k, n, mb = 4, 2, cfg.max_seq_len // 8
    pc.allocate(0, 3)
    pc.reserve_slots(0, k)
    packed = np.zeros((n, PACK_COLS + k + mb), np.int32)
    packed[0, :5] = (7, 3, 1, 1, 100)
    packed[0, 5] = -1
    table = pc.block_table(0)
    packed[0, PACK_COLS + k:PACK_COLS + k + len(table)] = table
    out, _ = fused_decode_chunk(params, pc.pools, jnp.asarray(packed), spec,
                                k)
    out = np.asarray(out)
    assert out.shape == (k + 2 + len(pm.COUNTERS), n)
    pairs, hit, full, batched, calls, fit2, fit4, load = out[k + 2:, 0]
    assert calls == fit2 == fit4 == k * 2       # PR 37: a call a layer, trip
    # one live row, 2 expert layers, top-2 with every expert held: 2 pairs
    # a layer and trip on 2 experts, and the dead row routes nowhere
    assert pairs == k * 2 * 2 and hit == pairs and load == 1
    # a load of 1 fits any capacity: every layer of every trip multiplied
    # batched over the experts (PR 36), none fell back
    assert full == 0 and batched == k * 2
    assert (out[k + 2:, 1] == out[k + 2:, 0]).all()


@pytest.mark.parametrize("config, feature", [
    (dict(kv_cache_dtype="int8"), "int8 KV pools"),
    (dict(enable_prefix_cache=True), "prefix cache"),
    (dict(enable_prefix_cache=True, host_tier_blocks=4), "prefix cache"),
])
def test_what_the_latent_layout_lacks_raises_by_name(config, feature):
    model, _, _ = _family()
    with pytest.raises(NotImplementedError, match=feature):
        _engine(model, 8, **config)


def test_the_host_tier_and_migration_raise_by_name_on_the_latent_layout():
    with pytest.raises(NotImplementedError, match="host tier"):
        PagedKVCache(1, (12,), 8, 4, host_tier_blocks=2)
    model, _, _ = _family()
    eng = _engine(model, 8)
    rid = eng.add_request(np.arange(5, dtype=np.int32),
                          SamplingParams(max_tokens=20))
    eng.step()
    with pytest.raises(NotImplementedError, match="migration"):
        eng.export_request(rid)
