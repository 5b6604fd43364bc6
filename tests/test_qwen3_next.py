"""Qwen3-Next at a small size on the CPU (float32): the family against the
plain reference, the chunk-wise Gated DeltaNet against the recurrence, the
softmax router and its shares, the hybrid cache (state slots beside paged
rows in one manager), and the family through `LLMEngine`."""
import functools
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed.moe import held_experts_mlp
from paddle_tpu.inference.serving import (EngineConfig, LLMEngine,
                                          SamplingParams)
from paddle_tpu.inference.serving.attention import (PACK_COLS,
                                                    fused_decode_chunk,
                                                    paged_decode_step)
from paddle_tpu.inference.serving.paged_cache import (CacheExhausted,
                                                      PagedKVCache, SeqState)
from paddle_tpu.models import qwen3_next as qn
from paddle_tpu.models.generation import extract_params

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "benchmarks"))
from lib import reference_qwen3_next as ref  # noqa: E402

SMALL = dict(vocab_size=256, hidden_size=64, num_hidden_layers=4,
             num_attention_heads=4, num_key_value_heads=2, head_dim=16,
             linear_num_key_heads=2, linear_num_value_heads=4,
             linear_key_head_dim=8, linear_value_head_dim=8,
             moe_intermediate_size=32, shared_expert_intermediate_size=32,
             num_experts=8, num_experts_per_tok=2, max_seq_len=64)
#: float32 on both sides: what a wrong mask, slot or position would exceed
#: by orders of magnitude, and bfloat16 weights by two
LIMIT = 1e-5


@pytest.fixture(autouse=True)
def _short_chunks(monkeypatch):
    """Prompts of 20 to 30 tokens cross the borders of a DeltaNet chunk."""
    monkeypatch.setattr(qn, "GDN_CHUNK", 8)


def _family(held=None, seed=5, **over):
    cfg = qn.Qwen3NextConfig(**{**SMALL, **over}, held_experts=held)
    paddle.seed(seed)
    model = qn.Qwen3Next(cfg)
    return model, cfg, extract_params(model)


@functools.lru_cache(maxsize=None)
def _reference_fn(cfg):
    return jax.jit(functools.partial(ref.logits, size=ref.sizes(cfg)))


def _reference_logits(params, ids, cfg):
    """One compilation a configuration: ids padded to max_seq_len (causal
    and recurrent, so the padding changes nothing before it)."""
    padded = np.zeros((cfg.max_seq_len,), np.int32)
    padded[:len(ids)] = ids
    return np.asarray(_reference_fn(cfg)(params, jnp.asarray(padded)))[
        :len(ids)]


# ------------------------------------------------------------ the family
@pytest.mark.parametrize("held", [None, (2, 2), (6, 2)])
def test_forward_matches_the_plain_reference(held):
    model, cfg, params = _family(held)
    ids = np.random.default_rng(1).integers(0, 256, (2, 21)).astype(np.int32)
    got = np.asarray(model(paddle.to_tensor(ids))._value)
    for b in range(2):
        np.testing.assert_allclose(
            got[b], _reference_logits(params, ids[b], cfg), atol=LIMIT,
            rtol=0)


def test_attention_in_query_blocks_equals_the_reference(monkeypatch):
    """A prompt longer than a block of queries, and no multiple of it."""
    _, cfg, params = _family((0, 4))
    monkeypatch.setattr(qn, "QUERY_BLOCK", 8)
    ids = np.random.default_rng(2).integers(0, 256, (1, 29)).astype(np.int32)
    got = jax.jit(lambda p, i: qn.forward(p, i, cfg))(params, ids)
    np.testing.assert_allclose(
        got[0], _reference_logits(params, ids[0], cfg), atol=LIMIT, rtol=0)


def test_the_expert_layer_in_token_blocks_counts_what_the_whole_counts(
        monkeypatch):
    """A prompt longer than a block of tokens and no multiple of it: the
    same logits, the same pairs, and the padding routed nowhere."""
    _, cfg, params = _family((0, 4))
    ids = np.random.default_rng(3).integers(0, 256, (1, 29)).astype(np.int32)
    whole = jax.jit(lambda p, i: qn._dense_layers(p, i, cfg))(params, ids)
    monkeypatch.setattr(qn, "MOE_TOKEN_BLOCK", 8)
    blocks = jax.jit(lambda p, i: qn._dense_layers(p, i, cfg))(params, ids)
    np.testing.assert_allclose(blocks[0], whole[0], atol=LIMIT, rtol=0)
    pairs, hit, full, batched, calls, fit2, fit4, state_rows, load = (
        np.asarray(c) for c in zip(whole[2], blocks[2]))
    assert pairs[0] == pairs[1] > 0             # every pair, exactly once
    assert list(state_rows) == [0, 0]           # a prefill steps no row
    assert hit[1] >= hit[0] and load[1] <= load[0]      # per block
    # PR 37: a call a layer, and in blocks of 8 tokens ceil(29 / 8) a layer
    assert list(calls) == [cfg.num_hidden_layers, 4 * cfg.num_hidden_layers]
    assert (fit2 <= fit4).all() and (fit4 <= calls).all()


def test_the_layers_alternate_as_the_interval_says():
    _, cfg, params = _family()
    spec = qn.serving_spec(cfg)
    assert spec.layer_caches == ("state", "state", "state", "rows")
    assert "layers.3.attn.q.weight" in params
    assert "layers.2.gdn.qkvz.weight" in params
    assert "layers.3.gdn.qkvz.weight" not in params
    # a query and a gate a head; 2 key-value heads under 4 query heads
    assert params["layers.3.attn.q.weight"].shape == (64, 4 * 2 * 16)
    assert params["layers.3.attn.k.weight"].shape == (64, 2 * 16)
    # zero-centred norms start at 0, the DeltaNet's output norm at 1
    assert float(jnp.abs(params["layers.0.norm1.weight"]).max()) == 0.0
    assert float(jnp.abs(params["layers.3.attn.q_norm.weight"]).max()) == 0.0
    assert float(params["layers.0.gdn.norm.weight"].min()) == 1.0
    assert float(params["layers.0.gdn.A_log"].max()) <= 0.0


def _delta_inputs(rng, T, H=3, dk=8, dv=6):
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)  # noqa
    return tuple(jnp.asarray(a, jnp.float32) for a in (
        unit(rng.normal(size=(T, H, dk))) / np.sqrt(dk),
        unit(rng.normal(size=(T, H, dk))), rng.normal(size=(T, H, dv)),
        -rng.uniform(0.01, 2.5, size=(T, H)), rng.uniform(0, 1, (T, H)),
        rng.normal(size=(H, dk, dv))))


@pytest.mark.parametrize("length", [1, 7, 8, 16, 19, 40])
def test_chunkwise_deltanet_equals_the_recurrence(length):
    """Lengths that are and are not multiples of the chunk of 8, from a
    state that is not zero."""
    q, k, v, g, beta, state = _delta_inputs(np.random.default_rng(length),
                                            length)
    want_o, want_s = qn.gdn_recurrence(q, k, v, g, beta, state)
    got_o, got_s = qn.gdn_chunked(q, k, v, g, beta, state, 8)
    np.testing.assert_allclose(got_o, want_o, atol=2e-6, rtol=0)
    np.testing.assert_allclose(got_s, want_s, atol=2e-6, rtol=0)


# ------------------------------------------- the decode step, in place
def _composed_step(q, k, v, g, beta, state):
    """The step as the family composed it of XLA operations before it went
    through the `kda_step` kernel: q, k [N, H, dk], v [N, H, dv], g, beta
    [N, H], state [N, H, dk, dv] -> (o [N, H, dv], new state)."""
    decayed = jnp.exp(g)[..., None, None] * state
    r = jnp.sum(decayed * k[..., :, None], axis=-2)
    sq = jnp.sum(decayed * q[..., :, None], axis=-2)
    d = beta[..., None] * (v - r)
    o = sq + d * jnp.sum(k * q, axis=-1, keepdims=True)
    return o, decayed + k[..., :, None] * d[..., None, :]


def _composed_decode(cfg, p, pre, h, states, histories, positions, live,
                     state_slots):
    """The family's decode step before the kernel, as it was: the rows'
    entries gathered by slot (the history flat, [slots, (K - 1) conv_dim]),
    stepped, scattered back (rows not live dropped)."""
    K, fresh = cfg.linear_conv_kernel_dim, positions == 0
    u, z, beta, g = qn.gdn_inputs(p, pre, h, cfg)
    history = jnp.where(fresh[:, None], 0, histories[state_slots]) \
        .reshape(h.shape[0], K - 1, -1)
    window = jnp.concatenate([history, u[:, None]], axis=1)
    q, k, v = qn.gdn_heads(qn.gdn_conv(
        p, pre, [window[:, j] for j in range(K)], h.dtype), cfg)
    state = jnp.where(fresh[:, None, None, None], 0, states[state_slots])
    o, state = _composed_step(q, k, v, g, beta, state)
    at = jnp.where(live, state_slots, states.shape[0])
    return (qn.gdn_output(p, pre, o, z, cfg, h.dtype),
            states.at[at].set(state, mode="drop"),
            histories.at[at].set(window[:, 1:].reshape(h.shape[0], -1),
                                 mode="drop"))


def _per_head_history(flat, cfg):
    """A flat history [slots, (K - 1) conv_dim] in the stored layout
    [slots, Hv, (K - 1) (2 dk + dv)]."""
    K = cfg.linear_conv_kernel_dim
    return qn.per_value_head(flat.reshape(flat.shape[0], K - 1, -1), cfg) \
        .transpose(0, 2, 1, 3).reshape(flat.shape[0],
                                       cfg.linear_num_value_heads, -1)


def _gdn_layer(seed):
    """Layer 0's DeltaNet parameters at the small widths (2 key heads
    under 4 value heads), drawn so that the conv, the decays (g between
    about -6 and 0) and the gates all matter; and the config."""
    cfg = qn.Qwen3NextConfig(**SMALL)
    rng = np.random.default_rng(seed)
    p = {name: jnp.asarray(rng.normal(size=shape) * 0.3, jnp.float32)
         for name, (shape, _) in qn.param_shapes(cfg).items()
         if name.startswith("layers.0.gdn.")}
    p["layers.0.gdn.A_log"] = jnp.asarray(
        rng.uniform(-1.5, 0.5, size=(4,)), jnp.float32)
    return cfg, p


def test_the_kernel_step_equals_the_composed_step():
    """`_gdn_decode` through `kda_step` (interpret mode) against the
    composed step it replaced, on rows dead (first and in the middle),
    live out of slot order, and fresh (position 0 over a slot that still
    holds its last owner's entry): the live rows' outputs and entries at
    float32 resolution, the history exactly, every other slot to the
    bit."""
    cfg, p = _gdn_layer(11)
    rng = np.random.default_rng(12)
    K, slots = cfg.linear_conv_kernel_dim, 8
    h = jnp.asarray(rng.normal(size=(6, 64)), jnp.float32)
    slot = jnp.asarray([5, 3, 0, 1, 6, 2], jnp.int32)
    live = jnp.asarray([False, True, True, False, True, True])
    positions = jnp.asarray([4, 0, 9, 2, 5, 1], jnp.int32)
    states = jnp.asarray(rng.normal(size=(slots, 4, 8, 8)), jnp.float32)
    flat = jnp.asarray(rng.normal(size=(slots, (K - 1) * cfg.conv_dim)),
                       jnp.float32)
    want_y, want_s, want_h = _composed_decode(
        cfg, p, "layers.0.", h, states, flat, positions, live, slot)
    y, (got_s, got_h) = qn._gdn_decode(
        cfg, p, "layers.0.", h, SeqState(states, _per_head_history(flat, cfg)),
        positions, live, slot)
    assert got_h.shape == (slots,) + cfg.state_shapes[1][0]
    on = np.asarray(live)
    np.testing.assert_allclose(np.asarray(y)[on], np.asarray(want_y)[on],
                               atol=2e-5, rtol=0)
    np.testing.assert_allclose(got_s, want_s, atol=2e-6, rtol=0)
    np.testing.assert_array_equal(got_h, _per_head_history(want_h, cfg))
    untouched = [s for s in range(slots) if s not in (3, 0, 6, 2)]
    assert untouched == [1, 4, 5, 7]
    for got, was in ((got_s, states), (got_h, _per_head_history(flat, cfg))):
        np.testing.assert_array_equal(np.asarray(got)[untouched],
                                      np.asarray(was)[untouched])
    # the fresh row read nothing of what its slot held
    assert np.abs(np.asarray(got_s[3] - states[3])).max() > 0.1


def test_a_decode_step_continues_from_a_prefills_state():
    """The layer's prefill over 19 positions, then five decode steps
    through the kernel in slot 2 of 3 (the others full of noise) are the
    recurrence as written over all 24, outputs and final state; so is the
    composed step from the same prefill."""
    cfg, p = _gdn_layer(3)
    pre, K, T, at = "layers.0.", cfg.linear_conv_kernel_dim, 24, 19
    h = jnp.asarray(np.random.default_rng(4).normal(size=(T, 64)),
                    jnp.float32)
    u, z, beta, g = qn.gdn_inputs(p, pre, h, cfg)
    padded = jnp.pad(u, ((K - 1, 0), (0, 0)))
    q, k, v = qn.gdn_heads(qn.gdn_conv(
        p, pre, [padded[j:j + T] for j in range(K)], h.dtype), cfg)
    want_o, want_s = qn.gdn_recurrence(q, k, v, g, beta,
                                       jnp.zeros((4, 8, 8), jnp.float32))
    want_y = qn.gdn_output(p, pre, want_o, z, cfg, h.dtype)
    y_pre, (s0, h0) = jax.jit(lambda p, h: qn._gdn_dense(p, pre, h, cfg))(
        p, h[None, :at])
    np.testing.assert_allclose(y_pre[0], want_y[:at], atol=2e-5, rtol=0)
    noise = np.random.default_rng(5)
    leaf = SeqState(
        jnp.asarray(noise.normal(size=(3, 4, 8, 8)), jnp.float32)
        .at[2].set(s0[0]),
        jnp.asarray(noise.normal(size=(3,) + h0.shape[1:]), jnp.float32)
        .at[2].set(h0[0]))
    others = [np.asarray(a)[:2] for a in leaf]
    rows = s0, u[at - K + 1:at].reshape(1, -1)  # the composed form's entry
    kernel, composed = (jax.jit(functools.partial(fn, cfg), static_argnums=1)
                        for fn in (qn._gdn_decode, _composed_decode))
    for t in range(at, T):
        args = (jnp.asarray([t], jnp.int32), jnp.asarray([True]),
                jnp.asarray([2], jnp.int32))
        y, leaf = kernel(p, pre, h[t][None], leaf, *args)
        np.testing.assert_allclose(y[0], want_y[t], atol=2e-5, rtol=0)
        y_c, *rows = composed(p, pre, h[t][None], *rows, args[0], args[1],
                              jnp.asarray([0], jnp.int32))
        np.testing.assert_allclose(y_c[0], want_y[t], atol=2e-5, rtol=0)
    np.testing.assert_allclose(leaf.arrays[0][2], want_s, atol=2e-6, rtol=0)
    np.testing.assert_allclose(rows[0][0], want_s, atol=2e-6, rtol=0)
    for got, was in zip(leaf, others):
        np.testing.assert_array_equal(np.asarray(got)[:2], was)


def test_strong_decay_leaves_no_overflow_in_a_chunk():
    """Every exponent of the chunked form is <= 0: a decay of exp(-40) a
    position neither overflows nor leaves a NaN behind the mask."""
    q, k, v, _, beta, state = _delta_inputs(np.random.default_rng(4), 16)
    g = jnp.full((16, 3), -40.0, jnp.float32)
    want_o, want_s = qn.gdn_recurrence(q, k, v, g, beta, state)
    got_o, got_s = qn.gdn_chunked(q, k, v, g, beta, state, 8)
    assert np.isfinite(np.asarray(got_o)).all()
    np.testing.assert_allclose(got_o, want_o, atol=2e-6, rtol=0)
    np.testing.assert_allclose(got_s, want_s, atol=2e-6, rtol=0)


# ------------------------------------------------------ the expert block
def _expert_weights(rng, n, h=32, f=16):
    return (jnp.asarray(rng.normal(size=(h, n)), jnp.float32),
            *(jnp.asarray(rng.normal(size=s) * 0.2, jnp.float32)
              for s in ((n, h, f), (n, h, f), (n, f, h))))


def test_softmax_scoring_equals_a_per_token_loop():
    rng = np.random.default_rng(2)
    router, wg, wu, wd = _expert_weights(rng, 8)
    x = jnp.asarray(rng.normal(size=(24, 32)), jnp.float32)
    routed, counts = held_experts_mlp(x, router, wg[2:6], wu[2:6], wd[2:6],
                                      (2, 4), 3, 1.0, scoring="softmax")
    probs = np.asarray(jax.nn.softmax(
        jnp.dot(x, router, precision="highest"), -1))
    want, pairs = np.zeros((24, 32), np.float32), 0
    for t in range(24):
        top = np.argsort(-probs[t])[:3]
        for e in top:
            if 2 <= e < 6:
                a = np.asarray(x[t] @ wg[e])
                want[t] += probs[t, e] / probs[t, top].sum() * np.asarray(
                    (a / (1 + np.exp(-a)) * np.asarray(x[t] @ wu[e]))
                    @ wd[e])
                pairs += 1
    np.testing.assert_allclose(routed, want, atol=2e-5, rtol=0)
    assert int(counts[0]) == pairs
    with pytest.raises(ValueError, match="scoring"):
        held_experts_mlp(x, router, wg, wu, wd, (0, 8), 3, 1.0,
                         scoring="tanh")


def test_the_sigmoid_path_lowers_as_it_did_without_the_argument():
    rng = np.random.default_rng(2)
    router, wg, wu, wd = _expert_weights(rng, 8)
    x = jnp.asarray(rng.normal(size=(24, 32)), jnp.float32)

    def text(**kw):
        return jax.jit(lambda *a: held_experts_mlp(
            *a, (0, 8), 2, 2.5, **kw)).lower(x, router, wg, wu, wd).as_text()

    assert text() == text(scoring="sigmoid") != text(scoring="softmax")
    # a softmax normalises over the experts: a reduction the sigmoid's
    # scoring has not
    assert text(scoring="softmax").count("stablehlo.reduce") \
        > text().count("stablehlo.reduce")


def test_the_shares_add_up_to_the_uncut_layer():
    """8 experts over 4 ranks: the routed parts of all ranks, with the
    gated shared expert counted once, equal the uncut reference layer."""
    rng = np.random.default_rng(2)
    router, wg, wu, wd = _expert_weights(rng, 8)
    x = jnp.asarray(rng.normal(size=(40, 32)), jnp.float32)
    shared = [jnp.asarray(rng.normal(size=s) * 0.2, jnp.float32)
              for s in ((32, 16), (32, 16), (16, 32), (32, 1))]
    parts, pairs = [], 0
    for rank in range(4):
        routed, counts = held_experts_mlp(
            x, router, wg[2 * rank:2 * rank + 2], wu[2 * rank:2 * rank + 2],
            wd[2 * rank:2 * rank + 2], (2 * rank, 2), 2, 1.0,
            scoring="softmax")
        parts.append(np.asarray(routed))
        pairs += int(counts[0])
    assert pairs == 40 * 2                      # every pair, exactly once
    p = {"router.weight": router, "experts.gate.weight": wg,
         "experts.up.weight": wu, "experts.down.weight": wd,
         "shared.gate.weight": shared[0], "shared.up.weight": shared[1],
         "shared.down.weight": shared[2], "shared_gate.weight": shared[3]}
    with jax.default_matmul_precision("highest"):
        whole, ref_pairs = ref._experts(
            p, "", x, {"held": (0, 8), "num_experts_per_tok": 2}, None)
        once = ref._mlp(x, *shared[:3], None) \
            * jax.nn.sigmoid(ref._mm(x, shared[3], None))
    assert int(ref_pairs) == pairs
    np.testing.assert_allclose(sum(parts) + np.asarray(once),
                               np.asarray(whole), atol=1e-5, rtol=0)


# --------------------------------------------------------- the hybrid cache
def _cache(slots=3, blocks=16, **kw):
    return PagedKVCache(
        4, (2, 16), blocks, 8, layer_caches=("state",) * 3 + ("rows",),
        state_shapes=(((4, 8, 8), "float32"), ((3 * 64,), "float32")),
        num_state_slots=slots, **kw)


def test_one_manager_owns_blocks_and_state_slots():
    pc = _cache()
    assert pc.layout == "hybrid" and pc.num_state_slots == 3
    assert [isinstance(p, SeqState) for p in pc.pools] == [True] * 3 + [False]
    assert pc.pools[0].arrays[0].shape == (3, 4, 8, 8)
    assert pc.pools[3][0].shape == (16, 8, 2, 16)
    # bytes a position: the one rows layer; bytes a sequence: three states
    assert pc.physical_bytes_per_token == 2 * 2 * 16 * 4
    assert pc.state_bytes_per_seq == 3 * (4 * 8 * 8 + 192) * 4
    pc.allocate("a", 9)
    pc.allocate("b", 1)
    assert (pc.state_slot("a"), pc.state_slot("b")) == (0, 1)
    assert pc.num_state_slots_used() == 2 and pc.num_used() == 3
    pc.free("a")
    pc.allocate("c", 17)
    assert pc.state_slot("c") == 0              # the freed slot, reused
    pc.allocate("d", 1)
    with pytest.raises(CacheExhausted, match="state slot") as e:
        pc.allocate("e", 1)
    assert (e.value.needed, e.value.free, e.value.total) == (1, 0, 3)
    assert not pc.has_seq("e") and pc.num_used() == 5   # no side effect
    for seq in "bcd":
        pc.free(seq)
    report = pc.check_integrity()
    assert not any(report.values()) and "state_slots_leaked" in report
    assert pc.blocks_allocated == pc.blocks_freed


def test_check_integrity_sees_a_leaked_and_a_doubly_owned_slot():
    pc = _cache()
    pc.allocate("a", 3)
    pc._state_free.pop()                        # a slot nobody holds
    with pytest.raises(RuntimeError, match="'state_slots_leaked': 1"):
        pc.check_integrity()
    pc = _cache()
    pc.allocate("a", 3)
    pc._state_free.append(pc.state_slot("a"))   # free AND owned
    with pytest.raises(RuntimeError, match="'state_slots_double_owned': 1"):
        pc.check_integrity()
    pc = _cache()
    pc.allocate("a", 3)
    pc._tables["ghost"], pc._lens["ghost"] = [], 0      # a table, no slot
    with pytest.raises(RuntimeError, match="'state_slots_without_table': 1"):
        pc.check_integrity()


def test_layer_caches_that_are_no_layout_are_refused():
    with pytest.raises(ValueError, match="layer_caches"):
        PagedKVCache(2, (2, 16), 8, 4, layer_caches=("state", "pages"))
    with pytest.raises(ValueError, match="layer_caches"):
        PagedKVCache(2, (2, 16), 8, 4, layer_caches=("state", "state"),
                     state_shapes=(((4,), "float32"),), num_state_slots=2)
    with pytest.raises(ValueError, match="state_shapes"):
        PagedKVCache(2, (2, 16), 8, 4, layer_caches=("state", "rows"))


@pytest.mark.parametrize("config, feature", [
    (dict(kv_cache_dtype="int8"), "int8 KV pools"),
    (dict(enable_prefix_cache=True), "prefix cache"),
    (dict(enable_prefix_cache=True, host_tier_blocks=4), "prefix cache"),
    (dict(host_tier_blocks=2), "host tier"),
])
def test_what_the_hybrid_layout_lacks_raises_by_name(config, feature):
    with pytest.raises(NotImplementedError,
                       match=f"hybrid cache layout.*{feature}"):
        _cache(**config)


def test_block_migration_raises_by_name_on_the_hybrid_layout():
    model, _, _ = _family()
    eng = _engine(model, 8)
    rid = eng.add_request(np.arange(5, dtype=np.int32),
                          SamplingParams(max_tokens=20))
    eng.step()
    with pytest.raises(NotImplementedError,
                       match="hybrid cache layout.*migration"):
        eng.export_request(rid)
    pc = _cache()
    with pytest.raises(NotImplementedError, match="import_blocks"):
        pc.import_blocks("x", ((None, None),), 0)


def test_write_prefill_puts_rows_in_blocks_and_the_state_in_the_slot():
    pc = _cache()
    rng = np.random.default_rng(0)
    pc.allocate("other", 2)                     # holds slot 0
    pc.allocate("s", 11)
    slot = pc.state_slot("s")
    before = [np.asarray(a) for a in pc.pools[1]]
    dense = tuple(
        SeqState(jnp.asarray(rng.normal(size=(2, 4, 8, 8)), jnp.float32),
                 jnp.asarray(rng.normal(size=(2, 192)), jnp.float32))
        for _ in range(3)) + ((
            jnp.asarray(rng.normal(size=(2, 2, 64, 16)), jnp.float32),
            jnp.asarray(rng.normal(size=(2, 2, 64, 16)), jnp.float32)),)
    pc.write_prefill("s", dense, 11, batch_index=1)
    for layer in range(3):
        for got, new in zip(pc.pools[layer], dense[layer]):
            np.testing.assert_array_equal(got[slot], new[1])
            np.testing.assert_array_equal(got[0], 0)    # the other's slot
    del before
    table = pc.block_table("s")
    k = np.asarray(pc.pools[3][0])[table].reshape(-1, 2, 16)
    np.testing.assert_array_equal(
        k[:11], np.asarray(dense[3][0])[1].transpose(1, 0, 2)[:11])


# ------------------------------------------------- through the engine
def _engine(model, chunk, **kw):
    return LLMEngine.from_model(model, EngineConfig(
        block_size=8, num_blocks=kw.pop("num_blocks", 48), max_num_seqs=4,
        decode_chunk_size=chunk, **kw))


def _serve(model, chunk, prompts, **kw):
    eng = _engine(model, chunk, **kw)
    for i, p in enumerate(prompts):
        eng.add_request(p, SamplingParams(max_tokens=12 + i),
                        request_id=f"r{i}")
    out = eng.run()
    assert not any(eng.cache.check_integrity().values())
    return eng, [out[f"r{i}"].tolist() for i in range(len(prompts))]


def _prefill_then_decode(cfg, params, prompt, steps=8, hold_slot_0=True):
    """[1 + steps, V] logits of the serving path: the prefill program,
    then greedy paged decode steps through blocks and the state slot."""
    spec = qn.serving_spec(cfg)
    eng = LLMEngine(params, spec, EngineConfig(block_size=8, num_blocks=32,
                                               max_num_seqs=4))
    cache, n = eng.cache, len(prompt)
    if hold_slot_0:
        cache.allocate("other", 3)
    cache.allocate("s", n)
    logits, dense, _ = spec.prefill(params, jnp.asarray(prompt[None]))
    cache.write_prefill("s", dense, n)
    rows, ids = [np.asarray(logits, np.float32)[0]], list(prompt)
    table = np.zeros((1, eng.max_blocks_per_seq), np.int32)
    for _ in range(steps):
        ids.append(int(rows[-1].argmax()))
        block, offset, pos = cache.append_slot("s")
        t = cache.block_table("s")
        table[0, :len(t)] = t
        logits, cache.pools = paged_decode_step(
            params, cache.pools, np.asarray(ids[-1:], np.int32),
            np.asarray([pos], np.int32), table,
            np.asarray([block], np.int32), np.asarray([offset], np.int32),
            spec, state_slots=np.asarray([cache.state_slot("s")], np.int32))
        rows.append(np.asarray(logits, np.float32)[0])
    return np.stack(rows), np.asarray(ids, np.int32), cache.state_slot("s")


def test_prefill_then_paged_decode_matches_the_reference_forward():
    _, cfg, params = _family((0, 4))
    prompt = np.random.default_rng(6).integers(0, 256, (13,)).astype(np.int32)
    rows, ids, slot = _prefill_then_decode(cfg, params, prompt)
    assert slot == 1                            # not the first slot
    want = _reference_logits(params, ids, cfg)[12:21]
    assert np.abs(rows - want).max() < LIMIT
    # the same program on bfloat16 weights fails the same limit
    low = {k: v.astype(jnp.bfloat16) if v.dtype == jnp.float32
           and "router" not in k and "A_log" not in k and "dt_bias" not in k
           else v for k, v in params.items()}
    low_cfg = qn.Qwen3NextConfig(**SMALL, held_experts=(0, 4),
                                 dtype="bfloat16")
    low_rows, low_ids, _ = _prefill_then_decode(low_cfg, low, prompt)
    assert np.abs(low_rows[0] - want[0]).max() > 100 * LIMIT


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
        assert (lg.max(-1) - lg[np.arange(len(toks)), toks]).max() < LIMIT
    assert eng8.stats.moe_pairs == eng1.stats.moe_pairs > 0
    # rows a position in 1 layer of 4: k and v of 2 x 16 float32; a state a
    # sequence in the other 3: 4 heads x 8 x 8 and 3 columns of 4 value
    # heads x 24 channels (its key head's q and k, its own v)
    assert eng8.stats.cache_bytes_per_token == 2 * 2 * 16 * 4 \
        == eng8.stats.cache_physical_bytes_per_token
    assert eng8.stats.state_bytes_per_seq == 3 * (256 + 288) * 4
    assert eng8.stats.state_slots_in_use == 0           # all drained
    # live rows summed over trips: every decoded token but each request's
    # first (its prefill's) took one trip
    assert eng8.stats.live_row_trips == eng1.stats.live_row_trips \
        == sum(len(t) - 1 for t in got8)


def test_chunked_prefill_starts_a_reused_slot_from_zeros():
    """Prompts fed through the scan (no prefill program writes the slot):
    five requests through four slots, so one starts in a slot whose last
    owner's state is still there, and must not read it."""
    model, _, _ = _family((0, 4))
    rng = np.random.default_rng(8)
    prompts = [rng.integers(0, 256, (n,)).astype(np.int32)
               for n in (21, 6, 28, 11, 17)]
    _, dense = _serve(model, 8, prompts)
    eng, chunked = _serve(model, 8, prompts, prefill_chunk_threshold=8)
    assert chunked == dense and eng.stats.prefill_chunks() > 0


def test_a_row_preempted_by_recompute_comes_back_with_its_state():
    """A pool too small for all rows: the scheduler preempts (blocks and
    slot go back), the request prefills again over prompt + tokens so far
    and writes a fresh state: the streams are those of a roomy pool."""
    model, _, _ = _family((0, 4))
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, 256, (n,)).astype(np.int32)
               for n in (20, 22, 18, 21)]
    _, roomy = _serve(model, 4, prompts)
    eng, tight = _serve(model, 4, prompts, num_blocks=12)
    assert eng.stats.preemptions > 0
    assert tight == roomy
    assert eng.cache.num_state_slots_used() == 0
    # every live row of every trip stepped in each of the 3 DeltaNet layers
    assert eng.stats.gdn_state_row_trips == 3 * eng.stats.live_row_trips > 0


def test_the_chunk_carries_the_slot_in_one_more_column():
    _, cfg, params = _family()                  # all 8 experts held
    spec = qn.serving_spec(cfg)
    pc = PagedKVCache(4, (2, 16), 16, 8, layer_caches=spec.layer_caches,
                      state_shapes=spec.state_shapes, num_state_slots=3)
    k, n, mb = 4, 2, cfg.max_seq_len // 8
    pc.allocate("other", 1)
    pc.allocate(0, 3)
    pc.reserve_slots(0, k)
    poison = jax.tree_util.tree_map(lambda a: jnp.full_like(a, jnp.nan),
                                    pc.pools[:3])
    pc.pools = poison + pc.pools[3:]            # what the last owners left
    packed = np.zeros((n, PACK_COLS + k + mb + 1), np.int32)
    packed[0, :5] = (7, 0, 1, 0, 100)           # position 0: a new sequence
    packed[0, 5] = -1
    packed[0, 10] = 3                           # 3 prompt tokens fed
    packed[0, PACK_COLS:PACK_COLS + 3] = (7, 8, 9)
    table = pc.block_table(0)
    packed[0, PACK_COLS + k:PACK_COLS + k + len(table)] = table
    packed[0, -1] = pc.state_slot(0)
    out, pools = fused_decode_chunk(params, pc.pools, jnp.asarray(packed),
                                    spec, k)
    out = np.asarray(out)
    assert out.shape == (k + 2 + len(spec.counters), n)   # five since PR 36
    assert out[k + 1, 0] == 0                   # no NaN reached the logits
    assert (out[:2, 0] == -1).all() and (out[2:k, 0] >= 0).all()
    state = np.asarray(pools[0].arrays[0])
    assert np.isfinite(state[1]).all() and np.abs(state[1]).max() > 0
    assert np.isnan(state[0]).all() and np.isnan(state[2]).all()
