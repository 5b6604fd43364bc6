"""Ling-3.0-flash at a small size on the CPU (float32): Kimi Delta
Attention's three forms (the recurrence as written, the chunks of the
prefill, the in-place decode kernel under `interpret=True`) held equal, the
group-limited biased router and the SwiGLU clamp against plain loops, the
shares of four ranks, the family against the plain reference, the cache
that keeps latent rows beside state slots, and the family through
`LLMEngine`."""
import functools
import hashlib
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
from paddle_tpu.inference.serving.attention import paged_decode_step
from paddle_tpu.inference.serving.paged_cache import (PagedKVCache,
                                                      SeqState)
from paddle_tpu.models import ling_flash as lf
from paddle_tpu.models import pangu_moe, qwen3_next
from paddle_tpu.models.generation import extract_params
from paddle_tpu.ops.pallas import kda_step as kk

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "benchmarks"))
from lib import reference_ling_flash as ref  # noqa: E402

#: four layers standing for published layers 0, 2, 3, 5 of a period of 3:
#: KDA (dense MLP), MLA, KDA, MLA; 16 experts in 4 groups
SMALL = dict(vocab_size=96, hidden_size=32, num_hidden_layers=4,
             published_layers=(0, 2, 3, 5), layer_group_size=3,
             first_k_dense_replace=1, num_attention_heads=2, head_dim=8,
             kv_lora_rank=8, qk_nope_head_dim=8, qk_rope_head_dim=4,
             v_head_dim=8, intermediate_size=48, moe_intermediate_size=16,
             moe_shared_expert_intermediate_size=16, num_experts=16,
             num_experts_per_tok=4, n_group=4, topk_group=2,
             max_seq_len=64)
#: float32 on both sides: what a wrong mask, slot, decay or position would
#: exceed by orders of magnitude
LIMIT = 1e-5


def _family(held=None, seed=5, **over):
    cfg = lf.LingFlashConfig(**{**SMALL, **over}, held_experts=held)
    paddle.seed(seed)
    model = lf.LingFlash(cfg)
    return model, cfg, extract_params(model)


@functools.lru_cache(maxsize=None)
def _reference_fn(cfg):
    return jax.jit(functools.partial(ref.logits, size=ref.sizes(cfg)))


def _reference_logits(params, ids, cfg):
    padded = np.zeros((cfg.max_seq_len,), np.int32)
    padded[:len(ids)] = ids
    return np.asarray(_reference_fn(cfg)(params, jnp.asarray(padded)))[
        :len(ids)]


# ------------------------------------------------- Kimi Delta Attention
def _kda_inputs(rng, T, H=3, dk=8, dv=6, g=None):
    q = rng.normal(size=(T, H, dk))
    k = rng.normal(size=(T, H, dk))
    q, k = (x / np.linalg.norm(x, axis=-1, keepdims=True) for x in (q, k))
    g = -rng.uniform(0, 5, (T, H, dk)) if g is None \
        else np.full((T, H, dk), g)
    return tuple(jnp.asarray(x, jnp.float32) for x in (
        q / np.sqrt(dk), k, rng.normal(size=(T, H, dv)), g,
        rng.uniform(size=(T, H))))


@pytest.mark.parametrize("length, g", [(1, None), (15, None), (16, None),
                                       (37, None), (256, -5.0),
                                       (300, -5.0)])
def test_chunked_kda_equals_the_recurrence(length, g):
    """Every channel at the lower bound -5 over T >= 256 is the case the
    factorised decay has to survive: a chunk's decay spans exp(-80), and
    factored about its start the small factor falls among float32's
    subnormals (1e-4 off here); about its middle, exp(+-40)."""
    rng = np.random.default_rng(length)
    q, k, v, gates, beta = _kda_inputs(rng, length, g=g)
    state = jnp.asarray(rng.normal(size=(3, 8, 6)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        o1, s1 = lf.kda_recurrence(q, k, v, gates, beta, state)
        o2, s2 = lf.kda_chunked(q, k, v, gates, beta, state)
    assert np.isfinite(np.asarray(o2)).all()
    np.testing.assert_allclose(np.asarray(o2), np.asarray(o1), atol=2e-5,
                               rtol=1e-4)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s1), atol=2e-5,
                               rtol=1e-4)


def test_a_decay_equal_over_a_heads_channels_is_gated_deltanet():
    rng = np.random.default_rng(3)
    q, k, v, _, beta = _kda_inputs(rng, 29)
    per_head = jnp.asarray(-rng.uniform(0, 3, (29, 3)), jnp.float32)
    gates = jnp.broadcast_to(per_head[..., None], (29, 3, 8))
    state = jnp.asarray(rng.normal(size=(3, 8, 6)), jnp.float32)
    o1, s1 = lf.kda_recurrence(q, k, v, gates, beta, state)
    o2, s2 = qwen3_next.gdn_recurrence(q, k, v, per_head, beta, state)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-6)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=1e-6)


def _step_inputs(rng, n=5, heads=4, dk=16, taps=4, slots=7,
                 dtype=jnp.float32):
    return dict(
        u=jnp.asarray(rng.normal(size=(n, heads, 3 * dk)), dtype),
        g=jnp.asarray(-rng.uniform(0, 5, (n, heads, dk)), jnp.float32),
        beta=jnp.asarray(rng.uniform(size=(n, heads)), jnp.float32),
        conv_w=jnp.asarray(rng.normal(size=(taps, heads, 3 * dk)) * 0.5,
                           dtype),
        states=jnp.asarray(rng.normal(size=(slots, heads, dk, dk)),
                           jnp.float32),
        history=jnp.asarray(
            rng.normal(size=(slots, heads, (taps - 1) * 3 * dk)), dtype))


@pytest.mark.parametrize("live, fresh", [
    ([0, 1, 1, 0, 1], [0, 0, 1, 0, 0]),     # dead rows before and between
    ([0, 0, 0, 0, 0], [0, 0, 0, 0, 0]),     # none live: nothing moves
    ([1, 1, 1, 1, 1], [1, 0, 0, 0, 1])])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_decode_kernel_equals_the_composed_step(live, fresh, dtype):
    """In place under `interpret=True`: the live rows' slots hold what the
    gather-step-scatter composition writes, every other slot is
    untouched to the bit."""
    a = _step_inputs(np.random.default_rng(1), dtype=dtype)
    slots = jnp.asarray([3, 0, 5, 6, 1], jnp.int32)
    live, fresh = jnp.asarray(live, bool), jnp.asarray(fresh, bool)
    want = kk.kda_step_reference(**a, slots=slots, live=live, fresh=fresh,
                                 scale=0.25)
    before = [np.asarray(a["states"]), np.asarray(a["history"])]
    got = kk.kda_step(**{**a, "states": jnp.array(a["states"]),
                         "history": jnp.array(a["history"])},
                      slots=slots, live=live, fresh=fresh, scale=0.25,
                      interpret=True)
    for x, y in zip(got, want):
        np.testing.assert_allclose(np.asarray(x, np.float32),
                                   np.asarray(y, np.float32), atol=1e-6)
    untouched = sorted(set(range(7)) - set(np.asarray(slots)[np.asarray(
        live)].tolist()))
    for new, old in zip(got[1:], before):
        np.testing.assert_array_equal(np.asarray(new)[untouched],
                                      old[untouched])


def test_decode_steps_continue_the_chunked_prefill():
    """A prefill's final state, then steps of the kernel, equal the
    recurrence over the whole sequence (q, k, v through the conv)."""
    rng = np.random.default_rng(4)
    heads, dk, taps, T, more = 2, 8, 4, 19, 5
    u = jnp.asarray(rng.normal(size=(T + more, heads, 3 * dk)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(taps, heads, 3 * dk)) * 0.5,
                    jnp.float32)
    g = jnp.asarray(-rng.uniform(0, 5, (T + more, heads, dk)), jnp.float32)
    beta = jnp.asarray(rng.uniform(size=(T + more, heads)), jnp.float32)
    padded = jnp.pad(u, ((taps - 1, 0), (0, 0), (0, 0)))
    window = [padded[j:j + T + more] for j in range(taps)]
    q, k, v = kk.conv_heads(window, w, dk, 1 / np.sqrt(dk), jnp.float32)
    zero = jnp.zeros((heads, dk, dk), jnp.float32)
    want, _ = lf.kda_recurrence(q, k, v, g, beta, zero)
    _, state = lf.kda_chunked(q[:T], k[:T], v[:T], g[:T], beta[:T], zero)
    history = padded[T:T + taps - 1].transpose(1, 0, 2).reshape(1, heads, -1)
    states = jnp.zeros((3, heads, dk, dk), jnp.float32).at[2].set(state)
    hist = jnp.zeros((3, heads, history.shape[-1]), jnp.float32) \
        .at[2].set(history[0])
    one = jnp.ones((1,), bool)
    for t in range(T, T + more):
        o, states, hist = kk.kda_step(
            u[t][None], g[t][None], beta[t][None], w, states, hist,
            jnp.asarray([2], jnp.int32), one, ~one, scale=1 / np.sqrt(dk),
            interpret=True)
        np.testing.assert_allclose(np.asarray(o[0]), np.asarray(want[t]),
                                   atol=2e-5)


# ------------------------------------------------------------ the router
def _expert_weights(rng, n, h=32, f=16):
    return (jnp.asarray(rng.normal(size=(h, n)), jnp.float32),
            *(jnp.asarray(rng.normal(size=s) * 0.2, jnp.float32)
              for s in ((n, h, f), (n, h, f), (n, f, h))))


def _plain_route(scores, bias, n_group, topk_group, top_k):
    """Token by token, as the published router reads."""
    out = []
    for s, c in zip(scores, scores + bias):
        groups = c.reshape(n_group, -1)
        best = np.argsort(-np.sort(groups, 1)[:, -2:].sum(1))[:topk_group]
        allowed = np.concatenate([np.arange(g * groups.shape[1],
                                            (g + 1) * groups.shape[1])
                                  for g in sorted(best)])
        out.append(sorted(allowed[np.argsort(-c[allowed])[:top_k]]))
    return np.asarray(out)


def test_the_grouped_biased_route_equals_a_per_token_loop():
    rng = np.random.default_rng(8)
    scores = rng.uniform(size=(40, 16)).astype(np.float32)
    bias = (rng.normal(size=16) * 0.1).astype(np.float32)
    want = _plain_route(scores, bias, 4, 2, 4)
    got = ref.route(jnp.asarray(scores), jnp.asarray(bias), {
        "n_group": 4, "topk_group": 2, "num_experts_per_tok": 4})
    np.testing.assert_array_equal(np.sort(np.asarray(got), 1), want)
    unbiased = _plain_route(scores, np.zeros(16, np.float32), 4, 2, 4)
    assert (unbiased != want).any(axis=1).sum() >= 4   # bias changes choices


def test_the_seeded_bias_changes_choices_at_the_published_widths():
    """The cell's router as the builder seeds it (W_r N(0, 0.02) over 2560
    x 512, the bias N(0, 0.1): `init_value`) on unit-RMS hidden states:
    the correction changes about two thirds of the picks (the 8 best of
    512 sigmoid scores lie within about 0.05 of each other), not all."""
    rng = np.random.default_rng(12)
    assert lf.init_value("layers.1.moe.router.bias") == ("normal", 0.1)
    x = rng.normal(size=(256, 2560)).astype(np.float32)
    scores = 1 / (1 + np.exp(-(x @ (rng.normal(size=(2560, 512)) * 0.02))))
    bias = (rng.normal(size=512) * 0.1).astype(np.float32)
    size = {"n_group": 8, "topk_group": 4, "num_experts_per_tok": 8}
    chosen = [np.sort(np.asarray(ref.route(jnp.asarray(scores, jnp.float32),
                                           jnp.asarray(b), size)), 1)
              for b in (bias, np.zeros_like(bias))]
    kept = np.mean([len(set(a) & set(b)) / 8 for a, b in zip(*chosen)])
    assert 0.1 < kept < 0.6


def _flat_layer_parts(rng, n=16, h=32, f=16, limit=0.0):
    router, wg, wu, wd = _expert_weights(rng, n, h, f)
    bias = jnp.asarray(rng.normal(size=n) * 0.5, jnp.float32)
    shared = [jnp.asarray(rng.normal(size=s) * 0.2, jnp.float32)
              for s in ((h, f), (h, f), (f, h))]
    p = {"router.weight": router, "router.bias": bias,
         "experts.gate.weight": wg, "experts.up.weight": wu,
         "experts.down.weight": wd, "shared.gate.weight": shared[0],
         "shared.up.weight": shared[1], "shared.down.weight": shared[2]}
    size = {"held": (0, n), "n_group": 4, "topk_group": 2,
            "num_experts_per_tok": 4, "routed_scaling_factor": 2.5,
            "expert_swiglu_limits": (limit,),
            "shared_swiglu_limits": (2 * limit,)}
    return p, size


@pytest.mark.parametrize("limit", [0.0, 0.3])
def test_the_four_ranks_and_the_shared_expert_once_are_the_uncut_layer(
        limit):
    """16 experts over 4 ranks with the grouped biased router: the routed
    parts of all ranks plus the shared expert counted once equal the
    uncut reference layer, every (token, expert) pair routed once."""
    rng = np.random.default_rng(2)
    p, size = _flat_layer_parts(rng, limit=limit)
    x = jnp.asarray(rng.normal(size=(40, 32)), jnp.float32)
    parts, pairs = [], 0
    for rank in range(4):
        held = slice(4 * rank, 4 * rank + 4)
        routed, counts = held_experts_mlp(
            x, p["router.weight"], p["experts.gate.weight"][held],
            p["experts.up.weight"][held], p["experts.down.weight"][held],
            (4 * rank, 4), 4, 2.5, bias=p["router.bias"], groups=(4, 2),
            limit=limit)
        parts.append(np.asarray(routed))
        pairs += int(counts[0])
    assert pairs == 40 * 4
    with jax.default_matmul_precision("highest"):
        whole, ref_pairs = ref._experts(p, "", x, size, 0, None)
        once = ref._swiglu(x, p["shared.gate.weight"], p["shared.up.weight"],
                           p["shared.down.weight"], 2 * limit, None)
    assert int(ref_pairs) == pairs
    np.testing.assert_allclose(sum(parts) + np.asarray(once),
                               np.asarray(whole), atol=1e-5, rtol=0)


def test_the_swiglu_clamp_bounds_the_gate_above_and_up_on_both_sides():
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.normal(size=(6, 8)) * 4, jnp.float32)
    wg, wu = (jnp.asarray(rng.normal(size=(8, 5)), jnp.float32)
              for _ in range(2))
    wd = jnp.eye(5, dtype=jnp.float32)
    got = np.asarray(lf.swiglu(x, wg, wu, wd, 1.5))
    gate, up = np.asarray(x @ wg), np.asarray(x @ wu)
    assert (gate > 1.5).any() and (np.abs(up) > 1.5).any()
    g = np.minimum(gate, 1.5)
    want = g / (1 + np.exp(-g)) * np.clip(up, -1.5, 1.5)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert not np.allclose(np.asarray(lf.swiglu(x, wg, wu, wd)), want)


# sha1[:16] of the jaxpr of cell 3's and cell 6's expert layers at the toy
# sizes below, taken on the tree before `held_experts_mlp` had its bias,
# groups and limit options (jax 0.9.0): with the options off the traced
# program is the same
EXPERT_LAYER_JAXPR = {"pangu": "ea8a5f98d89cf5ab", "qwen3": "801f6d3a1f56aa35"}


@pytest.mark.skipif(jax.__version__ != "0.9.0",
                    reason="the recorded text is jax 0.9.0's")
def test_router_options_off_leave_cell_3s_and_6s_layers_as_they_were():
    pc = pangu_moe.PanguMoEConfig(
        vocab_size=64, hidden_size=32, num_hidden_layers=2,
        first_k_dense_replace=1, num_attention_heads=2, q_lora_rank=16,
        kv_lora_rank=8, qk_nope_head_dim=8, qk_rope_head_dim=4,
        v_head_dim=8, intermediate_size=32, moe_intermediate_size=16,
        n_routed_experts=8, num_experts_per_tok=2, max_seq_len=32,
        held_experts=(2, 4))
    qc = qwen3_next.Qwen3NextConfig(
        vocab_size=64, hidden_size=32, num_hidden_layers=4,
        num_attention_heads=4, num_key_value_heads=2, head_dim=8,
        linear_num_key_heads=2, linear_num_value_heads=2,
        linear_key_head_dim=8, linear_value_head_dim=8,
        moe_intermediate_size=16, shared_expert_intermediate_size=16,
        num_experts=8, num_experts_per_tok=2, max_seq_len=32,
        held_experts=(0, 4))

    def zeros(mod, cfg):
        return {n: jnp.zeros(s, d)
                for n, (s, d) in mod.param_shapes(cfg).items()}

    x, live = jnp.zeros((6, 32), jnp.float32), jnp.ones((6,), bool)
    found = {
        "pangu": jax.make_jaxpr(lambda p, x, on: pangu_moe.layer_tail(
            p, 1, x, jnp.zeros((6, 16)), pc, on))(zeros(pangu_moe, pc), x,
                                                  live),
        "qwen3": jax.make_jaxpr(lambda p, x, on: qwen3_next.moe_block(
            p, 1, x, qc, on))(zeros(qwen3_next, qc), x, live)}
    assert {k: hashlib.sha1(str(v).encode()).hexdigest()[:16]
            for k, v in found.items()} == EXPERT_LAYER_JAXPR


# ------------------------------------------------------------ the family
def test_the_layers_are_the_published_ones_they_stand_for():
    _, cfg, params = _family()
    assert [cfg.is_mla(i) for i in range(4)] == [False, True, False, True]
    spec = lf.serving_spec(cfg)
    assert spec.layer_caches == ("state", "rows", "state", "rows")
    assert spec.cache_shape == (12,) and spec.pools_per_layer == 1
    assert "layers.0.mlp.gate.weight" in params
    assert "layers.1.moe.router.bias" in params
    assert float(jnp.abs(params["layers.1.moe.router.bias"]).max()) > 0


@pytest.mark.parametrize("held", [None, (4, 8)])
def test_forward_matches_the_plain_reference(held):
    model, cfg, params = _family(held)
    ids = np.random.default_rng(1).integers(0, 96, (37,)).astype(np.int32)
    got = np.asarray(lf.forward(params, jnp.asarray(ids[None]), cfg))[0]
    want = _reference_logits(params, ids, cfg)
    assert np.abs(got - want).max() < LIMIT
    assert np.abs(want).max() > 0.1


def _prefill_then_decode(cfg, params, prompt, steps=16):
    """[1 + steps, V] logits of the serving path: the prefill program,
    then greedy paged decode steps through the latent blocks and the state
    slot (slot 1: another sequence holds slot 0)."""
    spec = lf.serving_spec(cfg)
    eng = LLMEngine(params, spec, EngineConfig(block_size=8, num_blocks=32,
                                               max_num_seqs=4))
    cache, n = eng.cache, len(prompt)
    cache.allocate("other", 3)
    cache.allocate("s", n)
    logits, dense, counts = spec.prefill(params, jnp.asarray(prompt[None]))
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
    return np.stack(rows), np.asarray(ids, np.int32), counts


def test_prefill_then_16_paged_decode_steps_match_the_reference_forward():
    _, cfg, params = _family((0, 8))
    prompt = np.random.default_rng(6).integers(0, 96, (21,)).astype(np.int32)
    rows, ids, counts = _prefill_then_decode(cfg, params, prompt)
    want = _reference_logits(params, ids, cfg)[20:37]
    assert np.abs(rows - want).max() < LIMIT
    named = dict(zip(lf.COUNTERS, np.asarray(counts).tolist()))
    assert named["kda_prefill_chunks"] == 2 * 2       # 2 chunks, 2 layers
    assert named["kda_state_row_trips"] == 0


# --------------------------------------------- latent rows beside state
def _cache(slots=3, blocks=16, **kw):
    return PagedKVCache(
        4, (12,), blocks, 8, layer_caches=("state", "rows", "state", "rows"),
        state_shapes=(((2, 8, 8), "float32"), ((2, 72), "float32")),
        num_state_slots=slots, **kw)


def test_one_manager_owns_latent_blocks_and_state_slots():
    pc = _cache()
    assert pc.layout == "hybrid"
    assert isinstance(pc.pools[0], SeqState)
    assert pc.pools[1].shape == (16, 8, 12)           # ONE pool a layer
    pc.allocate("a", 11)
    pc.allocate("b", 3)
    assert len(pc.block_table("a")) == 2 and pc.state_slot("a") == 0
    assert pc.state_slot("b") == 1
    assert pc.physical_bytes_per_token == 2 * 12 * 4
    assert pc.state_bytes_per_seq == 2 * (2 * 64 + 144) * 4
    pc.free("a")
    pc.allocate("c", 9)
    assert pc.state_slot("c") == 0                     # the slot came back
    pc.free("b")
    pc.free("c")
    assert not any(pc.check_integrity().values())
    assert pc.num_free() == 16


def test_write_prefill_puts_latent_rows_in_blocks_and_the_state_in_slots():
    pc = _cache()
    rng = np.random.default_rng(0)
    pc.allocate("other", 2)
    pc.allocate("s", 11)
    slot = pc.state_slot("s")
    dense = (SeqState(jnp.asarray(rng.normal(size=(2, 2, 8, 8)), jnp.float32),
                      jnp.asarray(rng.normal(size=(2, 2, 72)), jnp.float32)),
             jnp.asarray(rng.normal(size=(2, 16, 12)), jnp.float32)) * 2
    pc.write_prefill("s", dense, 11, batch_index=1)
    for layer in (0, 2):
        for got, new in zip(pc.pools[layer], dense[layer]):
            np.testing.assert_array_equal(got[slot], new[1])
            np.testing.assert_array_equal(got[0], 0)
    rows = np.asarray(pc.pools[1])[pc.block_table("s")].reshape(-1, 12)
    np.testing.assert_array_equal(rows[:11], np.asarray(dense[1])[1, :11])


@pytest.mark.parametrize("config, feature", [
    (dict(kv_cache_dtype="int8"), "int8 KV pools"),
    (dict(enable_prefix_cache=True), "prefix cache"),
    (dict(host_tier_blocks=2), "host tier"),
])
def test_what_latent_beside_state_lacks_raises_by_name(config, feature):
    with pytest.raises(NotImplementedError,
                       match=f"hybrid cache layout.*{feature}"):
        _cache(**config)


def test_block_migration_and_latent_window_layers_are_refused():
    pc = _cache()
    pc.allocate("s", 4)
    with pytest.raises(NotImplementedError, match="hybrid cache layout"):
        pc.export_blocks("s")
    with pytest.raises(ValueError, match="window layers need"):
        PagedKVCache(2, (12,), 8, 4, layer_caches=("window", "rows"),
                     window=4, num_window_blocks=4)
    with pytest.raises(ValueError, match="state_shapes"):
        PagedKVCache(2, (12,), 8, 4, layer_caches=("state", "rows"))


# ------------------------------------------------- through the engine
def _serve(model, chunk, prompts, **kw):
    eng = LLMEngine.from_model(model, EngineConfig(
        block_size=8, num_blocks=48, max_num_seqs=4,
        decode_chunk_size=chunk, **kw))
    for i, p in enumerate(prompts):
        eng.add_request(p, SamplingParams(max_tokens=10 + i),
                        request_id=f"r{i}")
    out = eng.run()
    assert not any(eng.cache.check_integrity().values())
    return eng, [out[f"r{i}"].tolist() for i in range(len(prompts))]


def test_the_engine_serves_the_family_as_its_forward_decodes_greedily():
    model, cfg, params = _family((0, 8))
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, 96, (n,)).astype(np.int32)
               for n in (5, 17, 9, 23, 12)]      # five rows over four
    eng, streams = _serve(model, 4, prompts)
    fwd = jax.jit(functools.partial(lf.forward, cfg=cfg))
    for prompt, stream in zip(prompts, streams):
        # teacher-forced over the whole row (padded: causal and recurrent,
        # so one compilation): each token is the forward's greedy choice
        ids = np.zeros((cfg.max_seq_len,), np.int32)
        ids[:len(prompt) + len(stream)] = np.concatenate([prompt, stream])
        best = np.asarray(fwd(params, jnp.asarray(ids[None])))[0].argmax(-1)
        assert stream == best[len(prompt) - 1:len(prompt) - 1
                              + len(stream)].tolist()
    # every decode trip updated its live rows' state in the two KDA layers
    assert eng.stats.kda_state_row_trips == 2 * eng.stats.live_row_trips
    assert eng.stats.kda_prefill_chunks == 2 * sum(
        -(-len(p) // lf.KDA_CHUNK) for p in prompts)


def test_chunked_prefill_rides_the_scan_with_the_same_tokens():
    model, _, _ = _family((0, 8))
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, 96, (n,)).astype(np.int32) for n in (14, 6)]
    _, dense = _serve(model, 4, prompts)
    _, chunked = _serve(model, 4, prompts, prefill_chunk_threshold=8)
    assert dense == chunked
