"""What PR 37 made the program say of its own decisions: why admission
ended in a step (`ScheduledBatch.held_by`, counted by the engine in
`serving_admission_holds_total`), what the schedule, the prefill and the
decode spans carry in BOTH sinks (the profiler's annotation and the
in-process table), and how the calls of the expert layer count their
loads against twice and four times the uniform one."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import obs
from paddle_tpu.distributed.moe import held_experts_mlp, load_capacity
from paddle_tpu.inference.serving import (EngineConfig, LLMEngine,
                                          SamplingParams)
from paddle_tpu.inference.serving.paged_cache import (CacheExhausted,
                                                      PagedKVCache)
from paddle_tpu.inference.serving.scheduler import (HOLD_REASONS, Request,
                                                    Scheduler,
                                                    SchedulerConfig)
from paddle_tpu.models import pangu_moe as pm
from paddle_tpu.models import qwen3_next as qn
from test_span_catalog import _session   # a profiler session's host events


def _request(name, tokens, max_tokens=4):
    return Request(request_id=name, prompt_ids=np.ones(tokens, np.int32),
                   params=SamplingParams(max_tokens=max_tokens))


def _windowed_cache(window_blocks):
    # blocks of 8 with a window of 12: a prompt of 20 holds 2 window blocks
    return PagedKVCache(4, (2, 4), 16, 8, window=12,
                        layer_caches=("window", "rows", "window", "rows"),
                        num_window_blocks=window_blocks)


def _refusing(cache):
    def allocate(seq_id, num_tokens):
        raise CacheExhausted(seq_id, 1, 0, cache.num_blocks)
    cache.allocate = allocate
    return cache


#: reason -> (cache, scheduler settings, prompt lengths in arrival order,
#: requests admitted in the first step)
EXITS = {
    "none": (lambda: PagedKVCache(1, (2, 4), 16, 4), {}, [3, 3], 2),
    "rows": (lambda: PagedKVCache(1, (2, 4), 16, 4),
             {"max_num_seqs": 1}, [3, 3], 1),
    "budget": (lambda: PagedKVCache(1, (2, 4), 16, 4),
               {"max_prefill_tokens": 8}, [5, 5], 1),
    "watermark": (lambda: PagedKVCache(1, (2, 4), 8, 4),
                  {"cache_high_watermark": 0.45}, [7, 7], 1),
    "window": (lambda: _windowed_cache(3), {}, [20, 20], 1),
    # the watermark and `add` keep a request the pool cannot hold from
    # ever reaching `allocate`: the exit is a guard, driven here by a pool
    # that says no (admission never preempts)
    "blocks": (lambda: _refusing(PagedKVCache(1, (2, 4), 16, 4)), {}, [3],
               0),
}


@pytest.mark.parametrize("reason", HOLD_REASONS)
def test_the_schedule_says_which_exit_ended_admission(reason):
    make_cache, settings, prompts, admitted = EXITS[reason]
    sched = Scheduler(SchedulerConfig(**{"max_num_seqs": 4, **settings}),
                      make_cache())
    for i, tokens in enumerate(prompts):
        sched.add(_request(f"r{i}", tokens))
    batch = sched.schedule()
    assert batch.held_by == reason
    assert len(batch.prefill) == admitted and batch.chunked == 0
    assert sched.num_waiting() == len(prompts) - admitted


def test_a_request_admitted_to_ride_the_scan_is_counted_chunked():
    sched = Scheduler(SchedulerConfig(max_num_seqs=4, decode_chunk_size=8,
                                      prefill_chunk_threshold=4),
                      PagedKVCache(1, (2, 4), 16, 4))
    sched.add(_request("long", 9))
    sched.add(_request("short", 3))
    batch = sched.schedule()
    assert (batch.chunked, len(batch.prefill), len(batch.decode)) == (1, 1, 1)
    assert batch.held_by == "none"


# --------------------------------------------------- the engine's spans
def test_the_spans_stats_reach_both_sinks(tmp_path):
    """An expert family, three prompts against a budget of 8 tokens: the
    schedule's decision, the prefill's whole count list with `moe_shape`,
    and the chunk's row width are on the profiler's annotation AND in the
    in-process table, the same values in both."""
    paddle.seed(0)
    cfg = pm.PanguMoEConfig(
        vocab_size=64, hidden_size=32, num_hidden_layers=3,
        first_k_dense_replace=1, num_attention_heads=2, q_lora_rank=16,
        kv_lora_rank=8, qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
        intermediate_size=32, moe_intermediate_size=16, n_routed_experts=8,
        num_experts_per_tok=2, max_seq_len=32, held_experts=(2, 4))
    eng = LLMEngine.from_model(pm.PanguMoE(cfg), EngineConfig(
        block_size=8, num_blocks=16, max_num_seqs=4, max_prefill_tokens=8))
    assert eng.spec.expert_shape == (4, 32, 16)

    def work():
        for i in range(3):
            eng.add_request(np.arange(1, 6, dtype=np.int32) + i,
                            SamplingParams(max_tokens=12),
                            request_id=f"r{i}")
        eng.step()              # one prompt fits the budget, two wait
        eng.step()              # the second, beside the first's chunk

    obs.trace.enable()
    try:
        annotated = [(name, stats) for name, _, _, stats
                     in _session(tmp_path, work)]
        table = [(e.name, e.args) for e in obs.trace.events()
                 if e.name.startswith("serving.")]
    finally:
        obs.trace.disable()
    for name in ("serving.schedule", "serving.prefill", "serving.decode"):
        ours = [stats for n, stats in annotated if n == name]
        assert ours and ours == [args for n, args in table if n == name]
    first, second = (s for n, s in annotated if n == "serving.schedule")
    assert first == {"prefill": 1, "prefill_tokens": 5, "chunked": 0,
                     "decode": 0, "waiting": 2, "preempted": 0,
                     "free_blocks": 15, "held_by": "budget"}
    assert (second["held_by"], second["waiting"], second["decode"]) == \
        ("budget", 1, 1)
    prefill = next(s for n, s in annotated if n == "serving.prefill")
    assert {"request_id", "tokens", "moe_shape", *pm.COUNTERS} == set(prefill)
    assert prefill["moe_shape"] == "4x32x16"
    # 2 expert layers, one call each for a prompt of 5 tokens; 10 pairs of
    # 8 experts: twice the uniform load and four times it are one tile
    assert prefill["moe_layer_calls"] == prefill["moe_fit_4x"] == 2
    assert 0 < prefill["moe_max_load"] <= 5 and prefill["moe_experts_hit"] > 0
    (decode,) = (s for n, s in annotated if n == "serving.decode")
    assert (decode["rows"], decode["feeding_rows"], decode["num_seqs"]) \
        == (4, 0, 1)
    assert "moe_shape" not in decode     # no reader finds a chunk's by it
    assert decode["moe_layer_calls"] == 2 * decode["chunk"]
    # the counters: the three new sums, and the holds by reason (the
    # engine counts what each batch says; nothing else keeps a count)
    assert eng.stats.moe_layer_calls == 2 + 2 + decode["moe_layer_calls"]
    assert eng.stats.as_dict()["moe_fit_4x"] == eng.stats.moe_fit_4x > 0
    assert [eng.stats.admission_holds(r) for r in HOLD_REASONS] == \
        [0, 0, 2, 0, 0, 0]


def test_a_family_without_expert_layers_carries_no_expert_stat():
    from paddle_tpu.models.gpt import GPT, GPTConfig
    paddle.seed(0)
    model = GPT(GPTConfig(vocab_size=89, hidden_size=32, num_layers=2,
                          num_heads=4, max_seq_len=24))
    model.eval()
    eng = LLMEngine.from_model(model, EngineConfig(
        block_size=4, num_blocks=16, max_num_seqs=4))
    assert eng.spec.expert_shape == () and eng.spec.counters == ()
    obs.trace.enable()
    try:
        eng.add_request(np.arange(1, 6, dtype=np.int32),
                        SamplingParams(max_tokens=10))
        eng.run(max_steps=20)
        spans = {e.name: e.args for e in obs.trace.events()}
    finally:
        obs.trace.disable()
    assert set(spans["serving.prefill"]) == {"request_id", "tokens"}
    assert not [k for k in spans["serving.decode"] if k.startswith("moe_")]
    assert spans["serving.decode"]["rows"] == 4
    # a quotient of two counters, taken when asked for (the gauge went)
    assert eng.stats.host_syncs_per_token() == \
        eng.stats.host_syncs("decode") / eng.stats.generated_tokens
    assert not hasattr(eng.stats, "padding_waste")


# ------------------------------------------ the expert layer's new counts
def _numpy_counts(x, router, held, top_k, live, block):
    """(calls, fit_2x, fit_4x, max load) of `held_experts_mlp` over blocks
    of `block` tokens, counted in NumPy: softmax scores, the top_k picks,
    the loads of the held experts in each block."""
    first, count = held
    picks = np.argsort(-(np.asarray(x, np.float64) @ np.asarray(router)),
                       axis=-1, kind="stable")[:, :top_k]
    calls = fit2 = fit4 = most = 0
    for at in range(0, len(x), block):
        rows = picks[at:at + block][live[at:at + block]]
        load = np.bincount(rows.reshape(-1), minlength=router.shape[1])[
            first:first + count]
        pairs = block * top_k           # the block as the program pads it
        calls += 1
        fit2 += load.max() <= load_capacity(2, pairs, router.shape[1])
        fit4 += load.max() <= load_capacity(4, pairs, router.shape[1])
        most = max(most, load.max())
    return calls, fit2, fit4, most


@pytest.mark.parametrize("routing", ["flat", "crowded"])
def test_the_calls_that_fit_twice_and_four_times_the_uniform_load(
        routing, monkeypatch):
    """200 tokens in blocks of 64 (a padded tail of 8), top-2 of 16
    experts, 8 held: 128 pairs a block, a uniform load of 8, so twice it is
    one sublane tile of 16 rows and four times it 32. A flat router keeps
    every block under 32 and most under 16; one that crowds half the
    tokens on expert 3 passes both. Counted through `map_token_blocks`
    against a NumPy count of the same routing."""
    rng = np.random.default_rng(37)
    x = rng.normal(size=(200, 32)).astype(np.float32)
    router = rng.normal(size=(32, 16)).astype(np.float32) * 0.05
    if routing == "crowded":
        x[::2, 0], router[0, 3] = 6.0, 4.0      # every second token
    w = [jnp.asarray(rng.normal(size=s) * 0.05, jnp.float32)
         for s in ((8, 32, 16), (8, 32, 16), (8, 16, 32))]
    monkeypatch.setattr(qn, "MOE_TOKEN_BLOCK", 64)
    assert (load_capacity(2, 128, 16), load_capacity(4, 128, 16)) == (16, 32)

    def expert_tokens(flat, on):
        return held_experts_mlp(flat, jnp.asarray(router), *w, (2, 8), 2,
                                1.0, on, scoring="softmax")

    _, counts = jax.jit(lambda h: qn.map_token_blocks(expert_tokens, h))(
        jnp.asarray(x))
    counts = dict(zip(pm.COUNTERS, np.asarray(counts)))
    calls, fit2, fit4, most = _numpy_counts(
        x, router, (2, 8), 2, np.ones(200, bool), 64)
    assert (counts["moe_layer_calls"], counts["moe_fit_2x"],
            counts["moe_fit_4x"], counts["moe_max_load"]) == \
        (calls, fit2, fit4, most) and calls == 4
    if routing == "flat":
        assert fit4 == 4 and most <= 32
    else:       # 32 of a block's 64 tokens on one expert; the tail's 4 fit
        assert fit2 == 1 and most >= 32
    # the tail's padding is switched off and loads nothing: a whole call
    # of 8 live tokens fits any capacity
    _, tail = expert_tokens(jnp.asarray(np.pad(x[192:], ((0, 56), (0, 0)))),
                            jnp.arange(64) < 8)
    tail = dict(zip(pm.COUNTERS, np.asarray(tail)))
    assert tail["moe_layer_calls"] == tail["moe_fit_2x"] == 1
    assert tail["moe_max_load"] <= 8
