"""What PR 29 added to the benchmark (CPU, not slow): the new
configuration keeps to the catalog row except where `reduced` says so, its
plain reference agrees with the program at the rehearsal size, the open
loop offers every seed the same instants, and the operations and bytes of
the two new layer metrics are right on hand-made counters."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmarks"
sys.path.insert(0, str(BENCH))

from lib import arrivals, serve_work, traffic  # noqa: E402

MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CONFIG = json.loads(
    (BENCH / "configs" / "openpangu-ultra-moe-ep16.json").read_text())
#: the catalog row's `config` (model-configs guide, architectures.jsonl,
#: source_url = CONFIG["source"]), copied: there is no network here
CATALOG = {
    "attention_bias": False, "first_k_dense_replace": 3,
    "hidden_act": "silu", "hidden_size": 7680, "intermediate_size": 18432,
    "kv_lora_rank": 512, "max_position_embeddings": 131072,
    "model_type": "pangu_ultra_moe", "moe_intermediate_size": 2048,
    "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 61, "num_key_value_heads": 128,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
    "rope_theta": 25600000, "routed_scaling_factor": 2.5,
    "sandwich_norm": True, "tie_word_embeddings": False, "v_head_dim": 128,
    "vocab_size": 153600}
WIDTHS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
          "kv_lora_rank", "q_lora_rank", "qk_nope_head_dim",
          "qk_rope_head_dim", "v_head_dim", "num_experts_per_tok")


def test_the_configuration_is_the_catalog_row_except_what_reduced_lists():
    entry = next(c for c in MANIFEST["configs"]
                 if c["name"] == "openpangu-ultra-moe-ep16")
    differs = sorted(k for k, v in CATALOG.items() if CONFIG.get(k) != v)
    assert differs == sorted(entry["reduced"])
    assert not set(entry["reduced"]) & set(WIDTHS)
    # the published value and the deployment stand beside each reduced key
    assert CONFIG["published"] == {k: CATALOG[k] for k in entry["reduced"]}
    assert set(entry["reduced"]) <= set(CONFIG["deployment"])
    assert CONFIG["deployment"]["expert_parallel"] == {"size": 16, "rank": 0}
    assert CONFIG["n_routed_experts"] * 16 == CATALOG["n_routed_experts"]
    assert CONFIG["vocab_size"] * 8 == CATALOG["vocab_size"]
    assert entry["source"] == CONFIG["source"]
    for key in ("scoring", "rope", "served_dtype", "max_context"):
        assert key in CONFIG["assumed"]


def test_both_new_cells_are_one_chip_and_the_manifest_only_grew():
    cells = {w["name"]: w for w in MANIFEST["workloads"]}
    assert cells["pangu-ep16-serve-closed128"]["chips"] == 1
    assert cells["gpt2m-serve-open-r80"]["chips"] == 1
    assert [w["name"] for w in MANIFEST["workloads"]][:2] == \
        ["gpt2s-train-b24", "gpt2m-serve-closed16"]
    for m in MANIFEST["per_layer"]:
        if m["name"].startswith("ragged_kernel"):
            assert "pangu-ep16-serve-closed128" not in m["workloads"]
    mix = json.loads(
        (BENCH / "traffic" / "closed128-longout.json").read_text())
    assert (mix["clients"], mix["prompt_lens"], mix["max_tokens"]) == \
        (128, [128, 256, 512, 1024], [256, 1024])
    # every row fits its pool share: no row is ever preempted
    cell = json.loads((BENCH / "workloads"
                       / "pangu-ep16-serve-closed128.json").read_text())
    eng = cell["engine"]
    assert eng["num_blocks"] * eng["block_size"] == \
        eng["max_num_seqs"] * CONFIG["assumed"]["max_context"]
    assert max(mix["prompt_lens"]) + mix["max_tokens"][1] <= \
        CONFIG["assumed"]["max_context"]


def test_the_reference_agrees_with_the_program_at_the_rehearsal_size():
    import jax.numpy as jnp
    from lib import pangu_moe, reference_pangu_moe as ref
    from paddle_tpu.models import pangu_moe as family

    cfg = pangu_moe.program_config(CONFIG, rehearse=True)
    assert (cfg.n_routed_experts, cfg.held, cfg.vocab_size) == \
        (8, (0, 2), 256)
    full = pangu_moe.program_config(CONFIG, rehearse=False)
    assert (full.n_routed_experts, full.held, full.dtype, full.max_seq_len,
            full.latent_width) == (256, (0, 16), "bfloat16", 2048, 576)
    params = pangu_moe.seeded_weights(cfg, seed=2**31 + 5)
    assert set(params) == set(family.param_shapes(cfg))
    ids = traffic.prompt(7, 0, 96, cfg.vocab_size)
    got = np.asarray(family.forward(params, jnp.asarray(ids[None]), cfg))[0]
    want, pairs = ref.logits_and_pairs(params, jnp.asarray(ids),
                                       ref.sizes(cfg))
    np.testing.assert_allclose(got, np.asarray(want), atol=2e-5, rtol=0)
    assert abs(got).max() > 0.05 and 0 < int(pairs) <= 96 * 2 * 2
    # rounding every matmul operand to the precision below moves the
    # logits by far more than the program's own float32 does
    low = np.asarray(ref.logits(params, jnp.asarray(ids), ref.sizes(cfg),
                                "float8_e4m3fn"))
    assert np.abs(low - np.asarray(want)).max() > 1e-2
    # N(0, 0.02), norms at 1, the router float32, a function of the seed
    assert float(np.std(np.asarray(params["embed.weight"]))) == \
        pytest.approx(0.02, rel=0.05)
    assert np.all(np.asarray(params["layers.1.norm3.weight"]) == 1)
    assert params["layers.1.moe.router.weight"].dtype == jnp.float32
    again = pangu_moe.seeded_weights(cfg, seed=2**31 + 5)
    other = pangu_moe.seeded_weights(cfg, seed=2**31 + 6)
    assert np.array_equal(params["lm_head.weight"], again["lm_head.weight"])
    assert not np.array_equal(params["lm_head.weight"],
                              other["lm_head.weight"])


def test_the_open_loop_offers_every_seed_the_same_instants():
    mix = json.loads(
        (BENCH / "traffic" / "open-r6.9-mixed.json").read_text())

    def first(stream, n=400):
        it = arrivals.poisson_offsets(mix, stream)
        return [next(it) for _ in range(n)]

    window = first(0)
    assert window == first(0) != first(1)
    assert all(b > a for a, b in zip(window, window[1:]))
    # 6.9 a second: about 276 requests in a window of 40 s, every run
    assert sum(t < 40.0 for t in window) == 276
    assert np.mean(np.diff(window)) == pytest.approx(1 / 6.9, rel=0.1)
    closed = json.loads(
        (BENCH / "traffic" / "closed16-mixed.json").read_text())
    for key in ("prompt_lens", "weights", "max_tokens", "block"):
        assert mix[key] == closed[key]

    # --seed has no part in the instants; it orders the sizes
    def sizes(seed):
        it = traffic.closed_loop_sizes(mix, seed)
        return [next(it) for _ in range(20)]

    assert sizes(1) != sizes(2)         # paired and ordered by the seed
    for part in (0, 1):
        assert sorted(x[part] for x in sizes(1)) == \
            sorted(x[part] for x in sizes(2))


def _work_config():
    return {**{k: CONFIG[k] for k in (
        "hidden_size", "num_attention_heads", "q_lora_rank", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "intermediate_size", "moe_intermediate_size", "n_shared_experts",
        "num_hidden_layers", "first_k_dense_replace", "vocab_size")},
        "routed_experts_scored": 256, "itemsize": 2}


def test_operations_and_bytes_of_the_new_metrics_on_hand_made_counters():
    c = _work_config()
    m = serve_work.matrices(c)
    # the issue's table: parameters by part
    assert m["attention"] == 196_575_232
    assert m["dense_mlp"] == 424_673_280
    assert m["shared_expert"] == m["routed_expert"] == 47_185_920
    assert m["router"] == 1_966_080 and m["head"] == 147_456_000
    fixed = serve_work.per_token_fixed(c)
    assert fixed == 5 * m["attention"] + m["dense_mlp"] \
        + 4 * (m["shared_expert"] + m["router"])
    assert serve_work.attention_width(c) == (1088, 320)
    # one decoded token at 750 of context with two held experts a layer
    flops = serve_work.serve_flops(c, tokens=1, sampled=1, moe_pairs=8,
                                   decode_context=750, prefill_pairs=0)
    assert flops == 2.0 * (fixed + 8 * m["routed_expert"] + m["head"]
                           + 5 * 128 * 750 * 1088)
    # a prefill of 4 tokens: 10 causal pairs, expanded attention
    assert serve_work.serve_flops(c, 4, 1, 0, 0, 10) == 2.0 * (
        4 * fixed + m["head"] + 5 * 128 * 10 * 320)
    # one trip, every held expert of every layer hit, 128 rows at 750
    need = serve_work.decode_trip_bytes(c, trips=1, experts_hit=64,
                                        context_tokens=128 * 750)
    assert need == 2 * (fixed + m["head"]) + 2 * 4 * m["router"] \
        + 64 * 2 * m["routed_expert"] + 128 * 750 * 1152 * 5
    # the 9.85 GB held, less the embedding table (gathered, not streamed)
    assert 9.5e9 < 2 * (fixed + m["head"]) + 64 * 94_371_840 < 9.6e9
    # an expert nobody reached is not read; a trip more reads the rest again
    assert serve_work.decode_trip_bytes(c, 1, 63, 0) == \
        serve_work.decode_trip_bytes(c, 1, 64, 0) - 94_371_840
    assert serve_work.decode_trip_bytes(c, 2, 0, 0) == \
        2 * serve_work.decode_trip_bytes(c, 1, 0, 0)


def test_the_new_layer_metrics_read_a_record_and_leave_out_what_is_missing():
    from run import load_module                 # benchmarks/run.py
    mfu = load_module("layer_metrics", "serve_mfu")
    hbm = load_module("layer_metrics", "decode_trip_hbm_pct")
    c = _work_config()
    record = {"device": {"kind": "TPU v5 lite", "count": 1}, "facts": {
        "window_seconds": 2.0, "moe_pairs": 800, "work": {
            "config": c, "positions_through_layers": 100,
            "sampled_positions": 100, "decode_context_tokens": 75_000,
            "prefill_pairs": 0}}}
    want = serve_work.serve_flops(c, 100, 100, 800, 75_000, 0)
    assert mfu.compute(record, None) == \
        pytest.approx(100 * want / 2.0 / 197e12)
    # a record of another runner, a run without a trace: left out
    assert mfu.compute({"facts": {}}, None) is None
    assert hbm.compute({"facts": {}}, None) is None
    assert hbm.compute(dict(record, trace_dir=None), None) is None
