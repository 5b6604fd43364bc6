"""What PR 33 added to the benchmark (CPU, not slow): the hybrid family's
configuration keeps to the catalog row except where `reduced` says so and
its parameters add up, the rehearsal of both new cells passes while the
lower-precision reading fails the serving cell's limits, every metric that
lists a new cell has a file, and the operations and bytes of the new layer
metrics are right on hand-made counters."""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmarks"
sys.path.insert(0, str(BENCH))

from lib import serve_work_hybrid as work  # noqa: E402

MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
SERVE, TRAIN = "qwen3next-ep4-serve-closed128", "gpt2m-train-b8"
CONFIG = json.loads(
    (BENCH / "configs" / "qwen3-next-80b-a3b-ep4.json").read_text())
#: the catalog row's `config` (model-configs guide, architectures.jsonl,
#: source_url = CONFIG["source"]), copied: there is no network here
CATALOG = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512, "num_experts_per_tok": 10,
    "num_hidden_layers": 48, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}
WIDTHS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
          "shared_expert_intermediate_size", "head_dim",
          "linear_key_head_dim", "linear_value_head_dim",
          "num_experts_per_tok", "num_attention_heads",
          "num_key_value_heads", "linear_num_key_heads",
          "linear_num_value_heads", "linear_conv_kernel_dim")


def run_bench(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)          # one CPU device, as on one chip
    return subprocess.run([sys.executable, str(BENCH / "run.py"), *args],
                          cwd=ROOT, env=env, capture_output=True, text=True,
                          timeout=900)


def test_the_configuration_is_the_catalog_row_except_what_reduced_lists():
    entry = next(c for c in MANIFEST["configs"]
                 if c["name"] == "qwen3-next-80b-a3b-ep4")
    differs = sorted(k for k, v in CATALOG.items() if CONFIG.get(k, "-") != v)
    assert differs == sorted(entry["reduced"]) \
        == ["num_experts", "num_hidden_layers", "vocab_size"]
    assert not set(entry["reduced"]) & set(WIDTHS)
    # the published value and the deployment stand beside each reduced key
    assert CONFIG["published"] == {k: CATALOG[k] for k in entry["reduced"]}
    assert all(CONFIG[k] != CONFIG["published"][k] for k in entry["reduced"])
    assert set(entry["reduced"]) <= set(CONFIG["deployment"])
    assert CONFIG["deployment"]["expert_parallel"] == {"size": 4, "rank": 0}
    assert CONFIG["deployment"]["chips_that_share_a_layer"] == 4
    assert CONFIG["num_experts"] * 4 == CATALOG["num_experts"]
    assert CONFIG["vocab_size"] * 4 == CATALOG["vocab_size"]
    # two whole periods of 3 DeltaNet layers and 1 full-attention layer
    assert CONFIG["num_hidden_layers"] == 2 * CONFIG["full_attention_interval"]
    assert entry["source"] == CONFIG["source"]
    for key in ("state_dtype", "served_dtype", "max_context",
                "initializer_range", "qkvz_layout", "a_log"):
        assert key in CONFIG["assumed"]


def test_the_parameters_held_add_up_and_are_the_programs():
    from lib import qwen3_next as builder
    from paddle_tpu.models.qwen3_next import param_shapes
    held = CONFIG["parameters_held"]
    layers, experts = CONFIG["num_hidden_layers"], CONFIG["num_experts"]
    per_layer = held["router"] + held["shared_expert_and_gate"] \
        + held["norms_per_layer"] + experts * held["routed_expert"]
    total = 6 * held["deltanet_mixer"] + 2 * held["attention_mixer"] \
        + layers * per_layer + held["embedding_and_head"] \
        + held["final_norm"]
    assert total == 3_667_251_328 and round(total / 1e6) == 3667
    shapes = param_shapes(builder.program_config(CONFIG, rehearse=False))
    assert sum(int(np.prod(s)) for s, _ in shapes.values()) == total
    assert held["routed_expert"] == 3 * 2048 * 512
    assert held["deltanet_mixer"] == 2048 * 12288 + 2048 * 64 + 8192 * 4 \
        + 4096 * 2048 + 32 + 32 + 128
    assert held["attention_mixer"] == 2048 * 8192 + 2 * 2048 * 512 \
        + 4096 * 2048 + 2 * 256


def test_both_new_cells_are_one_chip_and_the_manifest_only_grew():
    cells = {w["name"]: w for w in MANIFEST["workloads"]}
    assert cells[SERVE]["chips"] == cells[TRAIN]["chips"] == 1
    assert [w["name"] for w in MANIFEST["workloads"]][:4] == [
        "gpt2s-train-b24", "gpt2m-serve-closed16",
        "pangu-ep16-serve-closed128", "gpt2m-serve-open-r80"]
    assert cells[TRAIN]["config"] == "gpt2-medium"
    mix = json.loads((BENCH / "traffic" / "closed128-midprompt.json")
                     .read_text())
    assert (mix["clients"], mix["prompt_lens"], mix["weights"],
            mix["max_tokens"], mix["block"]) == (
        128, [512, 1024, 2048, 4096], [0.30, 0.30, 0.25, 0.15],
        [256, 1024], 20)
    assert float(np.dot(mix["prompt_lens"], mix["weights"])) == \
        pytest.approx(1587.2)
    cell = json.loads((BENCH / "workloads" / f"{SERVE}.json").read_text())
    eng = cell["engine"]
    # every row fits its pool share: no row is ever preempted
    assert eng["num_blocks"] * eng["block_size"] == \
        eng["max_num_seqs"] * CONFIG["assumed"]["max_context"]
    assert max(mix["prompt_lens"]) + mix["max_tokens"][1] <= \
        CONFIG["assumed"]["max_context"]
    assert set(eng) == {"block_size", "max_num_seqs", "num_blocks"}
    train = json.loads((BENCH / "workloads" / f"{TRAIN}.json").read_text())
    first = json.loads((BENCH / "workloads" / "gpt2s-train-b24.json")
                       .read_text())
    assert train["trainer"] == first["trainer"] \
        and train["expect"] == first["expect"]
    tmix = json.loads((BENCH / "traffic" / "train-b8-t1024.json").read_text())
    assert (tmix["batch"], tmix["seq"], tmix["distinct_batches"],
            tmix["fetch_every"]) == (8, 1024, 16, 10)


@pytest.mark.parametrize("cell", [SERVE, TRAIN])
def test_every_metric_that_lists_a_new_cell_has_a_file(cell):
    named = [m["name"] for m in MANIFEST["per_layer"]
             if cell in m.get("workloads", [])]
    assert len(named) >= 5
    for name in named:
        path = BENCH / "layer_metrics" / f"{name}.py"
        if not path.is_file():
            path = BENCH / "layer_metrics" / f"{name.rsplit('.', 1)[0]}.py"
        assert path.is_file(), name
    e2e = [m["name"] for m in MANIFEST["end_to_end"]
           if cell in m.get("workloads", [cell])]
    assert "setup_s" in e2e and len(e2e) >= 2
    if cell == SERVE:       # the first token's median is too spread here
        assert sorted(e2e) == ["serve_tokens_per_s", "setup_s",
                               "token_gap_mean_ms"]
        assert {"serve_mfu_hybrid", "decode_trip_hbm_pct_hybrid",
                "gdn_step_ms", "gdn_step_hbm_pct"} <= set(named)


@pytest.fixture(scope="module")
def serve_rehearsal(tmp_path_factory):
    done = run_bench("--workload", SERVE, "--seed", str(2**31 + 33),
                     "--seconds", "2", "--trace", "0", "--rehearse", "--out",
                     str(tmp_path_factory.mktemp("pr33")))
    assert done.returncode == 0, done.stderr[-2000:]
    return [json.loads(line) for line in done.stdout.strip().splitlines()]


def test_the_serving_cells_rehearsal_passes_every_check(serve_rehearsal):
    last = serve_rehearsal[-1]
    assert last["rehearsal"] is True and last["correct"] is False
    assert last["checks_passed"] is True and last["failed"] == 0
    checks = next(x["checks"] for x in serve_rehearsal if "checks" in x)
    assert all(checks.values()) and {
        "prefill_and_paged_decode_logits_match_reference",
        "engine_tokens_within_tolerance_of_reference",
        "cache_bytes_as_the_spec_states",
        "state_slots_are_the_sequences_that_hold_cache"} <= set(checks)
    facts = next(x["facts"] for x in serve_rehearsal if "facts" in x)
    # both slots of check (a) were read and written
    assert [f["state_slot"] for f in facts["against_forward"]] == [0, 1, 0, 1]
    assert facts["engine"]["live_row_trips"] > 0
    assert 0 < facts["state_slots_in_use"] <= 4


def test_the_lower_precision_reading_fails_the_cells_limits(serve_rehearsal):
    from runners.serve_closed_family import within
    cell = json.loads((BENCH / "workloads" / f"{SERVE}.json").read_text())
    facts = next(x["facts"] for x in serve_rehearsal if "facts" in x)
    low = facts["lower_precision"]
    assert low["dtype"] == "float8_e4m3fn"
    # a rehearsal is float32 against float32 at toy widths, where logits
    # reach 0.6: it is held to its own limits (the cell's `rehearsal`
    # block), and float8 fails both of them in both checks
    limits = cell["rehearsal"]
    assert limits["logit_error"]["typical"] < cell["logit_error"]["typical"]
    for check in ("logit_error", "token_gap"):
        assert within(facts[check], limits[check])
        assert not within(low[check], limits[check])
        assert low[check]["typical"] > limits[check]["typical"]
        assert low[check]["largest"] > limits[check]["largest"]


@pytest.mark.parametrize("cell", [SERVE, "pangu-ep16-serve-closed128"])
def test_the_precision_witness_orders_the_dtypes(cell):
    """`precision_witness.py` at rehearsal size, for both expert families:
    68 rows a (seed, dtype), the reference against itself reads more the
    lower the precision, and the hybrid cell's rehearsal limits reject
    both."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, str(BENCH / "precision_witness.py"), "--workload",
         cell, "--seeds", str(2**31 + 33), "--low", "bfloat16",
         "float8_e4m3fn", "--rehearse"], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    half, quarter = (json.loads(x) for x in done.stdout.splitlines()[-2:])
    assert (half["low"], quarter["low"]) == ("bfloat16", "float8_e4m3fn")
    assert half["seed"] == quarter["seed"] == 2**31 + 33
    assert half["logit_error"]["count"] == 68
    assert 0 < half["logit_error"]["typical"] \
        < quarter["logit_error"]["typical"] < half["logit_abs_max"]
    if cell == SERVE:
        assert not half["within_limits"] and not quarter["within_limits"]


def test_the_training_cells_rehearsal_passes(tmp_path):
    done = run_bench("--workload", TRAIN, "--seed", str(2**31 + 33),
                     "--seconds", "1", "--trace", "0", "--rehearse", "--out",
                     str(tmp_path))
    assert done.returncode == 0, done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["checks_passed"] is True and last["rehearsal"] is True


# ------------------------------------------- operations and bytes by hand
def _work_config():
    keys = ("vocab_size", "hidden_size", "num_hidden_layers",
            "full_attention_interval", "num_attention_heads",
            "num_key_value_heads", "head_dim", "linear_num_key_heads",
            "linear_num_value_heads", "linear_key_head_dim",
            "linear_value_head_dim", "linear_conv_kernel_dim",
            "moe_intermediate_size", "shared_expert_intermediate_size",
            "num_experts_per_tok")
    return {**{k: CONFIG[k] for k in keys}, "experts_scored": 512,
            "experts_held": 128, "itemsize": 2, "state_itemsize": 4}


def test_operations_and_bytes_of_the_new_metrics_on_hand_made_counters():
    c = _work_config()
    m = work.matrices(c)
    assert work.layers(c) == (6, 2)
    # the issue's table: parameters by part
    assert m["deltanet"] == 33_718_272 and m["attention"] == 27_262_976
    assert m["routed_expert"] == 3_145_728 and m["router"] == 1_048_576
    assert m["shared_expert"] == 3_145_728 + 2048
    assert m["head"] == 2048 * 37_984
    fixed = work.per_token_fixed(c)
    assert fixed == 6 * m["deltanet"] + 2 * m["attention"] \
        + 8 * (m["shared_expert"] + m["router"])
    assert work.state_elements(c) == (32 * 128 * 128, 3 * 8192)
    # one decoded token at 1,900 of context with 20 held pairs
    flops = work.serve_flops(c, tokens=1, sampled=1, moe_pairs=20,
                             decode_context=1900, prefill_pairs=0)
    assert flops == 2.0 * (fixed + 20 * m["routed_expert"] + m["head"]
                           + 2 * 16 * 1900 * 512
                           + 6 * 32 * 3 * 128 * 128)
    # a prefill of 4 tokens: 10 causal pairs a full layer
    assert work.serve_flops(c, 4, 1, 0, 0, 10) == 2.0 * (
        4 * fixed + m["head"] + 2 * 16 * 10 * 512
        + 4 * 6 * 32 * 3 * 128 * 128)
    # a live row a trip: its state and conv history, read and written
    entry = 32 * 128 * 128 * 4 + 3 * 8192 * 2
    assert entry == 2_097_152 + 49_152
    assert work.state_bytes(c, 1) == 2 * 6 * entry
    assert work.state_bytes(c, 128 * 8, conv=False) == \
        2 * 6 * 128 * 8 * 2_097_152
    # one trip, 900 of the 1,024 held (expert, layer)s hit, 128 rows at 1,900
    need = work.decode_trip_bytes(c, trips=1, experts_hit=900,
                                  context_tokens=128 * 1900,
                                  live_row_trips=128)
    assert need == 2 * (fixed + m["head"]) + 2 * 8 * m["router"] \
        + 900 * 2 * m["routed_expert"] + 128 * 1900 * 2048 * 2 \
        + 128 * 2 * 6 * entry
    # the issue's reckoning: about 11 GB a trip, 3.2 GB of it the state
    assert 10.5e9 < need < 11.5e9
    assert 3.2e9 < work.state_bytes(c, 128) < 3.4e9
    assert work.decode_trip_bytes(c, 2, 0, 0, 0) == \
        2 * work.decode_trip_bytes(c, 1, 0, 0, 0)


def test_at_the_rehearsal_size_the_counts_are_the_programs():
    """`serve_work_hybrid.matrices` against the program's own parameter
    shapes at the rehearsal size: every matrix a token multiplies."""
    from lib import qwen3_next as builder
    from paddle_tpu.models.qwen3_next import param_shapes
    cfg = builder.program_config(CONFIG, rehearse=True)
    c = builder.work_config(CONFIG, cfg)
    shapes = {n: int(np.prod(s)) for n, (s, _) in param_shapes(cfg).items()}
    m = work.matrices(c)

    def layer(i, part):
        return sum(v for n, v in shapes.items()
                   if n.startswith(f"layers.{i}.{part}")
                   and not n.endswith(("norm.weight", "A_log", "dt_bias")))

    assert m["deltanet"] == layer(0, "gdn.")
    assert m["attention"] == layer(3, "attn.")
    assert m["router"] == shapes["layers.0.moe.router.weight"]
    assert m["shared_expert"] == layer(0, "moe.shared")
    assert m["routed_expert"] * c["experts_held"] == layer(0, "moe.experts.")
    assert m["head"] == shapes["lm_head.weight"]
    assert work.layers(c) == (3, 1)


def test_the_new_layer_metrics_read_a_record_and_leave_out_what_is_missing():
    from run import load_module                 # benchmarks/run.py
    mfu = load_module("layer_metrics", "serve_mfu_hybrid")
    c = _work_config()
    record = {"device": {"kind": "TPU v5 lite", "count": 1}, "facts": {
        "window_seconds": 2.0, "moe_pairs": 800, "work": {
            "config": c, "positions_through_layers": 100,
            "sampled_positions": 100, "decode_context_tokens": 75_000,
            "live_row_trips": 100, "prefill_pairs": 0}}}
    want = work.serve_flops(c, 100, 100, 800, 75_000, 0)
    assert mfu.compute(record, None) == \
        pytest.approx(100 * want / 2.0 / 197e12)
    # a record of another runner or family, a run without a trace: left out
    for name in ("serve_mfu_hybrid", "decode_trip_hbm_pct_hybrid",
                 "gdn_step_ms", "gdn_step_hbm_pct"):
        metric = load_module("layer_metrics", name)
        assert metric.compute({"facts": {}}, None) is None
        assert metric.compute({"facts": {"work": {"config": {}}}}, None) \
            is None
        if name != "serve_mfu_hybrid":
            assert metric.compute(dict(record, trace_dir=None), None) is None


def test_the_state_operations_are_found_by_shape():
    """`state_ops` on a hand-written trace: the operations inside a
    `serving.decode` span that name a float32 [., 32, 128, 128] array."""
    from lib import spans
    c = _work_config()
    dev, host = "/device:TPU:0", "/host:CPU"
    state = "f32[128,32,128,128]{3,2,1,0}"
    events = [
        (host, "main", "bench.window", 0, 1000, {}),
        (host, "main", "serving.decode", 100, 600,
         {"chunk": 2, "live_row_trips": 7, "context_tokens": 5,
          "moe_experts_hit": 3}),
        (dev, "XLA Ops", f"%gather.1 = {state} gather({state} %p, s32[128])",
         110, 40, {}),
        (dev, "XLA Ops", f"%fusion.2 = {state} fusion({state} %a)", 200, 60,
         {}),
        (dev, "XLA Ops", "%fusion.3 = bf16[128,2048] fusion(bf16[128,2048])",
         300, 50, {}),
        (dev, "XLA Ops", f"%fusion.9 = {state} fusion({state} %a)", 800, 60,
         {}),                                   # outside the span
        # the scan holds the others: its time is theirs, counted once
        (dev, "XLA Ops", f"%while.4 = (s32[], {state}) while((s32[], {state})"
         " %tuple.1), condition=%c, body=%b", 105, 400, {}),
    ]
    record = {"_spans": spans.Trace(events), "facts": {"work": {"config": c}}}
    seen = work.state_ops(record)
    assert seen == {"seconds": pytest.approx(100e-9), "ops": 2, "trips": 2,
                    "live_row_trips": 7}
