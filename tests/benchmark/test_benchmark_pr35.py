"""What PR 35 added to the benchmark (CPU, not slow): the sliding-window
family's configuration keeps to the catalog row except where `reduced`
says so and its parameters add up, both new cells rehearse while the
lower-precision reading fails the serving cell's limits, every metric that
lists a new cell has a file, and the operations and bytes of the new layer
metrics are right on hand-made counters and a hand-written trace."""
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

from lib import serve_work_swa as work  # noqa: E402

MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
SERVE, CHUNKED = "mellum2-pp4-serve-closed64", "gpt2m-serve-chunked"
NAME = "mellum2-12b-a2.5b-pp4"
CONFIG = json.loads((BENCH / "configs" / f"{NAME}.json").read_text())
SLIDING, FULL = "sliding_attention", "full_attention"
#: the catalog row's `config` (model-configs guide, architectures.jsonl,
#: source_url = CONFIG["source"]), copied: there is no network here
CATALOG = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 7168,
    "layer_types": [SLIDING, SLIDING, SLIDING, FULL] * 7,
    "mlp_layer_types": ["sparse"] * 28, "max_position_embeddings": 131072,
    "max_window_layers": 0, "model_type": "mellum",
    "moe_intermediate_size": 896, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 64, "num_experts_per_tok": 8,
    "num_hidden_layers": 28, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}},
    "sliding_window": 1024, "tie_word_embeddings": False,
    "vocab_size": 98304, "use_sliding_window": True}
WIDTHS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
          "head_dim", "num_experts_per_tok", "num_attention_heads",
          "num_key_value_heads", "sliding_window", "num_experts",
          "vocab_size")


def run_bench(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)          # one CPU device, as on one chip
    return subprocess.run([sys.executable, str(BENCH / "run.py"), *args],
                          cwd=ROOT, env=env, capture_output=True, text=True,
                          timeout=900)


def test_the_configuration_is_the_catalog_row_cut_in_depth_only():
    entry = next(c for c in MANIFEST["configs"] if c["name"] == NAME)
    differs = sorted(k for k, v in CATALOG.items() if CONFIG.get(k, "-") != v)
    assert differs == sorted(entry["reduced"]) \
        == ["layer_types", "mlp_layer_types", "num_hidden_layers"]
    assert not set(entry["reduced"]) & set(WIDTHS)
    # two whole periods, the pattern's first eight layers
    assert CONFIG["num_hidden_layers"] == 8
    assert CONFIG["layer_types"] == CATALOG["layer_types"][:8] \
        == [SLIDING, SLIDING, SLIDING, FULL] * 2
    assert CONFIG["mlp_layer_types"] == ["sparse"] * 8
    assert CONFIG["published"]["num_hidden_layers"] == 28
    assert set(entry["reduced"]) <= set(CONFIG["deployment"])
    assert CONFIG["deployment"]["chips_that_share_a_layer"] == 1
    assert CONFIG["deployment"]["pipeline_parallel"] == {
        "size": 4, "stage": 0, "layers_per_stage": [8, 8, 8, 4]}
    assert sum(CONFIG["deployment"]["pipeline_parallel"][
        "layers_per_stage"]) == CATALOG["num_hidden_layers"]
    assert "embedding_and_head" in CONFIG["deployment"]
    assert entry["source"] == CONFIG["source"]
    for key in ("served_dtype", "max_context", "initializer_range",
                "qk_norm", "yarn_correction_range"):
        assert key in CONFIG["assumed"]
    # the rehearsal crosses the window's edge: window 16 at blocks of 8,
    # prompts past it
    cell = json.loads((BENCH / "workloads" / f"{SERVE}.json").read_text())
    mix = json.loads((BENCH / "traffic" / "closed64-shortlong.json")
                     .read_text())
    assert CONFIG["rehearsal"]["sliding_window"] == 16
    assert cell["rehearsal"]["engine"]["block_size"] == 8
    assert max(mix["prompt_lens"]) * mix["rehearsal"]["scale"] > 16 * 4


def test_the_parameters_held_add_up_and_are_the_programs():
    from lib import mellum2 as builder
    from paddle_tpu.models.mellum import param_shapes
    held = CONFIG["parameters_held"]
    per_layer = held["attention"] + held["qk_norms"] + held["router"] \
        + CONFIG["num_experts"] * held["routed_expert"] \
        + held["norms_per_layer"]
    total = 8 * per_layer + held["embedding_and_head"] + held["final_norm"]
    assert per_layer == 417_747_712 and total == 3_794_968_832
    cfg = builder.program_config(CONFIG, rehearse=False)
    shapes = param_shapes(cfg)
    assert sum(int(np.prod(s)) for s, _ in shapes.values()) == total
    # 7.59 GB: bfloat16, the eight routers float32
    assert sum(int(np.prod(s)) * (4 if d == "float32" else 2)
               for s, d in shapes.values()) == 7_592_296_960
    assert held["attention"] == 2304 * 4096 + 2 * 2304 * 512 + 4096 * 2304
    assert held["routed_expert"] == 3 * 2304 * 896
    assert held["embedding_and_head"] == 2 * 98304 * 2304
    assert cfg.kinds == tuple(CONFIG["layer_types"])
    assert (cfg.sliding_window, cfg.max_seq_len, cfg.held) == (
        1024, 9216, (0, 64))


def test_both_new_cells_are_one_chip_and_the_manifest_only_grew():
    cells = {w["name"]: w for w in MANIFEST["workloads"]}
    assert cells[SERVE]["chips"] == cells[CHUNKED]["chips"] == 1
    assert [w["name"] for w in MANIFEST["workloads"]][:8] == [
        "gpt2s-train-b24", "gpt2m-serve-closed16",
        "pangu-ep16-serve-closed128", "gpt2m-serve-open-r80",
        "gpt2m-train-b8", "qwen3next-ep4-serve-closed128", SERVE, CHUNKED]
    assert [c["name"] for c in MANIFEST["configs"]][:5] == [
        "gpt2-small", "gpt2-medium", "openpangu-ultra-moe-ep16",
        "qwen3-next-80b-a3b-ep4", NAME]
    assert all(len(w["why"]) <= 200 for w in MANIFEST["workloads"])
    mix = json.loads((BENCH / "traffic" / "closed64-shortlong.json")
                     .read_text())
    assert (mix["clients"], mix["prompt_lens"], mix["weights"],
            mix["max_tokens"], mix["block"],
            mix["steady_state"]["finished_requests"]) == (
        64, [512, 2048, 4096, 8192], [0.30, 0.30, 0.25, 0.15],
        [256, 1024], 20, 32)
    assert float(np.dot(mix["prompt_lens"], mix["weights"])) == \
        pytest.approx(3020.8)
    cell = json.loads((BENCH / "workloads" / f"{SERVE}.json").read_text())
    eng = cell["engine"]
    # every row fits its pool share: no row is ever preempted
    assert eng["num_blocks"] * eng["block_size"] == \
        eng["max_num_seqs"] * CONFIG["assumed"]["max_context"]
    assert max(mix["prompt_lens"]) + mix["max_tokens"][1] <= \
        CONFIG["assumed"]["max_context"]
    # no new engine field: the window group is sized by the engine
    assert set(eng) == {"block_size", "max_num_seqs", "num_blocks"}
    assert "lower_precision" not in cell \
        and cell["rehearsal"]["lower_precision"] == "float8_e4m3fn"
    # the chunked cell is cell 2's engine with one field more
    second = json.loads((BENCH / "workloads" / "gpt2m-serve-closed16.json")
                        .read_text())
    chunked = json.loads((BENCH / "workloads" / f"{CHUNKED}.json")
                         .read_text())
    assert chunked["engine"] == {**second["engine"],
                                 "prefill_chunk_threshold": 128}
    assert (chunked["config"], chunked["runner"],
            chunked["logit_tolerance"]) == (
        "gpt2-medium", "serve_closed", second["logit_tolerance"])
    long_ = json.loads((BENCH / "traffic" / "closed16-longprompt.json")
                       .read_text())
    assert (long_["clients"], long_["prompt_lens"], long_["weights"],
            long_["max_tokens"], long_["block"],
            long_["steady_state"]["finished_requests"]) == (
        16, [512, 768], [0.75, 0.25], [32, 192], 20, 24)
    assert min(long_["prompt_lens"]) > 128


@pytest.mark.parametrize("cell", [SERVE, CHUNKED])
def test_every_metric_that_lists_a_new_cell_has_a_file(cell):
    named = [m["name"] for m in MANIFEST["per_layer"]
             if cell in m.get("workloads", [])]
    assert len(named) >= 5
    for name in named:
        path = BENCH / "layer_metrics" / f"{name}.py"
        if not path.is_file():
            path = BENCH / "layer_metrics" / f"{name.rsplit('.', 1)[0]}.py"
        assert path.is_file(), name
    e2e = sorted(m["name"] for m in MANIFEST["end_to_end"]
                 if cell in m.get("workloads", [cell]))
    if cell == SERVE:       # the first token's median is the queue's length
        assert e2e == ["serve_tokens_per_s", "setup_s", "token_gap_mean_ms"]
        assert {"serve_mfu_swa", "decode_trip_hbm_pct_swa", "swa_attn_ms",
                "swa_attn_hbm_pct"} <= set(named)
        for name in ("serve_mfu_swa", "decode_trip_hbm_pct_swa",
                     "swa_attn_ms", "swa_attn_hbm_pct"):
            assert next(m for m in MANIFEST["per_layer"]
                        if m["name"] == name)["workloads"] == [SERVE]
    else:                   # no dense prefill runs: its metrics are left out
        assert {"serve_tokens_per_s", "setup_s",
                "token_gap_mean_ms"} <= set(e2e)
        assert not {"prefill_ms", "prefill_write_cache_ms",
                    "prefill_device_ms", "ttft_wait_ms"} & set(named)
        assert {"ragged_kernel_ms", "decode_chunk_ms"} <= set(named)


@pytest.fixture(scope="module")
def serve_rehearsal(tmp_path_factory):
    done = run_bench("--workload", SERVE, "--seed", str(2**31 + 35),
                     "--seconds", "2", "--trace", "0", "--rehearse", "--out",
                     str(tmp_path_factory.mktemp("pr35")))
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
        "cache_bytes_as_the_spec_states", "check_a_crossed_the_window",
        "window_blocks_never_above_a_window_and_a_chunk_a_row",
        "both_groups_allocated_equal_freed"} <= set(checks)
    facts = next(x["facts"] for x in serve_rehearsal if "facts" in x)
    # three of the four lengths lie past the window; their tables start
    # behind block 0 and blocks came back inside the decode steps
    past = [f for f in facts["against_forward"] if f["past_the_window"]]
    assert len(past) == 3
    assert all(f["window_table_first_block"] > 0
               and f["window_blocks_released_behind"] > 0 for f in past)
    assert facts["engine"]["window_blocks_freed"] > 0
    assert 0 < facts["engine"]["window_context_tokens"] \
        < facts["engine"]["context_tokens"]
    pool = facts["pool"]
    assert pool["window_blocks_allocated"] == pool["window_blocks_freed"]
    assert pool["window_high_water"] <= pool["window_blocks"] == 4 * 4
    assert facts["work"]["window_pool_shape"] == [16, 8, 2, 32]


def test_the_lower_precision_reading_fails_the_cells_limits(serve_rehearsal):
    from runners.serve_closed_family import within
    cell = json.loads((BENCH / "workloads" / f"{SERVE}.json").read_text())
    facts = next(x["facts"] for x in serve_rehearsal if "facts" in x)
    low = facts["lower_precision"]
    assert low["dtype"] == "float8_e4m3fn"
    limits = cell["rehearsal"]
    assert limits["logit_error"]["typical"] < cell["logit_error"]["typical"]
    for check in ("logit_error", "token_gap"):
        assert within(facts[check], limits[check])
        assert not within(low[check], limits[check])
        assert low[check]["typical"] > limits[check]["typical"]
        assert low[check]["largest"] > limits[check]["largest"]
    # twice the other runners' 16 steps a length: 33 rows x 4 lengths
    assert facts["logit_error"]["count"] == 132


def test_the_precision_witness_reads_the_new_cell():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, str(BENCH / "precision_witness.py"), "--workload",
         SERVE, "--seeds", str(2**31 + 35), "--low", "bfloat16",
         "float8_e4m3fn", "--rehearse"], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    half, quarter = (json.loads(x) for x in done.stdout.splitlines()[-2:])
    assert (half["low"], quarter["low"]) == ("bfloat16", "float8_e4m3fn")
    assert half["prompt_lens"] == [16, 64, 128, 256]
    assert 0 < half["logit_error"]["typical"] \
        < quarter["logit_error"]["typical"] < half["logit_abs_max"]
    assert not half["within_limits"] and not quarter["within_limits"]


def test_the_chunked_cells_rehearsal_runs_no_dense_prefill(tmp_path):
    done = run_bench("--workload", CHUNKED, "--seed", str(2**31 + 35),
                     "--seconds", "1", "--trace", "0", "--rehearse", "--out",
                     str(tmp_path))
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [json.loads(x) for x in done.stdout.strip().splitlines()]
    assert lines[-1]["checks_passed"] is True
    facts = next(x["facts"] for x in lines if "facts" in x)
    # every prompt rode the decode scan: no prefill program, no its fetch
    assert facts["engine"]["prefill_tokens"] == 0
    assert facts["engine"]["syncs_prefill"] == 0
    assert facts["engine"]["generated_tokens"] > 0
    assert facts["reference_logit_gap_max"] < 1e-4


# ------------------------------------------- operations and bytes by hand
def _work_config():
    keys = ("vocab_size", "hidden_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "sliding_window", "moe_intermediate_size", "num_experts",
            "num_experts_per_tok")
    return {**{k: CONFIG[k] for k in keys}, "window_layers": 6,
            "full_layers": 2, "experts_held": 64, "itemsize": 2}


def test_operations_and_bytes_of_the_new_metrics_on_hand_made_counters():
    c = _work_config()
    m = work.matrices(c)
    held = CONFIG["parameters_held"]
    assert m["attention"] == held["attention"] == 21_233_664
    assert m["routed_expert"] == held["routed_expert"]
    assert m["router"] == held["router"] and m["head"] == 2304 * 98304
    fixed = work.per_token_fixed(c)
    assert fixed == 8 * (m["attention"] + m["router"])
    assert work.row_bytes(c) == 2048
    # one decoded token at 3,400 of context: the full layers read it all,
    # the window layers 1,024 of it
    flops = work.serve_flops(c, tokens=1, sampled=1, moe_pairs=64,
                             decode_context=3400, decode_window_context=1024,
                             prefill_pairs=0, prefill_window_pairs=0)
    assert flops == 2.0 * (fixed + 64 * m["routed_expert"] + m["head"]
                           + 32 * 2 * 128 * (2 * 3400 + 6 * 1024))
    # a prefill of 4 tokens: 10 causal pairs in every layer
    assert work.serve_flops(c, 4, 1, 0, 0, 0, 10, 10) == 2.0 * (
        4 * fixed + m["head"] + 32 * 2 * 128 * 8 * 10)
    assert work.window_bytes(c, 64 * 1024) == 6 * 64 * 1024 * 2048
    # one trip: every (expert, layer) hit, 64 rows at 3,400 of context
    need = work.decode_trip_bytes(
        c, trips=1, experts_hit=8 * 64, context_tokens=64 * 3400,
        window_context_tokens=64 * 1024)
    assert need == 2 * (fixed + m["head"]) + 2 * 8 * m["router"] \
        + 512 * 2 * m["routed_expert"] + 2 * 64 * 3400 * 2048 \
        + 6 * 64 * 1024 * 2048
    # the issue's reckoning: about 9 GB a trip; the weights are every
    # parameter held but the embedding (7.59 - 0.45 GB), 0.9 GB of full
    # rows, 0.8 of window rows
    assert 8.8e9 < need < 9.4e9
    assert work.decode_trip_bytes(c, 1, 512, 0, 0) \
        == 7_592_296_960 - 2 * 98304 * 2304 - 2 * (8 * 4608 + 2304 + 8 * 256)
    assert work.decode_trip_bytes(c, 2, 0, 0, 0) == \
        2 * work.decode_trip_bytes(c, 1, 0, 0, 0)


def test_at_the_rehearsal_size_the_counts_are_the_programs():
    from lib import mellum2 as builder
    from paddle_tpu.models.mellum import param_shapes
    cfg = builder.program_config(CONFIG, rehearse=True)
    c = builder.work_config(CONFIG, cfg)
    shapes = {n: int(np.prod(s)) for n, (s, _) in param_shapes(cfg).items()}
    m = work.matrices(c)
    assert m["attention"] == sum(
        v for n, v in shapes.items()
        if n.startswith("layers.0.attn.") and "norm" not in n)
    assert m["router"] == shapes["layers.0.moe.router.weight"]
    assert m["routed_expert"] * c["experts_held"] == sum(
        v for n, v in shapes.items() if n.startswith("layers.0.moe.experts."))
    assert m["head"] == shapes["lm_head.weight"]
    assert (c["window_layers"], c["full_layers"]) == (3, 1)


def _record(c, **work_facts):
    return {"device": {"kind": "TPU v5 lite", "count": 1}, "facts": {
        "window_seconds": 2.0, "moe_pairs": 800, "work": {
            "config": c, "positions_through_layers": 100,
            "sampled_positions": 100, "decode_context_tokens": 75_000,
            "decode_window_context_tokens": 40_000, "prefill_pairs": 0,
            "prefill_window_pairs": 0, "window_pool_shape": [2176, 32, 512],
            **work_facts}}}


def test_the_new_layer_metrics_read_a_record_and_leave_out_what_is_missing():
    from run import load_module                 # benchmarks/run.py
    mfu = load_module("layer_metrics", "serve_mfu_swa")
    c = _work_config()
    record = _record(c)
    want = work.serve_flops(c, 100, 100, 800, 75_000, 40_000, 0, 0)
    assert mfu.compute(record, None) == \
        pytest.approx(100 * want / 2.0 / 197e12)
    # a record of another runner or family, a run without a trace, the
    # parent (no such stat on its spans): left out, nothing raised
    for name in ("serve_mfu_swa", "decode_trip_hbm_pct_swa", "swa_attn_ms",
                 "swa_attn_hbm_pct"):
        metric = load_module("layer_metrics", name)
        assert metric.compute({"facts": {}}, None) is None
        assert metric.compute({"facts": {"work": {"config": {}}}}, None) \
            is None
        if name != "serve_mfu_swa":
            assert metric.compute(dict(record, trace_dir=None), None) is None


def test_the_window_operations_are_found_by_the_pools_shape():
    """`window_ops`, `traced` and the two device metrics on a hand-written
    trace: the operations inside a `serving.decode` span that name an array
    of the window pools' shape."""
    from lib import spans
    from run import load_module
    c = _work_config()
    dev, host = "/device:TPU:0", "/host:CPU"
    pool = "bf16[2176,32,512]{2,1,0}"
    full = "bf16[18432,32,512]{2,1,0}"
    events = [
        (host, "main", "bench.window", 0, 10_000, {}),
        (host, "main", "serving.decode", 100, 6000,
         {"chunk": 2, "context_tokens": 500, "window_context_tokens": 300,
          "moe_experts_hit": 40, "live_row_trips": 7}),
        (dev, "XLA Modules", "jit_fused_decode_chunk(123)", 150, 5000, {}),
        (dev, "XLA Ops", f"%fusion.1 = bf16[64,1088,512] fusion({pool} %p, "
         "s32[64,34] %t)", 200, 400, {}),
        (dev, "XLA Ops", f"%scatter.2 = {pool} scatter({pool} %p, "
         "s32[64,2], bf16[64,512])", 700, 100, {}),
        (dev, "XLA Ops", f"%fusion.3 = bf16[64,9216,512] fusion({full} %p, "
         "s32[64,288] %t)", 900, 2000, {}),        # the full layers' gather
        (dev, "XLA Ops", f"%fusion.9 = bf16[64,1088,512] fusion({pool} %p)",
         8000, 400, {}),                            # outside the span
        # the scan holds the others: its time is theirs, counted once
        (dev, "XLA Ops", f"%while.4 = (s32[], {pool}) while((s32[], {pool})"
         " %tuple.1), condition=%c, body=%b", 160, 4900, {}),
    ]
    record = {**_record(c), "_spans": spans.Trace(events)}
    seen = work.window_ops(record)
    assert seen == {"seconds": pytest.approx(500e-9), "ops": 2, "trips": 2,
                    "window_context_tokens": 300}
    assert load_module("layer_metrics", "swa_attn_ms").compute(
        record, None) == pytest.approx(1e3 * 500e-9 / 2)
    assert load_module("layer_metrics", "swa_attn_hbm_pct").compute(
        record, None) == pytest.approx(
        100 * 6 * 300 * 2048 / 500e-9 / 819e9)
    chunk = work.traced(record)
    assert (chunk["trips"], chunk["experts_hit"], chunk["context_tokens"],
            chunk["window_context_tokens"]) == (2, 40, 500, 300)
    assert chunk["program_seconds"] == pytest.approx(5000e-9)
    assert load_module("layer_metrics", "decode_trip_hbm_pct_swa").compute(
        record, None) == pytest.approx(
        100 * work.decode_trip_bytes(c, 2, 40, 500, 300) / 5000e-9 / 819e9)
    # a span without the stat (a program without window layers): nothing
    events[1] = (host, "main", "serving.decode", 100, 6000,
                 {"chunk": 2, "context_tokens": 500, "moe_experts_hit": 40})
    bare = {**_record(c), "_spans": spans.Trace(events)}
    assert work.window_ops(bare) is None and work.traced(bare) is None
