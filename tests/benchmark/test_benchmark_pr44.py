"""What PR 44 added to the benchmark (CPU, not slow): the Ling-3.0-flash
configuration keeps to the catalog row except where `reduced` says so and
its parameters add up, its one cell is one chip and shares cell 6's
traffic, the rehearsal passes every check while the lower-precision reading
fails its limits, every metric that lists the cell has a file, and the
operations and bytes of the new layer metrics are right on hand-made
counters."""
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

from lib import serve_work_kda as work  # noqa: E402

MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL, NAME = "ling3flash-ep4-serve-closed128", "ling-3.0-flash-ep4"
CONFIG = json.loads((BENCH / "configs" / f"{NAME}.json").read_text())
#: the catalog row's `config` (model-configs guide, architectures.jsonl,
#: source_url = CONFIG["source"]), copied: there is no network here
CATALOG = {
    "expert_swiglu_limit_list": [0] * 35 + [4] * 7,
    "first_k_dense_replace": 2,
    "gated_attention_proj_granularity_type": "head_wise",
    "group_norm_size": 1, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2560, "intermediate_size": 6144, "kda_lower_bound": -5,
    "kda_safe_gate": True, "kv_lora_rank": 512, "layer_group_size": 6,
    "linear_silu": True, "max_position_embeddings": 262144,
    "max_window_layers": 20, "moe_intermediate_size": 768,
    "moe_router_enable_expert_bias": True,
    "moe_shared_expert_intermediate_size": 768, "mtp_loss_scaling_factor": 0,
    "mtp_use_kda": False, "n_group": 8, "no_kda_lora": True,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 512,
    "num_experts_per_tok": 8, "num_hidden_layers": 42,
    "num_key_value_heads": 32, "num_kv_heads_for_linear_attn": 0,
    "num_nextn_predict_layers": 1, "num_shared_experts": 1,
    "partial_rotary_factor": 0.5, "q_lora_rank": None, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 6000000,
    "rotary_dim": 64, "routed_scaling_factor": 2.5,
    "scale_router_input": False, "score_function": "sigmoid",
    "scoring_func": "sigmoid", "seq_aux": True,
    "share_expert_swiglu_limit_list": [0] * 34 + [5] * 6 + [7] * 2,
    "short_conv_kernel_size": 4, "tie_word_embeddings": False,
    "topk_group": 4, "topk_method": "noaux_tc", "up_proj_norm": False,
    "use_bias": False, "use_kda_lora": False, "use_mla_nope": False,
    "use_nGPT": False, "use_qk_norm": True, "use_qkv_bias": False,
    "v_head_dim": 128, "value_norm": False, "vocab_size": 157184,
    "model_type": "bailing_hybrid"}
WIDTHS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
          "moe_shared_expert_intermediate_size", "head_dim", "kv_lora_rank",
          "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
          "num_experts_per_tok", "num_attention_heads",
          "short_conv_kernel_size")


def run_bench(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)          # one CPU device, as on one chip
    return subprocess.run([sys.executable, str(BENCH / "run.py"), *args],
                          cwd=ROOT, env=env, capture_output=True, text=True,
                          timeout=900)


def test_the_configuration_is_the_catalog_row_except_what_reduced_lists():
    entry = next(c for c in MANIFEST["configs"] if c["name"] == NAME)
    differs = sorted(k for k, v in CATALOG.items() if CONFIG.get(k, "-") != v)
    assert differs == sorted(entry["reduced"]) == sorted([
        "num_hidden_layers", "first_k_dense_replace", "num_experts",
        "vocab_size", "num_nextn_predict_layers", "expert_swiglu_limit_list",
        "share_expert_swiglu_limit_list"])
    assert not set(entry["reduced"]) & set(WIDTHS)
    assert entry["source"] == CONFIG["source"]
    assert set(entry["reduced"]) <= set(CONFIG["published"])
    assert CONFIG["num_experts"] * 4 == CATALOG["num_experts"]
    assert CONFIG["vocab_size"] * 4 == CATALOG["vocab_size"]
    assert CONFIG["deployment"]["expert_parallel"] == {"size": 4, "rank": 0}
    # the held layers are published 0 and 2-7; their limits are those
    held = CONFIG["published_layers"]
    assert held == [0, 2, 3, 4, 5, 6, 7]
    assert len(held) == CONFIG["num_hidden_layers"]
    for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        assert CONFIG[key] == [CATALOG[key][p] for p in held]
    mixers = ["MLA" if (p + 1) % CONFIG["layer_group_size"] == 0 else "KDA"
              for p in held]
    assert mixers == ["KDA"] * 4 + ["MLA", "KDA", "KDA"]
    for key in ("state_dtype", "served_dtype", "max_context", "kda_gate",
                "layer_rule", "norms", "router", "expert_bias",
                "swiglu_clamp", "initializer_range"):
        assert key in CONFIG["assumed"]


def test_the_parameters_held_add_up_and_are_the_programs():
    from lib import ling_flash as builder
    from paddle_tpu.models.ling_flash import param_shapes
    held = CONFIG["parameters_held"]
    experts = CONFIG["num_experts"]
    expert_layer = held["router_and_bias"] + held["shared_expert"] \
        + experts * held["routed_expert"] + held["norms_per_layer"]
    total = (held["kda_mixer"] + held["dense_mlp"] + held["norms_per_layer"]
             + 5 * (held["kda_mixer"] + expert_layer)
             + held["mla_mixer"] + expert_layer
             + held["embedding_and_head"] + held["final_norm"])
    assert total == held["total"] == 5_231_790_016
    shapes = param_shapes(builder.program_config(CONFIG, rehearse=False))
    assert sum(int(np.prod(s)) for s, _ in shapes.values()) == total
    assert held["routed_expert"] == 3 * 2560 * 768
    assert held["kda_mixer"] == 3 * 2560 * 4096 + 4 * 3 * 4096 \
        + 2560 * 4096 + 4096 + 32 + 2560 * 32 + 2560 * 4096 + 128 \
        + 4096 * 2560
    assert held["mla_mixer"] == 2560 * 32 * 192 + 2560 * 576 + 512 \
        + 512 * 32 * 256 + 2560 * 32 + 4096 * 2560


def test_the_cell_is_one_chip_on_cell_6s_traffic_and_the_manifest_grew():
    cells = {w["name"]: w for w in MANIFEST["workloads"]}
    assert cells[CELL]["chips"] == 1 and cells[CELL]["config"] == NAME
    assert cells[CELL]["traffic"] == \
        cells["qwen3next-ep4-serve-closed128"]["traffic"]
    # the nine cells this PR saw, none of them on four chips; later PRs
    # append theirs
    names = [w["name"] for w in MANIFEST["workloads"]]
    assert names.index(CELL) == 8
    assert all(w["chips"] == 1 for w in MANIFEST["workloads"][:9])
    cell = json.loads((BENCH / "workloads" / f"{CELL}.json").read_text())
    eng = cell["engine"]
    assert eng == {"block_size": 32, "max_num_seqs": 128,
                   "num_blocks": 20480}
    assert eng["num_blocks"] * eng["block_size"] == \
        eng["max_num_seqs"] * CONFIG["assumed"]["max_context"]
    assert cell["runner"] == "serve_closed_kda"
    assert "lower_precision" not in cell


def test_every_metric_that_lists_the_cell_has_a_file():
    named = [m["name"] for m in MANIFEST["per_layer"]
             if CELL in m.get("workloads", [])]
    for name in named:
        path = BENCH / "layer_metrics" / f"{name}.py"
        if not path.is_file():
            path = BENCH / "layer_metrics" / f"{name.rsplit('.', 1)[0]}.py"
        assert path.is_file(), name
    assert {"kda_step_ms", "kda_step_hbm_pct", "serve_mfu_kda",
            "decode_trip_hbm_pct_kda"} <= set(named)
    e2e = [m["name"] for m in MANIFEST["end_to_end"]
           if CELL in m.get("workloads", [CELL])]
    assert sorted(e2e) == ["serve_tokens_per_s", "setup_s",
                           "token_gap_mean_ms"]


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    done = run_bench("--workload", CELL, "--seed", str(2**31 + 44),
                     "--seconds", "2", "--trace", "1", "--rehearse", "--out",
                     str(tmp_path_factory.mktemp("pr44")))
    assert done.returncode == 0, done.stderr[-2000:]
    return [json.loads(line) for line in done.stdout.strip().splitlines()
            if line.startswith("{")]


def test_the_cells_rehearsal_passes_every_check(rehearsal):
    last = rehearsal[-1]
    assert last["rehearsal"] is True and last["correct"] is False
    assert last["checks_passed"] is True and last["failed"] == 0
    checks = next(x["checks"] for x in rehearsal if "checks" in x)
    assert all(checks.values()) and {
        "prefill_and_paged_decode_logits_match_reference",
        "engine_tokens_within_tolerance_of_reference",
        "cache_bytes_as_the_spec_states",
        "state_slots_are_the_sequences_that_hold_cache"} <= set(checks)
    facts = next(x["facts"] for x in rehearsal if "facts" in x)
    assert [f["state_slot"] for f in facts["against_forward"]] == [0, 1, 0, 1]
    # 6 KDA layers: every live row's state updated in each, a trip
    assert facts["work"]["state_row_trips"] == \
        6 * facts["work"]["live_row_trips"] > 0
    assert facts["work"]["prefill_chunks"] > 0
    assert facts["cache_physical_bytes_per_token"] >= \
        facts["cache_bytes_per_token"] > 0


def test_the_lower_precision_reading_fails_the_rehearsal_limits(rehearsal):
    from runners.serve_closed_family import within
    cell = json.loads((BENCH / "workloads" / f"{CELL}.json").read_text())
    facts = next(x["facts"] for x in rehearsal if "facts" in x)
    low, limits = facts["lower_precision"], cell["rehearsal"]
    assert low["dtype"] == "float8_e4m3fn"
    for check in ("logit_error", "token_gap"):
        assert within(facts[check], limits[check])
        assert not within(low[check], limits[check])


# ------------------------------------------- operations and bytes by hand
def _work_config():
    from lib import ling_flash as builder
    cfg = builder.program_config(CONFIG, rehearse=False)
    return builder.work_config(CONFIG, cfg)


def test_operations_and_bytes_of_the_new_metrics_on_hand_made_counters():
    c = _work_config()
    held = CONFIG["parameters_held"]
    assert work.layers(c) == (6, 1) and c["mla_layers"] == [4]
    m = work.matrices(c)
    assert m["kda"] == held["kda_mixer"] - 4096 - 32 - 128   # no vectors
    assert m["mla"] == held["mla_mixer"] - 512
    assert m["routed_expert"] == held["routed_expert"]
    assert m["router"] == 2560 * 512 and m["head"] == 2560 * 39296
    fixed = work.per_token_fixed(c)
    assert fixed == 6 * m["kda"] + m["mla"] + m["dense_mlp"] \
        + 6 * (m["shared_expert"] + m["router"])
    assert work.state_elements(c) == (32 * 128 * 128, 3 * 3 * 4096)
    # one decoded token at 1,900 of context with 16 held pairs
    flops = work.serve_flops(c, tokens=1, sampled=1, moe_pairs=16,
                             decode_context=1900, prefill_pairs=0)
    assert flops == 2.0 * (fixed + 16 * m["routed_expert"] + m["head"]
                           + 32 * 1900 * (512 + 64 + 512)
                           + 6 * 3 * 32 * 128 * 128)
    # a live row's entry: 2 MiB of float32 state and 72 KiB of history,
    # read and written once
    assert work.state_bytes(c, 1) == 2 * (2_097_152 + 73_728)
    trip = work.decode_trip_bytes(c, trips=1, experts_hit=6 * 111,
                                  context_tokens=128 * 1900,
                                  state_row_trips=6 * 128)
    # all 128 rows live at a context of 1,900, 111 of 128 experts reached
    # a layer (uniform routing at 2 tokens an expert): 12.6 GB a trip
    assert trip == pytest.approx(12.6e9, rel=0.05)


def test_the_new_readers_find_nothing_without_a_device_trace():
    from importlib import util
    for name in ("kda_step_ms", "kda_step_hbm_pct", "decode_trip_hbm_pct_kda"):
        spec = util.spec_from_file_location(
            name, BENCH / "layer_metrics" / f"{name}.py")
        mod = util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert mod.compute({"facts": {"work": {"config": _work_config()}}},
                           None) is None
