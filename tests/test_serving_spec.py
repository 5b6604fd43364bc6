"""The model seam of the serving engine (`models.spec.ModelSpec`): the
GPT-2 spec wraps models/generation.py unchanged, so its three programs keep
their names, arguments and text; the tuple geometry still names it; a span
takes stats from inside its scope."""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.inference.serving import EngineConfig, LLMEngine
from paddle_tpu.inference.serving.attention import (PACK_COLS, as_spec,
                                                    fused_decode_chunk)
from paddle_tpu.inference.serving.paged_cache import (PagedKVCache,
                                                      write_prefill_scatter)
from paddle_tpu.models import generation as gen
from paddle_tpu.models.gpt import GPT, GPTConfig
from paddle_tpu.models.spec import ModelSpec

GEOM = (2, 2, 64, 128)


@pytest.fixture(scope="module")
def gpt():
    model = GPT(GPTConfig(vocab_size=512, hidden_size=128, num_layers=2,
                          num_heads=2, max_seq_len=128))
    return model, gen.extract_params(model)


def _lowered(params):
    pc = PagedKVCache(2, (2, 64), 64, 8)
    k = 8
    packed = np.zeros((4, PACK_COLS + k + 16), np.int32)
    ids = jnp.zeros((1, 24), jnp.int32)
    _, dense = gen.prefill(params, ids, GEOM)
    return {
        "jit_fused_decode_chunk": fused_decode_chunk.lower(
            params, pc.pools, packed, GEOM, k, "ragged"),
        "jit_prefill": gen.prefill.lower(params, ids, GEOM),
        "jit_write_prefill_scatter": write_prefill_scatter.lower(
            pc.pools, dense, np.zeros((16,), np.int32), np.int32(0)),
    }


@pytest.mark.parametrize("name, args, results", [
    # arrays beside the parameters: (pools + packed) in, (out, pools) out
    ("jit_fused_decode_chunk", 4 + 1, 1 + 4),
    ("jit_prefill", 1, 1 + 4),
    ("jit_write_prefill_scatter", 4 + 4 + 2, 4),
])
def test_gpt2_programs_keep_names_and_argument_shapes(gpt, name, args,
                                                      results):
    """The three programs of the GPT-2 cells, as the benchmark's readers
    find them (`XLA Modules` names) and as the parent commit lowered them:
    on the tree of PR 29 their text was byte-equal to PR 28's (sha1
    compared on a scratch copy of the parent); what stays checkable here is
    the name, the argument and result counts, and that the spec and the
    tuple lower to the same text."""
    lowered = _lowered(gpt[1])[name]
    text = lowered.as_text()
    assert f"module @{name} " in text
    flat_in = jax.tree_util.tree_leaves(lowered.in_avals)
    held = 0 if name == "jit_write_prefill_scatter" else len(gpt[1])
    assert len(flat_in) == held + args
    assert len(jax.tree_util.tree_leaves(lowered.out_info)) == results


def test_the_tuple_and_the_spec_lower_the_chunk_to_the_same_text(gpt):
    params = gpt[1]
    pc = PagedKVCache(2, (2, 64), 64, 8)
    packed = np.zeros((4, PACK_COLS + 8 + 16), np.int32)
    texts = [fused_decode_chunk.lower(params, pc.pools, packed, g, 8,
                                      "ragged").as_text()
             for g in (GEOM, gen.serving_spec(GEOM))]
    assert hashlib.sha1(texts[0].encode()).hexdigest() \
        == hashlib.sha1(texts[1].encode()).hexdigest()
    assert texts[0].count("stablehlo.sort") == texts[1].count(
        "stablehlo.sort") > 0


def test_a_geometry_tuple_names_the_gpt2_spec(gpt):
    spec = as_spec(GEOM)
    assert isinstance(spec, ModelSpec) and spec is gen.serving_spec(GEOM)
    assert as_spec(spec) is spec
    assert (spec.family, spec.cache_layout, spec.cache_shape,
            spec.pools_per_layer, spec.counters) \
        == ("gpt2", "heads", (2, 64), 2, ())
    assert spec.cache_bytes_per_token == 2 * 2 * 2 * 64 * 4
    eng = LLMEngine.from_model(gpt[0], EngineConfig(block_size=8,
                                                    num_blocks=32))
    assert eng.geom is spec and eng.spec is spec
    assert eng.stats.cache_bytes_per_token == spec.cache_bytes_per_token
    assert [p.shape for p in eng.cache.pools[0]] == [(32, 8, 2, 64)] * 2
    # the counters of an expert family stay at zero for GPT-2
    assert eng.stats.moe_pairs == 0 and eng.stats.moe_experts_hit == 0


def test_the_scan_module_imports_no_family():
    """serving/attention.py is the scan, the packed upload and the sampler
    of EVERY family: at import time it names no module under
    paddle_tpu/models/ but the seam (`spec`)."""
    import ast
    import paddle_tpu.inference.serving.attention as attention
    tree = ast.parse(open(attention.__file__).read())
    named = []
    for node in tree.body:                       # top-level statements
        if isinstance(node, ast.Import):
            named += [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            base = "." * node.level + (node.module or "")
            named += [base] + [f"{base}.{a.name}" for a in node.names]
    models = [n for n in named if "models" in n.split(".")]
    assert models and all(
        n.lstrip(".").startswith("models.spec") for n in models), models


@pytest.mark.parametrize("family", ["latent", "hybrid"])
def test_the_other_families_chunk_keeps_its_name_and_argument_shapes(family):
    """The one decode program under every family: the latent family's
    upload is [rows, PACK_COLS + k + max_blocks] as the frozen runners
    shape it, a family with state layers carries ONE more column (the
    row's state slot) and its state leaves among the donated pools."""
    from paddle_tpu.inference.serving.attention import PACK_COLS
    from paddle_tpu.models import pangu_moe, qwen3_next
    if family == "latent":
        cfg = pangu_moe.PanguMoEConfig(
            vocab_size=64, hidden_size=32, num_hidden_layers=2,
            first_k_dense_replace=1, num_attention_heads=2, q_lora_rank=16,
            kv_lora_rank=8, qk_nope_head_dim=8, qk_rope_head_dim=4,
            v_head_dim=8, intermediate_size=32, moe_intermediate_size=16,
            n_routed_experts=4, num_experts_per_tok=2, max_seq_len=32)
        spec, shapes = pangu_moe.serving_spec(cfg), \
            pangu_moe.param_shapes(cfg)
        leaves, extra = 2, 0                    # one pool a layer
    else:
        cfg = qwen3_next.Qwen3NextConfig(
            vocab_size=64, hidden_size=32, num_hidden_layers=4,
            num_attention_heads=4, num_key_value_heads=2, head_dim=8,
            linear_num_key_heads=2, linear_num_value_heads=2,
            linear_key_head_dim=8, linear_value_head_dim=8,
            moe_intermediate_size=16, shared_expert_intermediate_size=16,
            num_experts=4, num_experts_per_tok=2, max_seq_len=32)
        spec, shapes = qwen3_next.serving_spec(cfg), \
            qwen3_next.param_shapes(cfg)
        leaves, extra = 3 * 2 + 2, 1            # (S, conv) x 3 and (k, v)
    params = {n: jnp.zeros(s, d) for n, (s, d) in shapes.items()}
    pc = PagedKVCache(spec.num_layers, spec.cache_shape, 8, 8,
                      layer_caches=spec.layer_caches,
                      state_shapes=spec.state_shapes, num_state_slots=2)
    packed = np.zeros((2, PACK_COLS + 8 + 32 // 8 + extra), np.int32)
    lowered = fused_decode_chunk.lower(params, pc.pools, packed, spec, 8)
    assert "module @jit_fused_decode_chunk " in lowered.as_text()
    flat_in = jax.tree_util.tree_leaves(lowered.in_avals)
    assert len(flat_in) == len(params) + leaves + 1
    # the result and the pools back, and the spec's counts as rows: the
    # expert layer's eight (five before PR 37's `moe_layer_calls`,
    # `moe_fit_2x`, `moe_fit_4x`), and the hybrid family's DeltaNet rows
    counts = {"latent": 8, "hybrid": 9}[family]
    assert len(spec.counters) == counts
    out = jax.tree_util.tree_leaves(lowered.out_info)
    assert len(out) == 1 + leaves and out[0].shape == (8 + 2 + counts, 2)
    # every pool leaf is donated: it aliases its output
    assert lowered.as_text().count("tf.aliasing_output") == leaves


def test_the_gpt_layer_names_its_own_spec(gpt):
    """`LLMEngine.from_model` asks every model the same question."""
    spec = gpt[0].serving_spec()
    assert spec is as_spec(GEOM) and spec is gen.serving_spec(GEOM)
    assert spec.config == GEOM


def test_a_span_takes_stats_from_inside_its_scope(tmp_path):
    from jax.profiler import ProfileData
    from paddle_tpu import obs
    jax.profiler.start_trace(str(tmp_path))
    with obs.span("serving.decode", args={"chunk": 8}) as ev:
        jnp.ones(3).block_until_ready()
        ev.set_stats(moe_pairs=7, moe_experts_hit=3)
        ev.set_stats()
    jax.profiler.stop_trace()
    assert ev.args == {"chunk": 8, "moe_pairs": 7, "moe_experts_hit": 3}
    path = next(tmp_path.rglob("*.xplane.pb"))
    found = [dict(e.stats) for pl in ProfileData.from_file(str(path)).planes
             for ln in pl.lines for e in ln.events
             if e.name == "serving.decode"]
    assert found == [{"chunk": 8, "moe_pairs": 7, "moe_experts_hit": 3}]


# sha1[:16] of the lowered text of the accepted cells' programs at the toy
# geometries below, taken on the parent of PR 35 (f1f9e0e) with jax 0.9.0
# under this harness's settings (conftest: matmul precision "highest"):
# a spec with no window layer gets the upload, the scan and the programs it
# got before window layers existed, byte for byte. A PR that changes one of
# these programs ON PURPOSE takes the new value from its own tree and says
# so; another jax prints other text, and the test then skips.
# PR 36 changed `latent.*` and `hybrid.*` on purpose (values from its own
# tree): `held_experts_mlp` has a batched form under its `switch` and a
# fifth count, `moe_batched_layers`, so every program with an expert layer
# differs; `gpt2.*` has none and stays the parent of PR 35's. PR 37 changed
# `latent.*` and `hybrid.*` again on purpose (values from its own tree):
# three counts more (`moe_layer_calls`, `moe_fit_2x`, `moe_fit_4x`), two
# compares a call; `gpt2.*` is still the parent of PR 35's. The hybrid
# family's DeltaNet decode step through the `kda_step` kernel changed
# `hybrid.*` again on purpose (values from its own tree): the chunk calls
# the kernel in place of the gather, step and scatter, both programs keep
# the conv history per value head, and a ninth count,
# `gdn_state_row_trips`, rides beside the expert layer's; `latent.*` and
# `gpt2.*` stay.
PARENT_OF_PR_35 = {
    "gpt2.chunk": "03d3b78e69ba813e", "gpt2.prefill": "094638537d5db785",
    "gpt2.scatter": "337b6f8e98105a15", "latent.chunk": "5cacecb5e963bce6",
    "latent.prefill": "f571d1216580742f", "hybrid.chunk": "16435f8054a988d9",
    "hybrid.prefill": "b52e5c6d2b0f7e3b",
    "hybrid.prefill.blocked": "1759582e5fe80c10",
}


def _sha(lowered):
    return hashlib.sha1(lowered.as_text().encode()).hexdigest()[:16]


def _lowered_by_family(family, gpt_params, monkeypatch):
    from paddle_tpu.models import pangu_moe, qwen3_next
    if family == "gpt2":
        low = _lowered(gpt_params)
        return {"gpt2.chunk": low["jit_fused_decode_chunk"],
                "gpt2.prefill": low["jit_prefill"],
                "gpt2.scatter": low["jit_write_prefill_scatter"]}
    if family == "latent":
        cfg = pangu_moe.PanguMoEConfig(
            vocab_size=64, hidden_size=32, num_hidden_layers=2,
            first_k_dense_replace=1, num_attention_heads=2, q_lora_rank=16,
            kv_lora_rank=8, qk_nope_head_dim=8, qk_rope_head_dim=4,
            v_head_dim=8, intermediate_size=32, moe_intermediate_size=16,
            n_routed_experts=4, num_experts_per_tok=2, max_seq_len=32)
        mod, extra = pangu_moe, 0
    else:
        cfg = qwen3_next.Qwen3NextConfig(
            vocab_size=64, hidden_size=32, num_hidden_layers=4,
            num_attention_heads=4, num_key_value_heads=2, head_dim=8,
            linear_num_key_heads=2, linear_num_value_heads=2,
            linear_key_head_dim=8, linear_value_head_dim=8,
            moe_intermediate_size=16, shared_expert_intermediate_size=16,
            num_experts=4, num_experts_per_tok=2, max_seq_len=32)
        mod, extra = qwen3_next, 1
    spec = mod.serving_spec(cfg)
    params = {n: jnp.zeros(s, d) for n, (s, d) in
              mod.param_shapes(cfg).items()}
    pc = PagedKVCache(spec.num_layers, spec.cache_shape, 8, 8,
                      layer_caches=spec.layer_caches,
                      state_shapes=spec.state_shapes, num_state_slots=2)
    packed = np.zeros((2, PACK_COLS + 8 + 4 + extra), np.int32)
    out = {f"{family}.chunk": fused_decode_chunk.lower(
               params, pc.pools, packed, spec, 8),
           f"{family}.prefill": mod.prefill.lower(
               params, jnp.zeros((1, 16), jnp.int32), cfg)}
    if family == "hybrid":      # a prompt through the token-block map
        monkeypatch.setattr(qwen3_next, "MOE_TOKEN_BLOCK", 8)
        out["hybrid.prefill.blocked"] = mod.prefill.lower(
            params, jnp.zeros((1, 20), jnp.int32), cfg)
    return out


@pytest.mark.skipif(jax.__version__ != "0.9.0",
                    reason="the recorded text is jax 0.9.0's")
@pytest.mark.parametrize("family", ["gpt2", "latent", "hybrid"])
def test_a_spec_without_window_layers_lowers_to_the_text_it_had(
        gpt, family, monkeypatch):
    """GPT-2's, the latent family's and the hybrid's programs (cells 2 and
    4, 3, 6) are the recorded ones to the byte: window layers are static on
    `spec.window > 0`, and what the sliding-window family shares with the
    hybrid one (`_rows_attention`, `map_token_blocks`) lowers as it did.
    GPT-2's are still the parent of PR 35's; the two expert families' were
    taken anew by PR 36 (the comment above)."""
    found = {name: _sha(low) for name, low in
             _lowered_by_family(family, gpt[1], monkeypatch).items()}
    assert found == {k: v for k, v in PARENT_OF_PR_35.items()
                     if k.startswith(family + ".")}


def test_no_serving_module_names_a_family():
    """The scan, the cache manager, the engine and the scheduler import
    `models.spec` and no family, the sliding-window one included."""
    import ast
    import paddle_tpu.inference.serving as serving
    from pathlib import Path
    families = ("mellum", "qwen3_next", "pangu_moe")
    for name in ("attention", "paged_cache", "engine", "scheduler"):
        path = Path(serving.__file__).parent / f"{name}.py"
        tree = ast.parse(path.read_text())
        named = [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
                 for a in n.names]
        named += [f"{n.module}.{a.name}" for n in ast.walk(tree)
                  if isinstance(n, ast.ImportFrom) for a in n.names]
        assert not [n for n in named if any(f in n for f in families)], name
