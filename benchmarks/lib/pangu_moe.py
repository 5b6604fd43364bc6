"""From `configs/openpangu-ultra-moe-*.json` to the program's family
(`paddle_tpu/models/pangu_moe.py`) with weights made on the device from the
seed, in the served dtype and in ONE jitted call: 4.9 G parameters have no
room for a float32 copy (19.7 GB), so nothing is made on the host or in
float32 first. N(0, `initializer_range`), norms at 1, the router float32.
"""
from __future__ import annotations

import numpy as np


def program_config(config: dict, rehearse: bool):
    """The file's keys as a `PanguMoEConfig`: this chip's share (the held
    experts and the vocabulary slice are the file's values), at rehearsal
    with the `rehearsal` block laid over them."""
    from paddle_tpu.models.pangu_moe import PanguMoEConfig
    c = dict(config)
    routed = config["published"]["n_routed_experts"]
    context, dtype = config["assumed"]["max_context"], \
        config["assumed"]["served_dtype"]
    rank = config["deployment"]["expert_parallel"]["rank"]
    if rehearse:
        c.update(config["rehearsal"])
        routed, context = c["n_routed_experts_total"], c["max_context"]
        dtype = "float32"           # the CPU multiplies bfloat16 slowly
    keys = ("vocab_size", "hidden_size", "num_hidden_layers",
            "first_k_dense_replace", "num_attention_heads", "q_lora_rank",
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "intermediate_size", "moe_intermediate_size",
            "num_experts_per_tok", "n_shared_experts",
            "routed_scaling_factor", "rms_norm_eps")
    return PanguMoEConfig(
        **{k: c[k] for k in keys}, n_routed_experts=routed,
        rope_theta=float(c["rope_theta"]), max_seq_len=context,
        held_experts=(rank * c["n_routed_experts"], c["n_routed_experts"]),
        dtype=dtype)


def seeded_weights(cfg, seed: int, std: float = 0.02) -> dict:
    """{name: array} for `param_shapes(cfg)`, a pure function of the seed,
    each array drawn in float32 and rounded to its dtype inside one jitted
    program (XLA fuses the draw with the rounding: no float32 array of a
    whole matrix is kept). Any whole number is a seed."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models.pangu_moe import param_shapes

    shapes = param_shapes(cfg)

    def make(key):
        out = {}
        for i, (name, (shape, dtype)) in enumerate(sorted(shapes.items())):
            if len(shape) == 1:
                out[name] = jnp.ones(shape, dtype)
            else:
                out[name] = (jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32)
                    * jnp.float32(std)).astype(dtype)
        return out

    key = jax.random.PRNGKey(np.uint32(seed & 0xFFFFFFFF))
    key = jax.random.fold_in(key, np.uint32((seed >> 32) & 0xFFFFFFFF))
    return jax.jit(make)(key)


def build_engine(config: dict, seed: int, engine_config, rehearse: bool):
    """`LLMEngine` through its own constructor: the parameter dict and the
    family's spec, the same one `from_model` calls."""
    from paddle_tpu.inference.serving import LLMEngine
    try:
        from paddle_tpu.models.pangu_moe import serving_spec
    except ImportError as e:
        raise SystemExit(f"benchmark: the program cannot serve this "
                         f"configuration's family: {e}")
    cfg = program_config(config, rehearse)
    params = seeded_weights(cfg, seed, config["assumed"]["initializer_range"])
    return LLMEngine(params, serving_spec(cfg), engine_config), cfg


def work_config(config: dict, cfg) -> dict:
    """What `lib/serve_work.py` computes from: the file's published keys at
    the size that runs, the number of experts the router scores, and the
    bytes a cached position costs ((kv_lora_rank + qk_rope_head_dim) x
    element size x layers)."""
    import jax.numpy as jnp
    keys = ("hidden_size", "num_attention_heads", "q_lora_rank",
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "intermediate_size", "moe_intermediate_size",
            "n_shared_experts", "num_hidden_layers",
            "first_k_dense_replace", "vocab_size")
    itemsize = jnp.dtype(cfg.dtype).itemsize
    return {**{k: getattr(cfg, k) for k in keys},
            "routed_experts_scored": cfg.n_routed_experts,
            "itemsize": itemsize,
            "cache_bytes_per_token":
                cfg.latent_width * itemsize * cfg.num_hidden_layers}
