"""From `configs/ling-3.0-flash-*.json` to the program's family
(`paddle_tpu/models/ling_flash.py`) with weights made on the device from
the seed, in the served dtype and in ONE jitted call: 5.2 G parameters
have no room for a float32 copy beside the cache, so nothing is made on the
host or in float32 first. Matrices N(0, `initializer_range`), the rest as
the family's `init_value` says (norms 1, `A_log` and `dt_bias` uniform, the
expert bias N(0, 0.1)), the router and its bias float32.
"""
from __future__ import annotations

import numpy as np

#: the published keys the program's config takes as they are
KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
        "layer_group_size", "first_k_dense_replace", "num_attention_heads",
        "head_dim", "short_conv_kernel_size", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "intermediate_size", "moe_intermediate_size",
        "moe_shared_expert_intermediate_size", "num_experts_per_tok",
        "n_group", "topk_group", "routed_scaling_factor", "rms_norm_eps")


def program_config(config: dict, rehearse: bool):
    """The file's keys as a `LingFlashConfig`: this chip's share (the held
    experts and the vocabulary slice are the file's values; the held layers
    are the published ones `published_layers` names), at rehearsal with the
    `rehearsal` block laid over them."""
    from paddle_tpu.models.ling_flash import LingFlashConfig
    c = dict(config)
    scored = config["published"]["num_experts"]
    context, dtype = config["assumed"]["max_context"], \
        config["assumed"]["served_dtype"]
    rank = config["deployment"]["expert_parallel"]["rank"]
    if rehearse:
        c.update(config["rehearsal"])
        scored, context = c["num_experts_total"], c["max_context"]
        dtype = "float32"           # the CPU multiplies bfloat16 slowly
    return LingFlashConfig(
        **{k: c[k] for k in KEYS}, num_experts=scored,
        published_layers=tuple(c["published_layers"]),
        kda_lower_bound=float(c["kda_lower_bound"]),
        rope_theta=float(c["rope_theta"]),
        expert_swiglu_limits=tuple(c["expert_swiglu_limit_list"]),
        shared_swiglu_limits=tuple(c["share_expert_swiglu_limit_list"]),
        max_seq_len=context,
        held_experts=(rank * c["num_experts"], c["num_experts"]),
        dtype=dtype)


def seeded_weights(cfg, seed: int, std: float = 0.02) -> dict:
    """{name: array} for `param_shapes(cfg)`, a pure function of the seed,
    each array drawn in float32 and rounded to its dtype inside one jitted
    program. Any whole number is a seed."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models.ling_flash import init_value, param_shapes

    shapes = param_shapes(cfg)

    def make(key):
        out = {}
        for i, (name, (shape, dtype)) in enumerate(sorted(shapes.items())):
            how, at = init_value(name), jax.random.fold_in(key, i)
            if how is None or how[0] == "normal":
                scale = std if how is None else how[1]
                out[name] = (jax.random.normal(at, shape, jnp.float32)
                             * jnp.float32(scale)).astype(dtype)
            elif how[0] == "constant":
                out[name] = jnp.full(shape, how[1], dtype)
            else:
                out[name] = jax.random.uniform(
                    at, shape, jnp.float32, how[1], how[2]).astype(dtype)
        return out

    key = jax.random.PRNGKey(np.uint32(seed & 0xFFFFFFFF))
    key = jax.random.fold_in(key, np.uint32((seed >> 32) & 0xFFFFFFFF))
    return jax.jit(make)(key)


def build_engine(config: dict, seed: int, engine_config, rehearse: bool):
    """`LLMEngine` through its own constructor: the parameter dict and the
    family's spec, the same one `from_model` calls."""
    from paddle_tpu.inference.serving import LLMEngine
    try:
        from paddle_tpu.models.ling_flash import serving_spec
    except ImportError as e:
        raise SystemExit(f"benchmark: the program cannot serve this "
                         f"configuration's family: {e}")
    cfg = program_config(config, rehearse)
    params = seeded_weights(cfg, seed, config["assumed"]["initializer_range"])
    return LLMEngine(params, serving_spec(cfg), engine_config), cfg


def work_config(config: dict, cfg) -> dict:
    """What `lib/serve_work_kda.py` computes from: the published keys at
    the size that runs, each layer's mixer, the experts the router scores
    and those held, and the element sizes (weights and rows; the recurrent
    state float32)."""
    import jax.numpy as jnp
    return {**{k: getattr(cfg, k) for k in KEYS},
            "mla_layers": [i for i in range(cfg.num_hidden_layers)
                           if cfg.is_mla(i)],
            "experts_scored": cfg.num_experts, "experts_held": cfg.held[1],
            "itemsize": jnp.dtype(cfg.dtype).itemsize, "state_itemsize": 4}
