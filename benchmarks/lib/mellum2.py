"""From `configs/mellum2-*.json` to the program's family
(`paddle_tpu/models/mellum.py`) with weights made on the device from the
seed, in the served dtype and in ONE jitted call: 3.8 G parameters have no
room for a float32 copy beside the cache, so nothing is made on the host or
in float32 first. Matrices N(0, `initializer_range`), norms 1
(`mellum.init_value`), the router float32.
"""
from __future__ import annotations

import numpy as np

#: the published keys the program's config takes as they are
KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
        "num_attention_heads", "num_key_value_heads", "head_dim",
        "sliding_window", "moe_intermediate_size", "num_experts",
        "num_experts_per_tok", "rms_norm_eps")
#: `rope_parameters.full_attention` -> the program's `yarn_*`
YARN = ("factor", "original_max_position_embeddings", "beta_fast",
        "beta_slow", "attention_factor")


def program_config(config: dict, rehearse: bool):
    """The file's keys as a `MellumConfig`: every layer whole (all experts,
    all heads, the whole vocabulary), at rehearsal with the `rehearsal`
    block laid over them."""
    from paddle_tpu.models.mellum import MellumConfig
    c = dict(config)
    context, dtype = config["assumed"]["max_context"], \
        config["assumed"]["served_dtype"]
    if rehearse:
        c.update(config["rehearsal"])
        context = c["max_context"]
        dtype = "float32"           # the CPU multiplies bfloat16 slowly
    rope = c["rope_parameters"]
    full = rope["full_attention"]
    assert rope["sliding_attention"]["rope_theta"] == full["rope_theta"]
    return MellumConfig(
        **{k: c[k] for k in KEYS}, layer_types=tuple(c["layer_types"]),
        rope_theta=float(full["rope_theta"]),
        **{"yarn_" + k: full[k] for k in YARN},
        max_seq_len=context, dtype=dtype)


def seeded_weights(cfg, seed: int, std: float = 0.02) -> dict:
    """{name: array} for `param_shapes(cfg)`, a pure function of the seed,
    each array drawn in float32 and rounded to its dtype inside one jitted
    program (XLA fuses the draw with the rounding: no float32 array of a
    whole matrix is kept). Any whole number is a seed."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models.mellum import init_value, param_shapes

    shapes = param_shapes(cfg)

    def make(key):
        out = {}
        for i, (name, (shape, dtype)) in enumerate(sorted(shapes.items())):
            how = init_value(name)
            out[name] = jnp.full(shape, how[1], dtype) if how else (
                jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32) * jnp.float32(std)
            ).astype(dtype)
        return out

    key = jax.random.PRNGKey(np.uint32(seed & 0xFFFFFFFF))
    key = jax.random.fold_in(key, np.uint32((seed >> 32) & 0xFFFFFFFF))
    return jax.jit(make)(key)


def build_engine(config: dict, seed: int, engine_config, rehearse: bool):
    """`LLMEngine` through its own constructor: the parameter dict and the
    family's spec, the same one `from_model` calls. A program without the
    family (the parent of the PR that added it) stops here, non-zero."""
    from paddle_tpu.inference.serving import LLMEngine
    try:
        from paddle_tpu.models.mellum import serving_spec
        cfg = program_config(config, rehearse)
    except ImportError as e:
        raise SystemExit(f"benchmark: the program cannot serve this "
                         f"configuration's family: {e}")
    params = seeded_weights(cfg, seed, config["assumed"]["initializer_range"])
    return LLMEngine(params, serving_spec(cfg), engine_config), cfg


def work_config(config: dict, cfg) -> dict:
    """What `lib/serve_work_swa.py` computes from: the published keys at
    the size that runs, how many layers of each kind, the experts held
    (all), and the element size of weights and rows."""
    import jax.numpy as jnp
    kinds = cfg.kinds
    return {**{k: getattr(cfg, k) for k in KEYS},
            "window_layers": sum(k == "sliding_attention" for k in kinds),
            "full_layers": sum(k == "full_attention" for k in kinds),
            "experts_held": cfg.held[1],
            "itemsize": jnp.dtype(cfg.dtype).itemsize}
