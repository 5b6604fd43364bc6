"""From a configuration file to the program's model, with weights made on
the device from the seed.

The configuration files under benchmarks/configs/ hold the published
config.json keys (n_embd, n_layer, ...). `gpt_config_kwargs` maps them to
`paddle_tpu.models.gpt.GPTConfig`; `seeded_weights` makes every parameter
in ONE jitted call (GPT-2's published initialisation: N(0, 0.02), the two
residual projections scaled by 1/sqrt(2 * n_layer), LayerNorm at 1 and 0,
biases 0), stacked by shape so that the program has seven random draws and
not three hundred.
"""
from __future__ import annotations

import math

import numpy as np


def sizes(config: dict, rehearse: bool) -> dict:
    """The published sizes, or with --rehearse the file's `rehearsal` block
    laid over them."""
    keys = ("n_embd", "n_layer", "n_head", "n_positions", "vocab_size")
    out = {k: config[k] for k in keys}
    if rehearse:
        out.update(config["rehearsal"])
    return out


def gpt_config_kwargs(size: dict) -> dict:
    return dict(vocab_size=size["vocab_size"], hidden_size=size["n_embd"],
                num_layers=size["n_layer"], num_heads=size["n_head"],
                max_seq_len=size["n_positions"])


def _kind(name: str, shape) -> str:
    if name.endswith(".bias"):
        return "zeros"
    if len(shape) == 1:
        return "ones"                      # LayerNorm scale
    if name.endswith(("attn.out.weight", "mlp.down.weight")):
        return "residual"
    return "normal"


def seeded_weights(shapes: dict, seed: int, n_layer: int, dtype):
    """{name: array of `dtype`} for {name: shape}, a pure function of the
    seed. Any whole number is a seed: it is folded in 32 bits at a time."""
    import jax
    import jax.numpy as jnp

    names = sorted(shapes)
    groups = {}                            # (kind, shape) -> [names]
    for n in names:
        shape = tuple(shapes[n])
        groups.setdefault((_kind(n, shape), shape), []).append(n)
    std = {"normal": 0.02, "residual": 0.02 / math.sqrt(2 * n_layer)}

    def make(key):
        out = {}
        for g, ((kind, shape), members) in enumerate(sorted(groups.items())):
            if kind in std:
                block = jax.random.normal(
                    jax.random.fold_in(key, g), (len(members),) + shape,
                    jnp.float32) * jnp.float32(std[kind])
                block = block.astype(dtype)
            else:
                block = jnp.full((len(members),) + shape,
                                 1 if kind == "ones" else 0, dtype)
            for i, n in enumerate(members):
                out[n] = block[i]
        return out

    key = jax.random.PRNGKey(np.uint32(seed & 0xFFFFFFFF))
    key = jax.random.fold_in(key, np.uint32((seed >> 32) & 0xFFFFFFFF))
    return jax.jit(make)(key)


def build_model(size: dict, seed: int):
    """The program's GPT at `size`, float32, its parameters replaced by
    `seeded_weights` (the constructor's own initialisation cannot be
    skipped; it is overwritten)."""
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPT, GPTConfig

    paddle.seed(seed & 0x7FFFFFFF)
    model = GPT(GPTConfig(**gpt_config_kwargs(size)))
    named = dict(model.named_parameters())
    weights = seeded_weights({k: tuple(p.shape) for k, p in named.items()},
                             seed, size["n_layer"], jnp.float32)
    for k, p in named.items():
        p._value = weights[k]
    return model
