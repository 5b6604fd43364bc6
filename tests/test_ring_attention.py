"""Ring attention (context parallelism) — parity vs full attention.

The long-context mechanism SURVEY.md §2.3 flags: Q sequence-sharded over
a mesh axis, K/V rotating via ppermute, online-softmax accumulation.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import paddle_tpu as paddle
from paddle_tpu.parallel import build_mesh, set_global_mesh, shard_map
from paddle_tpu.parallel.ring_attention import ring_attention


def _full_attention(q, k, v, causal):
    scale = 1.0 / np.sqrt(q.shape[-1])
    s = jnp.einsum("bhtd,bhsd->bhts", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        T = s.shape[-1]
        mask = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
        s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhts,bhsd->bhtd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


def _mesh_sp():
    mesh = build_mesh(dp=1, pp=1, tp=1, sp=8, sharding=1)
    set_global_mesh(mesh)
    return mesh


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_full(causal):
    mesh = _mesh_sp()
    rng = np.random.RandomState(0)
    B, H, T, D = 2, 3, 64, 16
    q = jnp.asarray(rng.randn(B, H, T, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, H, T, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, H, T, D), jnp.float32)

    f = shard_map(
        lambda q, k, v: ring_attention(q, k, v, "sp", causal=causal),
        mesh=mesh, in_specs=P(None, None, "sp", None),
        out_specs=P(None, None, "sp", None))
    got = np.asarray(f(q, k, v))
    want = np.asarray(_full_attention(q, k, v, causal))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_ring_attention_gradients_match_full():
    """jax.grad flows through the ppermute rotation; dq/dk/dv must match
    the full-attention gradients."""
    mesh = _mesh_sp()
    rng = np.random.RandomState(1)
    B, H, T, D = 1, 2, 32, 8
    q = jnp.asarray(rng.randn(B, H, T, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, H, T, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, H, T, D), jnp.float32)
    w = jnp.asarray(rng.randn(B, H, T, D), jnp.float32)  # cotangent seed

    def ring_loss(q, k, v):
        f = shard_map(
            lambda q, k, v: ring_attention(q, k, v, "sp", causal=True),
            mesh=mesh, in_specs=P(None, None, "sp", None),
            out_specs=P(None, None, "sp", None))
        return jnp.sum(f(q, k, v) * w)

    def full_loss(q, k, v):
        return jnp.sum(_full_attention(q, k, v, True) * w)

    g_ring = jax.grad(ring_loss, argnums=(0, 1, 2))(q, k, v)
    g_full = jax.grad(full_loss, argnums=(0, 1, 2))(q, k, v)
    for gr, gf in zip(g_ring, g_full):
        np.testing.assert_allclose(np.asarray(gr), np.asarray(gf),
                                   rtol=5e-5, atol=5e-5)


def test_gpt_context_parallel_loss_parity():
    """GPTConfig(context_parallel=True) routes attention through the
    ring over the 'sp' axis; 3-step training losses must match the dense
    attention path on the same mesh."""
    import paddle_tpu.optimizer as opt
    from paddle_tpu.models.gpt import GPT, GPTConfig, gpt_loss_fn
    from paddle_tpu.parallel import ShardedTrainStep

    rng = np.random.RandomState(3)
    xs = [rng.randint(0, 128, (4, 32)) for _ in range(3)]
    ys = [rng.randint(0, 128, (4, 32)) for _ in range(3)]

    def run(cp):
        mesh = build_mesh(dp=1, pp=1, tp=1, sp=8, sharding=1)
        set_global_mesh(mesh)
        paddle.seed(0)
        cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=2,
                        num_heads=4, max_seq_len=32, context_parallel=cp)
        model = GPT(cfg)
        optim = opt.AdamW(1e-3, parameters=model.parameters())
        step = ShardedTrainStep(model, gpt_loss_fn, optim, mesh=mesh)
        return [float(step(paddle.to_tensor(x), paddle.to_tensor(y))
                      .numpy()) for x, y in zip(xs, ys)]

    ring = run(True)
    dense = run(False)
    np.testing.assert_allclose(ring, dense, rtol=2e-4, atol=2e-4)


def test_gpt_context_parallel_composes_with_dp():
    """Partial-manual shard_map (axis_names={'sp'}): dp stays in GSPMD
    auto mode, so ring attention composes with data parallelism instead
    of replicating the batch."""
    import paddle_tpu.optimizer as opt
    from paddle_tpu.models.gpt import GPT, GPTConfig, gpt_loss_fn
    from paddle_tpu.parallel import ShardedTrainStep

    rng = np.random.RandomState(4)
    xs = [rng.randint(0, 128, (4, 32)) for _ in range(2)]
    ys = [rng.randint(0, 128, (4, 32)) for _ in range(2)]

    def run(cp):
        mesh = build_mesh(dp=2, pp=1, tp=1, sp=4, sharding=1)
        set_global_mesh(mesh)
        paddle.seed(0)
        cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=2,
                        num_heads=4, max_seq_len=32, context_parallel=cp)
        model = GPT(cfg)
        optim = opt.AdamW(1e-3, parameters=model.parameters())
        step = ShardedTrainStep(model, gpt_loss_fn, optim, mesh=mesh)
        return [float(step(paddle.to_tensor(x), paddle.to_tensor(y))
                      .numpy()) for x, y in zip(xs, ys)]

    np.testing.assert_allclose(run(True), run(False), rtol=2e-4,
                               atol=2e-4)


def test_ring_attention_bf16_long_sequence():
    """bf16 inputs at a longer sequence: fp32 online accumulation keeps
    the result at bf16 tolerance of the fp32 full-attention oracle."""
    mesh = _mesh_sp()
    rng = np.random.RandomState(2)
    B, H, T, D = 1, 2, 256, 32
    qf = rng.randn(B, H, T, D).astype(np.float32)
    kf = rng.randn(B, H, T, D).astype(np.float32)
    vf = rng.randn(B, H, T, D).astype(np.float32)
    q, k, v = (jnp.asarray(a, jnp.bfloat16) for a in (qf, kf, vf))

    f = shard_map(
        lambda q, k, v: ring_attention(q, k, v, "sp", causal=True),
        mesh=mesh, in_specs=P(None, None, "sp", None),
        out_specs=P(None, None, "sp", None))
    got = np.asarray(f(q, k, v)).astype(np.float32)
    want = np.asarray(_full_attention(
        jnp.asarray(qf), jnp.asarray(kf), jnp.asarray(vf), True))
    np.testing.assert_allclose(got, want, rtol=3e-2, atol=3e-2)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_attention_matches_full(causal):
    """All-to-all (Ulysses) sequence parallelism: heads scatter, sequence
    gathers, full attention per head subset, restore — must equal full
    attention."""
    from paddle_tpu.parallel import ulysses_attention

    mesh = _mesh_sp()
    rng = np.random.RandomState(6)
    B, H, T, D = 2, 8, 64, 16  # H == sp size: one head per device
    q = jnp.asarray(rng.randn(B, H, T, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, H, T, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, H, T, D), jnp.float32)

    f = shard_map(
        lambda q, k, v: ulysses_attention(q, k, v, "sp", causal=causal),
        mesh=mesh, in_specs=P(None, None, "sp", None),
        out_specs=P(None, None, "sp", None))
    got = np.asarray(f(q, k, v))
    want = np.asarray(_full_attention(q, k, v, causal))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_ulysses_gradients_match_full():
    from paddle_tpu.parallel import ulysses_attention

    mesh = _mesh_sp()
    rng = np.random.RandomState(7)
    B, H, T, D = 1, 8, 32, 8
    q = jnp.asarray(rng.randn(B, H, T, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, H, T, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, H, T, D), jnp.float32)
    w = jnp.asarray(rng.randn(B, H, T, D), jnp.float32)

    def u_loss(q, k, v):
        f = shard_map(
            lambda q, k, v: ulysses_attention(q, k, v, "sp", causal=True),
            mesh=mesh, in_specs=P(None, None, "sp", None),
            out_specs=P(None, None, "sp", None))
        return jnp.sum(f(q, k, v) * w)

    def full_loss(q, k, v):
        return jnp.sum(_full_attention(q, k, v, True) * w)

    g_u = jax.grad(u_loss, argnums=(0, 1, 2))(q, k, v)
    g_f = jax.grad(full_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_u, g_f):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-5, atol=5e-5)
