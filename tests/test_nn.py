"""nn layer/functional tests (reference test analogues:
python/paddle/fluid/tests/unittests/test_layers.py, test_conv2d_op.py,
test_batch_norm_op.py, test_transformer_api.py, test_rnn_*.py — here
checked against torch CPU as the numeric oracle, the same role the
reference's numpy reference implementations play in OpTest)."""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F

torch = pytest.importorskip("torch")
import torch.nn.functional as tF  # noqa: E402

import jax  # noqa: E402

# vs-torch-CPU tolerances: TPU hardware transcendentals (erf/tanh/exp
# approximations) and float reassociation differ from torch's CPU libm
# at the 1e-5 level (measured: activations 2.2e-05 max abs, pooling
# 1.3e-08 under a strict-equal default), so the real-chip lane runs the
# same oracles at a looser tolerance
_ATOL = 1e-4 if jax.default_backend() == "tpu" else 1e-5
_RTOL = 1e-3 if jax.default_backend() == "tpu" else 1e-4


def test_linear_matches_torch():
    x = np.random.randn(4, 6).astype("float32")
    w = np.random.randn(6, 3).astype("float32")
    b = np.random.randn(3).astype("float32")
    out = F.linear(paddle.to_tensor(x), paddle.to_tensor(w),
                   paddle.to_tensor(b)).numpy()
    ref = tF.linear(torch.tensor(x), torch.tensor(w.T),
                    torch.tensor(b)).numpy()
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("stride,padding,dilation,groups", [
    (1, 0, 1, 1), (2, 1, 1, 1), (1, 2, 2, 1), (1, 1, 1, 3),
])
def test_conv2d_matches_torch(stride, padding, dilation, groups):
    cin, cout = 6, 9
    x = np.random.randn(2, cin, 10, 10).astype("float32")
    w = np.random.randn(cout, cin // groups, 3, 3).astype("float32")
    out = F.conv2d(paddle.to_tensor(x), paddle.to_tensor(w), None,
                   stride=stride, padding=padding, dilation=dilation,
                   groups=groups).numpy()
    ref = tF.conv2d(torch.tensor(x), torch.tensor(w), None, stride=stride,
                    padding=padding, dilation=dilation,
                    groups=groups).numpy()
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4)


def test_conv2d_grad_matches_torch():
    x = np.random.randn(2, 3, 8, 8).astype("float32")
    w = np.random.randn(4, 3, 3, 3).astype("float32")
    px = paddle.to_tensor(x, stop_gradient=False)
    pw = paddle.to_tensor(w, stop_gradient=False)
    F.conv2d(px, pw, padding=1).sum().backward()
    tx = torch.tensor(x, requires_grad=True)
    tw = torch.tensor(w, requires_grad=True)
    tF.conv2d(tx, tw, padding=1).sum().backward()
    np.testing.assert_allclose(px.grad.numpy(), tx.grad.numpy(), rtol=1e-3,
                               atol=1e-4)
    np.testing.assert_allclose(pw.grad.numpy(), tw.grad.numpy(), rtol=1e-3,
                               atol=1e-4)


def test_conv_transpose_matches_torch():
    x = np.random.randn(2, 3, 8, 8).astype("float32")
    w = np.random.randn(3, 5, 4, 4).astype("float32")
    out = F.conv2d_transpose(paddle.to_tensor(x), paddle.to_tensor(w),
                             stride=2, padding=1).numpy()
    ref = tF.conv_transpose2d(torch.tensor(x), torch.tensor(w), stride=2,
                              padding=1).numpy()
    np.testing.assert_allclose(out, ref, rtol=1e-3, atol=1e-3)


def test_pooling_matches_torch():
    x = np.random.randn(2, 3, 8, 8).astype("float32")
    np.testing.assert_allclose(
        F.max_pool2d(paddle.to_tensor(x), 2, 2).numpy(),
        tF.max_pool2d(torch.tensor(x), 2, 2).numpy())
    np.testing.assert_allclose(
        F.avg_pool2d(paddle.to_tensor(x), 3, 2, 1).numpy(),
        tF.avg_pool2d(torch.tensor(x), 3, 2, 1,
                      count_include_pad=False).numpy(), rtol=1e-5,
        atol=1e-6)  # measured TPU deviation 1.3e-08; keep a tight oracle
    np.testing.assert_allclose(
        F.adaptive_avg_pool2d(paddle.to_tensor(x), 3).numpy(),
        tF.adaptive_avg_pool2d(torch.tensor(x), 3).numpy(), rtol=1e-4,
        atol=1e-5)


def test_norms_match_torch():
    x = np.random.randn(4, 6, 5, 5).astype("float32")
    g = np.random.rand(6).astype("float32") + 0.5
    b = np.random.randn(6).astype("float32")
    out = F.group_norm(paddle.to_tensor(x), 3, 1e-5, paddle.to_tensor(g),
                       paddle.to_tensor(b)).numpy()
    ref = tF.group_norm(torch.tensor(x), 3, torch.tensor(g),
                        torch.tensor(b)).numpy()
    np.testing.assert_allclose(out, ref, rtol=1e-3, atol=1e-4)
    out = F.instance_norm(paddle.to_tensor(x), weight=paddle.to_tensor(g),
                          bias=paddle.to_tensor(b)).numpy()
    ref = tF.instance_norm(torch.tensor(x), weight=torch.tensor(g),
                           bias=torch.tensor(b)).numpy()
    np.testing.assert_allclose(out, ref, rtol=1e-3, atol=1e-4)


def test_batch_norm_train_and_eval():
    bn = nn.BatchNorm2D(3, momentum=0.9)
    x = np.random.randn(8, 3, 4, 4).astype("float32")
    tb = torch.nn.BatchNorm2d(3, momentum=0.1)
    out = bn(paddle.to_tensor(x)).numpy()
    ref = tb(torch.tensor(x)).detach().numpy()
    np.testing.assert_allclose(out, ref, rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(bn._mean.numpy(), tb.running_mean.numpy(),
                               rtol=1e-3, atol=1e-5)
    np.testing.assert_allclose(bn._variance.numpy(),
                               tb.running_var.numpy(), rtol=1e-3, atol=1e-4)
    bn.eval()
    tb.eval()
    out = bn(paddle.to_tensor(x)).numpy()
    ref = tb(torch.tensor(x)).detach().numpy()
    np.testing.assert_allclose(out, ref, rtol=1e-3, atol=1e-4)


def test_fused_bn_act_matches_composed():
    """batch_norm_act (residual-light fused bn+(add+)relu, the
    fuse_bn_act_pass.cc / fused_bn_add_activation_op.cc analogue) must
    match composed bn -> (+z) -> relu in outputs, grads and running
    stats."""
    np.random.seed(7)
    x_np = np.random.randn(4, 6, 5, 5).astype("float32")
    z_np = np.random.randn(4, 6, 5, 5).astype("float32")
    w_np = (np.random.rand(6) + 0.5).astype("float32")
    b_np = (np.random.randn(6) * 0.1).astype("float32")

    for use_add in (False, True):
        ts = []
        for fused in (False, True):
            x = paddle.to_tensor(x_np); x.stop_gradient = False
            z = paddle.to_tensor(z_np); z.stop_gradient = False
            w = paddle.to_tensor(w_np); w.stop_gradient = False
            b = paddle.to_tensor(b_np); b.stop_gradient = False
            rm = paddle.to_tensor(np.zeros(6, "float32"))
            rv = paddle.to_tensor(np.ones(6, "float32"))
            if fused:
                out = F.batch_norm_act(x, rm, rv, w, b, training=True,
                                       add=z if use_add else None)
            else:
                out = F.batch_norm(x, rm, rv, w, b, training=True)
                if use_add:
                    out = out + z
                out = F.relu(out)
            (out * out).sum().backward()
            ts.append((out, x.grad, z.grad if use_add else None,
                       w.grad, b.grad, rm, rv))
        for a, bb in zip(ts[0], ts[1]):
            if a is None:
                assert bb is None
                continue
            np.testing.assert_allclose(a.numpy(), bb.numpy(),
                                       rtol=2e-5, atol=2e-5)
    # eval mode goes through the inference path
    bn_args = (paddle.to_tensor(np.zeros(6, "float32")),
               paddle.to_tensor(np.ones(6, "float32")))
    xe = paddle.to_tensor(x_np)
    fe = F.batch_norm_act(xe, *bn_args, paddle.to_tensor(w_np),
                          paddle.to_tensor(b_np), training=False)
    ce = F.relu(F.batch_norm(xe, *bn_args, paddle.to_tensor(w_np),
                             paddle.to_tensor(b_np), training=False))
    np.testing.assert_allclose(fe.numpy(), ce.numpy(), rtol=1e-6)


def test_resnet_blocks_custom_norm_and_frozen_stats():
    """the fused bn+relu fast path must not hijack custom norm layers or
    frozen-stats BN (use_global_stats=True keeps running stats untouched
    and normalizes with them even in train mode)."""
    import functools
    from paddle_tpu.vision.models.resnet import BottleneckBlock
    # custom norm layer: GroupNorm has none of BatchNorm's private attrs
    blk = BottleneckBlock(64, 16, norm_layer=lambda c: nn.GroupNorm(4, c))
    out = blk(paddle.to_tensor(np.random.randn(2, 64, 8, 8).astype("float32")))
    assert out.shape == [2, 64, 8, 8]
    # frozen-stats BN: running stats must survive a train-mode forward
    frozen = functools.partial(nn.BatchNorm2D, use_global_stats=True)
    blk2 = BottleneckBlock(64, 16, norm_layer=frozen)
    rm_before = blk2.bn1._mean.numpy().copy()
    blk2.train()
    blk2(paddle.to_tensor(np.random.randn(2, 64, 8, 8).astype("float32")))
    np.testing.assert_array_equal(blk2.bn1._mean.numpy(), rm_before)


def test_fused_bn_act_explicit_false_global_stats_in_eval():
    """use_global_stats=False is NOT the same as None: in eval mode it
    still normalizes with batch stats and updates the EMA (batch_norm
    semantics). The fused path must match the composed path exactly."""
    np.random.seed(3)
    x_np = np.random.randn(4, 6, 5, 5).astype("float32") + 2.0
    outs, stats = [], []
    for fused in (False, True):
        rm = paddle.to_tensor(np.zeros(6, "float32"))
        rv = paddle.to_tensor(np.ones(6, "float32"))
        x = paddle.to_tensor(x_np)
        if fused:
            out = F.batch_norm_act(x, rm, rv, training=False,
                                   use_global_stats=False)
        else:
            out = F.relu(F.batch_norm(x, rm, rv, training=False,
                                      use_global_stats=False))
        outs.append(out.numpy())
        stats.append((rm.numpy(), rv.numpy()))
    np.testing.assert_allclose(outs[0], outs[1], rtol=2e-5, atol=2e-5)
    for a, b in zip(stats[0], stats[1]):
        np.testing.assert_allclose(a, b, rtol=1e-6)
        assert not np.allclose(a, 0.0) or not np.allclose(b, 1.0)
    # and the EMA actually moved (mean shifted toward the +2 batch mean)
    assert stats[0][0].mean() > 0.05


def test_fused_bn_act_broadcastable_add_backward():
    """batch_norm_act with a broadcastable residual (e.g. a per-channel
    bias [1, C, 1, 1]) must reduce the z-cotangent to z's shape instead of
    crashing in the custom-vjp backward."""
    np.random.seed(4)
    x_np = np.random.randn(4, 6, 5, 5).astype("float32")
    z_np = np.random.randn(1, 6, 1, 1).astype("float32")
    grads = []
    for fused in (False, True):
        x = paddle.to_tensor(x_np); x.stop_gradient = False
        z = paddle.to_tensor(z_np); z.stop_gradient = False
        rm = paddle.to_tensor(np.zeros(6, "float32"))
        rv = paddle.to_tensor(np.ones(6, "float32"))
        if fused:
            out = F.batch_norm_act(x, rm, rv, training=True, add=z)
        else:
            out = F.relu(F.batch_norm(x, rm, rv, training=True) + z)
        (out * out).sum().backward()
        grads.append((x.grad.numpy(), z.grad.numpy()))
    assert grads[1][1].shape == z_np.shape
    np.testing.assert_allclose(grads[0][0], grads[1][0], rtol=2e-5,
                               atol=2e-5)
    np.testing.assert_allclose(grads[0][1], grads[1][1], rtol=2e-5,
                               atol=2e-4)


def test_attention_path_routing_by_seq_len():
    """Short sequences must route to the composed path DELIBERATELY (no
    fallback warning): at T=128/d=64 the flash custom-call's layout copies
    cost more than the tiny score matrix saves (BERT-base measured +71%
    composed on v5e). Long sequences keep trying flash."""
    import warnings
    from paddle_tpu.nn.functional import attention as attn_mod
    q = paddle.to_tensor(np.random.randn(2, 128, 4, 64).astype("float32"))
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)  # no fallback warn
        out = F.scaled_dot_product_attention(q, q, q)
    assert attn_mod.LAST_PATH == "composed"
    assert out.shape == [2, 128, 4, 64]
    # below the threshold flag, flash is attempted (falls back loudly on
    # CPU where the pallas kernel is unsupported — that IS the warning
    # path, proving the attempt happened)
    from paddle_tpu.core import flags as _flags
    prev_min_seq = _flags.flag("flash_attention_min_seq")
    paddle.set_flags({"FLAGS_flash_attention_min_seq": 64})
    try:
        import jax
        attn_mod._warned_fallback = False
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            F.scaled_dot_product_attention(q, q, q)
        if jax.default_backend() != "tpu":
            assert attn_mod.LAST_PATH == "composed"
            assert any("flash attention kernel unavailable" in str(x.message)
                       for x in w)
        else:
            assert attn_mod.LAST_PATH == "flash"
    finally:
        paddle.set_flags({"FLAGS_flash_attention_min_seq": prev_min_seq})
        attn_mod._warned_fallback = False


@pytest.mark.parametrize("packed", [False, True],
                         ids=["flash", "packed_pairs"])
def test_attention_kernel_failure_raises(monkeypatch, packed):
    """A kernel that fails on a geometry its gate accepted is a broken
    kernel: scaled_dot_product_attention raises, it does not hand back the
    composed result (which would pass every test at ~1.5x the time)."""
    from paddle_tpu.ops.pallas import flash_attention as fa

    def broken(*a, **k):
        raise RuntimeError("Mosaic refused the kernel")
    monkeypatch.setattr(fa, "supported", lambda *a, **k: True)
    monkeypatch.setattr(fa, "flash_attention", broken)
    monkeypatch.setattr(fa, "_packed_flash", broken)
    # [B, H, T, D] heads-major, T past flash_attention_min_seq
    q = paddle.to_tensor(np.zeros((1, 2, 512, 128), "float32"))
    with pytest.raises(RuntimeError, match="Mosaic refused"):
        F.scaled_dot_product_attention(q, q, q, is_causal=True,
                                       _heads_major=True,
                                       _packed_pairs=packed)


def test_losses_match_torch():
    logits = np.random.randn(8, 5).astype("float32")
    labels = np.random.randint(0, 5, 8)
    np.testing.assert_allclose(
        F.cross_entropy(paddle.to_tensor(logits),
                        paddle.to_tensor(labels)).numpy(),
        tF.cross_entropy(torch.tensor(logits),
                         torch.tensor(labels)).numpy(), rtol=1e-5)
    x = np.random.rand(6).astype("float32")
    y = (np.random.rand(6) > 0.5).astype("float32")
    np.testing.assert_allclose(
        F.binary_cross_entropy(paddle.to_tensor(x),
                               paddle.to_tensor(y)).numpy(),
        tF.binary_cross_entropy(torch.tensor(x), torch.tensor(y)).numpy(),
        rtol=1e-4)
    lx = np.random.randn(6).astype("float32")
    np.testing.assert_allclose(
        F.binary_cross_entropy_with_logits(paddle.to_tensor(lx),
                                           paddle.to_tensor(y)).numpy(),
        tF.binary_cross_entropy_with_logits(torch.tensor(lx),
                                            torch.tensor(y)).numpy(),
        rtol=1e-5)
    a = np.random.randn(4, 7).astype("float32")
    b = np.random.randn(4, 7).astype("float32")
    np.testing.assert_allclose(
        F.smooth_l1_loss(paddle.to_tensor(a), paddle.to_tensor(b)).numpy(),
        tF.smooth_l1_loss(torch.tensor(a), torch.tensor(b)).numpy(),
        rtol=1e-5)
    np.testing.assert_allclose(
        F.kl_div(paddle.to_tensor(a), paddle.to_tensor(np.abs(b))).numpy(),
        tF.kl_div(torch.tensor(a), torch.tensor(np.abs(b))).numpy(),
        rtol=1e-4, atol=1e-5)


def test_cross_entropy_ignore_index_and_weight():
    logits = np.random.randn(6, 4).astype("float32")
    labels = np.array([0, 1, -100, 3, -100, 2])
    w = np.random.rand(4).astype("float32") + 0.5
    out = F.cross_entropy(paddle.to_tensor(logits),
                          paddle.to_tensor(labels),
                          weight=paddle.to_tensor(w)).numpy()
    ref = tF.cross_entropy(torch.tensor(logits), torch.tensor(labels),
                           weight=torch.tensor(w)).numpy()
    np.testing.assert_allclose(out, ref, rtol=1e-4)


def test_activations_match_torch():
    x = np.random.randn(4, 8).astype("float32")
    cases = [
        (F.gelu, lambda t: tF.gelu(t)),
        (lambda v: F.gelu(v, approximate=True),
         lambda t: tF.gelu(t, approximate="tanh")),
        (F.silu, tF.silu),
        (F.softplus, tF.softplus),
        (F.elu, tF.elu),
        (F.selu, tF.selu),
        (F.hardswish, tF.hardswish),
        (F.mish, tF.mish),
        (lambda v: F.leaky_relu(v, 0.1),
         lambda t: tF.leaky_relu(t, 0.1)),
        (lambda v: F.log_softmax(v, -1),
         lambda t: tF.log_softmax(t, -1)),
    ]
    for mine, ref in cases:
        np.testing.assert_allclose(
            mine(paddle.to_tensor(x)).numpy(),
            ref(torch.tensor(x)).numpy(), rtol=_RTOL, atol=_ATOL)


def test_dropout_semantics():
    x = paddle.ones([1000])
    out = F.dropout(x, 0.5, training=True)
    kept = float((out.numpy() != 0).mean())
    assert 0.35 < kept < 0.65
    np.testing.assert_allclose(out.numpy()[out.numpy() != 0], 2.0)
    out_eval = F.dropout(x, 0.5, training=False)
    np.testing.assert_allclose(out_eval.numpy(), x.numpy())


def test_embedding_grad_and_padding():
    emb = nn.Embedding(10, 4, padding_idx=0)
    ids = paddle.to_tensor(np.array([[1, 0, 2]]))
    out = emb(ids)
    assert float(np.abs(out.numpy()[0, 1]).sum()) == 0.0
    out.sum().backward()
    g = emb.weight.grad.numpy()
    assert g[1].sum() != 0 and g[3].sum() == 0


def test_sdpa_matches_torch():
    q = np.random.randn(2, 8, 2, 16).astype("float32")
    k = np.random.randn(2, 8, 2, 16).astype("float32")
    v = np.random.randn(2, 8, 2, 16).astype("float32")
    out = F.scaled_dot_product_attention(
        paddle.to_tensor(q), paddle.to_tensor(k), paddle.to_tensor(v),
        is_causal=True).numpy()
    tq, tk, tv = (torch.tensor(a).permute(0, 2, 1, 3) for a in (q, k, v))
    ref = tF.scaled_dot_product_attention(
        tq, tk, tv, is_causal=True).permute(0, 2, 1, 3).numpy()
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-5)


def test_transformer_encoder_decoder():
    model = nn.Transformer(d_model=32, nhead=4, num_encoder_layers=2,
                           num_decoder_layers=2, dim_feedforward=64)
    src = paddle.randn([2, 6, 32])
    tgt = paddle.randn([2, 5, 32])
    out = model(src, tgt)
    assert out.shape == [2, 5, 32]
    out.mean().backward()
    assert all(p.grad is not None for p in model.parameters())


def test_rnn_shapes_and_grads():
    for cls, states in [(nn.SimpleRNN, 1), (nn.GRU, 1), (nn.LSTM, 2)]:
        m = cls(5, 7, num_layers=2)
        x = paddle.randn([3, 6, 5])
        y, final = m(x)
        assert y.shape == [3, 6, 7]
        y.sum().backward()
        assert all(p.grad is not None for p in m.parameters())


def test_lstm_cell_matches_torch():
    cell = nn.LSTMCell(4, 6)
    tcell = torch.nn.LSTMCell(4, 6)
    # copy weights
    cell.weight_ih.set_value(tcell.weight_ih.detach().numpy())
    cell.weight_hh.set_value(tcell.weight_hh.detach().numpy())
    cell.bias_ih.set_value(tcell.bias_ih.detach().numpy())
    cell.bias_hh.set_value(tcell.bias_hh.detach().numpy())
    x = np.random.randn(2, 4).astype("float32")
    h0 = np.random.randn(2, 6).astype("float32")
    c0 = np.random.randn(2, 6).astype("float32")
    _, (h, c) = cell(paddle.to_tensor(x),
                     (paddle.to_tensor(h0), paddle.to_tensor(c0)))
    th, tc = tcell(torch.tensor(x), (torch.tensor(h0), torch.tensor(c0)))
    np.testing.assert_allclose(h.numpy(), th.detach().numpy(), rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_allclose(c.numpy(), tc.detach().numpy(), rtol=1e-4,
                               atol=1e-5)


def test_layer_hooks_and_apply():
    m = nn.Linear(3, 3)
    calls = []
    h = m.register_forward_post_hook(lambda l, i, o: calls.append(1))
    m(paddle.ones([2, 3]))
    assert calls
    h.remove()
    m(paddle.ones([2, 3]))
    assert len(calls) == 1
    m.eval()
    assert not m.training
    m.train()
    assert m.training


def test_state_dict_roundtrip():
    m1 = nn.Sequential(nn.Linear(4, 4), nn.BatchNorm1D(4), nn.Linear(4, 2))
    m2 = nn.Sequential(nn.Linear(4, 4), nn.BatchNorm1D(4), nn.Linear(4, 2))
    m2.set_state_dict(m1.state_dict())
    x = paddle.randn([3, 4])
    m1.eval()
    m2.eval()
    np.testing.assert_allclose(m1(x).numpy(), m2(x).numpy(), rtol=1e-6)


def test_clip_grad_by_global_norm():
    m = nn.Linear(3, 3)
    (m(paddle.ones([2, 3])) * 100).sum().backward()
    clip = nn.ClipGradByGlobalNorm(1.0)
    pg = clip([(p, p.grad) for p in m.parameters()])
    total = np.sqrt(sum((g.numpy() ** 2).sum() for _, g in pg))
    np.testing.assert_allclose(total, 1.0, rtol=1e-4)


def test_weight_norm():
    from paddle_tpu.nn.utils import weight_norm, remove_weight_norm
    m = nn.Linear(4, 5)
    w0 = m.weight.numpy().copy()
    weight_norm(m, "weight")
    x = paddle.randn([2, 4])
    y1 = m(x).numpy()
    assert "weight_g" in dict(m.named_parameters())
    remove_weight_norm(m)
    y2 = m(x).numpy()
    np.testing.assert_allclose(y1, y2, rtol=1e-4, atol=1e-5)


def test_flash_lmdi_width1_patch_applies():
    """The vendored width-1 l/m/di rewrite must keep matching the upstream
    pallas flash kernel source (all guards hit); a False here means jax
    drifted and the bwd pass silently reverted to materialising 3x100MB
    broadcast copies per layer (or, worse, the fallback dq-di patch also
    stopped matching)."""
    from paddle_tpu.ops.pallas.flash_attention import _patch_lmdi_width1
    assert _patch_lmdi_width1() is True
