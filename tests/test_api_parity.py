"""Top-level namespace parity with the reference python/paddle/__init__.py
(mechanical audit, same spirit as tests/test_op_coverage.py for ops) +
behaviour tests for the distribution module and fluid-style aliases.
"""
import math
import re

import numpy as np
import pytest

import paddle_tpu as paddle

REF_BASE = "/root/reference/python/paddle"


def _reference_path(*parts):
    """A path under the reference checkout; the test that asks skips where
    the box has no reference (the audit compares names, it runs nothing)."""
    import os
    if not os.path.isdir(REF_BASE):
        pytest.skip(f"no reference checkout at {REF_BASE}")
    return os.path.join(REF_BASE, *parts)


def _names_from_source(path, use_all=False):
    """AST-walk a reference module: every `from X import a as b` exports
    b (the __init__ convention), plus `import paddle.x` submodules; for
    plain module files an explicit __all__ wins when use_all."""
    import ast as _ast
    tree = _ast.parse(open(path).read())
    if use_all:
        for node in tree.body:
            if isinstance(node, _ast.Assign) and any(
                    isinstance(t, _ast.Name) and t.id == "__all__"
                    for t in node.targets):
                try:
                    vals = _ast.literal_eval(node.value)
                    return {n for n in vals if not n.startswith("_")}
                except ValueError:
                    break
    names = set()
    for node in _ast.walk(tree):
        if isinstance(node, _ast.ImportFrom):
            if node.module == "__future__":
                continue
            for a in node.names:
                n = a.asname or a.name
                if n != "*" and not n.startswith("_"):
                    names.add(n)
        elif isinstance(node, _ast.Import):
            for a in node.names:
                if a.name.startswith("paddle."):
                    names.add(a.name.split(".")[1])
    return names


def _reference_top_level_names():
    return _names_from_source(_reference_path("__init__.py"))


def test_top_level_namespace_parity():
    missing = sorted(n for n in _reference_top_level_names()
                     if not hasattr(paddle, n))
    assert not missing, f"paddle.* names missing vs reference: {missing}"


# -- distribution ------------------------------------------------------------

def test_uniform_distribution():
    paddle.seed(0)
    u = paddle.distribution.Uniform(1.0, 3.0)
    s = u.sample([2000])
    arr = s.numpy()
    assert arr.shape == (2000,)
    assert arr.min() >= 1.0 and arr.max() <= 3.0
    assert abs(arr.mean() - 2.0) < 0.1
    np.testing.assert_allclose(float(u.entropy().numpy()),
                               math.log(2.0), rtol=1e-6)
    lp = u.log_prob(paddle.to_tensor([2.0, 5.0]))
    np.testing.assert_allclose(lp.numpy()[0], math.log(0.5), rtol=1e-6)
    assert lp.numpy()[1] == -np.inf  # outside support
    np.testing.assert_allclose(
        u.probs(paddle.to_tensor([2.0])).numpy()[0], 0.5, rtol=1e-6)


def test_normal_distribution_and_kl():
    paddle.seed(0)
    n = paddle.distribution.Normal(0.0, 2.0)
    s = n.sample([4000])
    arr = s.numpy()
    assert abs(arr.mean()) < 0.15 and abs(arr.std() - 2.0) < 0.15
    # entropy: 0.5 log(2 pi e sigma^2)
    want = 0.5 * math.log(2 * math.pi * math.e * 4.0)
    np.testing.assert_allclose(float(n.entropy().numpy()), want, rtol=1e-5)
    v = paddle.to_tensor([1.0])
    want_lp = -0.5 * (1.0 / 4.0) - math.log(2.0) \
        - 0.5 * math.log(2 * math.pi)
    np.testing.assert_allclose(n.log_prob(v).numpy()[0], want_lp,
                               rtol=1e-5)
    np.testing.assert_allclose(n.probs(v).numpy()[0],
                               math.exp(want_lp), rtol=1e-5)
    other = paddle.distribution.Normal(1.0, 1.0)
    # KL(N(0,2)||N(1,1)) = log(s1/s0) + (s0^2+(m0-m1)^2)/(2 s1^2) - 1/2
    want_kl = math.log(1.0 / 2.0) + (4.0 + 1.0) / 2.0 - 0.5
    np.testing.assert_allclose(float(n.kl_divergence(other).numpy()),
                               want_kl, rtol=1e-5)


def test_categorical_distribution():
    paddle.seed(0)
    logits = paddle.to_tensor([0.0, math.log(3.0)])  # probs 0.25/0.75
    c = paddle.distribution.Categorical(logits)
    s = c.sample([3000]).numpy()
    assert set(np.unique(s)) <= {0, 1}
    assert abs(s.mean() - 0.75) < 0.05
    want_h = -(0.25 * math.log(0.25) + 0.75 * math.log(0.75))
    np.testing.assert_allclose(float(c.entropy().numpy()), want_h,
                               rtol=1e-5)
    np.testing.assert_allclose(
        c.probs(paddle.to_tensor([0, 1])).numpy(), [0.25, 0.75],
        rtol=1e-5)
    np.testing.assert_allclose(
        c.log_prob(paddle.to_tensor([1])).numpy(), [math.log(0.75)],
        rtol=1e-5)
    d = paddle.distribution.Categorical(paddle.to_tensor([0.0, 0.0]))
    kl = float(c.kl_divergence(d).numpy())
    want_kl = (0.25 * math.log(0.25 / 0.5) + 0.75 * math.log(0.75 / 0.5))
    np.testing.assert_allclose(kl, want_kl, rtol=1e-5)


def test_categorical_batched_sample_shape():
    paddle.seed(0)
    logits = paddle.to_tensor(np.zeros((4, 6), np.float32))
    c = paddle.distribution.Categorical(logits)
    s = c.sample([2, 3])
    assert list(s.shape) == [2, 3, 4]


# -- fluid-style aliases -----------------------------------------------------

def test_elementwise_axis_broadcast():
    x = paddle.to_tensor(np.arange(24, dtype=np.float32).reshape(2, 3, 4))
    y = paddle.to_tensor(np.array([10.0, 20.0, 30.0], np.float32))
    out = paddle.elementwise_add(x, y, axis=1)  # y aligned to dim 1
    want = x.numpy() + y.numpy().reshape(1, 3, 1)
    np.testing.assert_allclose(out.numpy(), want)
    out2 = paddle.elementwise_sub(x, paddle.to_tensor(
        np.ones(4, np.float32)))
    np.testing.assert_allclose(out2.numpy(), x.numpy() - 1.0)


def test_reduce_aliases_and_overflow_checks():
    x = paddle.to_tensor(np.array([[1.0, 2.0], [3.0, 4.0]], np.float32))
    np.testing.assert_allclose(
        paddle.reduce_sum(x, dim=1, keep_dim=True).numpy(), [[3.0], [7.0]])
    np.testing.assert_allclose(float(paddle.reduce_prod(x).numpy()), 24.0)
    assert not bool(paddle.has_inf(x).numpy())
    assert bool(paddle.has_nan(
        paddle.to_tensor([np.nan, 1.0])).numpy())


def test_tanh_inplace():
    x = paddle.to_tensor(np.array([0.0, 1.0], np.float32))
    out = paddle.tanh_(x)
    np.testing.assert_allclose(x.numpy(), np.tanh([0.0, 1.0]), rtol=1e-6)
    assert out is x or np.allclose(out.numpy(), x.numpy())


def test_batch_reader():
    def reader():
        for i in range(5):
            yield i
    batches = list(paddle.batch(reader, 2)())
    assert batches == [[0, 1], [2, 3], [4]]
    batches = list(paddle.batch(reader, 2, drop_last=True)())
    assert batches == [[0, 1], [2, 3]]
    with pytest.raises(ValueError):
        paddle.batch(reader, 0)


def test_compat_and_misc():
    assert paddle.compat.to_text(b"abc") == "abc"
    assert paddle.compat.to_bytes("abc") == b"abc"
    assert paddle.compat.round(2.5) == 3.0
    assert paddle.compat.round(-2.5) == -3.0
    assert paddle.get_cudnn_version() is None
    assert paddle.is_compiled_with_xpu() is False
    assert paddle.framework.VarBase is paddle.Tensor
    assert paddle.VarBase is paddle.Tensor
    import os
    assert os.path.isdir(os.path.dirname(paddle.sysconfig.get_include()))
    with pytest.raises(NotImplementedError):
        paddle.onnx.export(None, "/tmp/x")
    st = paddle.get_cuda_rng_state()
    paddle.set_cuda_rng_state(st)
    paddle.set_printoptions(precision=4)
    np.set_printoptions()  # restore defaults for other tests


def _reference_module_names(relpath):
    """Exported names of a reference submodule: its __all__ when declared
    (plain module files), else its imports (the __init__ convention)."""
    import os
    p = _reference_path(*relpath.split("."))
    plain = os.path.isfile(p + ".py")
    p = p + ".py" if plain else os.path.join(p, "__init__.py")
    return _names_from_source(p, use_all=plain)


def test_submodule_namespace_parity():
    """Same mechanical audit as the top-level test, across the public
    submodules a reference user imports from."""
    import paddle_tpu as p
    mods = {
        "nn": p.nn, "nn.functional": p.nn.functional,
        "tensor": p.ops, "optimizer": p.optimizer,
        "optimizer.lr": p.optimizer.lr, "static": p.static,
        "io": p.io, "metric": p.metric, "amp": p.amp, "jit": p.jit,
        "distributed": p.distributed, "text": p.text,
        "vision": p.vision, "vision.transforms": p.vision.transforms,
        "vision.models": p.vision.models,
        "vision.datasets": p.vision.datasets, "vision.ops": p.vision.ops,
    }
    problems = {}
    for name, mod in mods.items():
        missing = sorted(n for n in _reference_module_names(name)
                         if not hasattr(mod, n))
        if missing:
            problems[name] = missing
    assert not problems, f"submodule names missing vs reference: {problems}"


# -- decode API + new functionals -------------------------------------------

def test_beam_search_decoder_dynamic_decode():
    paddle.seed(0)
    cell = paddle.nn.GRUCell(8, 16)
    proj = paddle.nn.Linear(16, 12)
    emb = paddle.nn.Embedding(12, 8)
    dec = paddle.nn.BeamSearchDecoder(cell, start_token=0, end_token=1,
                                      beam_size=4, embedding_fn=emb,
                                      output_fn=proj)
    h0 = paddle.to_tensor(np.random.RandomState(0).randn(3, 16)
                          .astype(np.float32))
    outs, states = paddle.nn.dynamic_decode(dec, inits=h0, max_step_num=7)
    ids = outs["predicted_ids"].numpy()
    assert ids.shape == (3, 7, 4) and ids.min() >= 0 and ids.max() < 12
    # scores decrease along the beam axis (sorted topk)
    sc = outs["scores"].numpy()
    assert (np.diff(sc[:, -1, :], axis=-1) <= 1e-5).all()
    # beams of one batch row must come from that row's state only:
    # identical rows => identical beams
    h_same = paddle.to_tensor(np.zeros((2, 16), np.float32))
    o2, _ = paddle.nn.dynamic_decode(dec, inits=h_same, max_step_num=5)
    a, b = o2["predicted_ids"].numpy()
    np.testing.assert_array_equal(a, b)


def test_hsigmoid_loss_layer_trains():
    paddle.seed(0)
    layer = paddle.nn.HSigmoidLoss(8, 6)
    import paddle_tpu.optimizer as opt
    optim = opt.SGD(0.5, parameters=layer.parameters())
    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.randn(16, 8).astype(np.float32))
    lab = paddle.to_tensor(rng.randint(0, 6, (16, 1)))
    first = None
    for _ in range(15):
        loss = layer(x, lab).mean()
        if first is None:
            first = float(loss.numpy())
        loss.backward()
        optim.step()
        optim.clear_grad()
    assert float(loss.numpy()) < first


def test_static_compat_helpers(tmp_path):
    import paddle_tpu.static as static
    # scope_guard actually swaps the global scope
    s = static.Scope()
    with static.scope_guard(s):
        assert static.global_scope() is s
    assert static.global_scope() is not s
    with static.name_scope("blockA") as ns:
        assert ns == "blockA"
    with static.device_guard("gpu:0"):
        pass
    assert len(static.cpu_places(2)) == 2
    # save_to_file/load_from_file round trip
    p = str(tmp_path / "blob.bin")
    static.save_to_file(p, b"xyz")
    assert static.load_from_file(p) == b"xyz"


def test_aux_namespace_parity():
    """utils / incubate / inference / reader / dataset — the remaining
    reference namespaces, audited the same mechanical way."""
    import paddle_tpu as p
    mods = {"utils": p.utils, "incubate": p.incubate,
            "inference": p.inference, "reader": p.reader,
            "dataset": p.dataset}
    problems = {}
    for name, mod in mods.items():
        # `import paddle.reader.decorator` inside reader/__init__ makes
        # the ast walker emit the module's own name — not an export
        missing = sorted(n for n in _reference_module_names(name)
                         if n != name and not hasattr(mod, n))
        if missing:
            problems[name] = missing
    assert not problems, f"aux namespaces missing: {problems}"


def test_reader_decorators():
    import paddle_tpu as p
    r10 = lambda: iter(range(10))
    assert sorted(p.reader.shuffle(r10, 4)()) == list(range(10))
    assert list(p.reader.firstn(r10, 3)()) == [0, 1, 2]
    assert list(p.reader.chain(r10, r10)()) == list(range(10)) * 2
    assert list(p.reader.map_readers(lambda a, b: a + b, r10, r10)()) == \
        [2 * i for i in range(10)]
    assert list(p.reader.compose(r10, r10)()) == \
        [(i, i) for i in range(10)]
    with pytest.raises(p.reader.ComposeNotAligned):
        list(p.reader.compose(r10, lambda: iter(range(5)))())
    assert sorted(p.reader.buffered(r10, 2)()) == list(range(10))
    out = list(p.reader.xmap_readers(lambda x: x * 2, r10, 3, 4,
                                     order=True)())
    assert out == [2 * i for i in range(10)]
    cached = p.reader.cache(r10)
    assert list(cached()) == list(cached())


def test_dataset_reader_adapters():
    import paddle_tpu as p
    img, lab = next(p.dataset.mnist.train()())
    assert img.shape == (784,) and 0 <= lab < 10
    x, y = next(p.dataset.uci_housing.test()())
    assert x.shape == (13,)
    ids, label = next(p.dataset.imdb.train(None)())
    assert isinstance(ids, list) and label in (0, 1)
    gram = next(p.dataset.imikolov.train(None, 5)())
    assert len(gram) >= 2
    # fluid-era pipeline end to end: batch over a dataset reader
    b = p.batch(p.dataset.uci_housing.train(), 8)
    first = next(b())
    assert len(first) == 8
    # image transforms
    im = np.arange(32 * 48 * 3, dtype=np.uint8).reshape(32, 48, 3)
    small = p.dataset.image.resize_short(im, 16)
    assert min(small.shape[:2]) == 16
    crop = p.dataset.image.center_crop(small, 12)
    assert crop.shape[:2] == (12, 12)
    chw = p.dataset.image.to_chw(crop)
    assert chw.shape[0] == 3
