"""The legacy fluid namespace: reference-era user code must run as-is
(`import paddle.fluid as fluid` style, reference python/paddle/fluid/).
"""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.fluid as fluid


def test_fluid_static_book_flow(tmp_path):
    """The reference book-test shape (test_recognize_digits style):
    build a program with fluid.layers, train with fluid.Executor,
    save/load persistables through fluid.io."""
    paddle.enable_static()
    try:
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data("x", shape=[4], dtype="float32")
            y = fluid.layers.data("y", shape=[1], dtype="int64")
            h = fluid.layers.fc(x, size=8, act="relu")
            logits = fluid.layers.fc(h, size=3)
            loss = fluid.layers.mean(
                fluid.layers.softmax_with_cross_entropy(logits, y))
            import paddle_tpu.optimizer as opt
            opt.SGD(0.5).minimize(loss)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        rng = np.random.RandomState(0)
        xs = rng.randn(32, 4).astype(np.float32)
        ys = (xs.sum(1, keepdims=True) > 0).astype(np.int64) * 2
        losses = []
        for _ in range(20):
            (lv,) = exe.run(main, feed={"x": xs, "y": ys},
                            fetch_list=[loss])
            losses.append(float(np.asarray(lv)))
        assert losses[-1] < losses[0] * 0.8, losses
        fluid.io.save_persistables(exe, str(tmp_path))
        fluid.io.load_persistables(exe, str(tmp_path))
        (lv2,) = exe.run(main, feed={"x": xs, "y": ys}, fetch_list=[loss])
        assert np.isfinite(float(np.asarray(lv2)))
    finally:
        paddle.disable_static()


def test_fluid_dygraph_flow():
    paddle.seed(0)
    with fluid.dygraph.guard():
        lin = fluid.dygraph.Linear(4, 2, act="relu")
        emb = fluid.dygraph.Embedding(size=[10, 4])
        ids = fluid.dygraph.to_variable(
            np.array([[1, 2], [3, 4]], np.int64))
        out = lin(emb(ids))
        assert list(out.shape) == [2, 2, 2]
        assert (out.numpy() >= 0).all()  # relu fused
        out.backward()
        assert emb.weight.grad is not None


def test_fluid_core_ops_and_misc():
    # core.ops.<op> fast-path callables (op_function_generator analogue)
    import jax.numpy as jnp
    r = fluid.core.ops.relu(jnp.asarray(np.array([-1.0, 2.0], np.float32)))
    np.testing.assert_allclose(np.asarray(r), [0.0, 2.0])
    assert "relu" in dir(fluid.core.ops)
    assert fluid.core.is_compiled_with_xpu() is False
    assert isinstance(fluid.core.Scope(), fluid.Scope)
    # layers delegation breadth: tensor/math/control-flow names resolve
    for name in ("concat", "reshape", "reduce_sum", "elementwise_add",
                 "fill_constant", "cast", "while_loop", "cond", "topk",
                 "softmax", "relu", "cross_entropy", "fc", "StaticRNN"):
        assert callable(getattr(fluid.layers, name)), name
    fluid.require_version("1.8.0")
    # save/load_dygraph round trip
    lin = fluid.dygraph.Linear(3, 2)
    import tempfile, os
    d = tempfile.mkdtemp()
    fluid.dygraph.save_dygraph(lin.state_dict(), os.path.join(d, "m"))
    params, opt = fluid.dygraph.load_dygraph(os.path.join(d, "m"))
    assert params is not None and "_linear.weight" in params


def test_fluid_save_load_inference_model(tmp_path):
    """fluid-era signature: feed by NAME, artifact under dirname."""
    paddle.enable_static()
    try:
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data("x", shape=[4], dtype="float32")
            out = fluid.layers.fc(x, size=2)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        d = str(tmp_path / "inf")
        fluid.io.save_inference_model(d, ["x"], [out], exe,
                                      main_program=main)
        res = fluid.io.load_inference_model(d, exe)
        assert res is not None
    finally:
        paddle.disable_static()


def test_dynamic_decode_minimal_decoder_and_impute():
    """A Decoder subclass without finalize must work (reference wraps
    finalize in try/except NotImplementedError); impute_finished freezes
    finished beams' states."""
    import paddle_tpu.nn as nn

    class CountDecoder(nn.Decoder):
        def initialize(self, inits):
            z = paddle.to_tensor(np.zeros((2,), np.float32))
            return z, z, paddle.to_tensor(np.array([False, False]))

        def step(self, time, inputs, states, **kwargs):
            nxt = states + 1.0
            fin = paddle.to_tensor(np.array([time >= 1, time >= 2]))
            return {"out": nxt}, nxt, nxt, fin

    outs, states = nn.dynamic_decode(CountDecoder(), max_step_num=4)
    assert outs["out"].shape[1] == 3  # stopped when all finished (t=2)

    paddle.seed(0)
    cell = paddle.nn.GRUCell(4, 8)
    emb = paddle.nn.Embedding(6, 4)
    proj = paddle.nn.Linear(8, 6)
    dec = nn.BeamSearchDecoder(cell, 0, 1, 2, embedding_fn=emb,
                               output_fn=proj)
    h0 = paddle.to_tensor(np.random.RandomState(0).randn(2, 8)
                          .astype(np.float32))
    o1, s1 = nn.dynamic_decode(dec, inits=h0, max_step_num=6,
                               impute_finished=True)
    assert o1["predicted_ids"].numpy().shape[0] == 2


def _reference_fluid_layers_names():
    import ast, os
    base = "/root/reference/python/paddle/fluid/layers"
    if not os.path.isdir(base):
        pytest.skip(f"no reference checkout at {base}")
    names = set()
    for fn in os.listdir(base):
        if not fn.endswith(".py"):
            continue
        tree = ast.parse(open(os.path.join(base, fn)).read())
        for node in tree.body:
            if isinstance(node, ast.Assign) and any(
                    isinstance(t, ast.Name) and t.id == "__all__"
                    for t in node.targets):
                try:
                    names.update(n for n in ast.literal_eval(node.value)
                                 if not n.startswith("_"))
                except ValueError:
                    pass
    return names


def test_fluid_layers_namespace_parity():
    """Every name the reference exports from fluid.layers (the union of
    all its submodules' __all__, 307 names) resolves here — or is in
    layers_adapters.NOT_PROVIDED with a documented reason."""
    from paddle_tpu.fluid.layers_adapters import NOT_PROVIDED
    names = _reference_fluid_layers_names()
    missing = sorted(n for n in names
                     if not hasattr(fluid.layers, n)
                     and n not in NOT_PROVIDED)
    assert not missing, f"fluid.layers names unaccounted: {missing}"
    stale = sorted(n for n in NOT_PROVIDED if n not in names)
    assert not stale, f"NOT_PROVIDED entries not in reference: {stale}"
    dead = sorted(n for n in NOT_PROVIDED if hasattr(fluid.layers, n))
    assert not dead, \
        f"NOT_PROVIDED entries that actually resolve (stale doc): {dead}"


def test_fluid_layers_adapters_behave():
    import math
    x = paddle.to_tensor(np.array([[1.0, -2.0], [3.0, 4.0]], np.float32))
    # activations
    np.testing.assert_allclose(
        fluid.layers.hard_sigmoid(x, 0.2, 0.5).numpy(),
        np.clip(0.2 * x.numpy() + 0.5, 0, 1), rtol=1e-6)
    np.testing.assert_allclose(
        fluid.layers.brelu(x, 0.0, 3.0).numpy(),
        np.clip(x.numpy(), 0.0, 3.0), rtol=1e-6)
    # losses
    h = fluid.layers.huber_loss(x, paddle.zeros_like(x), delta=1.0)
    np.testing.assert_allclose(h.numpy()[0, 0], 0.5, rtol=1e-6)
    np.testing.assert_allclose(h.numpy()[1, 1], 1.0 * (4 - 0.5), rtol=1e-6)
    sl1 = fluid.layers.smooth_l1(x, paddle.zeros_like(x))
    assert sl1.shape == [2, 1]
    # elementwise with fluid axis
    y = paddle.to_tensor(np.array([10.0, 20.0], np.float32))
    out = fluid.layers.elementwise_mul(
        paddle.to_tensor(np.ones((2, 2, 3), np.float32)), y, axis=0)
    np.testing.assert_allclose(out.numpy()[:, 0, 0], [10.0, 20.0])
    # reduce_all/any
    b = paddle.to_tensor(np.array([[True, False], [True, True]]))
    assert fluid.layers.reduce_all(b, dim=1).numpy().tolist() == \
        [False, True]
    # lr schedule adapters return working schedulers
    sched = fluid.layers.noam_decay(128, 100)
    import paddle_tpu.optimizer as opt
    assert isinstance(sched, opt.lr.LRScheduler)
    # ctc greedy decode: merge repeats, strip blanks
    probs = np.zeros((1, 5, 3), np.float32)
    for t, c in enumerate([1, 1, 0, 2, 2]):
        probs[0, t, c] = 5.0
    dec, lens = fluid.layers.ctc_greedy_decoder(
        paddle.to_tensor(probs), blank=0)
    assert dec.numpy()[0, :int(lens.numpy()[0])].tolist() == [1, 2]
    # beam_search one step
    pre_ids = paddle.to_tensor(np.zeros((2, 1), np.int64))
    pre_sc = paddle.to_tensor(np.zeros((2, 1), np.float32))
    sc = paddle.to_tensor(np.log(np.array(
        [[0.1, 0.6, 0.3], [0.5, 0.2, 0.3]], np.float32)))
    ids, scs = fluid.layers.beam_search(pre_ids, pre_sc, None, sc,
                                        beam_size=2, end_id=0)
    assert ids.shape == [2, 1]
    # MultivariateNormalDiag entropy/kl
    mvn = paddle.distribution.MultivariateNormalDiag(
        [0.0, 0.0], np.diag([1.0, 1.0]).astype(np.float32))
    want = 0.5 * (2 * (1 + math.log(2 * math.pi)))
    np.testing.assert_allclose(float(mvn.entropy().numpy()), want,
                               rtol=1e-5)
    mvn2 = paddle.distribution.MultivariateNormalDiag(
        [1.0, 0.0], np.diag([2.0, 1.0]).astype(np.float32))
    kl = float(mvn.kl_divergence(mvn2).numpy())
    want_kl = 0.5 * ((0.5 + 1.0) + (0.5 + 0.0) - 2 + math.log(2.0))
    np.testing.assert_allclose(kl, want_kl, rtol=1e-5)


def test_basic_decoder_helpers():
    paddle.seed(0)
    cell = paddle.nn.GRUCell(4, 8)
    proj = paddle.nn.Linear(8, 5)
    emb = paddle.nn.Embedding(5, 4)
    helper = paddle.nn.GreedyEmbeddingHelper(
        emb, np.zeros(3, np.int64), end_token=1)
    dec = paddle.nn.BasicDecoder(cell, helper, output_fn=proj)
    h0 = paddle.to_tensor(np.random.RandomState(0).randn(3, 8)
                          .astype(np.float32))
    outs, states = paddle.nn.dynamic_decode(dec, inits=h0, max_step_num=5)
    assert outs["sample_ids"].numpy().shape[0] == 3
    # training helper follows the ground-truth sequence
    gt = paddle.to_tensor(np.random.RandomState(1)
                          .randn(3, 4, 4).astype(np.float32))
    th = paddle.nn.TrainingHelper(gt)
    dec2 = paddle.nn.BasicDecoder(cell, th, output_fn=proj)
    outs2, _ = paddle.nn.dynamic_decode(dec2, inits=h0, max_step_num=10)
    assert outs2["cell_outputs"].numpy().shape[1] == 4  # stops at T


def test_dynamic_decode_finished_accumulates():
    """A sequence that emitted end_token must STAY finished even if a
    later step's sample is not end_token (review repro: decode used to
    run to max_step_num because finished could un-set)."""
    import paddle_tpu.nn as nn

    class FlipFlop(nn.Decoder):
        # seq0 "finishes" at t=0 then would report unfinished at t>=1
        def initialize(self, inits):
            z = paddle.to_tensor(np.zeros(2, np.float32))
            return z, z, paddle.to_tensor(np.array([False, False]))

        def step(self, time, inputs, states, **kwargs):
            fin = paddle.to_tensor(np.array([time == 0, time >= 2]))
            return {"o": states}, states, inputs, fin

    outs, _ = nn.dynamic_decode(FlipFlop(), max_step_num=10)
    assert outs["o"].numpy().shape[1] == 3  # stops at t=2, not 10


def test_beam_search_freezes_finished_and_global_parents():
    # beam 0 of each batch row already ended; it must only extend with
    # end_id at its pre_score, and parent indices must be GLOBAL rows
    end_id = 0
    pre_ids = paddle.to_tensor(
        np.array([[end_id], [5], [end_id], [5]], np.int64))
    pre_sc = paddle.to_tensor(
        np.array([[1.5], [0.5], [2.5], [0.1]], np.float32))
    sc = paddle.to_tensor(np.log(np.tile(np.array(
        [[0.1, 0.6, 0.3]], np.float32), (4, 1))) )
    ids, scs, parents = fluid.layers.beam_search(
        pre_ids, pre_sc, None, sc + pre_sc, beam_size=2, end_id=end_id,
        return_parent_idx=True)
    ids, scs, parents = ids.numpy(), scs.numpy(), parents.numpy()
    # batch 0: frozen beam (row 0, score 1.5 with token end_id) must win
    assert ids[0, 0] == end_id and abs(scs[0, 0] - 1.5) < 1e-5
    # batch 1 parents point at global rows 2..3, not 0..1
    assert parents[2] >= 2 and parents[3] >= 2


def test_fluid_data_negative_dims():
    paddle.enable_static()
    try:
        with fluid.program_guard(fluid.Program(), fluid.Program()):
            v = fluid.layers.data("a", shape=[3, -1])
            assert list(v.shape) == [3, -1]  # NOT [-1, 3, -1]
            w = fluid.layers.data("b", shape=[4])
            assert list(w.shape) == [-1, 4]
    finally:
        paddle.disable_static()
