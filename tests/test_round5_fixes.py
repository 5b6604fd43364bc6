"""Round-5 correctness fixes (ADVICE round 5 items).

Oracles: numpy put-along-axis accumulation loops and numpy
maximum.accumulate / argmax semantics, each run with NEGATIVE axis values
— the configurations that previously crashed (cummax: lax reject) or
silently scattered along the wrong dimension (put_along_axis reduce=).
"""

import numpy as np
import pytest

import paddle_tpu as paddle


def _np_put_along_axis(arr, idx, vals, axis, reduce):
    out = arr.copy()
    vals = np.broadcast_to(vals, idx.shape)
    for pos in np.ndindex(*idx.shape):
        dest = list(pos)
        dest[axis] = idx[pos]
        dest = tuple(dest)
        if reduce == "assign":
            out[dest] = vals[pos]
        elif reduce == "add":
            out[dest] += vals[pos]
        elif reduce == "mul":
            out[dest] *= vals[pos]
    return out


@pytest.mark.parametrize("reduce", ["assign", "add", "mul"])
@pytest.mark.parametrize("axis", [-1, -2])
def test_put_along_axis_negative_axis(reduce, axis):
    """axis=-1 with reduce='add'/'mul' previously built the scatter
    dnums for a shifted dimension (ADVICE round 5 high)."""
    rng = np.random.RandomState(5)
    arr = rng.rand(3, 4).astype("float32")
    idx = rng.randint(0, arr.shape[axis], size=(3, 2)).astype("int64")
    if axis == -2:
        idx = rng.randint(0, 3, size=(2, 4)).astype("int64")
    vals = rng.rand(*idx.shape).astype("float32")

    got = paddle.put_along_axis(paddle.to_tensor(arr), paddle.to_tensor(idx),
                                paddle.to_tensor(vals), axis, reduce=reduce)
    want = _np_put_along_axis(arr, idx, vals, axis + arr.ndim, reduce)
    np.testing.assert_allclose(got.numpy(), want, rtol=1e-6)

    # negative axis must agree exactly with its positive alias
    got_pos = paddle.put_along_axis(
        paddle.to_tensor(arr), paddle.to_tensor(idx), paddle.to_tensor(vals),
        axis + arr.ndim, reduce=reduce)
    np.testing.assert_array_equal(got.numpy(), got_pos.numpy())


@pytest.mark.parametrize("axis", [-1, -2])
def test_cummax_negative_axis(axis):
    """cummax(axis=-1) previously crashed: lax.cummax rejects negative
    axes and the index-grid reshape never matched them (ADVICE round 5)."""
    rng = np.random.RandomState(7)
    x = rng.rand(3, 4, 5).astype("float32")
    out, idx = paddle.cummax(paddle.to_tensor(x), axis=axis)
    np.testing.assert_allclose(out.numpy(),
                               np.maximum.accumulate(x, axis=axis), rtol=1e-6)
    pos_out, pos_idx = paddle.cummax(paddle.to_tensor(x), axis=axis + x.ndim)
    np.testing.assert_array_equal(out.numpy(), pos_out.numpy())
    np.testing.assert_array_equal(idx.numpy(), pos_idx.numpy())
    # indices index along the cummax axis: gathering with them rebuilds out
    take = np.take_along_axis(x, idx.numpy().astype("int64"), axis=axis)
    np.testing.assert_allclose(take, out.numpy(), rtol=1e-6)
