"""Debug ops: Print and Assert.

Reference: operators/print_op.cc (forward-print of a tensor with message,
first_n throttling) and operators/assert_op.cc (abort when a condition
tensor is false). TPU-native: eager mode prints/raises on host; under a
jit trace these lower to jax.debug.print / jax.debug.callback (host
callbacks), which the TPU runtime delivers: jax.debug.callback and
io_callback both came back on a v5e (chip run, PR 23).
"""
from __future__ import annotations

import numpy as np
import jax

from ..core.tensor import Tensor, to_tensor

_print_counts: dict = {}


def Print(input, first_n=-1, message=None, summarize=20,
          print_tensor_name=True, print_tensor_type=True,
          print_tensor_shape=True, print_tensor_lod=False, name=None):
    """reference: operators/print_op.cc — identity op that prints the
    tensor (throttled to first_n occurrences per site)."""
    t = input if isinstance(input, Tensor) else to_tensor(input)
    key = id(name) if name else message
    cnt = _print_counts.get(key, 0)
    if first_n >= 0 and cnt >= first_n:
        return t
    _print_counts[key] = cnt + 1
    prefix = (message or "") + (f" [{name}]" if name else "")
    v = t._value
    if isinstance(v, jax.core.Tracer):
        jax.debug.print(prefix + " {x}", x=v)
        return t
    arr = np.asarray(v)
    parts = [prefix]
    if print_tensor_shape:
        parts.append(f"shape={list(arr.shape)}")
    if print_tensor_type:
        parts.append(f"dtype={arr.dtype}")
    flat = arr.reshape(-1)[:summarize]
    parts.append(f"data={flat.tolist()}")
    print(" ".join(p for p in parts if p))
    return t


def Assert(cond, data=None, summarize=20, name=None):
    """reference: operators/assert_op.cc — raise when cond is False;
    `data` tensors are printed with the failure."""
    c = cond if isinstance(cond, Tensor) else to_tensor(cond)
    v = c._value
    if isinstance(v, jax.core.Tracer):
        def _check(ok, *tensors):
            if not bool(np.all(ok)):
                details = "; ".join(str(np.asarray(t).reshape(-1)[
                    :summarize]) for t in tensors)
                raise AssertionError(f"Assert op failed ({name}): {details}")
        extra = [
            (d if isinstance(d, Tensor) else to_tensor(d))._value
            for d in (data or [])]
        jax.debug.callback(_check, v, *extra)
        return
    if not bool(np.all(np.asarray(v))):
        details = "; ".join(
            str(np.asarray((d if isinstance(d, Tensor) else
                            to_tensor(d)).numpy()).reshape(-1)[:summarize])
            for d in (data or []))
        raise AssertionError(f"Assert op failed ({name or ''}): {details}")
