"""The general traffic generators. A traffic mix is a data file of
parameters under benchmarks/traffic/; what comes out is a pure function of
(file, seed, vocabulary).

Every seed gives a window the same work: the sizes are the file's, and the
seed decides the tokens that flow (prompts, batches, and through
lib/gpt2.py the weights) and the order the sizes come in.

`lm_batches`:        {"batch", "seq", "distinct_batches", "fetch_every"}
`closed_loop_sizes`: {"clients", "prompt_lens", "weights",
                      "max_tokens": [lo, hi], "block",
                      "steady_state": {"finished_requests"}}
"""
from __future__ import annotations

import numpy as np


def _rng(seed: int, *stream: int):
    return np.random.default_rng([int(seed), *stream])


def lm_batches(mix: dict, seed: int, vocab: int, seq: int = None):
    """`distinct_batches` pairs (x, y) of int32 [batch, seq] token ids."""
    rng = _rng(seed, 0)
    shape = (mix["batch"], seq or mix["seq"])
    return [(rng.integers(0, vocab, shape, dtype=np.int32),
             rng.integers(0, vocab, shape, dtype=np.int32))
            for _ in range(mix["distinct_batches"])]


def closed_loop_sizes(mix: dict, seed: int, scale: float = 1.0):
    """An endless iterator of (prompt_len, max_tokens). The stream is made
    of blocks of `block` requests; every block holds each prompt length in
    its stated share and `block` evenly spaced output lengths over
    [lo, hi], so every seed sends the same sizes. The seed pairs and orders
    them anew in every block: in a closed loop the order decides which
    prefills share a step, so a window has to hold several blocks for its
    tails to be the mix's and not one permutation's (PERF.md, PR 26).
    `scale` shrinks the lengths for a rehearsal."""
    n = mix["block"]
    counts = [round(w * n) for w in mix["weights"]]
    if sum(counts) != n:
        raise ValueError(f"weights x block must be whole: {counts} != {n}")
    lens = np.repeat(mix["prompt_lens"], counts)
    lo, hi = mix["max_tokens"]
    outs = np.round(np.linspace(lo, hi, n)).astype(int)
    rng = _rng(seed, 1)
    while True:
        for p, m in zip(rng.permutation(lens), rng.permutation(outs)):
            yield max(1, int(p * scale)), max(1, int(m * scale))


def prompt(seed: int, index: int, length: int, vocab: int, warm_up=False):
    """The `index`-th request's prompt (or warm-up request's): unshared
    random tokens."""
    return _rng(seed, 3 if warm_up else 2, index).integers(
        0, vocab, (length,), dtype=np.int32)
