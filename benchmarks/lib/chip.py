"""The chip's published peaks, the operations a GPT pass requires, and the
percentile the benchmark reports. All arithmetic of the yardstick that is
not a clock lives here."""
from __future__ import annotations

import numpy as np

#: per chip, keyed by jax's `device_kind`. Source: Google Cloud
#: documentation, "TPU v5e" system architecture page: 197 TFLOP/s bf16,
#: 16 GB HBM2e at 819 GB/s. A device that is not here is an error, never a
#: default (copied from bench.py `_PEAK_FLOPS`/`_PEAK_HBM_BW`).
PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


class UnknownDevice(KeyError):
    """The device is not in the table of peaks."""


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise UnknownDevice(f"no published peaks for device kind {device_kind!r}; "
                       f"add it to benchmarks/lib/chip.py with its source")
    return PEAKS[device_kind]


def gpt_matmul_params(n_embd: int, n_layer: int, vocab_size: int) -> int:
    """Parameters that sit in a matrix multiplication: per layer qkv 3h^2,
    attention out h^2, MLP 8h^2; the unembedding V*h once. The embeddings
    are gathered, not multiplied."""
    return n_layer * 12 * n_embd * n_embd + vocab_size * n_embd


def gpt_train_flops_per_token(n_embd: int, n_layer: int, vocab_size: int,
                              seq: int) -> float:
    """Operations the forward and backward pass REQUIRE per trained token:
    6 per matmul parameter, plus causal attention. bench.py scores the
    scores/values products as the full T x T square (12*L*h*T); a causal
    pass needs half of it, so this counts 6*L*h*T. Recomputation inside a
    kernel's backward is not model work and is not counted."""
    return (6.0 * gpt_matmul_params(n_embd, n_layer, vocab_size)
            + 6.0 * n_layer * n_embd * seq)


def percentile(values, q: float):
    """(q-th percentile by linear interpolation, sample count); (None, 0)
    for no samples."""
    if len(values) == 0:
        return None, 0
    return float(np.percentile(np.asarray(values, np.float64), q)), len(values)
