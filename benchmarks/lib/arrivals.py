"""Arrival instants of an open loop: a pure function of the traffic file.

`poisson_offsets`: {"rate_per_s", "arrival_seed"} -> an endless iterator of
seconds after the stream's start at which a request is due: a Poisson
process (independent exponential gaps of mean 1 / rate). `--seed` has no
part in it: every run of a cell is offered requests at the same instants,
and the seed decides which sizes and tokens arrive at them
(`lib/traffic.closed_loop_sizes`, `prompt`). `stream` picks one of the
file's independent streams (0: the measured window's; 1: the lead-in's;
2: the traced seconds').
"""
from __future__ import annotations

import numpy as np


def poisson_offsets(mix: dict, stream: int = 0):
    rng = np.random.default_rng([int(mix["arrival_seed"]), int(stream)])
    at = 0.0
    while True:
        for gap in rng.exponential(1.0 / mix["rate_per_s"], 256):
            at += float(gap)
            yield at
