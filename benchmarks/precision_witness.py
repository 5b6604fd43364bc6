"""What a cell's plain reference reads against ITSELF computed in a lower
precision, at the cell's size, on the rows of the runner's check (a).

    python3 benchmarks/precision_witness.py --workload <cell> \
        --seeds <n> [<n> ..] --low bfloat16 float8_e4m3fn [--rehearse]

The limits of `logit_error` in a cell of `serve_closed_family` or
`serve_closed_hybrid` are set between two readings (PERF.md): what the
engine reads against the float32 reference, and what the reference reads
when every matmul operand is rounded to the nearest precision below the
served one, which has to fail. The runner takes the second reading only
where the cell file has `lower_precision`, and as committed that is the
rehearsal. This takes it at the cell's size without the engine: the
configuration's builder makes the weights from each seed, the reference
runs once exact and once for each `--low` over one row a prompt length
(the mix's `prompt_lens`, each followed by `DECODE_STEPS` more tokens), and
a line a (seed, dtype) gives the errors of the 1 + `DECODE_STEPS` rows a
length in the runner's own form (`spread`: 90th percentile, largest,
median) beside the cell's limits. The served dtype as `--low` is the second
witness: the reference with the engine's rounding and none of its code.
Needs no TPU, but the cell's size does.
"""
from __future__ import annotations

import argparse
import functools
import importlib
import json
import sys
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent)]

from lib import traffic  # noqa: E402
from runners.serve_closed_family import (DECODE_STEPS, load_reference,  # noqa: E402
                                         padded, spread, within)


def load(kind: str, name: str) -> dict:
    return json.loads((BENCH / kind / f"{name}.json").read_text())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--low", nargs="+", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    import jax
    cell = load("workloads", args.workload)
    config, mix = load("configs", cell["config"]), load("traffic",
                                                        cell["traffic"])
    if args.rehearse:
        cell = {**cell, **cell["rehearsal"]}
        mix = {**mix, **mix.get("rehearsal", {})}
    builder = importlib.import_module("lib." + config["builder"])
    reference = load_reference(config)
    cfg = builder.program_config(config, args.rehearse)
    size = reference.sizes(cfg)
    run = {low: jax.jit(functools.partial(reference.gaps_and_rows,
                                          size=size, low=low))
           for low in [None] + args.low}
    lens = [max(1, int(n * mix.get("scale", 1.0)))
            for n in mix["prompt_lens"]]
    for seed in args.seeds:
        params = builder.seeded_weights(
            cfg, seed, config["assumed"]["initializer_range"])
        errors = {low: [] for low in args.low}
        reach = 0.0
        for i, n in enumerate(lens):
            row = padded(traffic.prompt(seed, 1000 + i, n + DECODE_STEPS,
                                        cfg.vocab_size, warm_up=True),
                         cfg.max_seq_len)
            at = np.arange(n - 1, n + DECODE_STEPS, dtype=np.int32)
            exact = np.asarray(run[None](params, row, row, at)[1])
            reach = max(reach, float(np.abs(exact).max()))
            for low in args.low:
                rows = np.asarray(run[low](params, row, row, at)[1])
                errors[low] += np.abs(rows - exact).max(axis=1).tolist()
        for low in args.low:
            found = spread(errors[low], 90)
            print(json.dumps({
                "workload": args.workload, "seed": seed, "low": low,
                "device": jax.devices()[0].device_kind,
                "prompt_lens": lens, "logit_abs_max": reach,
                "logit_error": found, "limits": cell["logit_error"],
                "within_limits": within(found, cell["logit_error"])}),
                flush=True)
        del params
    return 0


if __name__ == "__main__":
    sys.exit(main())
