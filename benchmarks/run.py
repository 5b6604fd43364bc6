"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

runs one cell of BENCHMARK.json on the machine it is started on and prints,
as its LAST line, one JSON object: `correct`, `attempted`, `failed`,
`metrics`, `device` (and with --trace 1 `breakdown`). With --trace 0 the
metrics are the cell's end-to-end metrics, with --trace 1 its per-layer
metrics. Earlier lines are JSON facts of the run (compiles, cache hits,
medians, sample counts, the checks behind `correct`). One process, no child.
Without a TPU (or with fewer chips than the cell asks for) it exits non-zero
and prints no result. Two options are the builder's, not the driver's:
`--rehearse` runs the same code at the configuration's `rehearsal` size on
whatever backend JAX finds, never prints `"correct": true` and gives no
values; `--out DIR` says where the trace goes (default `.bench_out/`).

Everything is found by name; nothing here names a cell, a model or a metric.

- a CELL is an entry of `workloads` in BENCHMARK.json plus
  `benchmarks/workloads/<name>.json`: {config, runner, traffic, chips, the
  settings of the system under test, why, who};
- a CONFIGURATION is `benchmarks/configs/<config>.json`: the published sizes
  under their published keys, departures, a `rehearsal` block, and the plain
  reference it is compared with (`benchmarks/lib/reference_*.py`);
- a TRAFFIC MIX is `benchmarks/traffic/<traffic>.json`: parameters of one of
  the general generators of `benchmarks/lib/traffic.py` (the runner says
  which it reads);
- a RUNNER is `benchmarks/runners/<runner>.py` with
  `run(ctx) -> record`: it builds the system from ctx.config / ctx.cell,
  warms up, calls `ctx.window_opens()` at the first measured instant, and
  returns {"end_to_end": {metric: value}, "facts": {...}, "checks":
  {name: bool}, "attempted", "failed", "memory_peak_bytes"} and, if it
  likes, "samples": raw samples, written to `<out>/<cell>/samples.json`;
- a LAYER METRIC is `benchmarks/layer_metrics/<metric>.py` with
  `compute(record, trace) -> number or None` (None: nothing to read, the
  metric is left out). `trace` is the reduction of the device trace
  (`benchmarks/lib/xplane.py`) or None. Metrics `<stem>.<suffix>` that
  have no file of their own share `<stem>.py`.

To add any of them, add the file and the entry in BENCHMARK.json; no file
that is there needs an edit.
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()          # process start, as near as Python gives it

import argparse                    # noqa: E402
import importlib.util              # noqa: E402
import json                        # noqa: E402
import sys                         # noqa: E402
from pathlib import Path           # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
#: seconds of the window that a --trace 1 run traces (what comes back from
#: the chip is capped, and tracing slows the host)
TRACE_SECONDS = 3.0


def say(**facts):
    """An earlier line: one JSON object of facts."""
    print(json.dumps(facts), flush=True)


def load_json(kind: str, name: str) -> dict:
    path = BENCH / kind / f"{name}.json"
    if not path.is_file():
        raise SystemExit(f"benchmark: no file {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


def load_module(kind: str, name: str):
    """`<kind>/<name>.py`, or for a quantity split by cells under dotted
    names (`device_idle_share.train`, `.serve`) the one `<kind>/<stem>.py`
    they share."""
    path = BENCH / kind / f"{name}.py"
    if not path.is_file() and "." in name:
        path = BENCH / kind / f"{name.rsplit('.', 1)[0]}.py"
    if not path.is_file():
        raise SystemExit(f"benchmark: no file {kind}/{name}.py under "
                         f"{BENCH.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metrics_of(manifest: dict, section: str, cell: str) -> list:
    """The section's metrics that this cell reports: those without a
    `workloads` key, and those that list the cell."""
    return [m for m in manifest[section]
            if cell in m.get("workloads", [cell])]


class Context:
    """What a runner gets."""

    def __init__(self, args, cell, config, traffic, clock, device):
        self.seed = args.seed
        self.seconds = args.seconds
        self.trace = bool(args.trace)
        self.rehearse = args.rehearse
        self.cell, self.config, self.traffic = cell, config, traffic
        self.clock, self.device = clock, device
        self.on_chip = device["platform"] == "tpu"
        self.trace_dir = str(Path(args.out).resolve() / args.workload / "trace")
        self.trace_seconds = min(TRACE_SECONDS, args.seconds / 2)
        self.setup_s = None

    def mix(self) -> dict:
        """The traffic parameters, at rehearsal with its overrides."""
        mix = dict(self.traffic)
        if self.rehearse:
            mix.update(mix.get("rehearsal", {}))
        return mix

    def setting(self, key: str):
        """A group of the cell's settings, at rehearsal with its overrides."""
        group = dict(self.cell.get(key, {}))
        if self.rehearse:
            group.update(self.cell.get("rehearsal", {}).get(key, {}))
        return group

    def window_opens(self) -> None:
        """The runner calls this at the first measured instant: all that
        came before is set-up."""
        self.setup_s = time.perf_counter() - _T0
        self._at_open = self.clock.snapshot()

    def compiled_in_window(self) -> int:
        return self.clock.snapshot().since(self._at_open).compiles


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default=str(ROOT / ".bench_out"))
    ap.add_argument("--manifest", default=str(ROOT / "BENCHMARK.json"))
    args = ap.parse_args(argv)

    sys.path[:0] = [str(BENCH), str(ROOT)]
    manifest = json.loads(Path(args.manifest).read_text())
    listed = sorted(w["name"] for w in manifest["workloads"])
    if args.workload not in listed:
        raise SystemExit(f"benchmark: {args.workload!r} is not a cell of "
                         f"{args.manifest}: {listed}")
    if args.seconds is None:
        args.seconds = float(manifest["run_seconds"])
    cell = load_json("workloads", args.workload)
    config = load_json("configs", cell["config"])
    traffic = load_json("traffic", cell["traffic"])
    runner = load_module("runners", cell["runner"])

    # the program has to be importable before anything of it is: a
    # directory that holds only the benchmark stops here, non-zero
    try:
        from paddle_tpu.utils.compile_cache import enable_compile_cache
    except ImportError as e:
        raise SystemExit(f"benchmark: the program is not in {ROOT}: {e}")
    # a rehearsal leaves no cache behind: the tests run it, and a CPU entry
    # read back on another machine type only warns
    cache_dir = None if args.rehearse else enable_compile_cache()
    import jax
    # small programs (an upload's convert, the weight init) are cached too:
    # the second run of a cell in a checkout compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    from lib.chip import UnknownDevice
    from lib.clock import Clock
    clock = Clock()

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if device["platform"] != "tpu" and not args.rehearse:
        raise SystemExit(f"benchmark: no TPU found (JAX reports {device}); "
                         "--rehearse runs the cell tiny without one")
    if len(devs) < cell["chips"]:
        raise SystemExit(f"benchmark: the cell needs {cell['chips']} chips, "
                         f"JAX reports {len(devs)}")
    devs = devs[:cell["chips"]]
    device["count"] = len(devs)
    say(cell=args.workload, seed=args.seed, seconds=args.seconds,
        trace=args.trace, rehearsal=args.rehearse, device=device,
        compile_cache=cache_dir, jax=jax.__version__,
        jaxlib=__import__("jaxlib").__version__)

    ctx = Context(args, cell, config, traffic, clock, device)
    record = runner.run(ctx)
    if ctx.setup_s is None:
        raise SystemExit(f"benchmark: runner {cell['runner']} never called "
                         "ctx.window_opens()")
    total = clock.snapshot()
    say(setup_s=ctx.setup_s, compile_seconds=total.compile_s,
        backend_compiles=total.compiles, cache_hits=total.hits,
        cache_misses=total.misses,
        compiles_in_window=record["facts"].get("compiles_in_window"))
    say(checks=record["checks"])
    say(facts={k: v for k, v in record["facts"].items()
               if not k.startswith("_")})

    if record.get("samples"):
        out = Path(args.out).resolve() / args.workload
        out.mkdir(parents=True, exist_ok=True)
        (out / "samples.json").write_text(json.dumps(record["samples"]))
    record["end_to_end"]["setup_s"] = ctx.setup_s
    record["device"] = device
    trace = None
    if args.trace and record.get("trace_dir"):
        from lib import xplane
        trace = xplane.reduce_dir(record["trace_dir"], len(devs))
        if trace is not None:
            device["busy_s"] = trace["busy_s"]
            device["window_s"] = trace["window_s"]
            say(traced_programs=trace["modules"])

    section = "per_layer" if args.trace else "end_to_end"
    values = {}
    for m in metrics_of(manifest, section, args.workload):
        if args.trace:
            try:
                v = load_module("layer_metrics", m["name"]).compute(
                    record, trace)
            except UnknownDevice:
                if not args.rehearse:
                    raise
                v = None        # a rehearsal's device has no peaks
        else:
            v = record["end_to_end"].get(m["name"])
        if v is not None:
            values[m["name"]] = {"value": float(v), "unit": m["unit"]}

    peak = max(int(record.get("memory_peak_bytes") or 0),
               max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devs))
    device["memory_peak_bytes"] = peak
    passed = all(record["checks"].values())
    line = {"correct": bool(passed) and not args.rehearse,
            "attempted": int(record["attempted"]),
            "failed": int(record["failed"]),
            "metrics": values, "device": device}
    if args.rehearse:
        # a CPU number never stands under a device metric's name
        line["rehearsal"] = True
        line["checks_passed"] = bool(passed)
        line["metrics"] = {k: {"value": None, "unit": v["unit"]}
                           for k, v in values.items()}
    if trace is not None:
        line["breakdown"] = trace["breakdown"]
    print(json.dumps(line), flush=True)
    return 1 if args.rehearse and not passed else 0


if __name__ == "__main__":
    sys.exit(main())
