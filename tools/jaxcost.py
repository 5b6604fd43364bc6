#!/usr/bin/env python
"""jaxcost CLI: static FLOP/bytes/peak-memory model with budget gates.

    python tools/jaxcost.py                        analyze all programs
    python tools/jaxcost.py --programs train_step  a subset
    python tools/jaxcost.py --format json          machine output
    python tools/jaxcost.py --budget write         re-baseline
                                                   jaxcost_budget.json
    python tools/jaxcost.py --budget check         fail if any program's
                                                   flops/peak-bytes/
                                                   comm-bytes exceed the
                                                   committed budget >5%
    python tools/jaxcost.py --list-programs        registry names

Also runs the donation audit (skip with --no-donation-audit):
unsuppressed findings — an argument dead after its last read with an
aval-matched output, not in donate_argnums — fail the run.

Exit status: 0 clean/within budget, 1 budget violations or unsuppressed
donation findings, 2 usage errors. Cost model: docs/static_cost.md.
Everything is computed from traced jaxprs on the CPU backend with a
forced 8-device host platform, so the numbers are identical on any
machine — that determinism is what makes the budget a commit-able file.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

# backend setup MUST precede the first jax import: the registry's
# collective programs shard over 4 virtual devices
os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

DEFAULT_BUDGET = os.path.join(_REPO, "jaxcost_budget.json")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="jaxcost", description=__doc__)
    ap.add_argument("--programs", action="append", default=[],
                    metavar="NAME", help="only these registry programs")
    ap.add_argument("--format", choices=("text", "json"), default="text")
    ap.add_argument("--budget", choices=("write", "check"))
    ap.add_argument("--budget-file", default=DEFAULT_BUDGET)
    ap.add_argument("--no-donation-audit", action="store_true")
    ap.add_argument("--list-programs", action="store_true")
    args = ap.parse_args(argv)

    import jax
    # the analyzer traces on the CPU whatever the machine holds: the
    # plan is machine-independent, and a chip belongs to one process
    jax.config.update("jax_platforms", "cpu")
    from paddle_tpu.analysis import jaxcost

    if args.list_programs:
        for name in jaxcost.registry_names():
            print(name)
        return 0

    names = args.programs or None
    try:
        costs = jaxcost.compute_costs(names)
    except KeyError as e:
        print(f"jaxcost: {e.args[0]}", file=sys.stderr)
        return 2

    findings = []
    if not args.no_donation_audit:
        findings = jaxcost.collect_donation_findings(names)
    unsuppressed = [f for f in findings if not f.suppressed]

    if args.budget == "write":
        jaxcost.write_budget(args.budget_file, costs)
        print(f"jaxcost: wrote {len(costs)} program budget(s) to "
              f"{os.path.relpath(args.budget_file, _REPO)}")
        return 1 if unsuppressed else 0

    violations = []
    if args.budget == "check":
        if not os.path.exists(args.budget_file):
            print(f"jaxcost: no budget file at {args.budget_file} "
                  f"(run --budget write first)", file=sys.stderr)
            return 2
        violations = jaxcost.check_budget(
            args.budget_file, costs,
            require_full_coverage=names is None)
        # cross-artifact gate: for programs committed in BOTH the
        # budget and the shard plan (shardplan.json), jaxshard's
        # explicit per-axis collective bytes must sum to this budget's
        # comm_bytes — both artifacts price collectives off the same
        # byte table, so disagreement means one of them is stale
        from paddle_tpu.analysis import jaxshard
        with open(args.budget_file) as f:
            committed = json.load(f)
        violations += jaxshard.crosscheck_with_budget(committed)

    if args.format == "json":
        print(json.dumps({
            "programs": {n: c.to_dict() for n, c in sorted(costs.items())},
            "donation_findings": [
                {"program": f.program, "argnum": f.argnum,
                 "nbytes": f.nbytes, "n_leaves": f.n_leaves,
                 "suppressed": f.suppressed} for f in findings],
            "budget_violations": violations,
        }, indent=2, sort_keys=True))
    else:
        for name in sorted(costs):
            print(costs[name].format())
        for f in findings:
            print(f.format())
        for v in violations:
            print(f"BUDGET VIOLATION: {v}")
        status = []
        if args.budget == "check":
            status.append(f"{len(violations)} budget violation(s)")
        status.append(f"{len(unsuppressed)} unsuppressed donation "
                      f"finding(s)")
        print(f"jaxcost: {len(costs)} program(s), " + ", ".join(status))

    return 1 if (violations or unsuppressed) else 0


if __name__ == "__main__":
    sys.exit(main())
