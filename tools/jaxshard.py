#!/usr/bin/env python
"""jaxshard CLI: static SPMD/sharding analyzer with a committed plan.

    python tools/jaxshard.py                  analyze + print reports
    python tools/jaxshard.py --plan write     commit shardplan.json
                                              (refuses while any finding
                                              is unsuppressed — triage
                                              first)
    python tools/jaxshard.py --plan check     fail on drift vs the
                                              committed shardplan.json
    python tools/jaxshard.py --programs a,b   restrict to named programs
    python tools/jaxshard.py --list-programs  registry names
    python tools/jaxshard.py --format json    machine output

The analyzer (analysis/jaxshard.py) abstract-interprets sharding specs
through each registry program's jaxpr and reports implicit collectives
(resharding edges with per-mesh-axis wire bytes), accidental >=1 MiB
replication, donation defeated by sharding, and per-device peak live
bytes vs the jaxplan HBM envelope. The check recomputes everything and
compares against shardplan.json: coverage both directions, structural
drift exact, bytes within the file's tolerance (5%) — same discipline
as the jaxcost budget and jaxplan gates.

Exit status: 0 clean, 1 violations/unsuppressed findings, 2 usage
errors. Traces run on the CPU backend with a forced 8-device host
platform, so the plan is machine-independent and commit-able.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

# backend setup MUST precede the first jax import: the registry's
# programs trace on virtual host devices
os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="jaxshard", description=__doc__)
    ap.add_argument("--plan", choices=("write", "check"))
    ap.add_argument("--plan-file", default=None,
                    help="plan path (default: <repo>/shardplan.json)")
    ap.add_argument("--programs", default=None,
                    help="comma-separated registry subset (ad-hoc "
                         "analysis only; plan modes always cover the "
                         "full registry)")
    ap.add_argument("--list-programs", action="store_true")
    ap.add_argument("--format", choices=("text", "json"),
                    default="text")
    args = ap.parse_args(argv)

    import jax
    # the analyzer traces on the CPU whatever the machine holds: the
    # plan is machine-independent, and a chip belongs to one process
    jax.config.update("jax_platforms", "cpu")
    from paddle_tpu.analysis import jaxshard

    if args.list_programs:
        for name in jaxshard.registry_names():
            print(name)
        return 0

    plan_file = args.plan_file or jaxshard.DEFAULT_PLAN_PATH
    if args.plan and args.programs:
        print("jaxshard: --programs conflicts with --plan (the plan "
              "always covers the full registry)", file=sys.stderr)
        return 2

    names = None
    if args.programs:
        names = [n.strip() for n in args.programs.split(",")
                 if n.strip()]
        try:
            jaxshard._build_shard_programs(names)
        except KeyError as e:
            print(f"jaxshard: {e.args[0]}", file=sys.stderr)
            return 2

    if args.plan == "check":
        violations = jaxshard.check_plan(plan_file)
        if args.format == "json":
            print(json.dumps({"plan_violations": violations},
                             indent=2, sort_keys=True))
        else:
            for v in violations:
                print(f"PLAN VIOLATION: {v}")
            print(f"jaxshard: {len(violations)} plan violation(s) "
                  f"against {os.path.relpath(plan_file, _REPO)}")
        return 1 if violations else 0

    reports = jaxshard.compute_reports(names)
    unsuppressed = jaxshard.unsuppressed_findings(reports)

    if args.plan == "write":
        if unsuppressed:
            for v in unsuppressed:
                print(f"UNSUPPRESSED: {v}", file=sys.stderr)
            print("jaxshard: refusing to commit a plan with "
                  "unsuppressed findings — fix them or add a triage "
                  "reason to the registry suppressions",
                  file=sys.stderr)
            return 1
        payload = jaxshard.write_plan(plan_file, reports)
        print(f"jaxshard: wrote plan to "
              f"{os.path.relpath(plan_file, _REPO)} "
              f"({len(payload['programs'])} program(s), "
              f"{sum(p['edge_count'] for p in payload['programs'].values())}"
              f" resharding edge(s))")
        return 0

    if args.format == "json":
        print(json.dumps(
            {"programs": {n: r.to_dict() for n, r in reports.items()},
             "unsuppressed": unsuppressed}, indent=2, sort_keys=True))
    else:
        for name in sorted(reports):
            print(reports[name].format())
    return 1 if unsuppressed else 0


if __name__ == "__main__":
    sys.exit(main())
