#!/usr/bin/env python
"""jaxnum CLI: whole-program numerics analyzer with a committed plan.

    python tools/jaxnum.py                   analyze + print reports
    python tools/jaxnum.py --plan write      commit numplan.json
                                             (refuses while any finding
                                             is unsuppressed — triage
                                             first)
    python tools/jaxnum.py --plan check      fail on drift vs the
                                             committed numplan.json
    python tools/jaxnum.py --programs a,b    restrict to named programs
    python tools/jaxnum.py --list-programs   registry names
    python tools/jaxnum.py --format json     machine output

The analyzer (analysis/jaxnum.py) forward-interprets a numerics state
(storage dtype, accumulation dtype census, worst-case relative error
in f32 ulps, value interval, round/downcast/quantization provenance)
through each registry program's jaxpr and reports NUM-ACC (sub-f32
accumulation whose bound grows with contraction/trip length),
NUM-CAST (lossy float round-trips, unproven integer narrowing),
NUM-FINITE (exp/log/div/rsqrt with an unclamped operand — static twin
of the runtime core/anomaly.py guard) and NUM-QUANT (a derived
quantization bound vs the registry's declared budget — the int8
KV-block codec's 0.5/127 pin). The check recomputes everything and
compares against numplan.json: coverage both directions, structural
drift exact, bounds within the file's tolerance (5%) — same
discipline as the jaxcost budget, shardplan and lockgraph gates.

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
    ap = argparse.ArgumentParser(prog="jaxnum", description=__doc__)
    ap.add_argument("--plan", choices=("write", "check"))
    ap.add_argument("--plan-file", default=None,
                    help="plan path (default: <repo>/numplan.json)")
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
    from paddle_tpu.analysis import jaxnum

    if args.list_programs:
        for name in jaxnum.registry_names():
            print(name)
        return 0

    plan_file = args.plan_file or jaxnum.DEFAULT_PLAN_PATH
    if args.plan and args.programs:
        print("jaxnum: --programs conflicts with --plan (the plan "
              "always covers the full registry)", file=sys.stderr)
        return 2

    names = None
    if args.programs:
        names = [n.strip() for n in args.programs.split(",")
                 if n.strip()]
        try:
            jaxnum._build_num_programs(names)
        except KeyError as e:
            print(f"jaxnum: {e.args[0]}", file=sys.stderr)
            return 2

    if args.plan == "check":
        violations = jaxnum.check_plan(plan_file)
        if args.format == "json":
            print(json.dumps({"plan_violations": violations},
                             indent=2, sort_keys=True))
        else:
            for v in violations:
                print(f"PLAN VIOLATION: {v}")
            print(f"jaxnum: {len(violations)} plan violation(s) "
                  f"against {os.path.relpath(plan_file, _REPO)}")
        return 1 if violations else 0

    reports = jaxnum.compute_reports(names)
    unsuppressed = jaxnum.unsuppressed_findings(reports)

    if args.plan == "write":
        if unsuppressed:
            for v in unsuppressed:
                print(f"UNSUPPRESSED: {v}", file=sys.stderr)
            print("jaxnum: refusing to commit a plan with "
                  "unsuppressed findings — fix them or add a triage "
                  "reason to the registry suppressions",
                  file=sys.stderr)
            return 1
        payload = jaxnum.write_plan(plan_file, reports)
        n_findings = sum(len(p["findings"])
                         for p in payload["programs"].values())
        print(f"jaxnum: wrote plan to "
              f"{os.path.relpath(plan_file, _REPO)} "
              f"({len(payload['programs'])} program(s), "
              f"{n_findings} triaged finding(s))")
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
