#!/usr/bin/env python
"""jaxplan CLI: static planner with a committed-plan gate.

    python tools/jaxplan.py                   compute + print the plan
    python tools/jaxplan.py --plan write      re-plan and commit
                                              jaxplan.json
    python tools/jaxplan.py --plan check      fail if re-planning under
                                              the committed envelope
                                              drifts from jaxplan.json
    python tools/jaxplan.py --envelope-gb 15.75
                                              HBM envelope for the remat
                                              planner (write mode)
    python tools/jaxplan.py --format json     machine output

Three planners run in one pass (analysis/jaxplan.py): remat policy
selection under the HBM envelope, donation policy backed by the
jaxcost audit, and the quadratic prefill admission cost model. The
check recomputes all three under the envelope recorded in the
committed file — structural drift (chosen policy, donation sets) or
numeric drift beyond the file's tolerance fails, exactly like the
jaxcost budget gate.

Exit status: 0 clean, 1 plan violations or unsuppressed donation
findings, 2 usage errors. Everything derives from traced jaxprs on the
CPU backend with a forced 8-device host platform, so the plan is
machine-independent — that determinism is what makes it commit-able.
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


def _print_text(payload: dict) -> None:
    remat = payload["remat"]["train_step"]
    print(f"remat plan (envelope {payload['envelope_bytes']:,} bytes):")
    for pol, c in sorted(remat["candidates"].items(),
                         key=lambda kv: -kv[1]["peak_bytes"]):
        chosen = " <- chosen" if pol == remat["policy"] else ""
        print(f"  {pol:10s} flops={c['flops']:>14,} "
              f"peak={c['peak_bytes']:>12,}{chosen}")
    print(f"  policy={remat['policy']} group_size={remat['group_size']} "
          f"predicted_peak={remat['predicted_peak_bytes']:,} "
          f"recompute_flops=+{remat['recompute_flops']:,}")
    print("donation plan:")
    for name, d in sorted(payload["donation"].items()):
        sup = "".join(f" !{k}" for k in sorted(d["suppressed"]))
        extra = "" if d["applies"] else " (n/a: collective)"
        print(f"  {name:30s} donate={d['donate_argnums']}{sup}{extra}")
    m = payload["admission"]["prefill_cost_model"]
    print(f"admission: cost(n) = {m['base_flops']:,.0f} + "
          f"{m['flops_per_token']:,.0f}*n + "
          f"{m['flops_per_token_sq']:,.1f}*n^2 flops "
          f"(fit at n={payload['admission']['fit_lengths']})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="jaxplan", description=__doc__)
    ap.add_argument("--plan", choices=("write", "check"))
    ap.add_argument("--plan-file", default=None,
                    help="plan path (default: <repo>/jaxplan.json)")
    ap.add_argument("--envelope-gb", type=float, default=None,
                    help="HBM envelope in GiB for the remat planner "
                         "(default 15.75, one v5e chip; check mode "
                         "always uses the committed file's envelope)")
    ap.add_argument("--format", choices=("text", "json"), default="text")
    args = ap.parse_args(argv)

    import jax
    # the analyzer traces on the CPU whatever the machine holds: the
    # plan is machine-independent, and a chip belongs to one process
    jax.config.update("jax_platforms", "cpu")
    from paddle_tpu.analysis import jaxplan

    plan_file = args.plan_file or jaxplan.DEFAULT_PLAN_PATH
    if args.plan == "check" and args.envelope_gb is not None:
        print("jaxplan: --envelope-gb conflicts with --plan check (the "
              "check replans under the committed file's envelope)",
              file=sys.stderr)
        return 2

    if args.plan == "check":
        violations = jaxplan.check_plan(plan_file)
        if args.format == "json":
            print(json.dumps({"plan_violations": violations},
                             indent=2, sort_keys=True))
        else:
            for v in violations:
                print(f"PLAN VIOLATION: {v}")
            print(f"jaxplan: {len(violations)} plan violation(s) against "
                  f"{os.path.relpath(plan_file, _REPO)}")
        return 1 if violations else 0

    envelope = jaxplan.DEFAULT_HBM_ENVELOPE if args.envelope_gb is None \
        else int(args.envelope_gb * 2 ** 30)
    try:
        payload, violations = jaxplan.compute_plan(envelope_bytes=envelope)
    except jaxplan.InfeasibleEnvelope as e:
        print(f"jaxplan: {e}", file=sys.stderr)
        return 1

    if args.plan == "write":
        if violations:
            for v in violations:
                print(f"PLAN VIOLATION: {v}", file=sys.stderr)
            print("jaxplan: refusing to commit a plan with unsuppressed "
                  "donation findings", file=sys.stderr)
            return 1
        jaxplan.write_plan(plan_file, payload)
        print(f"jaxplan: wrote plan to "
              f"{os.path.relpath(plan_file, _REPO)} "
              f"(remat={payload['remat']['train_step']['policy']}, "
              f"{len(payload['donation'])} donation program(s))")
        return 0

    if args.format == "json":
        print(json.dumps({"plan": payload, "plan_violations": violations},
                         indent=2, sort_keys=True))
    else:
        _print_text(payload)
        for v in violations:
            print(f"PLAN VIOLATION: {v}")
    return 1 if violations else 0


if __name__ == "__main__":
    sys.exit(main())
