"""paddle_tpu.analysis: framework-aware static analysis (ptlint).

The runtime invariants this package guards are the ones no unit test can
see until they break in production (docs/static_analysis.md):

- trace safety: jitted programs must stay trace-pure and recompile-free
  (rules/trace_safety.py — tracer branching, host materialization,
  Python side effects under trace, jit-in-loop recompile churn,
  non-hashable statics, host RNG under trace);
- jaxpr health: the compiled entry points (jit.TrainStep, the decode
  sub-programs) must not grow host callbacks, captured-constant bloat
  or silent dtype downcasts (jaxpr_audit.py — a trace-time check, the
  analogue of the reference's graph-pass validation in
  paddle/fluid/framework/ir);
- lock discipline: shared serving state annotated in a `_GUARDED_BY`
  map is only touched while holding its lock (rules/concurrency.py);
- static cost: jaxcost.py + liveness.py model FLOPs, bytes, collective
  volume and peak live-buffer bytes of every registered jitted program
  from its jaxpr, gate them against jaxcost_budget.json, and audit
  buffer donation (docs/static_cost.md); hlo_bytes.py is the shared
  HLO-text byte accounting used by tools/hlo_bytes.py and
  jaxcost.py.

The lint core (ast_core + rules + hlo_bytes) is stdlib-only so
`tools/ptlint.py` and `tools/hlo_bytes.py` run without importing jax;
`jaxpr_audit`, `liveness` and `jaxcost` need jax and are imported on
demand (never from this __init__).
"""
from __future__ import annotations

from .ast_core import (Finding, LintEngine, LintReport, load_baseline,
                       write_baseline)
from .rules import RULE_CATALOG, default_rules

__all__ = ["Finding", "LintEngine", "LintReport", "RULE_CATALOG",
           "default_rules", "holds_lock", "load_baseline",
           "write_baseline"]


def holds_lock(*locks):
    """Annotate a method as requiring its CALLER to already hold the
    named lock attribute(s) (e.g. ``@holds_lock("_lock")``).

    Runtime no-op; the ptlint concurrency rule (PT-C001) treats every
    access to a `_GUARDED_BY` field inside a decorated method as guarded.
    The annotation is a promise the call graph must keep — public entry
    points take the lock with ``with self._lock:`` and only they may call
    a ``holds_lock`` helper."""
    def deco(fn):
        fn._ptlint_holds_locks = tuple(locks)
        return fn
    return deco
