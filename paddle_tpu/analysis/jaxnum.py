"""jaxnum: whole-program numerics & mixed-precision analyzer.

The analyzer suite covers trace safety (ptlint), cost (jaxcost),
policy (jaxplan), locks (lockgraph) and sharding (jaxshard); numerics
was guarded by exactly one shallow convert_element_type check in
jaxpr_audit.py. This module gives precision the same artifact
discipline jaxshard gave sharding: a forward abstract interpreter over
jaxprs that propagates, per value, a numerics state — storage dtype,
the effective ACCUMULATION dtype of every dot/reduce/scan it flows
through, and a worst-case relative-error bound in ulps of the
committed f32 reference — through every equation, and commits the
per-program results to `numplan.json` (tools/jaxnum.py
`--plan write|check`, exit 0/1/2, write refuses unsuppressed findings,
check enforces coverage both directions + exact structural drift).

Rules emitted per program:

  NUM-ACC     sub-f32 accumulation in dot_general / reductions / scan
              carries without preferred_element_type / an explicit f32
              accumulator. The bound grows with the contraction or
              trip length (n * u(acc)), so a 4-layer toy passes while
              a flagship-size contraction fails — the gate scales with
              the model, not with the op count.
  NUM-CAST    lossy round-trips (float down-then-up casts that
              discarded mantissa) and integer narrowing whose operand
              range — inferred from clamp/iota/shape/literal
              provenance — cannot be proven to fit the target.
  NUM-FINITE  exp/log/div/rsqrt reachable with an unclamped operand
              whose interval cannot exclude 0 / overflow — the static
              twin of the runtime core/anomaly.py guard.
  NUM-QUANT   a quantize→dequantize pair (round+clip provenance
              flowing into an int convert and back out) whose derived
              scale cannot meet the registry's declared error budget
              for that program, or that has no declared budget at all.

Error model (deterministic, documented, NO-CANCELLATION: worst-case
relative errors are summed, which is the standard gamma_n bound and
ignores catastrophic cancellation — subtractions of near-equal values
are out of scope for a static bound):

  unit roundoff, in f32 ulps (u32 = 2^-24):
      f64 2^-29   f32 1   f16 2^13   bf16 2^16
  elementwise op        eps_out = sum(eps_in) + u(out)
  dot_general           eps_a + eps_b + n_contract * u(acc)
  reduce_sum            eps_in + (n-1) * u(acc)
  scan carry            eps_T = eps_0 + T * per-trip-delta
  quantize(levels=L)+dequantize: error 0.5/L of the tile fullscale
      (reported both as the program's quant bound and as
      (0.5/L)/2^-24 ulps on the dequantized value)

This module also owns the ONE shared dtype lattice: the
bfloat16-aware `jnp.issubdtype` downcast predicate that used to live
in jaxpr_audit.py (`lossy_float_downcast`) plus its integer-narrowing
extension (`lossy_int_narrowing`) — jaxpr_audit delegates here, so
ml_dtypes types outside numpy's hierarchy are handled in exactly one
place.

The registry reuses jaxcost's program registry (train_step, the five
decode sub-programs, serving prefill/paged/chunk/ragged/chunked-
prefill, the three explicit collectives) and adds
`serving.kv_block_codec` — the int8 KV-block codec
(inference/serving/kv_quant.py) whose derived dequant bound numplan
pins against its declared budget. First consumer: the paged cache's
`kv_cache_dtype="int8"` pool mode ships only because that bound is
committed and runtime-verified (tests/test_kv_quant.py parity gate).
"""
from __future__ import annotations

# ptlint: disable-file=PT-T004  registry builders reuse jaxcost's
# program registry, which constructs jit wrappers for TRACING only
# (one build per analysis run behind lru-cached setup; nothing here
# is a serving/training hot path)

import functools
import json
import math
import os
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "NumState", "NumFinding", "NumReport",
    "analyze_fn", "compute_reports", "registry_names",
    "DEFAULT_PLAN_PATH", "DEFAULT_TOLERANCE", "PLAN_VERSION",
    "write_plan", "check_plan", "diff_plans", "load_plan",
    "unsuppressed_findings",
    "ulps32", "lossy_float_downcast", "lossy_int_narrowing",
]

_REPO = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DEFAULT_PLAN_PATH = os.path.join(_REPO, "numplan.json")
PLAN_VERSION = 1
DEFAULT_TOLERANCE = 0.05

#: the committed reference dtype every bound is expressed in ulps of
REF_DTYPE = "float32"
_U32 = 2.0 ** -24          # f32 unit roundoff

#: NUM-ACC fires only at contraction/trip lengths >= this — a toy
#: model's hidden-32 contractions pass, flagship-size ones fail
NUM_ACC_MIN_ELEMS = 64
#: scan bodies are interpreted exactly up to this many trips; longer
#: scans extrapolate the (affine) per-trip error delta linearly
SCAN_EXACT_MAX = 256
#: while carries run to fixpoint; a carry still growing after this
#: many probes is charged this trip count and flagged
WHILE_FIXPOINT_MAX = 32
#: f32 exp overflow threshold: exp(x) is finite iff x < ln(f32 max)
EXP_OVERFLOW = 88.72
#: intervals are derived from captured consts only up to this many
#: elements (bigger consts would make analysis O(model size))
_CONST_INTERVAL_MAX = 65536

_INF = float("inf")


# ------------------------------------------------------- dtype lattice
#
# The one shared dtype table. jnp.issubdtype, not np.issubdtype:
# bfloat16 (ml_dtypes) sits outside numpy's type lattice and is
# exactly the sub-32-bit storage these checks exist to catch.

#: mantissa bits (excluding the implicit leading 1) per float dtype
_MANTISSA = {
    "float64": 52, "float32": 23, "float16": 10, "bfloat16": 7,
    "float8_e4m3fn": 3, "float8_e5m2": 2, "float8_e4m3": 3,
    "float8_e5m2fnuz": 2, "float8_e4m3fnuz": 3,
}


def _dt(dtype_like):
    """np.dtype where possible; opaque dtypes (PRNG keys, extended
    dtypes) pass through untouched and act as non-numeric below."""
    try:
        return np.dtype(dtype_like)
    except TypeError:
        return dtype_like


def _dt_name(dtype_like) -> str:
    d = _dt(dtype_like)
    return d.name if isinstance(d, np.dtype) else str(d)


def is_float(dt) -> bool:
    d = _dt(dt)
    return isinstance(d, np.dtype) and bool(
        jnp.issubdtype(d, jnp.floating))


def is_int(dt) -> bool:
    d = _dt(dt)
    return isinstance(d, np.dtype) and d.kind != "b" and bool(
        jnp.issubdtype(d, jnp.integer))


def unit_roundoff(dt) -> float:
    """Absolute unit roundoff 2^-(mantissa+1); 0 for non-floats."""
    d = _dt(dt)
    if not is_float(d):
        return 0.0
    m = _MANTISSA.get(d.name)
    if m is None:                      # unknown float: use finfo
        m = int(jnp.finfo(d).nmant)
    return 2.0 ** -(m + 1)


def ulps32(dt) -> float:
    """Unit roundoff of `dt` expressed in f32 ulps: u(dt)/u(f32).
    f64 -> 2^-29, f32 -> 1, f16 -> 2^13, bf16 -> 2^16; 0 for ints."""
    return unit_roundoff(dt) / _U32


def lossy_float_downcast(src, dst) -> bool:
    """The historical jaxpr_audit downcast predicate: a float convert
    that drops BELOW 32 bits. The package enables jax_enable_x64, so
    f64 -> f32 converts are everywhere and deliberate — only sub-32-bit
    precision drops are lossy here."""
    src, dst = _dt(src), _dt(dst)
    return (is_float(src) and is_float(dst)
            and src.itemsize >= 4 and dst.itemsize < 4)


def lossy_int_narrowing(src, dst) -> bool:
    """Integer convert to a strictly narrower integer (int64 -> int32
    table/length casts were invisible to the old downcast check)."""
    src, dst = _dt(src), _dt(dst)
    return is_int(src) and is_int(dst) and dst.itemsize < src.itemsize


def int_bounds(dt) -> Tuple[float, float]:
    info = jnp.iinfo(np.dtype(dt))
    return float(info.min), float(info.max)


# ------------------------------------------------------- value state
@dataclass(frozen=True)
class NumState:
    """Per-value numerics state the interpreter propagates.

    eps is the worst-case relative error in f32 ulps under the
    no-cancellation model; [lo, hi] the value interval (clamp/iota/
    literal/const provenance; unbounded when unknown); `rounded` marks
    integral-valued floats (round/floor/ceil outputs — quantization
    codes before their int convert); `was_downcast` marks float values
    that passed through a sub-32-bit storage dtype (NUM-CAST
    round-trip provenance); `qlevels` > 0 marks a quantization code
    (and its dequantized descendants) with that many positive levels.
    """
    dtype: object
    eps: float = 0.0
    lo: float = -_INF
    hi: float = _INF
    rounded: bool = False
    was_downcast: bool = False
    qlevels: int = 0

    def with_(self, **kw) -> "NumState":
        d = {"dtype": self.dtype, "eps": self.eps, "lo": self.lo,
             "hi": self.hi, "rounded": self.rounded,
             "was_downcast": self.was_downcast,
             "qlevels": self.qlevels}
        d.update(kw)
        return NumState(**d)

    @property
    def bounded(self) -> bool:
        return self.lo > -_INF and self.hi < _INF


def _unknown(dtype) -> NumState:
    return NumState(dtype=_dt(dtype))


# ------------------------------------------------------------ findings
@dataclass
class NumFinding:
    """One triaged numerics item; `key` is the suppression key
    committed in numplan.json (grouped rule:primitive:detail, same
    aggregation discipline as jaxshard's implicit-collective keys)."""
    key: str
    rule: str            # NUM-ACC | NUM-CAST | NUM-FINITE | NUM-QUANT
    message: str
    bound_ulps: float = 0.0
    count: int = 1
    example: str = ""
    suppressed: Optional[str] = None

    def to_dict(self) -> dict:
        return {"key": self.key, "rule": self.rule,
                "message": self.message,
                "bound_ulps": _round6(self.bound_ulps),
                "count": self.count, "example": self.example,
                "suppressed": self.suppressed}

    def format(self) -> str:
        tag = "suppressed" if self.suppressed else "UNSUPPRESSED"
        return (f"  [{tag}] {self.rule} {self.key}: {self.message}"
                + (f"  # {self.suppressed}" if self.suppressed else ""))


@dataclass
class NumReport:
    """Per-program numerics report, the unit numplan.json commits."""
    name: str
    ref_dtype: str = REF_DTYPE
    out_dtypes: List[str] = field(default_factory=list)
    acc_dtypes: List[str] = field(default_factory=list)
    max_error_ulps: float = 0.0
    quant: Optional[dict] = None
    findings: List[NumFinding] = field(default_factory=list)
    notes: List[str] = field(default_factory=list)

    def unsuppressed(self) -> List[NumFinding]:
        return [f for f in self.findings if not f.suppressed]

    def to_dict(self) -> dict:
        return {
            "name": self.name,
            "ref_dtype": self.ref_dtype,
            "out_dtypes": list(self.out_dtypes),
            "acc_dtypes": list(self.acc_dtypes),
            "max_error_ulps": _round6(self.max_error_ulps),
            "quant": dict(self.quant) if self.quant else None,
            "findings": {f.key: f.to_dict() for f in self.findings},
        }

    def format(self) -> str:
        lines = [f"{self.name}: max_error={self.max_error_ulps:g} "
                 f"ulps({self.ref_dtype}) "
                 f"out={','.join(self.out_dtypes)} "
                 f"acc={','.join(self.acc_dtypes) or '-'}"]
        if self.quant:
            lines.append(
                f"  quant: levels={self.quant['levels']} derived="
                f"{self.quant['derived_rel_err']:g} budget="
                f"{self.quant['budget_rel_err']:g}")
        for f in self.findings:
            lines.append(f.format())
        for n in self.notes[:6]:
            lines.append(f"  note: {n}")
        return "\n".join(lines)


def _round6(x: float) -> float:
    if not math.isfinite(x):
        return 1e30            # committed plans must stay strict JSON
    return float(f"{float(x):.6g}")


#: equations that run a single sub-jaxpr transparently
_TRANSPARENT_CALLS = frozenset({
    "custom_jvp_call", "custom_vjp_call", "custom_vjp_call_jaxpr",
    "remat", "checkpoint", "closed_call", "core_call", "custom_lin",
})

#: pure data-movement primitives: state passes through unchanged
_SHAPE_OPS = frozenset({
    "broadcast_in_dim", "reshape", "transpose", "squeeze",
    "expand_dims", "rev", "slice", "dynamic_slice", "copy",
    "device_put", "stop_gradient", "gather", "real", "bitcast_convert_type",
    "sharding_constraint", "optimization_barrier",
})

#: exact elementwise selections/sign ops: no new rounding error
_EXACT_ELEMENTWISE = frozenset({
    "neg", "abs", "sign", "max", "min", "and", "or", "xor", "not",
    "shift_left", "shift_right_logical", "shift_right_arithmetic",
    "is_finite", "select_n",
})

#: comparison ops: boolean outputs, exact
_CMP = frozenset({"eq", "ne", "lt", "le", "gt", "ge"})


# ----------------------------------------------------- interval helpers
def _ivl_add(a: NumState, b: NumState) -> Tuple[float, float]:
    return a.lo + b.lo, a.hi + b.hi


def _ivl_sub(a: NumState, b: NumState) -> Tuple[float, float]:
    return a.lo - b.hi, a.hi - b.lo


def _ivl_mul(a: NumState, b: NumState) -> Tuple[float, float]:
    cands = []
    for x in (a.lo, a.hi):
        for y in (b.lo, b.hi):
            p = x * y
            if math.isnan(p):      # 0 * inf
                p = 0.0
            cands.append(p)
    return min(cands), max(cands)


def _hull(states: Sequence[NumState]) -> Tuple[float, float]:
    return min(s.lo for s in states), max(s.hi for s in states)


def _contains_zero(s: NumState) -> bool:
    return s.lo <= 0.0 <= s.hi


# ------------------------------------------------------- interpreter
class _Interp:
    """Forward abstract interpretation of numerics state over one
    program's jaxpr (same handler-dispatch skeleton as jaxshard)."""

    def __init__(self, name: str):
        self.name = name
        self.states: Dict[object, NumState] = {}
        self.findings: Dict[str, NumFinding] = {}
        self.acc_dtypes: set = set()
        self.notes: List[str] = []
        self.quant_events: List[dict] = []

    # -------------------------------------------------------- plumbing
    def read(self, atom) -> NumState:
        if _lit(atom):
            return _literal_state(atom)
        got = self.states.get(atom)
        if got is None:
            got = _unknown(atom.aval.dtype)
        return got

    def write(self, var, st: NumState) -> None:
        self.states[var] = st

    def note(self, msg: str) -> None:
        if msg not in self.notes:
            self.notes.append(msg)

    def finding(self, rule: str, key: str, message: str,
                bound: float = 0.0, path: str = "",
                count: int = 1) -> None:
        got = self.findings.get(key)
        if got is None:
            self.findings[key] = NumFinding(
                key=key, rule=rule, message=message, bound_ulps=bound,
                count=count, example=path)
        else:
            got.count += count
            got.bound_ulps = max(got.bound_ulps, bound)

    def _out_dtype(self, eqn):
        return _dt(eqn.outvars[0].aval.dtype)

    # ------------------------------------------------------------ run
    def run(self, jaxpr_like, in_states: Sequence[NumState],
            path: str, mult: int = 1) -> List[NumState]:
        raw = getattr(jaxpr_like, "jaxpr", jaxpr_like)
        consts = getattr(jaxpr_like, "consts", None)
        for i, v in enumerate(getattr(raw, "constvars", ())):
            cval = consts[i] if consts is not None \
                and i < len(consts) else None
            self.write(v, _const_state(v, cval))
        for v, s in zip(raw.invars, in_states):
            self.write(v, s)
        for i, eqn in enumerate(raw.eqns):
            self.eqn(eqn, f"{path}:{i}", mult)
        return [self.read(v) for v in raw.outvars]

    # ------------------------------------------------------- dispatch
    def eqn(self, eqn, path: str, mult: int) -> None:
        name = eqn.primitive.name
        handler = getattr(self, f"_h_{name}", None)
        if handler is not None:
            handler(eqn, path, mult)
            return
        if name in _TRANSPARENT_CALLS:
            self._h_transparent(eqn, path, mult)
            return
        if name in _SHAPE_OPS:
            self._h_passthrough(eqn, path, mult)
            return
        if name in _CMP or name.startswith("random_") \
                or name in ("iota",):
            # handled below / exact producers
            if name == "iota":
                self._h_iota(eqn, path, mult)
            else:
                self._write_exact(eqn)
            return
        if name in _EXACT_ELEMENTWISE:
            self._h_exact_elementwise(eqn, path, mult)
            return
        if name.startswith("reduce_") or name.startswith("arg"):
            self._h_reduce(eqn, path, mult)
            return
        if name.startswith("cum"):
            self._h_cum(eqn, path, mult)
            return
        self._h_default(eqn, path, mult)

    # ------------------------------------------------ generic handlers
    def _h_default(self, eqn, path: str, mult: int) -> None:
        """Unknown/garden-variety elementwise op: worst-case operand
        errors add, plus one rounding of the output; interval and
        provenance are forgotten."""
        ins = [self.read(v) for v in eqn.invars]
        for ov in eqn.outvars:
            dt = _dt(ov.aval.dtype)
            eps = sum(s.eps for s in ins) + ulps32(dt)
            self.write(ov, NumState(
                dtype=dt, eps=eps if is_float(dt) else 0.0,
                was_downcast=any(s.was_downcast for s in ins)))

    def _h_passthrough(self, eqn, path: str, mult: int) -> None:
        src = self.read(eqn.invars[0])
        for ov in eqn.outvars:
            self.write(ov, src.with_(dtype=_dt(ov.aval.dtype)))

    def _write_exact(self, eqn) -> None:
        for ov in eqn.outvars:
            dt = _dt(ov.aval.dtype)
            lo, hi = (0.0, 1.0) if getattr(dt, "kind", "") == "b" \
                else (-_INF, _INF)
            self.write(ov, NumState(dtype=dt, lo=lo, hi=hi))

    def _h_exact_elementwise(self, eqn, path: str, mult: int) -> None:
        name = eqn.primitive.name
        ins = [self.read(v) for v in eqn.invars]
        dt = self._out_dtype(eqn)
        if name == "select_n":
            cases = ins[1:]
            lo, hi = _hull(cases)
            st = NumState(
                dtype=dt, eps=max(s.eps for s in cases), lo=lo, hi=hi,
                rounded=all(s.rounded for s in cases),
                was_downcast=any(s.was_downcast for s in cases),
                qlevels=min((s.qlevels for s in cases
                             if s.qlevels), default=0)
                if all(s.qlevels for s in cases) else 0)
        elif name == "neg":
            s = ins[0]
            st = s.with_(lo=-s.hi, hi=-s.lo, dtype=dt)
        elif name == "abs":
            s = ins[0]
            lo = 0.0 if _contains_zero(s) else min(abs(s.lo), abs(s.hi))
            st = s.with_(lo=lo, hi=max(abs(s.lo), abs(s.hi)), dtype=dt)
        elif name == "max":
            a, b = ins[0], ins[1]
            st = NumState(dtype=dt, eps=max(a.eps, b.eps),
                          lo=max(a.lo, b.lo), hi=max(a.hi, b.hi),
                          rounded=a.rounded and b.rounded,
                          was_downcast=a.was_downcast or b.was_downcast)
        elif name == "min":
            a, b = ins[0], ins[1]
            st = NumState(dtype=dt, eps=max(a.eps, b.eps),
                          lo=min(a.lo, b.lo), hi=min(a.hi, b.hi),
                          rounded=a.rounded and b.rounded,
                          was_downcast=a.was_downcast or b.was_downcast)
        else:
            eps = max((s.eps for s in ins), default=0.0)
            st = NumState(dtype=dt, eps=eps if is_float(dt) else 0.0)
        for ov in eqn.outvars:
            self.write(ov, st)

    # --------------------------------------------------- arithmetic
    def _binop(self, eqn, ivl_fn) -> NumState:
        a, b = self.read(eqn.invars[0]), self.read(eqn.invars[1])
        dt = self._out_dtype(eqn)
        lo, hi = ivl_fn(a, b)
        return NumState(
            dtype=dt,
            eps=(a.eps + b.eps + ulps32(dt)) if is_float(dt) else 0.0,
            lo=lo, hi=hi,
            was_downcast=a.was_downcast or b.was_downcast)

    def _h_add(self, eqn, path, mult):
        st = self._binop(eqn, _ivl_add)
        a, b = self.read(eqn.invars[0]), self.read(eqn.invars[1])
        self.write(eqn.outvars[0],
                   st.with_(rounded=a.rounded and b.rounded))

    def _h_sub(self, eqn, path, mult):
        st = self._binop(eqn, _ivl_sub)
        a, b = self.read(eqn.invars[0]), self.read(eqn.invars[1])
        self.write(eqn.outvars[0],
                   st.with_(rounded=a.rounded and b.rounded))

    def _h_mul(self, eqn, path, mult):
        st = self._binop(eqn, _ivl_mul)
        a, b = self.read(eqn.invars[0]), self.read(eqn.invars[1])
        # scale * quantization-code keeps the quant provenance: this
        # is the dequant multiply
        q = a.qlevels or b.qlevels
        self.write(eqn.outvars[0], st.with_(qlevels=q))

    def _h_div(self, eqn, path, mult):
        a, b = self.read(eqn.invars[0]), self.read(eqn.invars[1])
        dt = self._out_dtype(eqn)
        if is_float(dt) and _contains_zero(b):
            self.finding(
                "NUM-FINITE", f"finite:div:{self.name_of(eqn)}",
                "division whose denominator interval cannot exclude 0 "
                "(unclamped operand; static twin of the runtime "
                "core/anomaly.py guard)", path=path, count=mult)
        if b.lo > 0 or b.hi < 0:
            cands = [a.lo / b.lo, a.lo / b.hi, a.hi / b.lo, a.hi / b.hi]
            cands = [0.0 if math.isnan(c) else c for c in cands]
            lo, hi = min(cands), max(cands)
        else:
            lo, hi = -_INF, _INF
        self.write(eqn.outvars[0], NumState(
            dtype=dt,
            eps=(a.eps + b.eps + ulps32(dt)) if is_float(dt) else 0.0,
            lo=lo, hi=hi,
            was_downcast=a.was_downcast or b.was_downcast))

    def name_of(self, eqn) -> str:
        return eqn.primitive.name

    def _h_exp(self, eqn, path, mult):
        s = self.read(eqn.invars[0])
        dt = self._out_dtype(eqn)
        if s.hi >= EXP_OVERFLOW:
            self.finding(
                "NUM-FINITE", "finite:exp",
                f"exp of an operand whose interval reaches "
                f"{EXP_OVERFLOW} (f32 overflow): range analysis "
                f"cannot exclude inf without an upstream clamp",
                path=path, count=mult)
        lo = 0.0 if s.lo == -_INF else math.exp(min(s.lo, 700.0))
        hi = _INF if s.hi == _INF else math.exp(min(s.hi, 700.0))
        self.write(eqn.outvars[0], NumState(
            dtype=dt, eps=s.eps + ulps32(dt), lo=lo, hi=hi,
            was_downcast=s.was_downcast))

    def _h_log(self, eqn, path, mult):
        self._log_like(eqn, path, mult, "log", floor=0.0)

    def _h_log1p(self, eqn, path, mult):
        self._log_like(eqn, path, mult, "log1p", floor=-1.0)

    def _h_rsqrt(self, eqn, path, mult):
        self._log_like(eqn, path, mult, "rsqrt", floor=0.0)

    def _log_like(self, eqn, path, mult, what, floor):
        s = self.read(eqn.invars[0])
        dt = self._out_dtype(eqn)
        if s.lo <= floor:
            self.finding(
                "NUM-FINITE", f"finite:{what}",
                f"{what} of an operand whose interval cannot exclude "
                f"{floor} (unclamped operand; static twin of the "
                f"runtime core/anomaly.py guard)",
                path=path, count=mult)
        self.write(eqn.outvars[0], NumState(
            dtype=dt, eps=s.eps + ulps32(dt),
            was_downcast=s.was_downcast))

    def _h_sqrt(self, eqn, path, mult):
        s = self.read(eqn.invars[0])
        dt = self._out_dtype(eqn)
        lo = math.sqrt(max(s.lo, 0.0)) if s.lo > -_INF else 0.0
        hi = math.sqrt(s.hi) if 0 <= s.hi < _INF else _INF
        self.write(eqn.outvars[0], NumState(
            dtype=dt, eps=s.eps + ulps32(dt), lo=lo, hi=hi,
            was_downcast=s.was_downcast))

    def _h_tanh(self, eqn, path, mult):
        self._bounded_unary(eqn, -1.0, 1.0)

    def _h_logistic(self, eqn, path, mult):
        self._bounded_unary(eqn, 0.0, 1.0)

    def _h_erf(self, eqn, path, mult):
        self._bounded_unary(eqn, -1.0, 1.0)

    def _bounded_unary(self, eqn, lo, hi):
        s = self.read(eqn.invars[0])
        dt = self._out_dtype(eqn)
        self.write(eqn.outvars[0], NumState(
            dtype=dt, eps=s.eps + ulps32(dt), lo=lo, hi=hi,
            was_downcast=s.was_downcast))

    def _h_integer_pow(self, eqn, path, mult):
        self._pow_like(eqn, int(eqn.params.get("y", 2)))

    def _pow_like(self, eqn, y):
        s = self.read(eqn.invars[0])
        dt = self._out_dtype(eqn)
        lo, hi = -_INF, _INF
        if s.bounded:
            cands = [s.lo ** y, s.hi ** y]
            lo, hi = min(cands), max(cands)
            if y % 2 == 0:
                lo = 0.0 if _contains_zero(s) else min(cands)
        self.write(eqn.outvars[0], NumState(
            dtype=dt, eps=s.eps * max(abs(y), 1) + ulps32(dt),
            lo=lo, hi=hi, was_downcast=s.was_downcast))

    def _h_square(self, eqn, path, mult):
        # square_p carries no "y" param; NEVER write one into
        # eqn.params — jaxprs are shared via jax's tracing caches, and
        # square's lowering rejects the stray kwarg at compile time
        self._pow_like(eqn, 2)

    # ------------------------------------------------ rounding / clamp
    def _h_round(self, eqn, path, mult):
        s = self.read(eqn.invars[0])
        self.write(eqn.outvars[0], s.with_(
            dtype=self._out_dtype(eqn), rounded=True))

    _h_floor = _h_round
    _h_ceil = _h_round

    def _h_clamp(self, eqn, path, mult):
        lo_s = self.read(eqn.invars[0])
        x = self.read(eqn.invars[1])
        hi_s = self.read(eqn.invars[2])
        dt = self._out_dtype(eqn)
        self.write(eqn.outvars[0], x.with_(
            dtype=dt, lo=max(x.lo, lo_s.lo), hi=min(x.hi, hi_s.hi)))

    def _h_iota(self, eqn, path, mult):
        ov = eqn.outvars[0]
        dt = _dt(ov.aval.dtype)
        dim = eqn.params.get("dimension", 0)
        n = ov.aval.shape[dim] if ov.aval.shape else 1
        self.write(ov, NumState(dtype=dt, lo=0.0, hi=float(n - 1),
                                rounded=True))

    # -------------------------------------------------------- converts
    def _h_convert_element_type(self, eqn, path, mult):
        s = self.read(eqn.invars[0])
        src = _dt(s.dtype)
        dst = _dt(eqn.params.get("new_dtype",
                                      eqn.outvars[0].aval.dtype))
        st = s.with_(dtype=dst)
        if is_float(src) and is_float(dst):
            if ulps32(dst) > ulps32(src):          # losing mantissa
                st = st.with_(eps=s.eps + ulps32(dst),
                              was_downcast=st.was_downcast
                              or dst.itemsize < 4 <= src.itemsize)
            elif s.was_downcast and dst.itemsize >= 4:
                # down-then-up round trip: the mantissa is already
                # gone; the upcast only hides it
                self.finding(
                    "NUM-CAST", f"cast:roundtrip:{src.name}->{dst.name}",
                    f"lossy float round-trip: value was downcast below "
                    f"32 bits and is converted back up to {dst.name} "
                    f"— the discarded mantissa does not come back",
                    bound=s.eps, path=path, count=mult)
                st = st.with_(was_downcast=False)
        elif is_int(src) and is_int(dst):
            if lossy_int_narrowing(src, dst):
                lo, hi = int_bounds(dst)
                if not (s.lo >= lo and s.hi <= hi):
                    self.finding(
                        "NUM-CAST", f"cast:int:{src.name}->{dst.name}",
                        f"integer narrowing {src.name} -> {dst.name} "
                        f"whose operand range "
                        f"[{_fmt_b(s.lo)}, {_fmt_b(s.hi)}] cannot be "
                        f"proven to fit", path=path, count=mult)
        elif is_float(src) and is_int(dst):
            if s.rounded and s.bounded:
                levels = int(max(abs(s.lo), abs(s.hi)))
                ilo, ihi = int_bounds(dst)
                if levels > 0 and s.lo >= ilo and s.hi <= ihi:
                    # a quantize event: round+clip provenance entering
                    # integer storage
                    self.quant_events.append(
                        {"levels": levels, "path": path,
                         "dtype": dst.name, "dequantized": False})
                    st = st.with_(qlevels=levels)
        elif is_int(src) and is_float(dst):
            if s.qlevels:
                for ev in self.quant_events:
                    if ev["levels"] == s.qlevels:
                        ev["dequantized"] = True
                # the dequantized value's error is the quant bound,
                # relative to the tile fullscale, in f32 ulps
                st = st.with_(eps=(0.5 / s.qlevels) / _U32)
            elif s.bounded:
                exact = 2.0 ** (_MANTISSA.get(dst.name, 23) + 1)
                if max(abs(s.lo), abs(s.hi)) > exact:
                    st = st.with_(eps=s.eps + ulps32(dst))
        for ov in eqn.outvars:
            self.write(ov, st)

    # ---------------------------------------------------- accumulation
    def _h_dot_general(self, eqn, path, mult):
        a, b = self.read(eqn.invars[0]), self.read(eqn.invars[1])
        lhs = eqn.invars[0].aval
        (lc, _rc), _ = eqn.params["dimension_numbers"]
        n = 1
        for d in lc:
            n *= int(lhs.shape[d])
        out_dt = self._out_dtype(eqn)
        acc = eqn.params.get("preferred_element_type") or out_dt
        acc = _dt(acc)
        self.acc_dtypes.add(acc.name)
        u_acc = ulps32(acc)
        if u_acc > 1.0 and n >= NUM_ACC_MIN_ELEMS:
            self.finding(
                "NUM-ACC", f"acc:dot_general:{acc.name}",
                f"dot_general accumulates {n} products in {acc.name} "
                f"(error bound {n * u_acc:g} ulps grows with the "
                f"contraction); set preferred_element_type=float32 "
                f"or accumulate explicitly in f32",
                bound=n * u_acc, path=path, count=mult)
        eps = a.eps + b.eps + n * u_acc + ulps32(out_dt)
        self.write(eqn.outvars[0], NumState(
            dtype=out_dt, eps=eps if is_float(out_dt) else 0.0,
            was_downcast=a.was_downcast or b.was_downcast))

    def _h_reduce(self, eqn, path, mult):
        name = eqn.primitive.name
        s = self.read(eqn.invars[0])
        ov = eqn.outvars[0]
        dt = _dt(ov.aval.dtype)
        axes = eqn.params.get("axes", ())
        n = 1
        ishape = getattr(eqn.invars[0].aval, "shape", ())
        for d in axes:
            n *= int(ishape[d])
        if name in ("reduce_max", "reduce_min"):
            self.write(ov, s.with_(dtype=dt))
            return
        if name in ("reduce_and", "reduce_or", "reduce_xor"):
            self.write(ov, NumState(dtype=dt, lo=0.0, hi=1.0))
            return
        if name.startswith("arg"):
            hi = float(max(n - 1, 0))
            self.write(ov, NumState(dtype=dt, lo=0.0, hi=hi,
                                    rounded=True))
            return
        if name == "reduce_sum":
            self.acc_dtypes.add(dt.name)
            u_acc = ulps32(dt)
            if u_acc > 1.0 and n >= NUM_ACC_MIN_ELEMS:
                self.finding(
                    "NUM-ACC", f"acc:reduce_sum:{dt.name}",
                    f"reduce_sum over {n} elements accumulates in "
                    f"{dt.name} (error bound {(n - 1) * u_acc:g} "
                    f"ulps); cast to f32 before the reduction",
                    bound=(n - 1) * u_acc, path=path, count=mult)
            lo = min(n * s.lo, s.lo)
            hi = max(n * s.hi, s.hi)
            self.write(ov, NumState(
                dtype=dt,
                eps=(s.eps + (n - 1) * u_acc) if is_float(dt) else 0.0,
                lo=lo, hi=hi, was_downcast=s.was_downcast))
            return
        if name == "reduce_prod":
            self.acc_dtypes.add(dt.name)
            self.write(ov, NumState(
                dtype=dt,
                eps=(n * s.eps + (n - 1) * ulps32(dt))
                if is_float(dt) else 0.0,
                was_downcast=s.was_downcast))
            return
        self._h_default(eqn, path, mult)

    def _h_cum(self, eqn, path, mult):
        # cumsum/cumprod/cummax...: worst row accumulates like the
        # full reduction
        name = eqn.primitive.name
        s = self.read(eqn.invars[0])
        ov = eqn.outvars[0]
        dt = _dt(ov.aval.dtype)
        axis = eqn.params.get("axis", 0)
        n = int(getattr(ov.aval, "shape", (1,))[axis]) \
            if getattr(ov.aval, "shape", ()) else 1
        if name in ("cummax", "cummin"):
            self.write(ov, s.with_(dtype=dt))
            return
        u_acc = ulps32(dt)
        if name == "cumsum":
            self.acc_dtypes.add(dt.name)
            if u_acc > 1.0 and n >= NUM_ACC_MIN_ELEMS:
                self.finding(
                    "NUM-ACC", f"acc:cumsum:{dt.name}",
                    f"cumsum over {n} elements accumulates in "
                    f"{dt.name}", bound=(n - 1) * u_acc, path=path,
                    count=mult)
        self.write(ov, NumState(
            dtype=dt,
            eps=(s.eps + (n - 1) * u_acc) if is_float(dt) else 0.0,
            was_downcast=s.was_downcast))

    # -------------------------------------------------- control flow
    def _h_jit(self, eqn, path, mult):
        inner = eqn.params["jaxpr"]
        ins = [self.read(v) for v in eqn.invars]
        outs = self.run(inner, ins, f"{path}/pjit", mult)
        for ov, st in zip(eqn.outvars, outs):
            self.write(ov, st)

    def _h_shard_map(self, eqn, path, mult):
        inner = eqn.params["jaxpr"]
        ins = [self.read(v) for v in eqn.invars]
        outs = self.run(inner, ins, f"{path}/shard_map", mult)
        for ov, st in zip(eqn.outvars, outs):
            self.write(ov, st)

    def _h_transparent(self, eqn, path, mult):
        inner = None
        for key in ("call_jaxpr", "fun_jaxpr", "jaxpr"):
            cand = eqn.params.get(key)
            if cand is not None and (hasattr(cand, "jaxpr")
                                     or hasattr(cand, "eqns")):
                inner = cand
                break
        if inner is None:
            for val in eqn.params.values():
                if hasattr(val, "jaxpr") or hasattr(val, "eqns"):
                    inner = val
                    break
        if inner is None:
            self._h_default(eqn, path, mult)
            return
        ins = [self.read(v) for v in eqn.invars]
        raw = getattr(inner, "jaxpr", inner)
        ins = ins[:len(raw.invars)] if len(ins) >= len(raw.invars) \
            else ins + [_unknown(v.aval.dtype)
                        for v in raw.invars[len(ins):]]
        outs = self.run(inner, ins,
                        f"{path}/{eqn.primitive.name}", mult)
        for ov, st in zip(eqn.outvars, outs):
            self.write(ov, st)

    def _h_scan(self, eqn, path, mult):
        p = eqn.params
        T = int(p.get("length", 1))
        n_consts = int(p.get("num_consts", 0))
        n_carry = int(p.get("num_carry", 0))
        inner = p["jaxpr"]
        ins = [self.read(v) for v in eqn.invars]
        consts = ins[:n_consts]
        carry = list(ins[n_consts:n_consts + n_carry])
        xs = [s.with_() for s in ins[n_consts + n_carry:]]
        carry0_eps = [s.eps for s in carry]
        ys: List[NumState] = []
        trips = min(T, SCAN_EXACT_MAX)
        prev_eps = carry0_eps
        for _t in range(trips):
            outs = self.run(inner, consts + carry + xs,
                            f"{path}/scan", mult)
            carry = list(outs[:n_carry])
            ys = outs[n_carry:]
            prev2, prev_eps = prev_eps, [s.eps for s in carry]
            if prev_eps == prev2:
                break                       # carry error fixpoint
        if T > trips:
            # extrapolate the affine per-trip delta for the tail
            deltas = [cur - prev
                      for cur, prev in zip(prev_eps, prev2)]
            carry = [s.with_(eps=s.eps + max(d, 0.0) * (T - trips))
                     for s, d in zip(carry, deltas)]
            self.note(f"scan at {path}: {T} trips, interpreted "
                      f"{trips} exactly then extrapolated linearly")
        for st, e0 in zip(carry, carry0_eps):
            dt = _dt(st.dtype)
            u = ulps32(dt)
            if is_float(dt) and u > 1.0 and st.eps > e0 \
                    and T >= NUM_ACC_MIN_ELEMS:
                self.finding(
                    "NUM-ACC", f"acc:scan:{dt.name}",
                    f"scan carry accumulates in {dt.name} over {T} "
                    f"trips (error bound grows {st.eps - e0:g} ulps "
                    f"across the loop); carry an f32 accumulator",
                    bound=st.eps, path=path, count=mult)
        for ov, st in zip(eqn.outvars, carry + list(ys)):
            self.write(ov, st)

    def _h_while(self, eqn, path, mult):
        p = eqn.params
        cn = int(p.get("cond_nconsts", 0))
        bn = int(p.get("body_nconsts", 0))
        body = p["body_jaxpr"]
        ins = [self.read(v) for v in eqn.invars]
        bconsts = ins[cn:cn + bn]
        carry = list(ins[cn + bn:])
        prev_eps = [s.eps for s in carry]
        converged = False
        for _t in range(WHILE_FIXPOINT_MAX):
            outs = self.run(body, bconsts + carry,
                            f"{path}/while", mult)
            carry = [st.with_(lo=min(st.lo, old.lo),
                              hi=max(st.hi, old.hi))
                     for st, old in zip(outs, carry)]
            cur = [s.eps for s in carry]
            if cur == prev_eps:
                converged = True
                break
            prev_eps = cur
        if not converged:
            self.note(f"while at {path}: carry error still growing "
                      f"after {WHILE_FIXPOINT_MAX} probes; bound is "
                      f"a floor, not a ceiling")
            for st in carry:
                dt = _dt(st.dtype)
                if is_float(dt) and ulps32(dt) > 1.0:
                    self.finding(
                        "NUM-ACC", f"acc:while:{dt.name}",
                        f"while carry accumulates in {dt.name} with "
                        f"an unbounded trip count",
                        bound=st.eps, path=path, count=mult)
        for ov, st in zip(eqn.outvars, carry):
            self.write(ov, st)

    def _h_cond(self, eqn, path, mult):
        branches = eqn.params["branches"]
        ins = [self.read(v) for v in eqn.invars[1:]]
        per_branch = [self.run(br, ins, f"{path}/cond[{i}]", mult)
                      for i, br in enumerate(branches)]
        for j, ov in enumerate(eqn.outvars):
            cases = [outs[j] for outs in per_branch]
            lo, hi = _hull(cases)
            self.write(ov, NumState(
                dtype=_dt(ov.aval.dtype),
                eps=max(s.eps for s in cases), lo=lo, hi=hi,
                rounded=all(s.rounded for s in cases),
                was_downcast=any(s.was_downcast for s in cases)))

    # --------------------------------------------- structured updates
    def _h_concatenate(self, eqn, path, mult):
        ins = [self.read(v) for v in eqn.invars]
        dt = self._out_dtype(eqn)
        lo, hi = _hull(ins)
        self.write(eqn.outvars[0], NumState(
            dtype=dt, eps=max(s.eps for s in ins), lo=lo, hi=hi,
            rounded=all(s.rounded for s in ins),
            was_downcast=any(s.was_downcast for s in ins)))

    def _h_pad(self, eqn, path, mult):
        x, pad = self.read(eqn.invars[0]), self.read(eqn.invars[1])
        dt = self._out_dtype(eqn)
        lo, hi = _hull([x, pad])
        self.write(eqn.outvars[0], x.with_(dtype=dt, lo=lo, hi=hi))

    def _h_dynamic_update_slice(self, eqn, path, mult):
        x, upd = self.read(eqn.invars[0]), self.read(eqn.invars[1])
        dt = self._out_dtype(eqn)
        lo, hi = _hull([x, upd])
        self.write(eqn.outvars[0], NumState(
            dtype=dt, eps=max(x.eps, upd.eps), lo=lo, hi=hi,
            rounded=x.rounded and upd.rounded,
            was_downcast=x.was_downcast or upd.was_downcast,
            qlevels=x.qlevels if x.qlevels == upd.qlevels else 0))

    def _h_scatter(self, eqn, path, mult):
        self._h_dynamic_update_slice_like(eqn)

    _h_scatter_add = _h_scatter

    def _h_dynamic_update_slice_like(self, eqn):
        x, upd = self.read(eqn.invars[0]), self.read(eqn.invars[-1])
        dt = self._out_dtype(eqn)
        self.write(eqn.outvars[0], NumState(
            dtype=dt, eps=max(x.eps, upd.eps) + (
                ulps32(dt) if eqn.primitive.name.endswith("add")
                else 0.0),
            was_downcast=x.was_downcast or upd.was_downcast))


# ------------------------------------------------------------- helpers
def _lit(atom) -> bool:
    return type(atom).__name__ == "Literal" or hasattr(atom, "val")


def _literal_state(atom) -> NumState:
    dt = _dt(atom.aval.dtype)
    try:
        v = float(np.asarray(atom.val).reshape(()))
    except Exception:
        return _unknown(dt)
    rounded = math.isfinite(v) and float(v).is_integer()
    return NumState(dtype=dt, lo=v, hi=v, rounded=rounded)


def _const_state(var, cval) -> NumState:
    dt = _dt(var.aval.dtype)
    if cval is None:
        return _unknown(dt)
    try:
        arr = np.asarray(cval)
        if arr.size == 0 or arr.size > _CONST_INTERVAL_MAX \
                or arr.dtype.kind not in "ifu" \
                or arr.dtype.name == "bfloat16":
            return _unknown(dt)
        lo, hi = float(arr.min()), float(arr.max())
        if not (math.isfinite(lo) and math.isfinite(hi)):
            return _unknown(dt)
        rounded = bool(np.all(arr == np.round(
            arr.astype(np.float64)))) if arr.dtype.kind == "f" else True
        return NumState(dtype=dt, lo=lo, hi=hi, rounded=rounded)
    except Exception:
        return _unknown(dt)


def _fmt_b(x: float) -> str:
    return "inf" if x == _INF else "-inf" if x == -_INF else f"{x:g}"


# ------------------------------------------------------------- analyze
def analyze_fn(fn, *args, name: str,
               static_argnums: Sequence[int] = (),
               suppress: Optional[Dict[str, str]] = None,
               quant_budget: Optional[float] = None) -> NumReport:
    """Trace `fn` with the example args and abstract-interpret its
    numerics. `suppress` maps finding keys to triage reasons;
    `quant_budget` is the program's declared quantization error budget
    (relative fullscale), checked against the derived bound."""
    closed = jax.make_jaxpr(fn, static_argnums=tuple(static_argnums))(
        *args)
    interp = _Interp(name)
    flat_in = closed.jaxpr.invars
    in_states = [_unknown(v.aval.dtype) for v in flat_in]
    outs = interp.run(closed, in_states, name)

    report = NumReport(name=name)
    report.out_dtypes = [_dt_name(s.dtype) for s in outs]
    report.acc_dtypes = sorted(interp.acc_dtypes)
    float_eps = [s.eps for s in outs if is_float(s.dtype)]
    report.max_error_ulps = max(float_eps, default=0.0)
    report.notes = list(interp.notes)

    # ---- NUM-QUANT: derived bound vs the declared budget
    events = interp.quant_events
    if events:
        levels = min(ev["levels"] for ev in events)
        derived = 0.5 / levels
        report.quant = {
            "levels": levels,
            "derived_rel_err": _round6(derived),
            "budget_rel_err": _round6(quant_budget)
            if quant_budget is not None else None,
        }
        if quant_budget is None:
            interp.finding(
                "NUM-QUANT", "quant:undeclared",
                f"quantize→dequantize pair found (levels={levels}, "
                f"derived error {derived:g} fullscale) but the "
                f"registry declares no error budget for this program",
                bound=derived / _U32, path=events[0]["path"])
        elif derived > quant_budget * (1 + 1e-9):
            interp.finding(
                "NUM-QUANT", "quant:budget",
                f"derived quantization error {derived:g} exceeds the "
                f"declared budget {quant_budget:g} (levels={levels})",
                bound=derived / _U32, path=events[0]["path"])
        if not any(ev["dequantized"] for ev in events):
            report.notes.append(
                "quantize without a matching dequantize: codes leave "
                "the program still encoded")
    elif quant_budget is not None:
        interp.finding(
            "NUM-QUANT", "quant:missing",
            f"the registry declares a quantization error budget "
            f"({quant_budget:g}) but no quantize→dequantize pair was "
            f"found in the program")

    report.findings = [interp.findings[k]
                       for k in sorted(interp.findings)]
    _apply_suppressions(report, suppress or {})
    return report


def _apply_suppressions(report: NumReport,
                        suppress: Dict[str, str]) -> None:
    used = set()
    for f in report.findings:
        reason = suppress.get(f.key)
        if reason:
            f.suppressed = reason
            used.add(f.key)
    for key in sorted(set(suppress) - used):
        report.notes.append(
            f"unused suppression {key!r} (finding no longer emitted "
            f"— drop it from the registry)")


# ------------------------------------------------------------ registry
@dataclass(frozen=True)
class _NumProgram:
    name: str
    build: Callable          # () -> (fn, args, static_argnums)
    suppress: Dict[str, str] = field(default_factory=dict)
    quant_budget: Optional[float] = None


#: first-run triage: every finding the registry programs emit today,
#: each with the reason it is acceptable. The suppression IS the
#: review record — remove the root cause and the plan check will flag
#: the suppression as unused.
_SOFTMAX_EXP = ("softmax computes exp(x - max(x)) <= exp(0): the "
                "shared-max subtraction is a relational fact interval "
                "analysis cannot see; the runtime core/anomaly.py "
                "guard covers the residual risk")
_SOFTMAX_DIV = ("softmax denominator sum(exp(x - max(x))) >= 1 "
                "relationally (the max element contributes exp(0)); "
                "intervals lose the shared-max relation")
_CE_LOG = ("cross_entropy uses log-sum-exp: the log operand "
           "sum(exp(x - max(x))) >= 1 relationally (the max element "
           "contributes exp(0)); intervals lose the shared-max "
           "relation (nn/functional/loss.py lse)")
_LOGPROB = ("token-logprob tracking uses jax.nn.log_softmax, whose "
            "log operand sum(exp(x - max(x))) >= 1 relationally "
            "(models/generation.py decode_chunk sampler)")
_LABEL_NARROW = ("cross_entropy reshapes int64 label inputs (x64 mode "
                 "default) to int32 for the logprob gather; labels "
                 "are program inputs with no static range, but XLA "
                 "gather clamps out-of-range indices and the "
                 "vocab-size contract bounds them at runtime")

_SUPPRESS: Dict[str, Dict[str, str]] = {
    "train_step": {
        "finite:exp": _SOFTMAX_EXP,
        "finite:div:div": _SOFTMAX_DIV,
        "finite:log": _CE_LOG,
        "cast:int:int64->int32": _LABEL_NARROW,
    },
    "decode.qkv": {},
    "decode.attn": {
        "finite:exp": _SOFTMAX_EXP,
        "finite:div:div": _SOFTMAX_DIV,
    },
    "serving.prefill": {
        "finite:exp": _SOFTMAX_EXP,
        "finite:div:div": _SOFTMAX_DIV,
    },
    "serving.paged_decode": {
        "finite:exp": _SOFTMAX_EXP,
        "finite:div:div": _SOFTMAX_DIV,
    },
    "serving.decode_chunk": {
        "finite:exp": _SOFTMAX_EXP,
        "finite:div:div": _SOFTMAX_DIV,
        "finite:log": _LOGPROB,
    },
    "serving.chunked_prefill": {
        "finite:exp": _SOFTMAX_EXP,
        "finite:div:div": _SOFTMAX_DIV,
        "finite:log": _LOGPROB,
    },
    "serving.ragged_attention": {
        "finite:exp": _SOFTMAX_EXP,
        "finite:div:div": _SOFTMAX_DIV,
    },
    "serving.kv_block_codec": {
        "finite:div:div": (
            "the codec divides by where(scale > 0, scale, 1): the "
            "select guard excludes 0 relationally, but the interval "
            "hull of {scale, 1.0} still contains 0; an all-zero tile "
            "encodes to exact zeros either way "
            "(inference/serving/kv_quant.py _safe)"),
    },
    "collective.ring_attention": {
        "finite:exp": _SOFTMAX_EXP,
        "finite:div:div": _SOFTMAX_DIV,
    },
    "collective.ulysses_attention": {
        "finite:exp": _SOFTMAX_EXP,
        "finite:div:div": _SOFTMAX_DIV,
    },
}


def _kv_codec_build():
    from ..inference.serving import kv_quant
    x = jnp.zeros((4, 16, 4, 8), jnp.float32)
    return kv_quant.kv_block_roundtrip, (x,), ()


def registry_names() -> List[str]:
    from .jaxcost import registry_names as cost_names
    return list(cost_names()) + ["serving.kv_block_codec"]


def _build_num_programs(names: Optional[Sequence[str]] = None
                        ) -> List[_NumProgram]:
    from .jaxcost import _build_programs, registry_names as cost_names
    known = set(cost_names()) | {"serving.kv_block_codec"}
    if names is not None:
        unknown = sorted(set(names) - known)
        if unknown:
            raise KeyError(
                f"unknown program(s): {', '.join(unknown)}; known: "
                f"{', '.join(sorted(known))}")
    want_codec = names is None or "serving.kv_block_codec" in names
    cost_wanted = None if names is None else [
        n for n in names if n != "serving.kv_block_codec"]
    out: List[_NumProgram] = []
    if cost_wanted is None or cost_wanted:
        for p in _build_programs(cost_wanted):
            out.append(_NumProgram(
                name=p.name,
                build=(lambda p=p: (p.fn, p.args, p.static_argnums)),
                suppress=_SUPPRESS.get(p.name, {})))
    if want_codec:
        from ..inference.serving.kv_quant import KV_INT8_REL_ERR
        out.append(_NumProgram(
            name="serving.kv_block_codec", build=_kv_codec_build,
            suppress=_SUPPRESS.get("serving.kv_block_codec", {}),
            quant_budget=KV_INT8_REL_ERR))
    return out


def compute_reports(names: Optional[Sequence[str]] = None
                    ) -> Dict[str, NumReport]:
    """Analyze every (selected) registry program."""
    reports: Dict[str, NumReport] = {}
    for prog in _build_num_programs(names):
        fn, args, static = prog.build()
        reports[prog.name] = analyze_fn(
            fn, *args, name=prog.name, static_argnums=static,
            suppress=prog.suppress, quant_budget=prog.quant_budget)
    return reports


# ------------------------------------------------------------ plan I/O
def _plan_payload(reports: Dict[str, NumReport]) -> dict:
    return {
        "version": PLAN_VERSION,
        "tolerance": DEFAULT_TOLERANCE,
        "ref_dtype": REF_DTYPE,
        "programs": {name: rep.to_dict()
                     for name, rep in sorted(reports.items())},
    }


def write_plan(path: str, reports: Dict[str, NumReport]) -> dict:
    payload = _plan_payload(reports)
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")
    return payload


@functools.lru_cache(maxsize=16)
def _load_plan_cached(path: str, mtime_ns: int) -> dict:
    with open(path) as f:
        return json.load(f)


def load_plan(path: str = DEFAULT_PLAN_PATH) -> Optional[dict]:
    """Committed precision plan, or None when missing. stdlib-only."""
    try:
        mtime = os.stat(path).st_mtime_ns
    except OSError:
        return None
    return _load_plan_cached(path, mtime)


def _num_drift(cur, ref, tol: float) -> bool:
    lo, hi = sorted((float(cur), float(ref)))
    return hi - lo > tol * max(hi, 1.0)


def diff_plans(committed: dict, current: dict,
               tolerance: Optional[float] = None) -> List[str]:
    """Violations between a committed plan and a freshly computed one:
    coverage both directions, structural drift (dtypes, finding keys,
    quant levels) exact, error bounds within tolerance."""
    tol = tolerance if tolerance is not None else float(
        committed.get("tolerance", DEFAULT_TOLERANCE))
    out: List[str] = []
    if committed.get("ref_dtype", REF_DTYPE) != \
            current.get("ref_dtype", REF_DTYPE):
        out.append(f"reference dtype drift "
                   f"{committed.get('ref_dtype')} -> "
                   f"{current.get('ref_dtype')}")
    cp = committed.get("programs", {})
    np_ = current.get("programs", {})
    for name in sorted(set(cp) - set(np_)):
        out.append(f"{name}: committed but no longer in the registry")
    for name in sorted(set(np_) - set(cp)):
        out.append(f"{name}: registry program missing from the "
                   f"committed plan")
    for name in sorted(set(cp) & set(np_)):
        a, b = cp[name], np_[name]
        for fieldname in ("ref_dtype", "out_dtypes", "acc_dtypes"):
            if a.get(fieldname) != b.get(fieldname):
                out.append(f"{name}: {fieldname} drift "
                           f"{a.get(fieldname)} -> {b.get(fieldname)}")
        if _num_drift(b.get("max_error_ulps", 0),
                      a.get("max_error_ulps", 0), tol):
            out.append(
                f"{name}: max_error_ulps drifted "
                f"{a.get('max_error_ulps', 0):g} -> "
                f"{b.get('max_error_ulps', 0):g} (> {tol:.0%})")
        qa, qb = a.get("quant"), b.get("quant")
        if (qa is None) != (qb is None):
            out.append(f"{name}: quantization pattern "
                       f"{'appeared' if qb else 'disappeared'}")
        elif qa is not None:
            if qa.get("levels") != qb.get("levels"):
                out.append(f"{name}: quant levels drift "
                           f"{qa.get('levels')} -> {qb.get('levels')}")
            for k in ("derived_rel_err", "budget_rel_err"):
                va, vb = qa.get(k), qb.get(k)
                if (va is None) != (vb is None) or (
                        va is not None and _num_drift(vb, va, tol)):
                    out.append(f"{name}: quant {k} drifted "
                               f"{va} -> {vb}")
        af, bf = a.get("findings", {}), b.get("findings", {})
        if sorted(af) != sorted(bf):
            out.append(f"{name}: finding keys drifted "
                       f"{sorted(af)} -> {sorted(bf)}")
        else:
            for key in af:
                sa = af[key].get("suppressed")
                sb = bf[key].get("suppressed")
                if bool(sa) != bool(sb):
                    out.append(f"{name}: finding {key} suppression "
                               f"changed ({bool(sa)} -> {bool(sb)})")
                elif _num_drift(bf[key].get("bound_ulps", 0),
                                af[key].get("bound_ulps", 0), tol):
                    out.append(
                        f"{name}: finding {key} bound drifted "
                        f"{af[key].get('bound_ulps', 0):g} -> "
                        f"{bf[key].get('bound_ulps', 0):g}")
    return out


def unsuppressed_findings(reports: Dict[str, NumReport]) -> List[str]:
    out = []
    for name, rep in sorted(reports.items()):
        for f in rep.unsuppressed():
            out.append(f"{name}: {f.key}: {f.message}")
    return out


def check_plan(path: str = DEFAULT_PLAN_PATH,
               reports: Optional[Dict[str, NumReport]] = None,
               ) -> List[str]:
    """Violations of the committed plan: missing/stale file, version
    drift, structural/numeric drift vs a fresh analysis, and any
    unsuppressed finding."""
    committed = load_plan(path)
    if committed is None:
        return [f"no committed precision plan at {path} — run "
                f"tools/jaxnum.py --plan write"]
    if committed.get("version") != PLAN_VERSION:
        return [f"plan version {committed.get('version')} != analyzer "
                f"version {PLAN_VERSION} — re-write the plan"]
    if reports is None:
        reports = compute_reports()
    out = unsuppressed_findings(reports)
    out += diff_plans(committed, _plan_payload(reports))
    return out


def committed_codec_bound(path: str = DEFAULT_PLAN_PATH
                          ) -> Optional[float]:
    """The int8 KV codec's committed worst-case dequant error
    (relative fullscale) from numplan.json — the runtime parity tests
    gate against THIS number, so a loosened codec cannot pass without
    re-committing the plan. None when no plan is committed."""
    plan = load_plan(path)
    if not plan:
        return None
    entry = plan.get("programs", {}).get("serving.kv_block_codec")
    if not entry or not entry.get("quant"):
        return None
    return float(entry["quant"]["derived_rel_err"])
