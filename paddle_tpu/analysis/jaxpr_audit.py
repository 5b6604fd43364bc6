"""Trace-time jaxpr auditor: inspect what actually got staged.

The AST rules catch what the SOURCE says; this module checks what the
COMPILER sees. After tracing an entry point to a jaxpr it walks every
equation (recursing through pjit/scan/cond/while sub-jaxprs) for:

- forbidden primitives ("callbacks"): host callbacks (pure_callback,
  io_callback, debug_callback, ...) — each one is a device→host round
  trip buried in the hot program;
- oversized captured constants ("consts"): closure-captured arrays are
  baked into the executable and re-uploaded per compile; big ones mean
  someone closed over parameters instead of passing them as arguments;
- unintended dtype downcasts ("downcasts"): convert_element_type from a
  >=32-bit float to a sub-32-bit float. NOTE the package enables
  jax_enable_x64, so f64→f32 converts are everywhere and deliberate —
  only precision drops BELOW 32 bits are flagged. The dtype predicate
  itself lives in analysis/jaxnum.py (`lossy_float_downcast`) — ONE
  bfloat16-aware lattice shared with the whole-program numerics
  analyzer;
- integer narrowing ("int_narrowing", opt-in): convert_element_type to
  a strictly narrower integer (int64→int32 table/length casts). Not in
  DEFAULT_CHECKS because gather-index casts (`lab.astype(int32)`) are
  deliberate and this trace-level check has no value-range analysis to
  tell them apart — jaxnum's NUM-CAST rule is the range-aware version,
  and numplan.json is where its findings are triaged and gated.

Entry points: `audit_fn` on any callable, `audit_train_step` on a
jit.TrainStep, `audit_decode_programs` on the four decode sub-programs
that serve both the dense and paged paths (models/generation.py).
Call these before timing, so that a perf run fails loudly instead
of quietly timing a host round-trip.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Iterable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# the shared dtype lattice: jaxnum owns the bfloat16-aware downcast /
# narrowing predicates (module-level import is cycle-safe — jaxnum's
# registry imports run lazily inside its builder functions)
from . import jaxnum as _lattice

__all__ = ["AuditIssue", "JaxprAuditError", "FORBIDDEN_PRIMITIVES",
           "audit_jaxpr", "audit_fn", "audit_train_step",
           "audit_decode_programs", "assert_clean",
           "train_step_args", "decode_programs"]

#: primitives that smuggle host work into a compiled program
FORBIDDEN_PRIMITIVES = frozenset({
    "pure_callback", "io_callback", "debug_callback", "callback",
    "outside_call", "host_callback", "device_get", "host_local_array",
})

DEFAULT_CHECKS = ("callbacks", "consts", "downcasts")
#: every check audit_jaxpr knows; "int_narrowing" is opt-in (see the
#: module docstring for why)
ALL_CHECKS = ("callbacks", "consts", "downcasts", "int_narrowing")
#: one closure-captured array bigger than this means someone baked
#: state into the executable instead of passing it as an argument
DEFAULT_MAX_CONST_BYTES = 1 << 20


@dataclass(frozen=True)
class AuditIssue:
    kind: str        # "callback" | "const" | "downcast" | "int_narrowing"
    where: str       # entry-point name (+ sub-jaxpr path)
    message: str

    def format(self) -> str:
        return f"[{self.kind}] {self.where}: {self.message}"


class JaxprAuditError(RuntimeError):
    def __init__(self, issues: Sequence[AuditIssue]):
        self.issues = list(issues)
        lines = "\n  ".join(i.format() for i in self.issues)
        super().__init__(
            f"jaxpr audit failed with {len(self.issues)} issue(s):\n"
            f"  {lines}")


def _sub_jaxprs(eqn) -> Iterable[Tuple[str, object]]:
    """Yield (label, jaxpr-like) for every sub-program an equation
    carries (pjit bodies, scan/while carries, cond branches, ...)."""
    for key, val in eqn.params.items():
        vals = val if isinstance(val, (list, tuple)) else [val]
        for i, v in enumerate(vals):
            if hasattr(v, "jaxpr") or hasattr(v, "eqns"):
                label = key if len(vals) == 1 else f"{key}[{i}]"
                yield label, v


def _iter_eqns(jaxpr_like, path: str):
    """DFS over equations; yields (eqn, path). Accepts ClosedJaxpr or
    raw Jaxpr; also yields each ClosedJaxpr met (for const checks)."""
    closed = jaxpr_like if hasattr(jaxpr_like, "jaxpr") else None
    raw = closed.jaxpr if closed is not None else jaxpr_like
    yield ("__closed__", closed, path)
    for eqn in raw.eqns:
        yield ("__eqn__", eqn, path)
        for label, sub in _sub_jaxprs(eqn):
            sub_path = f"{path}/{eqn.primitive.name}.{label}"
            yield from _iter_eqns(sub, sub_path)


def _nbytes(x) -> int:
    try:
        return int(np.asarray(jax.core.get_aval(x).dtype.itemsize)
                   * np.prod(jax.core.get_aval(x).shape, dtype=np.int64))
    except Exception:
        arr = np.asarray(x)
        return int(arr.nbytes)


def _dtype_of(var):
    aval = getattr(var, "aval", None)
    return getattr(aval, "dtype", None)


def _is_literal(var) -> bool:
    return type(var).__name__ == "Literal" or hasattr(var, "val")


def audit_jaxpr(jaxpr_like, name: str = "<jaxpr>",
                checks: Sequence[str] = DEFAULT_CHECKS,
                max_const_bytes: int = DEFAULT_MAX_CONST_BYTES
                ) -> List[AuditIssue]:
    """Audit one (Closed)Jaxpr; returns the list of issues (empty =
    clean). `checks` selects from {"callbacks", "consts", "downcasts"}."""
    checks = set(checks)
    issues: List[AuditIssue] = []
    for tag, obj, path in _iter_eqns(jaxpr_like, name):
        if tag == "__closed__":
            if obj is None or "consts" not in checks:
                continue
            for c in getattr(obj, "consts", []):
                n = _nbytes(c)
                if n > max_const_bytes:
                    shape = tuple(getattr(jax.core.get_aval(c), "shape",
                                          ()))
                    issues.append(AuditIssue(
                        "const", path,
                        f"captured constant of {n} bytes (shape {shape})"
                        f" baked into the executable (> "
                        f"{max_const_bytes}); pass it as an argument "
                        f"instead of closing over it"))
            continue
        eqn = obj
        pname = eqn.primitive.name
        if "callbacks" in checks and pname in FORBIDDEN_PRIMITIVES:
            issues.append(AuditIssue(
                "callback", path,
                f"forbidden primitive '{pname}' — a host round-trip "
                f"inside the compiled program"))
        if pname == "convert_element_type" and (
                "downcasts" in checks or "int_narrowing" in checks):
            invar = eqn.invars[0]
            if _is_literal(invar):
                continue  # literal converts are free trace-time consts
            src = _dtype_of(invar)
            dst = eqn.params.get("new_dtype")
            if src is None or dst is None:
                continue
            src = np.dtype(src)
            dst = np.dtype(dst)
            if "downcasts" in checks and \
                    _lattice.lossy_float_downcast(src, dst):
                issues.append(AuditIssue(
                    "downcast", path,
                    f"float downcast {src.name} -> {dst.name}: "
                    f"sub-32-bit precision entered the program; if "
                    f"intentional, audit with checks excluding "
                    f"'downcasts'"))
            if "int_narrowing" in checks and \
                    _lattice.lossy_int_narrowing(src, dst):
                issues.append(AuditIssue(
                    "int_narrowing", path,
                    f"integer narrowing {src.name} -> {dst.name}: "
                    f"values past 2^{8 * dst.itemsize - 1} wrap; "
                    f"jaxnum's NUM-CAST rule proves or refutes the "
                    f"range — prefer gating via numplan.json"))
    return issues


def audit_fn(fn, *args, name: Optional[str] = None,
             static_argnums: Sequence[int] = (),
             checks: Sequence[str] = DEFAULT_CHECKS,
             max_const_bytes: int = DEFAULT_MAX_CONST_BYTES,
             ) -> List[AuditIssue]:
    """Trace `fn` with the example args and audit the result. Works on
    plain callables and jitted wrappers alike (jit bodies show up as
    pjit sub-jaxprs and are recursed into)."""
    label = name or getattr(fn, "__name__", repr(fn))
    closed = jax.make_jaxpr(fn, static_argnums=tuple(static_argnums))(
        *args)
    return audit_jaxpr(closed, name=label, checks=checks,
                       max_const_bytes=max_const_bytes)


def assert_clean(issues: Sequence[AuditIssue]) -> None:
    if issues:
        raise JaxprAuditError(issues)


# ----------------------------------------------------------- entry points
def decode_programs(params, geom, batch: int = 2):
    """[(name, fn, example_args, static_argnums), ...] for the five
    top-level jitted decode sub-programs every decode path (dense
    generate() AND paged serving) compiles: _token_embed, _decode_qkv,
    _cache_write, _decode_attn, _decode_head. `params`/`geom` as for
    models.generation (geom = (L, H, D, S)). Shared by the trace-time
    audit below and jaxcost's cost/donation registry."""
    from ..models import generation as g

    L, H, D, S = geom
    C = H * D
    dtype = jnp.asarray(params["wte.weight"]).dtype
    B = batch
    tokens = jnp.zeros((B,), jnp.int32)
    positions = jnp.zeros((B,), jnp.int32)
    x = jnp.zeros((B, 1, C), dtype)
    q = jnp.zeros((B, H, 1, D), dtype)
    kc = jnp.zeros((B, H, S, D), dtype)
    vc = jnp.zeros((B, H, S, D), dtype)
    k_new = jnp.zeros((B, H, 1, D), dtype)
    v_new = jnp.zeros((B, H, 1, D), dtype)
    pos = jnp.zeros((), jnp.int32)
    return [
        ("token_embed", g._token_embed,
         (params, tokens, positions), ()),
        ("qkv", g._decode_qkv, (params, 0, x, geom), (1, 3)),
        ("cache_write", g._cache_write,
         (kc, vc, k_new, v_new, pos), ()),
        ("attn", g._decode_attn,
         (params, 0, x, q, kc, vc, positions, geom), (1, 7)),
        ("head", g._decode_head, (params, x), ()),
    ]


def audit_decode_programs(params, geom,
                          batch: int = 2,
                          checks: Sequence[str] = DEFAULT_CHECKS,
                          max_const_bytes: int = DEFAULT_MAX_CONST_BYTES,
                          ) -> List[AuditIssue]:
    """Audit the decode sub-programs (see `decode_programs`)."""
    issues: List[AuditIssue] = []
    for name, fn, args, static in decode_programs(params, geom, batch):
        issues += audit_fn(fn, *args, name=f"decode.{name}",
                           static_argnums=static, checks=checks,
                           max_const_bytes=max_const_bytes)
    return issues


def train_step_args(step, *batch):
    """Assemble the example argument tuple for a jit.TrainStep's raw
    step — the same assembly as TrainStep._dispatch, without running
    anything. Shared by the trace-time audit and jaxcost."""
    from ..core.tensor import Tensor

    params_t, frozen_t, buffers_t = step._collect_state()
    params = {k: p._value for k, p in params_t}
    frozen = {k: p._value for k, p in frozen_t}
    buffers = {k: b._value for k, b in buffers_t}
    opt_state = step._opt_state
    if opt_state is None:
        opt_state = step.optimizer.init_opt_state(params)
    lr = jnp.asarray(float(step.optimizer.get_lr()), jnp.float32)
    key_root = step._key_root
    if key_root is None:
        key_root = jax.random.PRNGKey(0)
    rng_ctr = jnp.asarray(1, jnp.uint32)
    arr = [a._value if isinstance(a, Tensor) else jnp.asarray(a)
           for a in batch]
    return (params, frozen, buffers, opt_state, lr, key_root, rng_ctr,
            *arr)


def audit_train_step(step, *batch,
                     checks: Sequence[str] = DEFAULT_CHECKS,
                     max_const_bytes: int = DEFAULT_MAX_CONST_BYTES,
                     ) -> List[AuditIssue]:
    """Audit a jit.TrainStep's full compiled program (fwd + bwd +
    optimizer) against an example batch, mirroring the argument
    assembly of TrainStep._dispatch without running the step."""
    return audit_fn(step._raw_step, *train_step_args(step, *batch),
                    name=type(step).__name__, checks=checks,
                    max_const_bytes=max_const_bytes)
